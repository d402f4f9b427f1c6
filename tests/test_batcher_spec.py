"""Adaptive speculation in the serving path (VERDICT r3 #7).

The batcher routes a low-depth all-greedy queue through the speculative
tree decoder (incremental wave API — one bounded fused dispatch per loop
iteration) and keeps deeper / sampled / opted-out load on the paged
engine. Invariants:

- greedy outputs are bit-exact vs the vanilla paged engine either way
  (the verify pass is an argmax match against the same target weights);
- requests arriving mid-wave decode on the paged engine concurrently —
  a spec wave never blocks admission;
- per-request opt-out (`params={"speculative": False}`) and sampled
  requests never enter the spec path.

Reference contrast: its speculative engine is a standalone whole-request
path (worker/engines/speculative.py); the batcher there never mixes modes.
"""

import asyncio

import pytest

pytestmark = pytest.mark.slow

from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
)
from distributed_gpu_inference_tpu.runtime.engine import EngineConfig, TPUEngine
from distributed_gpu_inference_tpu.runtime.speculative import (
    SpeculativeConfig,
    SpeculativeDecoder,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)

MODEL = "llama3-tiny"


def _req(seed_tok, n=12, temperature=0.0, spec_opt=None):
    prompt = [(seed_tok * 7 + i * 13) % 500 for i in range(20)]
    r = InferenceRequest(
        prompt_token_ids=prompt,
        sampling=SamplingParams(max_new_tokens=n, temperature=temperature,
                                seed=0 if temperature else None),
    )
    if spec_opt is not None:
        r.params["speculative"] = spec_opt
    return r


@pytest.fixture(scope="module")
def stack():
    from distributed_gpu_inference_tpu.models.configs import get_model_config

    # f32 end-to-end (cfg-level so the spec decoder's own KV pools are f32
    # too): bit-exact greedy equality across the two decode paths needs
    # identical numerics, same as tests/test_runtime_speculative.py
    cfg = get_model_config(MODEL, dtype="float32")
    eng = TPUEngine(
        cfg,
        EngineConfig(max_batch_size=4, max_seq_len=128, block_size=16,
                     prefill_buckets=(32,), dtype="float32",
                     enable_prefix_cache=False),
        seed=0,
    )
    spec = SpeculativeDecoder(
        cfg, params=eng.params,
        spec_cfg=SpeculativeConfig(widths=(2, 2), adaptive=False),
        max_batch_size=2, max_seq_len=128, block_size=16,
        prefill_buckets=(32,),
    )
    oracle = TPUEngine(
        cfg,
        EngineConfig(max_batch_size=4, max_seq_len=128, block_size=16,
                     prefill_buckets=(32,), dtype="float32",
                     enable_prefix_cache=False),
        params=eng.params, seed=0,
    )
    return eng, spec, oracle


def _run(coro):
    return asyncio.get_event_loop_policy().new_event_loop().run_until_complete(
        coro
    )


def test_low_depth_greedy_routes_spec_bit_exact(stack):
    eng, spec, oracle = stack
    want = {r.request_id: resp.token_ids
            for r, resp in ((r, oracle.generate([r])[0])
                            for r in (_req(1), _req(2)))}

    async def main():
        b = ContinuousBatcher(
            eng, BatcherConfig(max_wait_ms=20.0, spec_max_batch=2),
            spec=spec,
        )
        b.start()
        r1, r2 = _req(1), _req(2)
        got = await asyncio.gather(b.submit(r1), b.submit(r2))
        await b.stop()
        return {r.request_id: g for r, g in zip((r1, r2), got)}

    got = _run(main())
    for rid, resp in got.items():
        assert resp.error is None
        # same prompts as the oracle pairs (request ids differ; match by
        # order of construction)
    toks = sorted(tuple(r.token_ids) for r in got.values())
    assert toks == sorted(tuple(v) for v in want.values())


def test_spec_stats_and_deep_load_vanilla(stack):
    eng, spec, oracle = stack

    async def main():
        b = ContinuousBatcher(
            eng, BatcherConfig(max_wait_ms=20.0, spec_max_batch=2),
            spec=spec,
        )
        b.start()
        # 1) low-depth greedy pair -> spec wave
        await asyncio.gather(b.submit(_req(3)), b.submit(_req(4)))
        waves_after_low = b.stats["spec_waves"]
        # 2) burst of 4 -> exceeds spec_max_batch -> vanilla paged
        await asyncio.gather(*(b.submit(_req(10 + i)) for i in range(4)))
        waves_after_deep = b.stats["spec_waves"]
        # 3) sampled request -> vanilla even at depth 1
        await b.submit(_req(20, temperature=0.7))
        # 4) explicit opt-out -> vanilla
        await b.submit(_req(21, spec_opt=False))
        waves_final = b.stats["spec_waves"]
        stats = b.get_stats()
        await b.stop()
        return waves_after_low, waves_after_deep, waves_final, stats

    low, deep, final, stats = _run(main())
    assert low >= 1, "low-depth greedy load must route through spec"
    assert deep == low, "burst above spec_max_batch must decode vanilla"
    assert final == deep, "sampled/opted-out must never enter spec"
    assert stats["spec_completed"] >= 2
    assert stats["spec"]["drafted"] > 0


def test_mid_wave_arrivals_decode_paged_concurrently(stack):
    eng, spec, oracle = stack
    longr = _req(30, n=48)
    want_long = oracle.generate([_req(30, n=48)])[0].token_ids
    want_mid = [oracle.generate([_req(40 + i)])[0].token_ids
                for i in range(3)]

    async def main():
        b = ContinuousBatcher(
            eng, BatcherConfig(max_wait_ms=5.0, spec_max_batch=1),
            spec=spec,
        )
        b.start()
        t_long = asyncio.create_task(b.submit(longr))
        # wait until the spec wave is actually in flight
        for _ in range(300):
            if b._spec_wave is not None:
                break
            await asyncio.sleep(0.005)
        assert b._spec_wave is not None, "spec wave never started"
        # 3 arrivals mid-wave: depth > spec_max_batch? no — wave active, so
        # they must admit to the PAGED engine while the wave continues
        mids = [asyncio.create_task(b.submit(_req(40 + i)))
                for i in range(3)]
        done_mid = await asyncio.gather(*mids)
        done_long = await t_long
        stats = b.get_stats()
        await b.stop()
        return done_long, done_mid, stats

    done_long, done_mid, stats = _run(main())
    assert done_long.error is None
    assert done_long.token_ids == want_long
    assert [r.token_ids for r in done_mid] == want_mid
    assert stats["spec_waves"] == 1
    # mid-wave arrivals must go PAGED (ragged admission rounds) while
    # the wave continues
    assert stats["ragged_admissions"] >= 3, "mid-wave arrivals must go paged"


def test_spec_max_active_unsticks_routing(stack):
    """Round-5 routing fix (VERDICT r4 #4): with spec_max_active > 0 a
    greedy single arriving while a paged slot is STILL DECODING routes to
    a spec wave — the round-4 idle-engine requirement made routing sticky
    at steady rates (the first paged request kept the engine active
    whenever the next arrived, so no wave ever started again)."""
    eng, spec, oracle = stack
    want_long = oracle.generate([_req(50, n=48, spec_opt=False)])[0].token_ids
    want_next = oracle.generate([_req(51)])[0].token_ids

    async def main():
        b = ContinuousBatcher(
            eng, BatcherConfig(max_wait_ms=5.0, spec_max_batch=2,
                               spec_max_active=2),
            spec=spec,
        )
        b.start()
        # a long opted-out request occupies a paged slot for many rounds
        t_long = asyncio.create_task(b.submit(_req(50, n=48, spec_opt=False)))
        for _ in range(300):
            if eng.num_active > 0:
                break
            await asyncio.sleep(0.005)
        assert eng.num_active > 0, "paged request never became active"
        # greedy single arrives while the engine is BUSY: must still spec
        got_next = await b.submit(_req(51))
        got_long = await t_long
        stats = b.get_stats()
        await b.stop()
        return got_long, got_next, stats

    got_long, got_next, stats = _run(main())
    assert got_long.error is None and got_long.token_ids == want_long
    assert got_next.error is None and got_next.token_ids == want_next
    assert stats["spec_waves"] >= 1, (
        "wave must start despite an active paged slot"
    )
    assert stats["spec_completed"] >= 1


def test_spec_max_active_zero_keeps_round4_veto(stack):
    """spec_max_active=0 restores the idle-engine requirement: a greedy
    single arriving while a paged slot decodes stays on the paged path."""
    eng, spec, oracle = stack
    want_next = oracle.generate([_req(61)])[0].token_ids

    async def main():
        b = ContinuousBatcher(
            eng, BatcherConfig(max_wait_ms=5.0, spec_max_batch=2,
                               spec_max_active=0),
            spec=spec,
        )
        b.start()
        t_long = asyncio.create_task(b.submit(_req(60, n=48, spec_opt=False)))
        for _ in range(300):
            if eng.num_active > 0:
                break
            await asyncio.sleep(0.005)
        waves_before = b.stats["spec_waves"]
        got_next = await b.submit(_req(61))
        await t_long
        waves_after = b.stats["spec_waves"]
        await b.stop()
        return got_next, waves_before, waves_after

    got_next, waves_before, waves_after = _run(main())
    assert got_next.error is None and got_next.token_ids == want_next
    assert waves_after == waves_before, (
        "spec_max_active=0 must veto waves while the engine is active"
    )
