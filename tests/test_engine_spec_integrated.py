"""Engine-integrated speculative decoding (EngineConfig.speculative):
greedy byte-equivalence vs the vanilla engine across seeds (incl. EOS
mid-verify-window and mixed sampled batches), speculative KV rollback
(block refcounts / free list / prefix index match a never-speculated
engine, incl. int8 KV), and the batcher wiring."""

import numpy as np
import pytest

from distributed_gpu_inference_tpu.runtime.engine import EngineConfig, TPUEngine
from distributed_gpu_inference_tpu.runtime.speculative import SpecDecodeConfig
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)

MODEL = "llama3-tiny"


def _cfg(**kw):
    # f32 numerics: bit-exact greedy equality across the two decode paths
    # needs identical arithmetic
    base = dict(max_batch_size=4, max_seq_len=128, block_size=16,
                prefill_buckets=(16, 32), multi_step=8, dtype="float32")
    base.update(kw)
    return EngineConfig(**base)


def _req(prompt, max_new=12, **kw):
    return InferenceRequest(
        prompt_token_ids=prompt,
        sampling=SamplingParams(max_new_tokens=max_new, **kw),
    )


def _pair(seed=0, k=4, **cfg_kw):
    """(vanilla, speculative) engines sharing the same target weights."""
    e1 = TPUEngine(MODEL, _cfg(**cfg_kw), seed=seed)
    e2 = TPUEngine(
        MODEL,
        _cfg(**cfg_kw, speculative=SpecDecodeConfig(num_draft_tokens=k)),
        params=e1.params, seed=seed,
    )
    return e1, e2


PROMPTS = [list(range(10, 30)), list(range(40, 70)), list(range(5, 22))]


@pytest.mark.parametrize("seed", [0, 3])
def test_greedy_byte_identical_across_seeds(seed):
    e1, e2 = _pair(seed=seed)
    r1 = e1.generate([_req(p) for p in PROMPTS], use_multi_step=True)
    r2 = e2.generate([_req(p) for p in PROMPTS], use_multi_step=True)
    for a, b in zip(r1, r2):
        assert a.token_ids == b.token_ids
        assert a.finish_reason == b.finish_reason
    st = e2.get_stats()
    assert st["spec_steps"] > 0
    assert 0.0 <= st["spec_accept_rate"] <= 1.0
    assert st["spec_tokens_per_step"] >= 1.0


def test_eos_mid_verify_window():
    """A stop token landing inside the speculative window must truncate
    exactly where the vanilla engine stops (acceptance-rule correctness
    at the trickiest boundary)."""
    e1, e2 = _pair(seed=1)
    free = e1.generate([_req(PROMPTS[0], max_new=16)], use_multi_step=True)[0]
    assert len(free.token_ids) == 16
    # stop positions across the window: start, middle, and straddling
    for stop_idx in (1, 5, 6, 10):
        stop_at = free.token_ids[stop_idx]
        a = e1.generate(
            [_req(PROMPTS[0], max_new=16, stop_token_ids=(stop_at,))],
            use_multi_step=True,
        )[0]
        b = e2.generate(
            [_req(PROMPTS[0], max_new=16, stop_token_ids=(stop_at,))],
            use_multi_step=True,
        )[0]
        assert a.token_ids == b.token_ids, stop_idx
        assert a.finish_reason == b.finish_reason == "stop"


def test_mixed_sampled_batch_identical():
    """Sampled slots ride the spec graph at one token per step with the
    same key-fold positions as vanilla decode — seeded streams must match
    exactly; greedy neighbors still speculate."""
    e1, e2 = _pair(seed=2)
    reqs = lambda: [  # noqa: E731
        _req(PROMPTS[0], temperature=0.8, top_k=40, top_p=0.9, seed=7),
        _req(PROMPTS[1]),
        _req(PROMPTS[2], temperature=0.5, seed=11),
    ]
    r1 = e1.generate(reqs(), use_multi_step=True)
    r2 = e2.generate(reqs(), use_multi_step=True)
    for a, b in zip(r1, r2):
        assert a.token_ids == b.token_ids


def test_per_step_api_matches_multi_round():
    e1, e2 = _pair(seed=0)
    want = e1.generate([_req(PROMPTS[0])], use_multi_step=True)[0]
    slot = e2.submit(_req(PROMPTS[0]))
    while e2.slots[slot] is not None and \
            e2.slots[slot].finish_reason is None:
        e2.spec_decode_step()
    got = e2.finish_slot(slot)
    assert got.token_ids == want.token_ids


def _manager_fingerprint(eng):
    m = eng.manager
    return {
        "free": m.num_free,
        "cached": len(m.cached_lru),
        "radix": len(m.radix),
        "metas": len(m.metas),
        "active_seqs": len(m.seq_blocks),
        "refcounts_zero": all(
            meta.ref_count == 0 for meta in m.metas.values()
        ),
    }


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_spec_kv_rollback_matches_never_speculated(kv_dtype):
    """After generations full of rejected windows, block refcounts, the
    free list, and the prefix-cache index must match a never-speculated
    engine serving the same requests — no leaked or corrupted blocks."""
    kw = dict(kv_cache_dtype=kv_dtype) if kv_dtype else {}
    # random draft head => almost every window rejects => maximal rollback
    e1, e2 = _pair(seed=4, **kw)
    reqs = [_req(p, max_new=10) for p in PROMPTS]
    r1 = e1.generate(reqs, use_multi_step=True)
    r2 = e2.generate([_req(p, max_new=10) for p in PROMPTS],
                     use_multi_step=True)
    for a, b in zip(r1, r2):
        assert a.token_ids == b.token_ids  # int8 included: same quant path
    f1, f2 = _manager_fingerprint(e1), _manager_fingerprint(e2)
    assert f1 == f2
    assert f2["refcounts_zero"] and f2["active_seqs"] == 0
    # conservation: every non-reserved block is free or cached
    assert f2["free"] + f2["cached"] == e2.manager.num_blocks - 1
    # prefix-cache index equivalence: the same full blocks are findable
    for p, resp in zip(PROMPTS, r2):
        full = p + resp.token_ids
        assert len(e2.manager.radix.match_prefix(full)) == \
            len(e1.manager.radix.match_prefix(full))


def test_trim_keeps_per_step_footprint():
    """Mid-flight, a speculating sequence holds exactly the blocks its
    committed+pending tokens occupy after each round (trim_reserved) —
    the same footprint a per-step engine keeps."""
    _, e2 = _pair(seed=0)
    slot = e2.submit(_req(PROMPTS[0], max_new=24))
    s = e2.slots[slot]
    bs = e2.cfg.block_size
    for _ in range(4):
        e2.spec_decode_step()
        if s.finish_reason is not None:
            break
        held = len(e2.manager.seq_blocks[s.seq_id])
        need = max(1, -(-len(e2.manager.seq_tokens[s.seq_id]) // bs))
        assert held == need
    e2.finish_slot(slot)


def test_prefix_cache_composes_with_speculation():
    e1, e2 = _pair(seed=5)
    p = list(range(30, 70))   # 40 tokens -> 2 cacheable full blocks
    want = e1.generate([_req(p)], use_multi_step=True)[0]
    first = e2.generate([_req(p)], use_multi_step=True)[0]
    second = e2.generate([_req(p)], use_multi_step=True)[0]
    assert second.cached_tokens >= 32
    assert first.token_ids == want.token_ids
    assert second.token_ids == want.token_ids


def test_slots_join_and_leave_mid_flight():
    """Continuous batching semantics: a new request admitted while others
    are mid-speculation decodes correctly, and the finished slot recycles."""
    e1, e2 = _pair(seed=6)
    want = {i: e1.generate([_req(p, max_new=16)], use_multi_step=True)[0]
            for i, p in enumerate(PROMPTS)}
    s0 = e2.submit(_req(PROMPTS[0], max_new=16))
    e2.spec_decode_step()
    s1 = e2.submit(_req(PROMPTS[1], max_new=16))
    e2.spec_decode_step()
    s2 = e2.submit(_req(PROMPTS[2], max_new=16))
    got = {}
    while e2.num_active:
        e2.decode_multi(4)
        for i, s in enumerate(list(e2.slots)):
            if s is not None and s.finish_reason is not None:
                resp = e2.finish_slot(i)
                got[{s0: 0, s1: 1, s2: 2}[i]] = resp
    for i in range(3):
        assert got[i].token_ids == want[i].token_ids


def test_batcher_serves_spec_engine_bit_exact():
    """The continuous batcher drives the speculative engine unchanged —
    multi-token commits per round, identical outputs vs a vanilla oracle,
    and speculation efficiency surfaced in its stats."""
    import asyncio

    from distributed_gpu_inference_tpu.runtime.batcher import (
        BatcherConfig,
        ContinuousBatcher,
    )

    e1, e2 = _pair(seed=7)
    want = [e1.generate([_req(p)], use_multi_step=True)[0].token_ids
            for p in PROMPTS]

    async def main():
        b = ContinuousBatcher(e2, BatcherConfig(max_wait_ms=10.0))
        b.start()
        got = await asyncio.gather(*(b.submit(_req(p)) for p in PROMPTS))
        stats = b.get_stats()
        await b.stop()
        return got, stats

    got, stats = asyncio.get_event_loop_policy().new_event_loop()\
        .run_until_complete(main())
    assert [g.token_ids for g in got] == want
    assert "spec_integrated" in stats
    assert stats["spec_integrated"]["steps"] > 0


def test_worker_stream_routes_through_speculation():
    """Token streaming on a speculative engine emits identical text while
    actually running draft→verify rounds (one per flush, up to K+1 tokens
    each) instead of silently falling back to 1-token vanilla steps."""
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    def mk(spec):
        cfg = {"model": "llama3-tiny", "max_batch_size": 2,
               "max_seq_len": 64}
        if spec:
            cfg.update(speculative_decode=True, spec_num_draft_tokens=3)
        e = TPULLMEngine(cfg)
        e.load_model()
        return e

    a, b = mk(False), mk(True)   # same model + default seed => same weights
    pa = list(a.stream({"prompt": "hello", "max_tokens": 8}))
    pb = list(b.stream({"prompt": "hello", "max_tokens": 8}))
    text = lambda chunks: "".join(  # noqa: E731
        c.get("text_delta", "") for c in chunks
    )
    assert text(pa) == text(pb)
    assert pa[-1]["usage"] == pb[-1]["usage"]
    assert b.engine.get_stats()["spec_steps"] > 0


def test_engine_error_recovery_resets_spec_state():
    """A failed speculative dispatch must invalidate device state and
    leave the engine serviceable (the draft hidden rebuilds as zeros)."""
    _, e2 = _pair(seed=8)
    out = e2.generate([_req(PROMPTS[0])], use_multi_step=True)[0]
    e2._invalidate_device_state()
    assert e2._dev_spec_h is None
    again = e2.generate([_req(PROMPTS[0])], use_multi_step=True)[0]
    assert again.token_ids == out.token_ids
