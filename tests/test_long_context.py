"""Long-context serving (round 17): the per-round prefill token budget,
32k-scale wire formats, and the deployed-path guarantees that let a 32k
prompt ride the batcher's ragged rounds without wrecking short-request
tails.

Tier-1 half (unmarked): ``split_prefill_budget`` water-fill properties,
the configurable prefix-fingerprint depth, the machine-readable
``over_length`` rejection, and a budgeted-scheduler smoke that drives the
REAL ContinuousBatcher round loop with a fake ragged engine (every
engine-building test in this repo is slow-marked, so this is the one
budget test the fast gate runs).

Slow half: wire formats at size (32k PreemptedSequence round-trip,
many-piece streamed KV handoff), the ragged kernel's per-sequence block
tables at multi-q-tile row counts, and engine-backed byte-identity
(budgeted vs unbudgeted, plain and sliding-window). The true-32k
deployed-path run additionally carries ``longctx`` (HEAVY CI shard).
"""

import asyncio
import json
import os
import subprocess
import sys
from typing import Dict, List

import numpy as np
import pytest

from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
    split_prefill_budget,
)
from distributed_gpu_inference_tpu.runtime.engine import (
    PreemptedSequence,
    RequestOverLength,
)
from distributed_gpu_inference_tpu.testing.fakes import FakeRaggedEngine
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)
from distributed_gpu_inference_tpu.utils.prefixes import (
    _max_blocks_default,
    prefix_fingerprints,
    sanitize_fingerprints,
)


def _req(prompt, max_new=4, priority=0):
    return InferenceRequest(
        prompt_token_ids=list(prompt), priority=priority,
        sampling=SamplingParams(max_new_tokens=max_new),
    )


def _run(coro):
    return asyncio.run(coro)


# --------------------------------------------------------------------- #
# split_prefill_budget: the water-fill contract
# --------------------------------------------------------------------- #


class TestSplitPrefillBudget:
    def test_ample_budget_grants_every_need(self):
        assert split_prefill_budget([8, 3, 5], 100) == [8, 3, 5]
        assert split_prefill_budget([8, 3, 5], 16) == [8, 3, 5]

    def test_small_admissions_finish_inside_their_share(self):
        # the 5-token admission completes; the giants split the remainder
        # evenly (±1 from integer shares) — first-come never takes all
        grants = split_prefill_budget([100, 5, 100], 64)
        assert grants[1] == 5
        assert sum(grants) == 64
        assert abs(grants[0] - grants[2]) <= 1

    def test_rotating_start_moves_the_odd_token(self):
        a = split_prefill_budget([100, 5, 100], 64, start=0)
        b = split_prefill_budget([100, 5, 100], 64, start=1)
        assert sum(a) == sum(b) == 64 and a != b
        assert a[1] == b[1] == 5

    def test_never_exceeds_budget_or_need(self):
        for budget in (1, 2, 7, 31, 64, 1000):
            for needs in ([1], [3, 3, 3], [50, 1, 9, 200], [0, 4, 0]):
                g = split_prefill_budget(list(needs), budget)
                assert sum(g) <= budget
                assert all(gi <= ni for gi, ni in zip(g, needs))
                assert sum(g) == min(budget, sum(needs))

    def test_starvation_free_under_one_token_budget(self):
        # budget < admission count: the rotating start must hand the
        # scarce token to every admission within len(needs) rounds
        fed = set()
        for start in range(3):
            g = split_prefill_budget([10, 10, 10], 1, start=start)
            assert sum(g) == 1
            fed.add(g.index(1))
        assert fed == {0, 1, 2}

    def test_degenerate_inputs(self):
        assert split_prefill_budget([], 10) == []
        assert split_prefill_budget([5, 5], 0) == [0, 0]
        assert split_prefill_budget([5, 5], -3) == [0, 0]
        assert split_prefill_budget([0, 0], 10) == [0, 0]

    def test_deterministic(self):
        args = ([17, 4, 90, 33], 41)
        assert split_prefill_budget(*args) == split_prefill_budget(*args)


# --------------------------------------------------------------------- #
# configurable prefix-fingerprint depth (routing resolution at 32k)
# --------------------------------------------------------------------- #


class TestPrefixFingerprintDepth:
    def test_default_depth_is_32(self, monkeypatch):
        monkeypatch.delenv("TPU_PREFIX_MAX_BLOCKS", raising=False)
        assert _max_blocks_default() == 32

    def test_env_override_and_floor(self, monkeypatch):
        monkeypatch.setenv("TPU_PREFIX_MAX_BLOCKS", "512")
        assert _max_blocks_default() == 512
        monkeypatch.setenv("TPU_PREFIX_MAX_BLOCKS", "0")
        assert _max_blocks_default() == 1
        monkeypatch.setenv("TPU_PREFIX_MAX_BLOCKS", "-4")
        assert _max_blocks_default() == 1
        monkeypatch.setenv("TPU_PREFIX_MAX_BLOCKS", "not-a-number")
        assert _max_blocks_default() == 32

    def test_deeper_cap_distinguishes_deep_long_context_prefixes(self):
        # two 32k-ish prompts sharing the first 4096 chars: at the default
        # 32-block depth they fingerprint IDENTICALLY (the router cannot
        # tell them apart past 2048 chars); a deeper cap separates them
        shared = "s" * 4096
        a, b = shared + "a" * 4096, shared + "b" * 4096
        assert prefix_fingerprints(a) == prefix_fingerprints(b)
        deep_a = prefix_fingerprints(a, max_blocks=128)
        deep_b = prefix_fingerprints(b, max_blocks=128)
        assert len(deep_a) == len(deep_b) == 128
        assert deep_a != deep_b
        # shared boundaries still match — prefix monotonicity holds
        assert deep_a[:64] == deep_b[:64]

    def test_sanitize_honors_explicit_cap(self):
        fps = [f"{i:04x}" for i in range(64)]
        assert len(sanitize_fingerprints(fps, max_blocks=16)) == 16
        assert len(sanitize_fingerprints(fps, max_blocks=64)) == 64

    def test_env_binds_module_default_at_import(self):
        # MAX_PREFIX_BLOCKS is read once at import: check in a subprocess
        code = (
            "from distributed_gpu_inference_tpu.utils import prefixes as p;"
            "print(p.MAX_PREFIX_BLOCKS, len(p.prefix_fingerprints('x'*8192)))"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            check=True,
            env={**os.environ, "TPU_PREFIX_MAX_BLOCKS": "96"},
        )
        assert out.stdout.split() == ["96", "96"]


async def _drive(engine: FakeRaggedEngine, cfg: BatcherConfig,
                 prompts: List[List[int]], max_new=4):
    b = ContinuousBatcher(engine, cfg)
    b.start()
    resps = await asyncio.gather(
        *[b.submit(_req(p, max_new=max_new)) for p in prompts]
    )
    stats = b.get_stats()
    await b.stop()
    return resps, stats


# --------------------------------------------------------------------- #
# tier-1 smoke: a many-chunk admission through the budgeted round loop
# --------------------------------------------------------------------- #


class TestBudgetedScheduler:
    def test_budget_caps_per_round_prefill_and_all_complete(self):
        eng = FakeRaggedEngine(ragged_chunk=8)
        prompts = [list(range(64)), list(range(100, 148)),
                   list(range(200, 212))]
        resps, stats = _run(_drive(
            eng, BatcherConfig(max_wait_ms=20, prefill_budget=10), prompts,
        ))
        assert all(r.ok and r.completion_tokens == 4 for r in resps)
        # the budget shaped real rounds: with >1 admission in flight no
        # round lands more prefill tokens than the budget allows
        assert stats["budgeted_rounds"] > 0
        multi = [g for g in eng.round_grants if len(g) > 1]
        assert multi, "admissions never shared a round"
        assert all(sum(g.values()) <= 10 for g in multi)
        # and every admission still drained its full prompt
        total = sum(sum(g.values()) for g in eng.round_grants)
        assert total == sum(len(p) for p in prompts)

    def test_budget_off_passes_none_caps(self):
        eng = FakeRaggedEngine(ragged_chunk=8)
        resps, stats = _run(_drive(
            eng, BatcherConfig(max_wait_ms=10, prefill_budget=0),
            [list(range(40)), list(range(50, 90))],
        ))
        assert all(r.ok for r in resps)
        # budget OFF is byte-identical to pre-budget by construction:
        # the engine must receive the pre-PR call shape (caps=None)
        assert eng.caps_seen and all(c is None for c in eng.caps_seen)
        assert stats["budgeted_rounds"] == 0

    def test_identical_outputs_budgeted_vs_unbudgeted(self):
        prompts = [list(range(48)), list(range(60, 84)),
                   list(range(90, 96))]

        def leg(budget):
            eng = FakeRaggedEngine(ragged_chunk=8)
            resps, _ = _run(_drive(
                eng, BatcherConfig(max_wait_ms=20, prefill_budget=budget),
                prompts,
            ))
            return [r.token_ids for r in resps]

        assert leg(0) == leg(12) == leg(3)

    def test_one_token_budget_is_starvation_free(self):
        # budget < admission count: the rotating start must still drain
        # every admission (slowly) rather than starving a subset forever
        eng = FakeRaggedEngine(ragged_chunk=8)
        resps, stats = _run(_drive(
            eng, BatcherConfig(max_wait_ms=20, prefill_budget=1),
            [list(range(12)), list(range(20, 32)), list(range(40, 52))],
            max_new=2,
        ))
        assert all(r.ok and r.completion_tokens == 2 for r in resps)
        assert stats["budget_skipped_admissions"] > 0
        assert all(sum(g.values()) <= 1 for g in eng.round_grants)

    def test_reconfigure_pushes_budget_and_chunk_live(self):
        async def go():
            eng = FakeRaggedEngine(ragged_chunk=8)
            b = ContinuousBatcher(eng, BatcherConfig(max_wait_ms=5))
            b.start()
            b.reconfigure(prefill_budget=24, ragged_chunk=4)
            assert b.cfg.prefill_budget == 24
            assert eng.cfg.ragged_chunk == 4
            with pytest.raises(ValueError, match="ragged_chunk"):
                b.reconfigure(ragged_chunk=0)
            # the rejected push mutated nothing (all-or-nothing)
            assert eng.cfg.ragged_chunk == 4
            r = await b.submit(_req(list(range(16))))
            await b.stop()
            return r, eng

        r, eng = _run(go())
        assert r.ok
        # the pushed 4-wide chunk shaped the admission's rounds
        widths = [w for g in eng.round_grants for w in g.values()]
        assert widths and max(widths) <= 4

    def test_over_length_error_code_reaches_the_response(self):
        eng = FakeRaggedEngine(max_seq_len=64)
        resps, _ = _run(_drive(
            eng, BatcherConfig(max_wait_ms=5), [list(range(80))],
        ))
        (r,) = resps
        assert not r.ok
        assert r.error_code == "over_length"
        assert "max_seq_len" in r.error

    def test_over_length_class_is_machine_readable(self):
        assert issubclass(RequestOverLength, ValueError)
        assert RequestOverLength.error_code == "over_length"
        err = RequestOverLength("too big")
        assert getattr(err, "error_code", None) == "over_length"


# --------------------------------------------------------------------- #
# one admission path: no switch, no second set of stats, nobody starved
# --------------------------------------------------------------------- #


def test_one_admission_path_has_no_switch_and_no_legacy_stats():
    """``BatcherConfig`` cannot select an admission path and the batcher
    counts one: what the wave / chunk-interleaved admission counted is
    gone with it, and every admission is a ragged one."""
    assert not hasattr(BatcherConfig(), "ragged")
    resps, stats = _run(_drive(
        FakeRaggedEngine(), BatcherConfig(max_wait_ms=5),
        [list(range(30)), list(range(40, 46))],
    ))
    assert all(r.ok for r in resps)
    for gone in ("chunked_admissions", "batched_waves", "ragged_mode"):
        assert gone not in stats
    assert stats["ragged_admissions"] == 2 and stats["ragged_rounds"] > 0
    assert stats["admitted"] == 2


def test_two_long_prompts_in_flight_do_not_starve_shorts():
    """While one long prompt is mid prefill, a second long prompt at the
    head of the admission order takes a slot beside it — both prefill in
    the same rounds — and the short requests behind them ride those rounds
    too: they finish while the long prompts are still streaming in."""
    eng = FakeRaggedEngine(max_batch_size=4, ragged_chunk=8)
    finished: List[str] = []

    async def go():
        b = ContinuousBatcher(eng, BatcherConfig(max_wait_ms=1))
        b.start()

        async def one(name, prompt, priority=0):
            resp = await b.submit(_req(prompt, max_new=3, priority=priority))
            finished.append(name)
            return resp

        long_a = asyncio.ensure_future(one("a", range(120), priority=1))
        while not eng.round_grants:         # A's prefill is under way
            await asyncio.sleep(0.001)
        rest = [one("b", range(200, 320), priority=9)] + [
            one(f"short{i}", range(400 + 10 * i, 406 + 10 * i))
            for i in range(2)]
        resps = await asyncio.gather(long_a, *rest)
        stats = b.get_stats()
        await b.stop()
        return resps, stats

    resps, stats = _run(go())
    assert all(r.ok and r.completion_tokens == 3 for r in resps)
    assert stats["ragged_admissions"] == 4
    # both long prompts landed a full piece in one and the same round
    assert any(sum(1 for w in g.values() if w == 8) >= 2
               for g in eng.round_grants)
    # and the shorts did not wait for either of them
    assert set(finished[:2]) == {"short0", "short1"}


# --------------------------------------------------------------------- #
# wire formats at size (slow: real 32k payloads)
# --------------------------------------------------------------------- #


@pytest.mark.slow
def test_preempted_sequence_wire_roundtrip_at_32k():
    """A 32k-prompt checkpoint must survive to_wire → JSON text →
    from_wire byte-identically — this is the payload a worker piggybacks
    on heartbeats so a long-context sequence can fail over mid-stream."""
    prompt = [(i * 2654435761) % 512 for i in range(32768)]
    generated = [(i * 40503) % 512 for i in range(512)]
    pre = PreemptedSequence(
        request=InferenceRequest(
            request_id="ckpt-32k", model="llama3-tiny",
            prompt_token_ids=prompt,
            sampling=SamplingParams(max_new_tokens=1024),
            priority=3, session_id="sess-9",
        ),
        prompt_len=len(prompt), generated=generated,
        slot_key=(0x12345678, 0x9ABCDEF0),
        start_time=1700000000.25, first_token_time=1700000042.5,
        cached_tokens=4096, preempt_count=2,
    )
    text = json.dumps(pre.to_wire())
    back = PreemptedSequence.from_wire(json.loads(text))
    assert back.request.prompt_token_ids == prompt
    assert back.generated == generated
    assert back.prompt_len == 32768
    assert back.slot_key == (0x12345678, 0x9ABCDEF0)
    assert back.cached_tokens == 4096 and back.preempt_count == 2
    assert back.request.request_id == "ckpt-32k"
    assert back.request.sampling.max_new_tokens == 1024
    # and the round-trip is a fixed point: same wire bytes again
    assert json.dumps(back.to_wire()) == text


@pytest.mark.slow
def test_streamed_handoff_many_pieces_at_long_context_block_counts():
    """PD handoff of a long-context sequence: hundreds of pieces through
    the production HandoffReceiver with full coverage accounting (the
    receiver must commit only when EVERY block arrived — a 32k sequence
    is ~2048 16-token blocks, far past the short-prompt piece counts the
    e2e suites exercise)."""
    from distributed_gpu_inference_tpu.runtime.kv_handoff import (
        HandoffReceiver,
    )
    from distributed_gpu_inference_tpu.testing.fakes import (
        FakeEngineConfig,
        FakeKVEngine,
        make_stream_messages,
        stream_kind,
    )

    # 8192 prompt tokens at the fake's 4-token blocks = 2049 blocks — the
    # same block-table width a 32k sequence has at the engine's 16-token
    # blocks; piece_blocks=8 makes a ~257-piece stream
    prompt = [(i * 2654435761) % 512 for i in range(8192)]
    recv = FakeKVEngine(
        cfg=FakeEngineConfig(max_blocks_per_seq=2064, max_seq_len=8256),
        num_blocks=2112,
    )
    receiver = HandoffReceiver(recv)
    msgs = make_stream_messages("lc1", prompt, piece_blocks=8)
    assert sum(1 for m in msgs if stream_kind(m) == "piece") >= 256
    result = None
    for msg in msgs:
        result = receiver.handle(msg)
    assert result is not None and result["state"] == "committed"
    assert recv.binds == 1
    assert recv.leaked_blocks() == 0


# --------------------------------------------------------------------- #
# kernel: per-sequence block tables across many q tiles (slow)
# --------------------------------------------------------------------- #


@pytest.mark.slow
@pytest.mark.ragged
def test_ragged_kernel_long_chunk_rows_split_across_q_tiles():
    """A long prefill chunk row splits host-side into multiple query
    tiles that all index ONE per-sequence block-table row (the round-17
    fix: tables are [B, M] with row = tile // q_tiles, not repeated per
    tile — repeating them would blow SMEM at 32k). Verify a multi-tile
    long row plus a decode row against the XLA oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_gpu_inference_tpu.ops.attention import (
        paged_attention_xla,
    )
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        _ragged_q_tile,
        ragged_paged_attention,
    )

    block, m, nh, hkv, d = 16, 80, 4, 2, 32
    span, kv_len = 1024, 1280  # 1024-token chunk splits into many q tiles
    assert span // _ragged_q_tile(span, nh // hkv) >= 4
    rows = [(span, kv_len), (1, 640)]
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    b, s = len(rows), span
    num_blocks = 1 + b * m
    k_pool = jax.random.normal(ks[0], (num_blocks, hkv, block, d),
                               jnp.float32)
    v_pool = jax.random.normal(ks[1], (num_blocks, hkv, block, d),
                               jnp.float32)
    q = jax.random.normal(ks[2], (b, s, nh, d), jnp.float32)
    tables = np.zeros((b, m), np.int32)
    positions = np.full((b, s), -1, np.int32)
    lens = np.zeros((b,), np.int32)
    nxt = 1
    for i, (sp, kl) in enumerate(rows):
        tables[i] = np.arange(nxt, nxt + m)
        nxt += m
        lens[i] = kl
        positions[i, :sp] = np.arange(kl - sp, kl)
    got = ragged_paged_attention(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(positions),
        jnp.asarray(lens), block_size=block, interpret=True,
    )
    want = paged_attention_xla(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(positions),
        jnp.asarray(lens), block_size=block,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------- #
# engine-backed byte-identity (slow: real models, compile-heavy)
# --------------------------------------------------------------------- #


def _engine(model="llama3-tiny", **kw):
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )

    # prefix cache OFF: the identity tests run the same prompts through
    # one engine twice, and a fully-cached second leg would leave the
    # budget nothing to shape (fresh ~ empty)
    cfg = dict(max_batch_size=4, max_seq_len=512, block_size=16,
               prefill_buckets=(16, 32, 64), ragged_chunk=32,
               dtype="float32", enable_prefix_cache=False)
    cfg.update(kw)
    return TPUEngine(model, EngineConfig(**cfg))


def _serve(engine, prompts, budget, max_new=6):
    async def go():
        b = ContinuousBatcher(
            engine, BatcherConfig(max_wait_ms=25, prefill_budget=budget),
        )
        b.start()
        resps = await asyncio.gather(
            *[b.submit(_req(p, max_new=max_new)) for p in prompts]
        )
        stats = b.get_stats()
        await b.stop()
        return resps, stats

    return _run(go())


@pytest.mark.slow
def test_budgeted_long_prompt_byte_identical_on_real_engine():
    """The tentpole invariant on a REAL paged engine: a many-chunk long
    prompt co-admitted with short requests produces byte-identical greedy
    tokens with the prefill budget ON vs OFF — the budget reshapes WHEN
    chunk rows land, never what they compute."""
    eng = _engine()
    long_p = [(i * 7) % 256 for i in range(300)]   # ~10 chunks of 32
    shorts = [[(i * 11 + j) % 256 for i in range(24)] for j in range(2)]
    prompts = [long_p] + shorts

    unbudgeted, s0 = _serve(eng, prompts, budget=0)
    budgeted, s1 = _serve(eng, prompts, budget=48)
    assert all(r.ok for r in unbudgeted + budgeted)
    assert [r.token_ids for r in unbudgeted] == \
        [r.token_ids for r in budgeted]
    assert s0["budgeted_rounds"] == 0
    assert s1["budgeted_rounds"] > 0


@pytest.mark.slow
@pytest.mark.pressure
def test_budgeted_long_prompt_byte_identical_under_sliding_window():
    """Budget x SWA: mid-prefill window release (long-context admission
    frees out-of-window blocks as chunks land, instead of holding the
    whole prompt's pages) must compose with budget-shaped chunk widths —
    same greedy bytes budgeted vs unbudgeted on the windowed model."""
    prompts = [[(i * 13) % 256 for i in range(280)],
               [(i * 5) % 256 for i in range(20)]]

    def leg(budget):
        eng = _engine("mistral-tiny")
        resps, _ = _serve(eng, prompts, budget=budget)
        assert all(r.ok for r in resps)
        return [r.token_ids for r in resps]

    assert leg(0) == leg(40)


# --------------------------------------------------------------------- #
# the deployed path at true 32k (longctx: HEAVY shard only)
# --------------------------------------------------------------------- #


@pytest.mark.slow
@pytest.mark.longctx
def test_32k_prompt_through_deployed_serving_path():
    """A true 32k prompt through the worker's deployed front door
    (TPULLMEngine -> BatcherServing -> ragged rounds) with the prefill
    budget pushed through the live serving-config path, while short
    requests ride the same rounds. Completion (not latency) is the
    assertion — the mixed-traffic frontier is the bench's job."""
    import threading

    from distributed_gpu_inference_tpu.worker.engines.llm import (
        TPULLMEngine,
    )

    long_len, max_new = 32768, 4
    long_blocks = -(-(long_len + max_new + 16) // 16)
    llm = TPULLMEngine({
        "model": "llama3-tiny",
        "max_batch_size": 3,
        "max_seq_len": long_len + max_new + 16,
        # pool sized for the actual working set, not 1.5x batch x 32k
        "num_blocks": long_blocks + 2 * 8 + 64,
        "prefill_buckets": (2048,),
        "serving": {"max_wait_ms": 2.0, "default_timeout_s": 1800.0,
                    "ragged_chunk": 2048, "prefill_budget": 2048},
    })
    llm.load_model()
    try:
        assert llm.serving.batcher.cfg.prefill_budget == 2048
        results: Dict[str, Dict] = {}

        def one(name, prompt_len, seed):
            prompt = "".join(
                chr(97 + (seed + i * 7) % 26) for i in range(prompt_len)
            )
            results[name] = llm.inference(
                {"prompt": prompt, "max_new_tokens": max_new}
            )

        threads = [
            threading.Thread(target=one, args=("long", long_len, 0)),
            threading.Thread(target=one, args=("s1", 64, 3)),
            threading.Thread(target=one, args=("s2", 64, 11)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1700)
        assert set(results) == {"long", "s1", "s2"}
        for name, r in results.items():
            assert r.get("error") is None, (name, r)
            assert r["usage"]["completion_tokens"] == max_new, (name, r)
        assert results["long"]["usage"]["prompt_tokens"] == long_len
        stats = llm.serving.get_stats()
        assert stats["ragged_rounds"] > 0
        assert stats["budgeted_rounds"] > 0
    finally:
        llm.unload()
