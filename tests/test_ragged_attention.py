"""Ragged paged attention (round 6): ONE kernel invocation over a mixed
row batch — decode rows (q_len = 1), speculative verify rows (q_len =
2..K+1) and prefill chunk rows (q_len up to the chunk width) — vs the XLA
oracle, plus the serving-level contract: ragged rounds are the DEFAULT
path and stay byte-identical to the split prefill/decode dispatches they
replaced (greedy byte-identical, seeded sampling stable), so the round
3-5 preemption/checkpoint/failover machinery carries over unchanged."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.ragged

from distributed_gpu_inference_tpu.ops.attention import (
    micro_read_xla_min_batch,
    paged_attention,
    paged_attention_xla,
    resolve_impl,
)


# --------------------------------------------------------------------- #
# kernel level: ragged row batches vs the XLA oracle (interpret mode)
# --------------------------------------------------------------------- #

def _ragged_setup(rows, nh, hkv, d, block, m, seed=0):
    """Build one ragged batch from per-row (span, kv_len) specs.

    Each row's queries sit at the TAIL of its context — positions
    ``kv_len - span .. kv_len - 1`` — which is exactly the state every
    producer dispatches: a decode row feeds its pending token (span 1), a
    spec verify row its K+1 window, an admission chunk row its freshly
    written chunk (lens_after = off + n). span 0 marks an inactive row
    (all queries padded). Rows pad to the widest span with position -1."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    b = len(rows)
    s = max(max(span for span, _ in rows), 1)
    num_blocks = 1 + b * m
    k_pool = jax.random.normal(ks[0], (num_blocks, hkv, block, d), jnp.float32)
    v_pool = jax.random.normal(ks[1], (num_blocks, hkv, block, d), jnp.float32)
    q = jax.random.normal(ks[2], (b, s, nh, d), jnp.float32)
    tables = np.zeros((b, m), np.int32)
    positions = np.full((b, s), -1, np.int32)
    lens = np.zeros((b,), np.int32)
    nxt = 1
    for i, (span, kv_len) in enumerate(rows):
        tables[i] = np.arange(nxt, nxt + m)
        nxt += m
        lens[i] = kv_len
        if span:
            positions[i, :span] = np.arange(kv_len - span, kv_len)
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(positions), jnp.asarray(lens))


def _compare(args, block, window=None, atol=2e-5):
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        ragged_paged_attention,
    )

    q, k_pool, v_pool, tables, positions, lens = args
    want = paged_attention_xla(
        q, k_pool, v_pool, tables, positions, lens, block, window=window
    )
    got = ragged_paged_attention(
        q, k_pool, v_pool, tables, positions, lens, block, window=window,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=atol)
    return got


@pytest.mark.slow
def test_decode_only_rows():
    # a ragged round with no admission in flight degenerates to the decode
    # shape: every row one live query at its context tail
    _compare(_ragged_setup([(1, 9), (1, 23), (1, 64)],
                           nh=4, hkv=2, d=64, block=16, m=4), 16)


@pytest.mark.slow
def test_prefill_only_row():
    # one wide chunk row alone (multi-page context, multiple page groups)
    _compare(_ragged_setup([(32, 300)],
                           nh=8, hkv=4, d=64, block=16, m=20), 16)


@pytest.mark.slow
def test_mixed_decode_verify_prefill_rows():
    # THE tentpole batch shape: decode rows, a spec verify row (q_len =
    # K+1 = 3) and a prefill chunk row coexist in one invocation with
    # wildly different spans and context lengths
    _compare(_ragged_setup([(1, 40), (3, 25), (16, 90), (1, 7)],
                           nh=4, hkv=2, d=64, block=16, m=8), 16)


@pytest.mark.slow
def test_mid_prompt_chunk_row():
    # an admission's NON-final chunk: queries end mid-prompt (kv_len =
    # off + n < prompt length) — later pages of the table are garbage the
    # in-length mask must fence off
    _compare(_ragged_setup([(16, 48), (1, 30)],
                           nh=4, hkv=2, d=64, block=16, m=8), 16)


@pytest.mark.slow
def test_inactive_row_zero_output():
    args = _ragged_setup([(1, 12), (0, 0), (4, 20)],
                         nh=4, hkv=2, d=64, block=16, m=2)
    got = _compare(args, 16)
    assert np.all(np.asarray(got)[1] == 0.0)


@pytest.mark.slow
def test_padded_tail_queries_zero():
    # rows narrower than the batch width: their padded tail queries must
    # come back as exact zeros (the XLA-path contract)
    args = _ragged_setup([(8, 33), (2, 17), (1, 5)],
                         nh=4, hkv=2, d=64, block=16, m=4)
    got = np.asarray(_compare(args, 16))
    assert np.all(got[1, 2:] == 0.0)
    assert np.all(got[2, 1:] == 0.0)


@pytest.mark.slow
@pytest.mark.parametrize("window", [4, 16])
def test_sliding_window_fences(window):
    # Mistral SWA across mixed spans: each query sees (p-window, p] only;
    # the kernel's per-row group walk may skip leading dead groups
    _compare(_ragged_setup([(1, 150), (6, 80), (16, 200)],
                           nh=4, hkv=2, d=64, block=16, m=16), 16,
             window=window)


@pytest.mark.slow
def test_q_tile_split():
    # span wider than the per-cell query tile (qpk=2 → T=32 at the default
    # VMEM bound): the row splits into independent q-tiles; softmax state
    # is per query so tiles must agree with the one-shot oracle exactly
    _compare(_ragged_setup([(48, 80), (1, 11)],
                           nh=4, hkv=2, d=64, block=16, m=8), 16)


@pytest.mark.slow
def test_int8_pool_mixed_rows():
    from distributed_gpu_inference_tpu.ops.attention import dequantize_kv
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        quantize_kv_pool,
        ragged_paged_attention,
    )

    q, k_pool, v_pool, tables, positions, lens = _ragged_setup(
        [(1, 40), (3, 25), (8, 60)], nh=4, hkv=2, d=64, block=32, m=4
    )
    k_i8, k_s = quantize_kv_pool(k_pool)
    v_i8, v_s = quantize_kv_pool(v_pool)
    k_deq = dequantize_kv(k_i8, k_s[:, None, :, :])
    v_deq = dequantize_kv(v_i8, v_s[:, None, :, :])
    want = paged_attention_xla(q, k_deq, v_deq, tables, positions, lens, 32)
    got = ragged_paged_attention(
        q, k_i8, v_i8, tables, positions, lens, 32, interpret=True,
        k_scale=k_s, v_scale=v_s,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_ragged_matches_multiquery_alias():
    # the pre-round-6 small-q entry point is now a thin alias — uniform
    # spans through either name must be the SAME array
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_attention_pallas_multiquery,
        ragged_paged_attention,
    )

    q, k_pool, v_pool, tables, positions, lens = _ragged_setup(
        [(4, 30), (4, 55)], nh=4, hkv=2, d=64, block=16, m=4
    )
    a = ragged_paged_attention(q, k_pool, v_pool, tables, positions, lens,
                               16, interpret=True)
    b = paged_attention_pallas_multiquery(
        q, k_pool, v_pool, tables, positions, lens, 16, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# dispatch: resolve_impl owns the crossovers (satellite: the micro-bench
# read crossover moved here; MICRO_READ_XLA_MIN_BATCH is an override only)
# --------------------------------------------------------------------- #

def test_resolve_impl_multi_token_is_ragged():
    assert resolve_impl(1, 128, 1024, backend_is_tpu=True) == "pallas"
    for s in (2, 8, 9, 64, 512):
        assert resolve_impl(s, 128, 1024, backend_is_tpu=True) == "ragged"
    # the small-table / head-dim / backend guards still win
    assert resolve_impl(4, 64, 1024, backend_is_tpu=True) == "xla"
    assert resolve_impl(4, 128, 128, backend_is_tpu=True) == "xla"
    assert resolve_impl(4, 128, 1024, backend_is_tpu=False) == "xla"


def test_resolve_impl_bare_read_row_crossover(monkeypatch):
    monkeypatch.delenv("MICRO_READ_XLA_MIN_BATCH", raising=False)
    # bare reads (fused=False) cross to the one-gather XLA path at the
    # measured row count; the fused serving path never flips on rows
    cut = micro_read_xla_min_batch()
    assert cut == 16
    assert resolve_impl(1, 128, 1024, backend_is_tpu=True,
                        rows=cut - 1, fused=False) == "pallas"
    assert resolve_impl(1, 128, 1024, backend_is_tpu=True,
                        rows=cut, fused=False) == "xla"
    assert resolve_impl(1, 128, 1024, backend_is_tpu=True,
                        rows=cut, fused=True) == "pallas"
    # env var is an OVERRIDE only (re-tuning without a code change)
    monkeypatch.setenv("MICRO_READ_XLA_MIN_BATCH", "4")
    assert micro_read_xla_min_batch() == 4
    assert resolve_impl(1, 128, 1024, backend_is_tpu=True,
                        rows=8, fused=False) == "xla"
    monkeypatch.setenv("MICRO_READ_XLA_MIN_BATCH", "not-a-number")
    assert micro_read_xla_min_batch() == 16


def test_paged_attention_routes_ragged_impl(monkeypatch):
    # impl="ragged" (and the legacy "pallas_mq" alias) route through the
    # public entry point to the ragged kernel — asserted by interception
    # (actually RUNNING the kernel on CPU needs interpret mode, which the
    # interpret-mode comparisons above cover)
    from distributed_gpu_inference_tpu.ops import paged_attention_pallas

    calls = []

    def fake(q, *a, **kw):
        calls.append("ragged")
        return q

    monkeypatch.setattr(
        paged_attention_pallas, "ragged_paged_attention", fake
    )
    args = _ragged_setup([(3, 20), (1, 9)], nh=4, hkv=2, d=64, block=16, m=2)
    q, k_pool, v_pool, tables, positions, lens = args
    for impl in ("ragged", "pallas_mq"):
        paged_attention(q, k_pool, v_pool, tables, positions, lens,
                        block_size=16, impl=impl)
    assert calls == ["ragged", "ragged"]
    want = paged_attention_xla(q, k_pool, v_pool, tables, positions, lens, 16)
    got = paged_attention(q, k_pool, v_pool, tables, positions, lens,
                          block_size=16, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


# --------------------------------------------------------------------- #
# serving level: the batcher's ragged rounds are byte-identical to
# generate()'s whole-wave prefill (checkpoints and failover ride on it)
# --------------------------------------------------------------------- #

from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
)
from distributed_gpu_inference_tpu.runtime.engine import (
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.testing.fakes import FakeRaggedEngine
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)

CFG = get_model_config("llama3-tiny", dtype="float32")


def _ecfg(**over):
    base = dict(max_batch_size=4, max_seq_len=128, block_size=16,
                prefill_buckets=(16, 32), dtype="float32", multi_step=4,
                enable_prefix_cache=False)
    base.update(over)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def params():
    return TPUEngine(CFG, _ecfg(), seed=0).params


def _req(prompt, max_new=8, temperature=0.0, seed=None):
    return InferenceRequest(
        prompt_token_ids=list(prompt),
        sampling=SamplingParams(max_new_tokens=max_new,
                                temperature=temperature, seed=seed),
    )


def _reference(params, reqs):
    """The same requests through ``TPUEngine.generate()`` on a fresh
    engine: the whole-wave and single-chunk prefill, no ragged round."""
    return TPUEngine(CFG, _ecfg(), params=params).generate(
        reqs, use_multi_step=True)


def _serve(params, reqs):
    """Run one request set through a fresh batcher; returns (responses in
    submit order, batcher stats)."""
    eng = TPUEngine(CFG, _ecfg(), params=params)
    cfg = BatcherConfig(max_wait_ms=2)

    async def go():
        b = ContinuousBatcher(eng, cfg)
        b.start()
        resps = await asyncio.gather(*[b.submit(r) for r in reqs])
        stats = b.get_stats()
        await b.stop()
        return resps, stats

    return asyncio.run(go())


def _mixed_workload():
    return [
        _req([(i * 17 + 3) % 500 for i in range(12)]),           # short
        _req([(i * 7 + 1) % 500 for i in range(30)]),            # one bucket
        _req([(i * 29 + 5) % 500 for i in range(70)], max_new=6),  # chunks
        _req([(i * 11 + 2) % 500 for i in range(20)]),           # short
        _req([(i * 13 + 9) % 500 for i in range(55)], max_new=5),  # chunks
    ]


@pytest.mark.slow
def test_ragged_is_default_and_greedy_byte_identical(params):
    got, gs = _serve(params, _mixed_workload())
    want = _reference(params, _mixed_workload())
    assert all(r.ok for r in got) and all(r.ok for r in want)
    for g, w in zip(got, want):
        assert g.token_ids == w.token_ids      # byte-identical greedy
    # every admission was appended to rounds: no prefill dispatch of its own
    assert gs["ragged_admissions"] == len(_mixed_workload())
    assert gs["ragged_rounds"] > 0


@pytest.mark.slow
def test_ragged_seeded_sampling_stable(params):
    reqs = [
        _req([(i * 17 + 3) % 500 for i in range(12)],
             temperature=0.8, seed=11),
        _req([(i * 29 + 5) % 500 for i in range(40)],
             temperature=0.7, seed=42, max_new=6),
        _req([(i * 11 + 2) % 500 for i in range(20)]),   # greedy alongside
    ]
    got, _ = _serve(params, reqs)
    want = _reference(params, reqs)
    for g, w in zip(got, want):
        assert g.ok and w.ok
        assert g.token_ids == w.token_ids      # sampler folds position


@pytest.mark.slow
def test_ragged_long_prompt_admitted_mid_decode(params):
    """A long prompt arriving while decodes are active rides the shared
    rounds as chunk rows — both outputs match ``generate()``'s byte for
    byte (greedy rows do not depend on what shares their round)."""
    early_req = _req([(i * 7 + 1) % 500 for i in range(12)], max_new=12)
    late_req = _req([(i * 23 + 4) % 500 for i in range(90)], max_new=5)
    eng = TPUEngine(CFG, _ecfg(), params=params)

    async def go():
        b = ContinuousBatcher(eng, BatcherConfig(max_wait_ms=1))
        b.start()
        first = asyncio.ensure_future(b.submit(early_req))
        await asyncio.sleep(0.05)   # let decoding start
        late = await b.submit(late_req)
        early = await first
        await b.stop()
        return early, late

    ge, gl = asyncio.run(go())
    we, wl = _reference(params, [early_req, late_req])
    assert ge.ok and gl.ok and ge.token_ids == we.token_ids
    assert gl.token_ids == wl.token_ids


def test_use_ragged_resolution():
    """The batcher has one admission path and names its protocol once: a
    DEFAULT BatcherConfig on an engine that speaks it serves ragged rounds
    (so the pressure/failover/batcher_serving suites — which construct
    default batchers — exercise them); an engine that says
    ``supports_ragged`` is False (today only ``kv_seq_sharded``) is
    refused with the fence named; a stub without the attribute is taken to
    speak the protocol."""
    assert not hasattr(BatcherConfig(), "ragged")

    async def serve():
        eng = FakeRaggedEngine()
        b = ContinuousBatcher(eng, BatcherConfig(max_wait_ms=1))
        b.start()
        resp = await b.submit(_req(range(20), max_new=3))
        stats = b.get_stats()
        await b.stop()
        return resp, stats, eng

    resp, stats, eng = asyncio.run(serve())
    assert resp.ok and resp.completion_tokens == 3
    assert stats["ragged_admissions"] == 1 and stats["ragged_rounds"] >= 1
    assert sum(sum(g.values()) for g in eng.round_grants) == 20

    class _Cfg:
        speculative = None

    class _Stub:
        cfg = _Cfg()

    class _ShardedEng(_Stub):
        supports_ragged = False

    ContinuousBatcher(_Stub(), BatcherConfig())     # accepted
    with pytest.raises(ValueError, match="kv_seq_sharded"):
        ContinuousBatcher(_ShardedEng(), BatcherConfig())


@pytest.mark.slow
def test_supports_ragged_engine_facts(params):
    import dataclasses

    eng = TPUEngine(CFG, _ecfg(), params=params)
    assert eng.supports_ragged
    # seq-sharded pools have no ragged round (their decode rows read
    # through a dedicated shard_map op) and the batcher refuses them;
    # spec-integrated engines serve ragged since round 8. Flip the config fact on the live object —
    # constructing a seq-sharded engine needs a mesh
    orig = eng.cfg
    try:
        eng.cfg = dataclasses.replace(orig, kv_seq_sharded=True)
        assert not eng.supports_ragged
    finally:
        eng.cfg = orig
    assert eng.supports_ragged
