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
# the chunk's KV path in place (PR 28): the page write into the stacked
# pools by layer index and the ragged kernel reading them there, against
# the scatter into the sliced layer and the same kernel on that slice
# --------------------------------------------------------------------- #

def _in_place_against_layer_copy(rows, nh, hkv, m, block=16, d=128,
                                 layers=3, layer=1, window=None,
                                 dtype=jnp.float32, seed=0):
    """``rows``: (span, kv_len) a row, its span at the tail of its context
    as in ``_ragged_setup``. Stacked pools of random bytes, shuffled
    tables that never name block 0. The pools must come out EQUAL — the
    written layer, the layers beside it, the unwritten slots of written
    pages, block 0 — and attention equal to the existing tolerance."""
    from distributed_gpu_inference_tpu.models.llama import _write_kv_pages
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        page_write_plan,
        ragged_paged_attention,
        write_kv_pages_in_place,
    )

    rng = np.random.default_rng(seed)
    b = len(rows)
    s = max(max(span for span, _ in rows), 2)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    k_pool = normal(layers, 1 + b * m, hkv, block, d)
    v_pool = normal(layers, 1 + b * m, hkv, block, d)
    q, new_k, new_v = normal(b, s, nh, d), normal(b, s, hkv, d), \
        normal(b, s, hkv, d)
    tables = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    positions = np.full((b, s), -1, np.int32)
    for i, (span, kv_len) in enumerate(rows):
        positions[i, :span] = np.arange(kv_len - span, kv_len)
    positions = jnp.asarray(positions)
    lens = jnp.asarray([kv_len for _, kv_len in rows], jnp.int32)

    want_k = k_pool.at[layer].set(
        _write_kv_pages(k_pool[layer], new_k, tables, positions, block))
    want_v = v_pool.at[layer].set(
        _write_kv_pages(v_pool[layer], new_v, tables, positions, block))
    want = ragged_paged_attention(
        q, want_k[layer], want_v[layer], tables, positions, lens, block,
        window=window, interpret=True)

    plan = page_write_plan(tables, positions, block,
                           hkv * block * d * k_pool.dtype.itemsize)
    got_k, got_v = write_kv_pages_in_place(
        new_k.reshape(-1, hkv, d), new_v.reshape(-1, hkv, d), k_pool, v_pool,
        jnp.int32(layer), plan, interpret=True)
    got = ragged_paged_attention(
        q, got_k, got_v, tables, positions, lens, block, window=window,
        interpret=True, layer_idx=jnp.int32(layer))

    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_array_equal(f32(got_k), f32(want_k))
    np.testing.assert_array_equal(f32(got_v), f32(want_v))
    np.testing.assert_allclose(f32(got), f32(want), rtol=2e-5, atol=2e-5)
    # and the write changed something, where a row had a token
    assert any(span for span, _ in rows) == bool(
        np.any(f32(got_k[layer]) != f32(k_pool[layer])))


_IN_PLACE_ROUNDS = {
    # a chunk that starts on a page's first slot and ends on one's last
    "page_aligned_256_token_chunk": ([(256, 512)], 32),
    # a span that starts and ends mid-page: first and last page are
    # read-modify-write, the ones between are written whole
    "span_from_mid_page_to_mid_page": ([(40, 45)], 4),
    # one-token rows, as a round's decode rows are: slots 0, 7 and 15
    "decode_rows_at_slots_0_7_15": ([(1, 17), (1, 24), (1, 32)], 2),
    # rows with nothing to write, and rows narrower than the rectangle
    "all_pad_rows_and_pad_tails": ([(0, 0), (9, 30), (0, 0), (2, 2)], 2),
    "nothing_to_write_at_all": ([(0, 0), (0, 0)], 2),
    # a round as serving has them: decode rows beside two pieces
    "decode_rows_beside_two_pieces": (
        [(1, 101), (50, 50), (1, 38), (33, 97)], 8),
    # the span ends on the last slot of the table's last page
    "last_page_of_the_table": ([(20, 64), (1, 64)], 4),
}


@pytest.mark.parametrize("case", sorted(_IN_PLACE_ROUNDS))
def test_in_place_chunk_equals_layer_copy(case):
    rows, m = _IN_PLACE_ROUNDS[case]
    _in_place_against_layer_copy(rows, nh=4, hkv=2, m=m)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_in_place_chunk_touches_only_its_layer(layer):
    """A layer of a 3-layer stack: the other two keep every byte (the
    whole-pool comparison), whichever it is."""
    _in_place_against_layer_copy([(1, 20), (21, 21)], nh=4, hkv=2, m=2,
                                 layer=layer)


@pytest.mark.parametrize("nh,hkv", [(32, 8), (28, 4), (16, 16)],
                         ids=["gqa_32_8", "gqa_28_4", "mha_16_16"])
def test_in_place_chunk_head_geometries(nh, hkv):
    """Mistral's, Qwen2.5's and OLMoE's heads of 128, in bf16 pools."""
    _in_place_against_layer_copy([(1, 40), (37, 70), (0, 0)], nh=nh,
                                 hkv=hkv, m=5, dtype=jnp.bfloat16)


@pytest.mark.parametrize("window", [4096, 24])
def test_in_place_chunk_under_a_sliding_window(window):
    """Mistral's window (4096: wider than the context served) and one
    that cuts inside the table."""
    _in_place_against_layer_copy([(1, 150), (33, 97), (6, 80)], nh=4, hkv=2,
                                 m=10, window=window)


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


# --------------------------------------------------------------------- #
# PR 49: a window SHORTER than the context (no configuration before it
# served one: Mistral's 4096 at 2048) at GQA groups that are no multiples
# of 8, contexts over several page groups: both kernels, interpreted,
# against the XLA oracle
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("nh,window", [(12, 512), (18, 512), (18, 100),
                                       (12, None)],
                         ids=["group6-w512", "group9-w512", "group9-w100",
                              "group6-full"])
def test_a_window_shorter_than_the_context_at_groups_of_6_and_9(nh, window):
    """Laguna-S-2.1's two kinds over two KV heads: decode rows and pieces
    whose contexts pass one, two and three 512-token page groups, the
    window's first key inside a group, on a group's first token and on its
    last."""
    rows = [(1, 700), (64, 1100), (1, 513), (48, 1024), (1, 1536), (0, 0),
            (1, 1023)]
    _compare(_ragged_setup(rows, nh=nh, hkv=2, d=32, block=16, m=96),
             16, window=window)


@pytest.mark.parametrize("nh,window", [(12, None), (18, 512), (18, 100)],
                         ids=["group6-full", "group9-w512", "group9-w100"])
def test_the_fused_decode_kernel_under_a_window_shorter_than_the_context(
        nh, window):
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_fused,
    )

    lens = [700, 513, 1536, 1023, 1024, 40]
    q, k_pool, v_pool, tables, positions, kv_lens = _ragged_setup(
        [(1, n) for n in lens], nh=nh, hkv=2, d=32, block=16, m=96)
    rng = np.random.default_rng(1)
    new_k = jnp.asarray(rng.standard_normal((len(lens), 1, 2, 32)),
                        jnp.float32)
    new_v = jnp.asarray(rng.standard_normal((len(lens), 1, 2, 32)),
                        jnp.float32)
    got, got_k, got_v = paged_decode_attention_fused(
        q, new_k, new_v, k_pool[None], v_pool[None], jnp.int32(0), tables,
        positions, kv_lens, 16, window=window, interpret=True)
    from distributed_gpu_inference_tpu.models.llama import _write_kv_pages

    want_k = _write_kv_pages(k_pool, new_k, tables, positions, 16)
    want_v = _write_kv_pages(v_pool, new_v, tables, positions, 16)
    want = paged_attention_xla(q, want_k, want_v, tables, positions, kv_lens,
                               16, window=window)
    np.testing.assert_array_equal(np.asarray(got_k[0]), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v[0]), np.asarray(want_v))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized,hkv,d,stages", [(False, 16, 256, 4),
                                                    (True, 32, 512, 3)],
                         ids=["f32", "int8"])
def test_the_fused_write_in_chunks_of_its_staging_pages(quantized, hkv, d,
                                                        stages):
    """Pages so large that half the kernel's VMEM budget stages fewer of
    them than the batch has rows: the write goes through its five phases a
    chunk of rows, the last chunk a short one, a row with nothing to write
    (position -1) inside a chunk. Pools bit-equal to the scatter's (an int8
    pool: to the rows quantised on the host by the same contract), the
    attention over them the oracle's."""
    from distributed_gpu_inference_tpu.models.llama import _write_kv_pages
    from distributed_gpu_inference_tpu.ops import paged_attention_pallas as pp

    block, lens = 32, [40, 0, 33, 96, 1, 0, 64]
    b = len(lens)
    page_bytes = hkv * block * d * (1 if quantized else 4) \
        + (block * d * 2 if quantized else 0)
    assert pp._VMEM_BUDGET_BYTES // 2 // (2 * page_bytes) == stages < b
    q, k_pool, v_pool, tables, positions, kv_lens = _ragged_setup(
        [(1 if n else 0, n) for n in lens], nh=hkv, hkv=hkv, d=d,
        block=block, m=3)
    rng = np.random.default_rng(2)
    new_k = jnp.asarray(rng.standard_normal((b, 1, hkv, d)), jnp.float32)
    new_v = jnp.asarray(rng.standard_normal((b, 1, hkv, d)), jnp.float32)
    if not quantized:
        got, got_k, got_v = pp.paged_decode_attention_fused(
            q, new_k, new_v, k_pool[None], v_pool[None], jnp.int32(0),
            tables, positions, kv_lens, block, interpret=True)
        want_k = _write_kv_pages(k_pool, new_k, tables, positions, block)
        want_v = _write_kv_pages(v_pool, new_v, tables, positions, block)
        np.testing.assert_array_equal(np.asarray(got_k[0]),
                                      np.asarray(want_k))
        np.testing.assert_array_equal(np.asarray(got_v[0]),
                                      np.asarray(want_v))
        want = paged_attention_xla(q, want_k, want_v, tables, positions,
                                   kv_lens, block)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        return

    def on_the_host(pool, new):
        """(int8 pool, scales) with the rows written as the kernel's
        contract quantises them: one scale a token, stored as bfloat16."""
        codes, scales = pp.quantize_kv_pool(pool)
        rows, row_scales = pp._quantize_token_rows(new[:, 0], (1, 2))
        wide = jnp.broadcast_to(
            row_scales.astype(jnp.bfloat16), (b, 1, d))[:, None]
        return (_write_kv_pages(codes, rows[:, None], tables, positions,
                                block),
                _write_kv_pages(scales[:, None], wide, tables, positions,
                                block)[:, 0],
                codes, scales)

    want_k, want_ks, k8, ks = on_the_host(k_pool, new_k)
    want_v, want_vs, v8, vs = on_the_host(v_pool, new_v)
    got, got_k, got_v, got_ks, got_vs = pp.paged_decode_attention_fused(
        q, new_k, new_v, k8[None], v8[None], jnp.int32(0), tables, positions,
        kv_lens, block, interpret=True, k_scale=ks[None], v_scale=vs[None])
    for have, wanted in ((got_k, want_k), (got_v, want_v),
                         (got_ks, want_ks), (got_vs, want_vs)):
        np.testing.assert_array_equal(np.asarray(have[0], np.float32),
                                      np.asarray(wanted, np.float32))

    def real(codes, scales):
        return codes.astype(jnp.float32) \
            * scales.astype(jnp.float32)[:, None, :, :]

    want = paged_attention_xla(q, real(want_k, want_ks),
                               real(want_v, want_vs), tables, positions,
                               kv_lens, block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)
