"""The plain ragged round packs its live tokens onto one axis (PR 24): the
dense work runs over ``Tp`` rows, attention and the page write over the
``[B, S]`` rectangle as before. Held here, on the CPU at a tiny size:

- packed against rectangle on the same rounds: two engines with the same
  weights run the same script; one of them has its round graph replaced by
  ``forward_chunk``'s rectangle form (what the graph was before the
  packing), fed from the very operands the packed round was given;
- ``lower_serving_graphs`` covers every shape serving reaches, in no more
  graphs than the rectangle took.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.runtime.engine import (
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)


def _ecfg(**over):
    base = dict(max_batch_size=4, max_seq_len=128, block_size=16,
                prefill_buckets=(16, 32), ragged_chunk=32, dtype="float32",
                multi_step=4, enable_prefix_cache=False)
    base.update(over)
    return EngineConfig(**base)


def _req(n, salt, max_new=6):
    return InferenceRequest(
        prompt_token_ids=[(i * salt + 3) % 500 for i in range(n)],
        sampling=SamplingParams(max_new_tokens=max_new, temperature=0.0),
    )


def _rectangle(eng):
    """Replace ``eng``'s round graph by the rectangle form: the packed
    operands laid out as ``[B, S]`` token and position arrays on the host,
    through ``forward_chunk`` with no packing."""
    fwd = functools.partial(llama.forward_chunk, pallas=eng.mesh is None)

    @functools.partial(jax.jit, donate_argnums=(1, 5))
    def rect_round(params, kv, toks_pos, tables, lens_after, core, flag):
        out = fwd(eng.model_cfg, params, toks_pos[0], toks_pos[1], kv,
                  tables, lens_after, block_size=eng.cfg.block_size,
                  last_only=True)
        toks = jnp.argmax(out.logits[:, 0, :], axis=-1).astype(jnp.int32)
        core = dict(core)
        core["last"] = jnp.where(flag > 0, toks, core["last"])
        core["lens"] = jnp.where(flag > 0, lens_after, core["lens"])
        return out.kv, core, toks

    def call(params, kv, tok_at, tables, lens_last, core, flag, mode, width):
        assert mode == "greedy"
        b = tables.shape[0]
        tok, pos, row, col = np.asarray(tok_at)
        live = row < b
        toks_pos = np.zeros((2, b, width), np.int32)
        toks_pos[1] = -1
        toks_pos[0, row[live], col[live]] = tok[live]
        toks_pos[1, row[live], col[live]] = pos[live]
        return rect_round(params, kv, toks_pos, tables, lens_last[0], core,
                          flag)

    eng._ragged_round_fn = call
    return eng


def _run(eng, script):
    """Rounds of ``ragged_round``: before round ``i`` admit ``script[i]``
    (requests), then run every admission still in flight beside the rows
    that decode. Returns, round by round, the live tokens the round held,
    what it sampled, and the device's slot state and pools after it."""
    flying, seen = [], []
    for new in script:
        flying += [eng.submit_chunked_start(r) for r in new]
        live = sum(min(len(a.fresh), eng.cfg.ragged_chunk) for a in flying) \
            + sum(1 for s in eng.slots if s is not None and not s.prefilling)
        out = eng.ragged_round(flying)
        flying = [a for a in flying if not a.done]
        core = eng._dev_core
        seen.append((live, out, np.asarray(core["last"]),
                     np.asarray(core["lens"]),
                     jax.tree.map(np.asarray, eng.kv)))
    assert not flying
    return seen


def _same_rounds(model, script, mesh=None, **over):
    cfg = get_model_config(model, dtype=over.get("dtype", "float32"))
    packed = TPUEngine(cfg, _ecfg(**over), seed=0, mesh=mesh)
    rect = _rectangle(TPUEngine(cfg, _ecfg(**over), params=packed.params,
                                mesh=mesh))
    got, want = _run(packed, script()), _run(rect, script())
    assert len(got) == len(want)
    for (_, g_out, g_last, g_lens, g_kv), (_, w_out, w_last, w_lens,
                                           w_kv) in zip(got, want):
        assert g_out == w_out               # greedy tokens, row for row
        # ``core``: rows that sampled hold the same token and length
        np.testing.assert_array_equal(g_last, w_last)
        np.testing.assert_array_equal(g_lens, w_lens)
        # the pools: the same pages written with the same rows, nothing
        # else touched (a code of an int8 pool may differ by one step
        # where a float sum rounded the other way)
        for name, w in w_kv.items():
            g = g_kv[name].astype(np.float32)
            if w.dtype == np.int8:
                assert np.abs(g - w.astype(np.float32)).max() <= 1
            else:
                tol = 1e-5 if w.dtype == np.float32 else 2e-2
                np.testing.assert_allclose(g, w.astype(np.float32),
                                           rtol=tol, atol=tol)
    # the dense work ran over each round's rung, not rows x bucket
    stats = packed.get_stats()
    assert stats["ragged_positions_live"] == sum(r[0] for r in got)
    assert stats["ragged_positions_dispatched"] \
        == sum(packed._ragged_shape(r[0])[0] for r in got)
    assert stats["ragged_positions_dispatched"] \
        < len(got) * len(packed.slots) * 16


# one script a case: what is admitted before each round
SCRIPTS = {
    # a round of nothing but decode rows (the third and fourth)
    "decode_rows_only": lambda: [[_req(9, 17), _req(12, 7)], [], []],
    # one piece, no row decoding
    "one_piece": lambda: [[_req(20, 11)]],
    # three pieces of unequal width beside a decode row
    "unequal_pieces_with_decode_rows": lambda: [
        [_req(10, 5)], [_req(7, 13), _req(30, 29), _req(18, 3)], []],
    # a 70-token prompt: two intermediate chunks and a final one, the
    # later ones beside a row that decodes
    "intermediate_and_final_chunk": lambda: [
        [_req(70, 29)], [_req(5, 7)], [], []],
}


@pytest.mark.parametrize("case", sorted(SCRIPTS))
def test_packed_round_equals_rectangle(case):
    _same_rounds("llama3-tiny", SCRIPTS[case])


def test_packed_round_equals_rectangle_cached_admission():
    """A prompt whose prefix is in the radix cache: its piece starts past
    the cached pages (``off`` > 0) and is all that runs."""
    cfg = get_model_config("llama3-tiny", dtype="float32")
    packed = TPUEngine(cfg, _ecfg(enable_prefix_cache=True), seed=0)
    rect = _rectangle(TPUEngine(cfg, _ecfg(enable_prefix_cache=True),
                                params=packed.params))
    outs = []
    for eng in (packed, rect):
        first = eng.submit_chunked_start(_req(40, 11, max_new=2))
        seen = [eng.ragged_round([first]), eng.ragged_round([first])]
        assert first.done
        seen.append(eng.ragged_round([]))
        eng.finish_slot(first.slot)
        second = eng.submit_chunked_start(_req(40, 11, max_new=2))
        assert second.off == 32 and len(second.fresh) == 8
        seen.append(eng.ragged_round([second]))
        assert second.done
        outs.append((seen, jax.tree.map(np.asarray, eng.kv)))
    assert outs[0][0] == outs[1][0]
    for name, want in outs[1][1].items():
        np.testing.assert_allclose(outs[0][1][name], want, rtol=1e-5,
                                   atol=1e-5)
    # cached or not, the same first token
    assert outs[0][0][1] == outs[0][0][3]


@pytest.mark.parametrize("over", [
    {"dtype": "bfloat16"},
    {"kv_cache_dtype": "int8", "block_size": 32, "max_seq_len": 256},
], ids=["bf16_pools", "int8_kv_pools"])
def test_packed_round_equals_rectangle_pool_dtypes(over):
    _same_rounds("llama3-tiny", SCRIPTS["unequal_pieces_with_decode_rows"],
                 **over)


def test_packed_round_equals_rectangle_on_a_mesh(cpu_devices):
    """``pallas=False``: a mesh engine's XLA paths, heads sharded over
    ``model`` and the token axes not."""
    mesh = Mesh(np.array(cpu_devices[:2]), ("model",))
    _same_rounds("llama3-tiny", SCRIPTS["unequal_pieces_with_decode_rows"],
                 mesh=mesh)


@pytest.mark.parametrize("model", ["qwen2.5-tiny", "mixtral-tiny"])
def test_packed_round_equals_rectangle_other_layers(model):
    """Qwen's q/k/v biases and a top-2 mixture-of-experts layer."""
    _same_rounds(model, SCRIPTS["unequal_pieces_with_decode_rows"])


# --------------------------------------------------------------------- #
# lower_serving_graphs covers what serving reaches
# --------------------------------------------------------------------- #

def test_ladder_is_no_longer_than_the_buckets_it_replaces():
    """Worker geometry (8 rows, chunk 256, buckets 16..2048): widths
    16/32/64/128/256 took five ragged graphs; the ladder takes at most
    five, ends at rows x chunk, and holds a full piece beside seven
    decode rows in one rung."""
    eng = TPUEngine(get_model_config("llama3-tiny", dtype="float32"),
                    EngineConfig(max_batch_size=8, max_seq_len=2048,
                                 dtype="float32", enable_prefix_cache=False),
                    seed=0)
    ladder = eng._ragged_ladder()
    assert len(ladder) <= 5 and ladder[-1] == 8 * 256
    assert any(256 + 7 <= t < 2 * 256 for t in ladder)
    graphs = eng.lower_serving_graphs([], [16, 32, 64, 128, 256])
    assert list(graphs) == [f"ragged_round[Tp={t}]" for t in ladder]
    # the rectangle attention sees is a function of Tp alone, within the
    # chunk
    for tp in ladder:
        assert eng._ragged_shape(tp) == (tp, min(tp, 256))
    # narrow traffic reaches only the rungs eight pieces of it fill
    assert list(eng.lower_serving_graphs([], [16])) == [
        f"ragged_round[Tp={t}]" for t in ladder[:2]]
    # with a scan length comes the one program that schedules a scan
    # dispatched behind an unread one (PR 32)
    assert eng.lower_serving_graphs([4], []).keys() == {
        "decode_multi[T=4]", "chain_sched"}


@pytest.mark.parametrize("seed", [0, 1])
def test_lowered_graphs_cover_randomised_rounds(seed):
    """Lower and compile for a width set, then serve randomised rounds
    (1-4 admissions of up to ``max(widths)`` tokens, 0-3 rows decoding):
    nothing compiles."""
    widths = (16, 32)
    eng = TPUEngine(get_model_config("llama3-tiny", dtype="float32"),
                    _ecfg(max_seq_len=256), seed=0)
    for low in eng.lower_serving_graphs([], widths).values():
        low.compile()
    rng = np.random.default_rng(seed)

    def fill(n_adm):
        return [eng.submit_chunked_start(
            _req(int(rng.integers(1, max(widths) + 1)),
                 int(rng.integers(3, 40)), max_new=40))
            for _ in range(n_adm)]

    # the small programs around a round (slot state uploads) once
    warm = fill(1)
    eng.ragged_round(warm)
    eng.finish_slot(warm[0].slot)
    before = eng.get_stats()["compiles"]
    shapes = set()
    for _ in range(12):
        for i, s in enumerate(eng.slots):
            # leave 0-3 rows decoding
            if s is not None and rng.random() < 0.5:
                eng.finish_slot(i)
        free = len(eng.free_slots())
        adms = fill(int(rng.integers(1, free + 1))) if free else []
        live = len(adms) and sum(len(a.fresh) for a in adms) + sum(
            1 for s in eng.slots if s is not None and not s.prefilling)
        eng.ragged_round(adms)
        assert all(a.done for a in adms)
        shapes.add(eng._ragged_shape(max(live, 1))[0])
    assert len(shapes) > 1                  # more than one rung was run
    assert eng.get_stats()["compiles"] == before


# --------------------------------------------------------------------- #
# the in-place KV path (PR 28): write and read the stacked pools
# --------------------------------------------------------------------- #

def _packed_round(cfg, params, kv, tables, pieces, width):
    """One packed ``forward_chunk``: ``pieces`` = (row, first position,
    tokens). → (updated pools, the logits of every piece's last token)."""
    b = tables.shape[0]
    tok = np.concatenate([np.asarray(t) for _, _, t in pieces])
    tp = 8 * -(-len(tok) // 8)
    pad = tp - len(tok)
    row = np.concatenate([np.full(len(t), r) for r, _, t in pieces])
    col = np.concatenate([np.arange(len(t)) for _, _, t in pieces])
    pos = np.concatenate([p + np.arange(len(t)) for _, p, t in pieces])
    last = np.zeros(b, np.int32)
    lens = np.zeros(b, np.int32)
    for r, p, t in pieces:
        last[r] = int(np.flatnonzero(row == r)[-1])
        lens[r] = p + len(t)
    i32 = lambda a, fill: jnp.asarray(
        np.concatenate([a, np.full(pad, fill)]), jnp.int32)
    out = llama.forward_chunk(
        cfg, params, i32(tok, 0), i32(pos, -1), kv, tables,
        jnp.asarray(lens), block_size=16,
        packing=llama.Packing(i32(row, b), i32(col, 0), jnp.asarray(last),
                              width),
    )
    return out.kv, np.asarray(out.logits[[r for r, _, _ in pieces], 0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_in_place_kv_path_equals_layer_copy_over_two_rounds(monkeypatch,
                                                            dtype):
    """The in-place path (``dgi_paged_write`` into the stacked pools, the
    ragged kernel reading them by layer index; interpret mode) against
    the slice / scatter / write-back branch with the same kernel on the
    sliced layer: the same pools, exactly — every layer, every page, the
    unwritten slots of written pages — and the same logits, over two
    rounds of one sequence, so that round 2 reads what round 1 wrote: a
    21-token piece that ends mid-page, then its decode row beside a
    second sequence's piece."""
    from distributed_gpu_inference_tpu.models.configs import ModelConfig
    from distributed_gpu_inference_tpu.ops import attention
    from distributed_gpu_inference_tpu.ops import paged_attention_pallas as pap

    cfg = ModelConfig(
        name="in-place-probe", vocab_size=256, hidden_size=256, num_layers=3,
        num_heads=4, num_kv_heads=2, intermediate_size=256, head_dim=128,
        dtype=dtype,
    )
    monkeypatch.setattr(attention, "pallas_backend", lambda: True)
    traced = []                              # kernels traced, by name

    def interpreted(name):
        kernel = getattr(pap, name)

        def call(*args, **kwargs):
            traced.append(name)
            return kernel(*args, interpret=True, **kwargs)

        return call

    for name in ("write_kv_pages_in_place", "ragged_paged_attention"):
        monkeypatch.setattr(pap, name, interpreted(name))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rows, pages = 3, 32                      # 512 tokens of context a row
    rng = np.random.default_rng(5)
    tables = jnp.asarray(
        1 + rng.permutation(rows * pages).reshape(rows, pages), jnp.int32)
    # pools that are not zero: an untouched byte that moved would show
    kv0 = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        llama.init_kv_pools(cfg, 1 + rows * pages, 16, dtype=jnp.dtype(dtype)),
    )
    first = rng.integers(1, 200, size=21)
    rounds = [
        ([(1, 0, first)], 32),
        ([(1, 21, [7]), (2, 0, rng.integers(1, 200, size=37))], 64),
    ]

    def run(path):
        if path == "layer_copy":
            monkeypatch.setattr(llama, "ragged_kv_path",
                                lambda *a, **k: "layer_copy")
        kv, seen = kv0, []
        for pieces, width in rounds:
            kv, logits = _packed_round(cfg, params, kv, tables, pieces, width)
            seen.append((jax.tree.map(np.asarray, kv), logits))
        return seen

    assert llama.ragged_kv_path(cfg, pages * 16, False) == "in_place"
    got = run("in_place")
    assert set(traced) == {"write_kv_pages_in_place",
                           "ragged_paged_attention"}
    del traced[:]
    want = run("layer_copy")
    assert set(traced) == {"ragged_paged_attention"}
    for (g_kv, g_logits), (w_kv, w_logits) in zip(got, want):
        for name in ("k", "v"):
            np.testing.assert_array_equal(
                g_kv[name].astype(np.float32), w_kv[name].astype(np.float32))
        np.testing.assert_allclose(g_logits, w_logits, rtol=1e-5, atol=1e-5)
    # block 0 (the null block no table names) kept its bytes
    np.testing.assert_array_equal(
        got[-1][0]["k"][:, 0].astype(np.float32),
        np.asarray(kv0["k"][:, 0], np.float32))
