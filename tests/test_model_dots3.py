"""dots3-note-prev on the deployed path: latent (MLA) pages of TWO kinds --
``full`` layers under a lightning indexer beside ``sliding`` layers with a
head count, latent ranks, head sizes and a rotation of their own that attend
the last ``sliding_window`` positions, their rows in a second, wider latent
pool under the window kind's block table -- a gate a head on every layer's
attention and a constant rescale on the two normed latents, held to the
benchmark's plain reference
(``benchmark/harness/reference_mla_window_sparse_moe.py``, which shares no
code with the program) on ``dots3-note-tiny`` (window 9, ``index_topk`` 8:
contexts of 10 and more tokens slide and select).

Tolerances: float32 activations over the same weights differ from the
reference by float32 rounding over nine layers (1e-4 asserted; the least of
the planted faults is off by 0.2, the others by 1 and more)."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:      # as benchmark/tests/conftest.py does
    sys.path.insert(0, str(BENCH))

from harness import reference_mla_window_sparse_moe as reference  # noqa: E402

from distributed_gpu_inference_tpu.models import llama, mla  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    get_model_config,
)
from distributed_gpu_inference_tpu.models.loader import (  # noqa: E402
    init_quantized_streamed,
)
from distributed_gpu_inference_tpu.ops import (  # noqa: E402
    mla_attention_pallas as mla_k,
)
from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (  # noqa: E402
    page_write_plan,
)
from distributed_gpu_inference_tpu.runtime.engine import (  # noqa: E402
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (  # noqa: E402
    InferenceRequest,
    SamplingParams,
)

MODEL = "dots3-note-tiny"
SHARE = (2, 4)          # the held subset: experts 2..5 of 8
TOL = 1e-4
MARGIN = 1e-3
BLOCK = 4


def published(mc):
    """The configuration as the benchmark's file states it."""
    first, count = mc.held_experts or (0, mc.num_experts)
    full, sw = mc.latent_kind("full"), mc.latent_kind("sliding")
    return {
        "hidden_size": mc.hidden_size, "num_attention_heads": full.heads,
        "q_lora_rank": full.q_rank, "kv_lora_rank": full.kv_rank,
        "qk_nope_head_dim": full.nope, "qk_rope_head_dim": full.rope,
        "v_head_dim": full.v, "rope_theta": full.theta,
        "swa_num_attention_heads": sw.heads, "swa_q_lora_rank": sw.q_rank,
        "swa_kv_lora_rank": sw.kv_rank, "swa_qk_nope_head_dim": sw.nope,
        "swa_qk_rope_head_dim": sw.rope, "swa_v_head_dim": sw.v,
        "swa_rope_theta": sw.theta, "sliding_window_size": mc.sliding_window,
        "layer_types": [f"{k}_attention" for k in mc.layer_types],
        "apply_mla_qkv_lora_rescale": mc.mla_lora_rescale,
        "attention_gate_type": "headwise" if mc.head_gate else None,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "num_hidden_layers": mc.num_layers,
        "first_k_dense_replace": mc.first_k_dense,
        "vocab_size": mc.vocab_size, "n_routed_experts": count,
        "expert_share": {"first": first, "count": count,
                         "of": mc.num_experts},
        "n_shared_experts": mc.n_shared_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "rms_norm_eps": mc.rms_norm_eps,
        "index_n_heads": mc.index_num_heads,
        "index_head_dim": mc.index_head_dim, "index_topk": mc.index_topk,
    }


def _f32(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)


@pytest.fixture(scope="module")
def tiny():
    """The tiny model holding a SHARE of its experts, int8 as served."""
    mc = get_model_config(MODEL, held_experts=SHARE)
    params = init_quantized_streamed(mc, "int8", seed=0)
    return mc, params, reference.SeedStream(published(mc), 0)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed + n)
    return [int(t) for t in rng.integers(4, 260, n)]


def _tables(rows, pages):
    """The two block tables side by side, the full kind's first."""
    one = 1 + np.arange(rows * pages).reshape(rows, pages)
    return jnp.asarray(np.concatenate([one, one], axis=1), jnp.int32)


def _pools(mc, rows=1, pages=16):
    blocks = 1 + rows * pages
    return llama.init_kv_pools(mc, blocks, BLOCK, jnp.float32,
                               window_blocks=blocks)


def _run(mc, params, piece, start, kv, tables, **kw):
    pos = jnp.arange(start, start + len(piece))[None]
    return llama.forward_chunk(
        mc, params, jnp.asarray([piece]), pos, kv, tables,
        jnp.asarray([start + len(piece)]), block_size=BLOCK, **kw)


# --------------------------------------------------------------------- #
# the configuration
# --------------------------------------------------------------------- #

def test_registry_and_the_cut():
    mc = get_model_config("dots3-note-prev-ep8-9l")
    assert mc.latent_kv and mc.mixed_attention and mc.head_gate
    assert mc.held_experts == (0, 32) and mc.num_experts == 256
    assert mc.layer_types == ("full", "full") + ("sliding",) * 3 \
        + ("full",) + ("sliding",) * 3
    # the full layers hold the indexer, a sliding layer none
    assert mc.index_kinds == tuple(
        "full" if k == "full" else "none" for k in mc.layer_types)
    assert (mc.num_cache_layers, mc.num_window_layers,
            mc.num_index_layers) == (3, 6, 3)
    full, sw = mc.latent_kind("full"), mc.latent_kind("sliding")
    assert (full.heads, full.kv_rank, full.qk, full.window) == \
        (128, 512, 192, None)
    assert (sw.heads, sw.kv_rank, sw.qk, sw.v, sw.window) == \
        (64, 1024, 256, 128, 513)
    # a layer's own ranks: full 2.236 / 3.162, sliding 2.236 / 2.236
    assert np.allclose([full.q_scale, full.kv_scale, sw.q_scale, sw.kv_scale],
                       [5 ** 0.5, 10 ** 0.5, 5 ** 0.5, 5 ** 0.5])
    assert mla.layer_groups(mc) == (
        ("ix_dense_layers", 1), ("ix_layers", 2), ("sw_layers", 6))
    # the dense full layer, then ONE traced period F,S,S,S twice
    assert mla.layer_units(mc) == (
        (1, (("ix_dense_layers", 1),)),
        (2, (("ix_layers", 1), ("sw_layers", 3))))
    # 7.6 GB of layers at a byte a weight (W_UK / W_UV, router, gates two)
    assert 7.5e9 < mc.num_params < 7.8e9
    # a pool a kind: 576 values in 640 lanes, 1,088 in 1,152
    pools = jax.eval_shape(
        lambda: llama.init_kv_pools(mc, 5, 16, window_blocks=3))
    assert pools[mla.POOL].shape == (3, 5, 16, 640)
    assert pools[mla.POOL_WIN].shape == (6, 3, 16, 1152)
    assert pools[mla.INDEX_KEYS].shape == (3, 5, 16, 128)
    specs = mla.leaf_specs(mc, "sw_layers")
    assert specs["w_uk"][0] == (64, 1024, 192)
    assert specs["w_hgate"][0] == (5120, 64) and "wqi" not in specs
    assert mla.leaf_specs(mc, "ix_layers")["w_hgate"][0] == (5120, 128)
    assert mla.leaf_specs(mc, "ix_layers")["wqi"][0] == (1024, 64 * 128)


@pytest.mark.parametrize("model,fields,match", [
    ("openpangu-ultra-moe-tiny", dict(layer_types=("full",) * 3),
     "one kind over latent"),
    (MODEL, dict(index_types=("full",) * 9), "beside layer_types"),
    (MODEL, dict(sandwich_norm=True), "beside layer_types over latent"),
    (MODEL, dict(mla_use_nope=True), "beside layer_types over latent"),
    (MODEL, dict(partial_rotary_factor=0.5), "K/V recipe"),
    (MODEL, dict(sliding_q_lora_rank=0, q_lora_rank=0,
                 index_query_input="hidden", mla_lora_rescale=False,
                 sliding_kv_lora_rank=16), None),
    (MODEL, dict(q_lora_rank=0, index_query_input="hidden",
                 mla_lora_rescale=False), "one attention kind and not"),
    (MODEL, dict(sliding_window=None), "needs sliding_window"),
    ("glm-5.2-tiny", dict(sliding_kv_lora_rank=64), "two kinds"),
    ("glm-5.2-tiny", dict(mla_lora_rescale=True, q_lora_rank=0,
                          index_query_input="hidden"), "needs kv_lora_rank"),
    ("llama3-tiny", dict(mla_lora_rescale=True), "needs kv_lora_rank"),
    ("laguna-tiny", dict(sliding_v_head_dim=8), "two kinds"),
    ("laguna-tiny", dict(index_topk=8, index_num_heads=2, index_head_dim=16),
     "an indexer with sliding_window is not built"),
    ("laguna-tiny", dict(sliding_num_heads=3), "K/V heads that differ"),
], ids=["one-kind", "index-share", "sandwich", "nope", "partial-rotary",
        "no-q-rank-both", "q-rank-one-kind", "no-window", "swa-one-kind",
        "rescale-no-q", "rescale-kv", "swa-kv-recipe", "kv-indexer-mixed",
        "kv-heads-by-kind"])
def test_a_combination_that_is_not_built_is_refused(model, fields, match):
    if match is None:       # built: no query low-rank on either kind
        assert get_model_config(model, **fields).latent_kind("sliding") \
            .q_rank == 0
        return
    with pytest.raises(ValueError, match=match):
        get_model_config(model, **fields)


def test_seed_stream_is_the_programs_init_bit_for_bit(tiny):
    mc, params, weights = tiny
    tree = reference.FromTree(params, reference.dims(published(mc)))
    for layer in range(mc.num_layers):
        a, b = weights.layer(layer), tree.layer(layer)
        assert set(a) == set(b), layer
        for name in a:
            assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), \
                (layer, name)
    assert np.array_equal(np.asarray(weights.head()), np.asarray(tree.head()))
    assert "wqi" in params["ix_layers"] and "wqi" not in params["sw_layers"]
    assert params["sw_layers"]["w_hgate"].shape == (6, 64, 2)


# --------------------------------------------------------------------- #
# forward_chunk against the reference
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("n", [5, 24, 45])
def test_forward_chunk_matches_the_reference_in_float32(tiny, n):
    mc, params, ours = tiny
    prompt = _prompt(n)
    (want,), (routes,) = reference.forward(published(mc), ours, [prompt])
    out = _run(mc, _f32(params), prompt, 0, _pools(mc), _tables(1, 16),
               collect_routing=True)
    assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() < TOL
    assert np.array_equal(np.sort(np.asarray(out.routing), -1),
                          np.sort(routes, -1))


def test_the_sharp_draw_is_the_same_program_in_float32():
    """``assumed.weights_scale``: the weights that read a rescaled latent
    drawn at their fan-in's deviation instead (the first chip run's draw:
    attention scores ``a_q x a_kv`` larger, 3 of 10 probes outside the
    reference's top five in bfloat16) are served to the same tolerance in
    float32: what the draw moves is how far bfloat16 rounding carries, not
    what the program computes."""
    mc = get_model_config(MODEL, held_experts=SHARE)
    sharp = dataclasses.replace(mc, mla_lora_rescale=False)     # the draw's
    params = init_quantized_streamed(sharp, "int8", seed=0)
    ours = reference.SeedStream(published(sharp), 0)
    mild = reference.SeedStream(published(mc), 0).layer(1)  # a full layer
    assert float(jnp.std(ours.layer(1)["w_uk"]) / jnp.std(mild["w_uk"])) \
        == pytest.approx((mc.hidden_size / mc.kv_lora_rank) ** 0.5, rel=0.02)
    for n in (13, 45):
        prompt = _prompt(n)
        (want,), _ = reference.forward(published(mc), ours, [prompt])
        out = _run(mc, _f32(params), prompt, 0, _pools(mc), _tables(1, 16))
        assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() < TOL


@pytest.mark.parametrize("variant", reference.VARIANTS)
def test_a_block_that_departs_from_the_description_fails(tiny, variant):
    """Each planted fault of the chip's comparison moves the reference's
    logits off the served ones by a thousand times the tolerance and more
    (the least, the sliding layers rotated at the full kind's theta, by
    0.2 at 45 tokens; the margin is asserted at 500 x)."""
    mc, params, ours = tiny
    prompt = _prompt(45)
    (want,), _ = reference.forward(published(mc), ours, [prompt],
                                   variant=variant)
    got = _run(mc, _f32(params), prompt, 0, _pools(mc), _tables(1, 16))
    assert np.abs(np.asarray(got.logits[0, 0]) - want[0]).max() > 500 * TOL


@pytest.mark.parametrize("broken", [
    lambda mc: dataclasses.replace(mc, mla_lora_rescale=False),
    lambda mc: dataclasses.replace(mc, head_gate=False),
    lambda mc: dataclasses.replace(mc, sliding_window=8),
    lambda mc: dataclasses.replace(mc, index_topk=2 ** 20),
    lambda mc: dataclasses.replace(mc, sliding_rope_theta=mc.rope_theta),
    lambda mc: dataclasses.replace(mc, rope_interleave=False),
], ids=["no-rescale", "no-gate", "window-short", "dense-full-layers",
        "sliding-theta", "rope-halves"])
def test_a_served_model_that_departs_from_the_description_fails(tiny, broken):
    """The faults as ``compare_logits_mla_window.py`` plants them: in the
    served model's configuration, against the one true reference."""
    mc, params, ours = tiny
    prompt = _prompt(45)
    (want,), _ = reference.forward(published(mc), ours, [prompt])
    got = _run(broken(mc), _f32(params), prompt, 0, _pools(mc),
               _tables(1, 16))
    assert np.abs(np.asarray(got.logits[0, 0]) - want[0]).max() > 500 * TOL


def test_pieces_then_decode_through_both_latent_pools(tiny):
    """Positions 0-16 and 17-29 as two chunks (the expanded form), then 6
    single-token steps (the absorbed form), the window kind's blocks before
    the window taken away as the row advances (table entries 0): each
    against the reference's full forward pass over the same tokens, past
    the window (9) and past ``index_topk`` (8) from the first piece on."""
    mc, params, ours = tiny
    f32 = _f32(params)
    tables, tokens = np.array(_tables(1, 16)), _prompt(30)

    def run(piece, start, kv):
        dead = max(start - mc.sliding_window + 1, 0) // BLOCK
        tables[0, 16:16 + dead] = 0
        return _run(mc, f32, piece, start, kv, jnp.asarray(tables))

    out = run(tokens[:17], 0, _pools(mc))
    out = run(tokens[17:], 17, out.kv)
    for _ in range(6):
        (want,), _ = reference.forward(published(mc), ours, [tokens])
        assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() < TOL
        tokens = tokens + [int(want[0].argmax())]
        out = run(tokens[-1:], len(tokens) - 1, out.kv)
        # a step reports what its three full layers' selections fetched
        assert int(out.index_fetched) > 0
    assert (tables[0, 16:] == 0).sum() == (35 - 9 + 1) // BLOCK


def test_a_packed_round_of_a_piece_beside_a_decode_row(tiny):
    """The plain ragged round's form: a 24-token piece of one row and one
    decode token of another packed on one axis, both kinds' pools."""
    mc, params, ours = tiny
    f32 = _f32(params)
    long, short = _prompt(56), _prompt(20, seed=2)
    tables = _tables(2, 16)
    toks = np.zeros((2, 32), np.int32)
    pos = -np.ones((2, 32), np.int32)
    toks[0], pos[0] = long[:32], np.arange(32)
    toks[1, :19], pos[1, :19] = short[:19], np.arange(19)
    kv = llama.forward_chunk(
        mc, f32, jnp.asarray(toks), jnp.asarray(pos), _pools(mc, 2), tables,
        jnp.asarray([32, 19]), block_size=BLOCK, with_logits=False).kv
    live = [(0, j, long[32 + j], 32 + j) for j in range(24)] + \
        [(1, 0, short[19], 19)]
    tp = 32
    row = np.full((tp,), 2, np.int32)
    col = np.zeros((tp,), np.int32)
    tok = np.zeros((tp,), np.int32)
    where = -np.ones((tp,), np.int32)
    for n, (r, c, t, p) in enumerate(live):
        row[n], col[n], tok[n], where[n] = r, c, t, p
    out = llama.forward_chunk(
        mc, f32, jnp.asarray(tok), jnp.asarray(where), kv, tables,
        jnp.asarray([56, 20]), block_size=BLOCK,
        packing=llama.Packing(jnp.asarray(row), jnp.asarray(col),
                              jnp.asarray([23, 24], jnp.int32), 24))
    want = reference.last_logits(published(mc), ours, [long, short])
    for i in range(2):
        assert np.abs(np.asarray(out.logits[i, 0]) - want[i]).max() < TOL


def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    """Guide section 4: the parts of a routed layer's result that the
    chips' shares give (here four shares of two experts; eight of 32 at the
    published size), with the shared expert counted once, add up to what
    the uncut reference layer gives; and the program's expert layer over
    the held share is that share's part."""
    mc, params, ours = tiny
    cfg = published(mc)
    s = reference.dims(cfg)
    layer = 5                               # the second full expert layer
    w = ours.layer(layer)
    x = jax.random.normal(jax.random.PRNGKey(5), (32, mc.hidden_size))
    per = 2
    with jax.default_matmul_precision("highest"):
        parts = [reference.SeedStream(
            dict(cfg, n_routed_experts=per, expert_share={
                "first": first, "count": per, "of": mc.num_experts}),
            0).layer(layer) for first in range(0, mc.num_experts, per)]
        whole_w = dict(w, **{name: jnp.concatenate([p[name] for p in parts])
                             for name in ("we_gate", "we_up", "we_down")})
        whole, _ = reference.expert_layer(s, whole_w, x, 0, mc.num_experts)
        total = jnp.zeros_like(x)
        for n, p in enumerate(parts):
            out, _ = reference.expert_layer(
                s, dict(w, **{k: p[k] for k in
                              ("we_gate", "we_up", "we_down")}),
                x, per * n, per, shared=n == 0)
            total = total + out
        want, _ = reference.expert_layer(s, w, x)
    assert np.abs(np.asarray(total - whole)).max() < 1e-4
    lp = jax.tree.map(lambda a: a[1], _f32(params)["ix_layers"])

    def proj(x_, name):
        return llama.qmm(x_, lp[name], False)

    got, stats, _ = llama.expert_layer(
        x[None], lp, mc, proj, live=None, stacked=None, layer_idx=0)
    assert np.abs(np.asarray(got[0] - want)).max() < TOL
    assert int(stats["pairs_routed"]) == 3 * 32
    assert 0 < int(stats["assignments"]) < int(stats["pairs_routed"])


# --------------------------------------------------------------------- #
# the kernel forms, interpreted (tests/test_tpu_lowering.py compiles them)
# --------------------------------------------------------------------- #

def _windowed_case(shape, nh, rkv, dn, dr, dv, window, bk, m, rows, seed=0):
    """A pool of the window kind's width, queries of ``rows`` = ((cached
    before, queries), ...) and the XLA form's answer lifted through W_UV."""
    mc = get_model_config(
        MODEL, dtype="float32", sliding_num_heads=nh,
        sliding_kv_lora_rank=rkv, sliding_qk_nope_head_dim=dn,
        sliding_qk_rope_head_dim=dr, sliding_v_head_dim=dv,
        sliding_window=window)
    kind = mc.latent_kind("sliding")
    w = mla.pool_width(mc, "sliding")
    rng = np.random.default_rng(seed)
    b, s = len(rows), max(n for _, n in rows)
    pool = jnp.asarray(rng.normal(size=(2, 1 + b * m, bk, w)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(b * m).reshape(b, m), jnp.int32)
    pos = np.full((b, s), -1, np.int32)
    for r, (start, n) in enumerate(rows):
        pos[r, :n] = start + np.arange(n)
    lens = jnp.asarray([start + n for start, n in rows], jnp.int32)
    q_n = jnp.asarray(rng.normal(size=(b, s, nh, dn)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(b, s, nh, dr)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(size=(nh, rkv, dn)), jnp.float32) / 8
    w_uv = jnp.asarray(rng.normal(size=(nh, rkv, dv)), jnp.float32) / 8
    want = mla.latent_attention_xla(
        mc, q_n, q_r, w_uk, w_uv, pool[1][tables].reshape(b, -1, w),
        jnp.asarray(pos), lens, "expanded", kind=kind)
    q_cat = jnp.concatenate([
        jnp.einsum("bshd,hcd->bshc", q_n, w_uk), q_r,
        jnp.zeros((b, s, nh, w - rkv - dr), jnp.float32)], axis=-1)
    return kind, pool, tables, pos, lens, q_cat, w_uv, want


_WINDOW_CASES = {
    # the published widths: latent 1,024 in a 1,152-lane row, 64 heads; a
    # step whose window starts inside the second of three page groups
    "published-step": dict(nh=64, rkv=1024, dn=192, dr=64, dv=128,
                           window=513, bk=16, m=80, group=512,
                           rows=((1100, 1), (300, 1), (0, 0), (512, 1))),
    # small widths, groups of 8 tokens, a window of 9: rows whose window
    # starts in a later group than their neighbour's, an idle row, a row
    # younger than the window
    "step": dict(nh=2, rkv=128, dn=24, dr=8, dv=16, window=9, bk=4, m=14,
                 group=8, rows=((36, 1), (7, 1), (0, 0), (55, 1))),
    "chunk": dict(nh=2, rkv=128, dn=24, dr=8, dv=16, window=9, bk=4, m=14,
                  group=8, rows=((20, 12), (3, 5), (0, 0), (44, 12))),
    "packed": dict(nh=2, rkv=128, dn=24, dr=8, dv=16, window=9, bk=4, m=14,
                   group=8, rows=((36, 1), (9, 11), (0, 0), (44, 12))),
}


@pytest.mark.parametrize("shape", sorted(_WINDOW_CASES))
def test_the_windowed_kernel_is_the_xla_form(monkeypatch, shape):
    """``dgi_mla_window_decode`` / ``dgi_mla_window_ragged`` in interpret
    mode against ``latent_attention_xla`` (expanded, windowed) over the
    same pool: the walk starts at the group that holds the tile's first
    visible key, positions at or under ``p - window`` are masked. Every
    page the walk may not read is NaN."""
    case = dict(_WINDOW_CASES[shape])
    group, rows, bk, m = (case.pop(k) for k in ("group", "rows", "bk", "m"))
    monkeypatch.setattr(mla_k, "_GROUP_TOKENS", group)
    mla_k.latent_paged_attention.clear_cache()
    kind, pool, tables, pos, lens, q_cat, w_uv, want = _windowed_case(
        shape, bk=bk, m=m, rows=rows, **case)
    b, s = pos.shape
    # pages wholly before every group a row's walk reads: poisoned
    poisoned = np.array(pool)
    for r, (start, n) in enumerate(rows):
        if not n:
            continue
        first = max(start - kind.window + 1, 0) // group * group // bk
        for page in np.asarray(tables[r, :first]):
            poisoned[1, page] = np.nan
    pool = jnp.asarray(poisoned)
    common = dict(scale=kind.qk ** -0.5, latent=kind.kv_rank,
                  interpret=True, window=kind.window)
    if shape == "packed":
        at = [(r, c) for r in range(b) for c in range(s) if pos[r, c] >= 0]
        tp = 32
        row = np.full((tp,), b, np.int32)
        col, ppos = np.zeros((tp,), np.int32), np.full((tp,), -1, np.int32)
        for i, (r, c) in enumerate(at):
            row[i], col[i], ppos[i] = r, c, pos[r, c]
        q_packed = jnp.zeros((tp, *q_cat.shape[2:]), jnp.float32) \
            .at[:len(at)].set(q_cat[tuple(np.asarray(at).T)])
        tiles = mla_k.packed_tiles(jnp.asarray(row), jnp.asarray(col),
                                   jnp.asarray(ppos), b, s, kind.heads)
        u_packed = mla_k.latent_paged_attention_packed(
            q_packed, tiles, pool, jnp.int32(1), tables, lens, bk, **common)
        assert np.all(np.asarray(u_packed[len(at):]) == 0)
        u = jnp.zeros((b, s, kind.heads, kind.kv_rank), jnp.float32).at[
            tuple(np.asarray(at).T)].set(u_packed[:len(at)])
    else:
        u = mla_k.latent_paged_attention(
            q_cat, pool, jnp.int32(1), tables, jnp.asarray(pos), lens, bk,
            decode=s == 1, **common)
    mla_k.latent_paged_attention.clear_cache()
    got = jnp.einsum("bshc,hcd->bshd", u, w_uv)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-4


def test_a_windowed_call_takes_no_selection():
    q = jnp.zeros((1, 1, 2, 256))
    pool = jnp.zeros((1, 3, 4, 256))
    with pytest.raises(ValueError, match="attends no selection"):
        mla_k.latent_paged_attention(
            q, pool, jnp.int32(0), jnp.ones((1, 2), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), jnp.ones((1,), jnp.int32), 4,
            scale=1.0, latent=128, interpret=True, window=3,
            keep=jnp.ones((1, 1, 8)))


def test_the_window_pools_page_write_is_a_scatter():
    """``dgi_mla_write`` over the window kind's pool (a wider row, fewer
    blocks, its own plan) leaves what a scatter of the rows leaves."""
    rng = np.random.default_rng(3)
    layers, n, bk, w, b, m, s = 2, 9, 4, 256, 2, 4, 6
    pool = jnp.asarray(rng.normal(size=(layers, n, bk, w)), jnp.float32)
    tables = np.asarray([[0, 0, 3, 4], [5, 6, 0, 0]], np.int32)
    pos = np.asarray([[9, 10, 11, 12, 13, -1], [2, 3, 4, 5, -1, -1]],
                     np.int32)
    rows = jnp.asarray(rng.normal(size=(b * s, w)), jnp.float32)
    plan = page_write_plan(jnp.asarray(tables), jnp.asarray(pos), bk,
                           page_bytes=bk * w * 4)
    new = mla_k.write_latent_pages_in_place(rows, pool, jnp.int32(1), plan,
                                            interpret=True)
    want = np.array(pool)
    for r in range(b):
        for c in range(s):
            if pos[r, c] >= 0:
                want[1, tables[r, pos[r, c] // bk], pos[r, c] % bk] = \
                    np.asarray(rows[r * s + c])
    assert np.array_equal(np.asarray(new)[:, 1:], want[:, 1:])


def test_forward_chunk_through_the_kernels_matches_the_xla_path(monkeypatch):
    """The whole model with ``kernels_on`` forced and every Pallas call in
    interpret mode: a rectangle, a packed round (a piece beside a decode
    row), then a step, against the XLA forms on the same pools, both
    kinds' pools written in place."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(mla_k, "_GROUP_TOKENS", 16)
    monkeypatch.setattr(mla_k, "_WALK_GROUP_TOKENS", 32)
    mc = get_model_config(MODEL, dtype="float32", kv_lora_rank=128,
                          sliding_kv_lora_rank=256)
    params = llama.init_params(mc, jax.random.PRNGKey(2), jnp.float32)
    tables = _tables(2, 16)

    def serve(kernels):
        monkeypatch.setattr(mla, "kernels_on", lambda *a, **kw: kernels)
        common = dict(block_size=BLOCK, pallas=False)
        kv = _pools(mc, 2)
        tok = jnp.asarray([_prompt(30), _prompt(21)[:21] + [0] * 9])
        pos = jnp.stack([jnp.arange(30), jnp.where(
            jnp.arange(30) < 21, jnp.arange(30), -1)]).astype(jnp.int32)
        out = llama.forward_chunk(mc, params, tok, pos, kv, tables,
                                  jnp.asarray([30, 21]), **common)
        # a packed round: row 0 sends a piece of 7, row 1 a decode token
        row = jnp.asarray([0] * 7 + [1] + [2] * 8, jnp.int32)
        col = jnp.asarray(list(range(7)) + [0] + [0] * 8, jnp.int32)
        ppos = jnp.asarray(list(range(30, 37)) + [21] + [-1] * 8, jnp.int32)
        ptok = jnp.asarray(_prompt(7, 5) + [9] + [0] * 8, jnp.int32)
        rnd = llama.forward_chunk(
            mc, params, ptok, ppos, out.kv, tables, jnp.asarray([37, 22]),
            packing=llama.Packing(row, col, jnp.asarray([6, 7]), 8),
            **common)
        step = llama.forward_chunk(
            mc, params, jnp.asarray([[3], [4]]), jnp.asarray([[37], [22]]),
            rnd.kv, tables, jnp.asarray([38, 23]), **common)
        return out, rnd, step

    want, got = serve(False), serve(True)
    for a, b in zip(want, got):
        assert np.abs(np.asarray(a.logits - b.logits)).max() < 2e-4
    for name in (mla.POOL, mla.POOL_WIN, mla.INDEX_KEYS):
        # block 0 is the pad block: whatever lands there is never read
        assert np.abs(np.asarray(want[2].kv[name] - got[2].kv[name])[:, 1:]
                      ).max() < TOL, name
    assert int(want[2].index_fetched) == int(got[2].index_fetched) > 0


# --------------------------------------------------------------------- #
# through the engine: ragged rounds, scans, the prefix index, refusals
# --------------------------------------------------------------------- #

def _engine(**kw):
    cfg = dict(max_batch_size=3, max_seq_len=256, block_size=BLOCK,
               dtype="float32", quantization="int8",
               prefill_buckets=(16, 32, 64), ragged_chunk=32, multi_step=8)
    cfg.update(kw)
    return TPUEngine(get_model_config(MODEL, held_experts=SHARE),
                     EngineConfig(**cfg), seed=0)


def _req(prompt, new, **kw):
    return InferenceRequest(
        prompt_token_ids=list(prompt), sampling=SamplingParams(
            max_new_tokens=new, temperature=0.0, ignore_eos=True, **kw))


def _admit(eng, prompts, new):
    """Every prompt through ``ragged_round`` to its first token."""
    flying = [eng.submit_chunked_start(_req(p, n))
              for p, n in zip(prompts, new)]
    slots = [a.slot for a in flying]
    first = {a.slot: [] for a in flying}
    while flying:
        for slot, toks in eng.ragged_round(flying).items():
            first[slot] += toks
        flying = [a for a in flying if not a.done]
    return slots, first


def _follows_the_reference(eng, prompt, generated):
    """Every generated token is the reference's argmax over the sequence
    before it, wherever the reference's two best are a margin apart."""
    cfg = published(eng.model_cfg)
    weights = reference.FromTree(eng.params, reference.dims(cfg))
    seq = list(prompt) + list(generated)
    at = list(range(len(prompt) - 1, len(seq) - 1))
    (want,), _ = reference.forward(cfg, weights, [seq[:-1]], at=[at])
    for row, tok in zip(want, generated):
        top2 = np.sort(row)[-2:]
        if top2[1] - top2[0] > MARGIN:
            assert tok == int(row.argmax())


def test_engine_rounds_and_scans_follow_the_reference_and_count():
    eng = _engine()
    assert eng.stats["kv_layout"] == "latent+index+window"
    assert eng.stats["ragged_kv_path"] == "in_place"
    assert set(eng.kv) == {mla.POOL, mla.POOL_WIN, mla.INDEX_KEYS}
    # eight windows of three blocks a slot, and the pad block
    assert eng.kv[mla.POOL_WIN].shape[:2] == (6, 1 + 3 * 8 * 3)
    assert eng.kv[mla.INDEX_KEYS].shape[0] == eng._scan_keys.shape[0] == 3
    prompts, new = [_prompt(70), _prompt(5, seed=1)], 9
    slots, first = _admit(eng, prompts, [new, new])
    scan = eng.decode_multi(new - 1)
    for prompt, slot in zip(prompts, slots):
        _follows_the_reference(eng, prompt, first[slot] + scan[slot])
    st = eng.get_stats()
    rows = [(len(p) + len(first[s]) - 1, len(scan[s]))
            for p, s in zip(prompts, slots)]     # (cached before, steps)
    assert st["attn_row_steps_scan"] == st["mla_row_steps_scan"] \
        == st["index_row_steps_scan"] == sum(n for _, n in rows) > 8
    assert st["attn_full_context_tokens_scan"] \
        == st["mla_context_tokens_scan"] == sum(
            before + j for before, n in rows for j in range(1, n + 1))
    assert st["attn_window_context_tokens_scan"] == sum(
        min(before + j, 9) for before, n in rows for j in range(1, n + 1))
    assert 0 < st["kv_window_resident_tokens_scan"] \
        <= 6 * BLOCK * st["attn_row_steps_scan"]
    assert st["attn_pairs_ragged_full"] > st["attn_pairs_ragged_window"] > 0
    # three full layers score a pass; no layer borrows a selection
    passes = st["ragged_rounds"] + new - 1
    assert st["index_layers_scored"] == 3 * passes
    assert st["index_layers_shared"] == 0
    assert st["index_fetched_tokens_scan"] > 0
    kv = st["kv_cache"]
    # a live row holds every block in the full kind, its window's in the
    # other: 79 and 14 tokens are 20 + 4 blocks against 3-4 + 3-4
    assert kv["blocks_in_use"] == 24 and 6 <= kv["window_blocks_in_use"] <= 8
    assert kv["window_released_blocks"] >= 16


def test_a_request_on_a_prefix_hit_is_the_same_request_served_cold():
    """The second request shares the first one's 96-token document: its
    full-kind pages and index keys are a prefix hit and the window kind
    still holds the document's last window, so only the question is
    prefilled; the reply is the one a cold engine gives, and the
    reference's."""
    doc, q1, q2 = _prompt(96), _prompt(21, seed=1), _prompt(30, seed=2)
    eng = _engine()
    first = eng.generate([_req(doc + q1, 12)], use_multi_step=True)[0]
    warm = eng.generate([_req(doc + q2, 12)], use_multi_step=True)[0]
    cold = _engine().generate([_req(doc + q2, 12)], use_multi_step=True)[0]
    assert first.cached_tokens == 0 and cold.cached_tokens == 0
    assert warm.cached_tokens == 96
    assert warm.token_ids == cold.token_ids
    _follows_the_reference(eng, doc + q2, warm.token_ids)
    kv = eng.get_stats()["kv_cache"]
    assert kv["prefix_lookups_matched"] == 1
    assert kv["prefix_hits_cut_by_window"] == 0
    assert eng.stats["prefix_hit_tokens_cut_by_window"] == 0


def test_a_hit_whose_window_pages_are_gone_is_cut_back_and_still_right():
    """The window pool is taken back from under a cached document: the
    full kind still matches it, the window kind cannot back it, the hit is
    cut to nothing and counted, and the reply is still the cold one."""
    doc, q1, q2 = _prompt(96), _prompt(21, seed=1), _prompt(30, seed=2)
    eng = _engine()
    eng.generate([_req(doc + q1, 12)], use_multi_step=True)
    win = eng.manager.win
    while win.num_parked:                   # what pressure would do
        win.free_list.append(win.evict_one(eng.manager.stats))
    warm = eng.generate([_req(doc + q2, 12)], use_multi_step=True)[0]
    cold = _engine().generate([_req(doc + q2, 12)], use_multi_step=True)[0]
    assert warm.cached_tokens == 0 and warm.token_ids == cold.token_ids
    kv = eng.get_stats()["kv_cache"]
    assert kv["prefix_hits_cut_by_window"] == 1
    assert kv["prefix_hit_tokens_cut_by_window"] == 96
    assert eng.stats["prefix_hit_tokens_cut_by_window"] == 96


def test_preempt_and_resume_continue_on_a_hit_of_both_kinds():
    prompt = _prompt(70)
    want = _engine().generate([_req(prompt, 20)], use_multi_step=True)[0]
    eng = _engine()
    slots, first = _admit(eng, [prompt], [20])
    got = first[slots[0]] + eng.decode_multi(6)[slots[0]]
    assert got == want.token_ids[:len(got)]
    pre = eng.preempt_slot(slots[0])
    assert eng.manager.win.in_use == 0
    assert eng.manager.get_stats()["blocks_in_use"] == 0
    slot = eng.resume(pre)
    assert eng.manager.stats.prefix_hit_tokens > 0
    assert eng.manager.stats.prefix_hits_cut_by_window == 0
    while eng.slots[slot].finish_reason is None:
        eng.decode_multi(8)
    after = list(eng.slots[slot].generated)
    assert len(after) == 20 and after[:len(got)] == got
    _follows_the_reference(eng, prompt, after)


@pytest.mark.parametrize("kw,match", [
    (dict(kv_cache_dtype="int8"), "activation dtype"),
    (dict(kv_cache_dtype="fp8"), "activation dtype"),
    (dict(spill_host_blocks=8), "spill tiers"),
    (dict(speculative="chain"), "speculative"),
    (dict(mesh=True), "one chip"),
])
def test_what_cannot_carry_latent_pages_of_two_kinds_refuses_the_model(
        kw, match, cpu_devices):
    from distributed_gpu_inference_tpu.runtime.speculative import (
        SpecDecodeConfig,
    )

    mesh = None
    if kw.pop("mesh", False):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(cpu_devices[:2]), ("model",))
    if kw.get("speculative"):
        kw["speculative"] = SpecDecodeConfig(num_draft_tokens=2)
    with pytest.raises(ValueError, match=match):
        TPUEngine(get_model_config(MODEL), EngineConfig(
            max_batch_size=2, max_seq_len=64, block_size=BLOCK,
            dtype="float32", **kw), mesh=mesh)


def test_a_one_byte_window_pool_is_refused_where_the_pools_are_made():
    with pytest.raises(NotImplementedError, match="one-byte latent pools"):
        llama.init_kv_pools(get_model_config(MODEL), 9, BLOCK,
                            jnp.float8_e4m3fn, window_blocks=9)
    with pytest.raises(ValueError, match="number of blocks"):
        llama.init_kv_pools(get_model_config(MODEL), 9, BLOCK)


def test_a_worker_with_a_handoff_role_drops_the_model():
    from distributed_gpu_inference_tpu.runtime import kv_handoff
    from distributed_gpu_inference_tpu.utils.config import WorkerConfig
    from distributed_gpu_inference_tpu.worker.engines import EngineLoadError
    from distributed_gpu_inference_tpu.worker.main import Worker

    cfg = WorkerConfig.model_validate({
        "name": "w", "task_types": ["llm"], "role": "prefill",
        "engines": {"llm": {"model": MODEL, "dtype": "float32", "extra": {
            "max_seq_len": 64, "max_batch_size": 2, "block_size": BLOCK,
            "prefill_buckets": [16, 32]}}},
    })
    with pytest.raises(EngineLoadError):
        Worker(cfg).load_engines()
    with pytest.raises(ValueError, match="latent pages"):
        kv_handoff.require_kv_pages(_engine())


def test_the_window_pool_is_the_engines_eight_windows_a_slot():
    """The sliding kind's pool follows from the slots and the window alone,
    as the K/V recipe's does: no key of a worker's ``extra`` sizes it."""
    eng = _engine()
    assert eng.kv[mla.POOL_WIN].shape[1] == 1 + 3 * 8 * 3
    assert eng.stats["window_pool_blocks"] == eng.manager.win.num_blocks == 73
