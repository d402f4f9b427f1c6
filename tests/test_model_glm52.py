"""GLM-5.2 on the deployed path: latent (MLA) pages under a lightning
indexer whose selection three layers in four borrow from the layer before
them (IndexShare), rope by adjacent pairs, an index-key pool with a layer a
FULL layer, a leading dense layer, sigmoid-routed experts with a selection
bias beside a shared one -- held to the benchmark's plain reference
(``benchmark/harness/reference_mla_sparse_moe.py``, which shares no code
with the program) on ``glm-5.2-tiny`` (``index_topk`` 8: contexts of 9 and
more tokens select).

Tolerances: float32 activations over the same weights differ from the
reference by float32 rounding over nine layers (1e-4 asserted, where a
dropped selection, a stale one, rope by halves or an unnormalised router is
off by 0.01 and more)."""

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

from harness import reference_mla_sparse_moe as reference  # noqa: E402

from distributed_gpu_inference_tpu.models import llama, mla  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    get_model_config,
)
from distributed_gpu_inference_tpu.models.loader import (  # noqa: E402
    init_quantized_streamed,
)
from distributed_gpu_inference_tpu.ops import (  # noqa: E402
    index_select,
    mla_attention_pallas as mla_k,
)
from distributed_gpu_inference_tpu.runtime.engine import (  # noqa: E402
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (  # noqa: E402
    InferenceRequest,
    SamplingParams,
)

MODEL = "glm-5.2-tiny"
SHARE = (2, 4)          # the held subset: experts 2..5 of 8
TOL = 1e-4
MARGIN = 1e-3
BLOCK = 4


def published(mc):
    """The configuration as the benchmark's file states it."""
    first, count = mc.held_experts or (0, mc.num_experts)
    lead = mc.first_k_dense
    return {
        "hidden_size": mc.hidden_size, "num_attention_heads": mc.num_heads,
        "q_lora_rank": mc.q_lora_rank, "kv_lora_rank": mc.kv_lora_rank,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim, "v_head_dim": mc.v_head_dim,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "num_hidden_layers": mc.num_layers,
        "mlp_layer_types": ["dense"] * lead
        + ["sparse"] * (mc.num_layers - lead),
        "indexer_types": list(mc.index_kinds),
        "vocab_size": mc.vocab_size, "n_routed_experts": count,
        "expert_share": {"first": first, "count": count,
                         "of": mc.num_experts},
        "n_shared_experts": mc.n_shared_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "rope_parameters": {"rope_theta": mc.rope_theta},
        "rms_norm_eps": mc.rms_norm_eps,
        "index_n_heads": mc.index_num_heads,
        "index_head_dim": mc.index_head_dim, "index_topk": mc.index_topk,
        "rope_interleave": mc.rope_interleave,
        "indexer_rope_interleave": mc.rope_interleave,
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
    return jnp.asarray(1 + np.arange(rows * pages).reshape(rows, pages),
                       jnp.int32)


def _pools(mc, rows=1, pages=16):
    return llama.init_kv_pools(mc, 1 + rows * pages, BLOCK, jnp.float32)


def _run(mc, params, piece, start, kv, tables, **kw):
    pos = jnp.arange(start, start + len(piece))[None]
    return llama.forward_chunk(
        mc, params, jnp.asarray([piece]), pos, kv, tables,
        jnp.asarray([start + len(piece)]), block_size=BLOCK, **kw)


def test_registry_and_the_cut():
    mc = get_model_config("glm-5.2-ep16-9l")
    # head_dim carries the published 192 (qk_nope_head_dim): no layer reads it
    assert mc.latent_kv and mc.head_dim == 192 and mc.qk_head_dim == 256
    assert mc.held_experts == (0, 16) and mc.num_experts == 256
    assert mc.index_kinds == ("full",) + ("shared",) * 3 + ("full",) \
        + ("shared",) * 3 + ("full",)
    assert mc.num_index_layers == 3
    assert mla.layer_groups(mc) == (
        ("ix_dense_layers", 1), ("ix_layers", 2), ("layers", 6))
    # the dense full layer, then ONE traced period (shared x 3, full) twice
    assert mla.layer_units(mc) == (
        (1, (("ix_dense_layers", 1),)),
        (2, (("layers", 3), ("ix_layers", 1))))
    # 7.03 GB of layers at a byte a weight (W_UK / W_UV, router two)
    assert 7.0e9 < mc.num_params < 7.3e9
    # 576 values a token a layer, and a 128-value index key a FULL layer
    assert mc.kv_bytes_per_token() == (9 * 576 + 3 * 128) * 2
    pools = jax.eval_shape(lambda: llama.init_kv_pools(mc, 5, 16))
    assert pools[mla.POOL].shape == (9, 5, 16, 640)
    assert pools[mla.INDEX_KEYS].shape == (3, 5, 16, 128)
    tiny_mc = get_model_config(MODEL)
    assert mla.layer_units(tiny_mc)[1][0] == 2


@pytest.mark.parametrize("model,fields,match", [
    (MODEL, dict(head_dim=32), "no layer reads it"),
    (MODEL, dict(index_types=("shared",) + ("full",) * 8), "first of them"),
    (MODEL, dict(index_types=("full", "shared")), "each of the 9 layers"),
    (MODEL, dict(index_query_input="q_latent", q_lora_rank=0), "q_lora_rank"),
    (MODEL, dict(index_rope_dims=6, index_head_dim=4), "even part"),
    (MODEL, dict(index_topk=0, index_num_heads=0, index_head_dim=0),
     "without index_topk"),
    (MODEL, dict(qk_norm_per_head=True), "latent pages"),
    ("kimi-linear-tiny", dict(index_topk=8, index_num_heads=2,
                              index_head_dim=16), "hybrid"),
    ("llama3-tiny", dict(rope_interleave=True),
     "only the latent-attention model"),
    ("keye-vl-tiny", dict(index_types=("full", "shared", "shared")),
     "only the latent-attention model"),
], ids=["head-dim", "shared-first", "short-list", "no-q-latent", "odd-rope",
        "no-indexer", "kv-norm", "hybrid", "kv-interleave", "kv-share"])
def test_a_field_no_code_would_read_is_refused(model, fields, match):
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
    assert "wqi" in params["ix_layers"] and "wqi" not in params["layers"]


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


@pytest.mark.parametrize("broken", [
    lambda mc: dataclasses.replace(mc, index_topk=2 ** 20),
    lambda mc: dataclasses.replace(mc, index_topk=4),
    lambda mc: dataclasses.replace(mc, rope_interleave=False),
    lambda mc: dataclasses.replace(mc, index_rope_dims=16),
    lambda mc: dataclasses.replace(mc, norm_topk_prob=False),
    lambda mc: dataclasses.replace(mc, router_selection_bias=False),
], ids=["dense", "topk-half", "rope-halves", "index-rope-all",
        "unnormalised", "no-bias"])
def test_a_block_that_departs_from_the_description_fails(tiny, broken):
    mc, params, ours = tiny
    prompt = _prompt(45)
    (want,), _ = reference.forward(published(mc), ours, [prompt])
    got = _run(broken(mc), _f32(params), prompt, 0, _pools(mc),
               _tables(1, 16))
    assert np.abs(np.asarray(got.logits[0, 0]) - want[0]).max() > 100 * TOL


@pytest.mark.parametrize("variant", ["shared_dense", "shared_stale",
                                     "no_index_rope", "rope_halves"])
def test_the_references_planted_faults_move_the_logits(tiny, variant):
    """What ``compare_logits_mla_sparse.py`` plants on the served side has
    a twin in the reference; each departs from the description far past
    the tolerance (so the served path, which agrees with the description,
    does none of them)."""
    mc, _, ours = tiny
    prompt = _prompt(45)
    (want,), _ = reference.forward(published(mc), ours, [prompt])
    (got,), _ = reference.forward(published(mc), ours, [prompt],
                                  variant=variant)
    assert np.abs(got[0] - want[0]).max() > 100 * TOL


def test_pieces_then_decode_through_the_two_pools(tiny):
    """Positions 0-16 and 17-29 as two chunks (the expanded form under
    ``keep``), then 5 single-token steps (the absorbed form), each against
    the reference's full forward pass over the same tokens."""
    mc, params, ours = tiny
    f32 = _f32(params)
    tables, tokens = _tables(1, 16), _prompt(30)
    out = _run(mc, f32, tokens[:17], 0, _pools(mc), tables)
    out = _run(mc, f32, tokens[17:], 17, out.kv, tables)
    for _ in range(5):
        (want,), _ = reference.forward(published(mc), ours, [tokens])
        assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() < TOL
        tokens = tokens + [int(want[0].argmax())]
        out = _run(mc, f32, tokens[-1:], len(tokens) - 1, out.kv, tables)
        # a step reports what its nine layers' selections fetched
        assert int(out.index_fetched) > 0


def test_a_scan_that_carries_its_keys_selects_what_a_gather_a_step_selects(
        tiny):
    """``decode_multi``'s scan keys have a layer a FULL layer; a step that
    scores from them gives what a step that gathers from the pool gives."""
    mc, params, _ = tiny
    f32 = _f32(params)
    tables, tokens = _tables(1, 16), _prompt(30)
    out = _run(mc, f32, tokens, 0, _pools(mc), tables)
    shape = index_select.scan_keys_shape(
        out.kv[mla.INDEX_KEYS].shape, 1, 16, mc.index_topk)
    assert shape[0] == mc.num_index_layers == 3
    kv = llama.scan_index_keys(
        mc, {**out.kv, llama.INDEX_SCAN_KEYS: jnp.zeros(shape, jnp.float32)},
        tables, jnp.asarray([30]), jnp.asarray([True]), 2)
    a = _run(mc, f32, [7], 30, kv, tables)
    b = _run(mc, f32, [7], 30, out.kv, tables)
    assert np.array_equal(np.asarray(a.logits), np.asarray(b.logits))
    assert llama.INDEX_SCAN_KEYS in a.kv


def test_a_shared_layer_attends_the_selection_it_is_given():
    """A full layer and a shared one, the full layer's output and both
    MLPs zeroed: the shared layer's input is the embedding whatever the
    full layer's indexer is, so another ``wqi`` moves the result through
    the selection alone; the index-key pool has the full layer's one layer
    and the shared layer reads none of it."""
    mc = dataclasses.replace(
        get_model_config(MODEL, dtype="float32"), num_layers=2,
        first_k_dense=0, index_types=("full", "shared"))
    params = llama.init_params(mc, jax.random.PRNGKey(1), jnp.float32)
    assert set(params) >= {"ix_layers", "layers"}
    for group in ("ix_layers", "layers"):
        for name in ("we_down", "ws_down"):
            params[group][name] = jnp.zeros_like(params[group][name])
    params["ix_layers"]["wo"] = jnp.zeros_like(params["ix_layers"]["wo"])
    prompt, tables = _prompt(40), _tables(1, 16)
    kv = _pools(mc)
    assert kv[mla.INDEX_KEYS].shape[0] == 1 and kv[mla.POOL].shape[0] == 2
    a = _run(mc, params, prompt, 0, kv, tables, with_logits=False)
    other = dict(params, ix_layers=dict(
        params["ix_layers"], wqi=-params["ix_layers"]["wqi"]))
    b = _run(mc, other, prompt, 0, _pools(mc), tables, with_logits=False)
    moved = np.abs(np.asarray(a.hidden - b.hidden))[0]
    # queries under topk keep everything whatever the indexer says
    assert moved[:mc.index_topk].max() == 0 and moved[mc.index_topk:].max() \
        > 1e-3
    # the shared layer wrote its latents and no index key
    assert np.array_equal(np.asarray(a.kv[mla.INDEX_KEYS]),
                          np.asarray(b.kv[mla.INDEX_KEYS]))
    dense = dataclasses.replace(mc, index_topk=2 ** 20)
    c = _run(dense, params, prompt, 0, _pools(mc), tables, with_logits=False)
    assert np.abs(np.asarray(a.hidden - c.hidden))[0, mc.index_topk:].max() \
        > 1e-3


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Two chips of four experts each: their routed parts and the shared
    expert, counted once, against the reference's uncut layer (the
    selection bias picks, the scores weigh)."""
    mc = get_model_config(MODEL, dtype="float32", held_experts=None)
    params = llama.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    m = jax.random.normal(jax.random.PRNGKey(4), (1, 11, mc.hidden_size),
                          jnp.float32)
    s = reference.dims(published(mc))
    uncut, _ = reference.expert_layer(
        s, reference.FromTree(params, s).layer(1), m[0])

    def proj(x, name):
        return x @ lp[name]

    total = 0
    for first in (0, 4):
        share = dataclasses.replace(mc, held_experts=(first, 4))
        held = dict(lp, **{k: lp[k][first:first + 4]
                           for k in ("we_gate", "we_up", "we_down")})
        out, stats, topi = mla._experts(
            m, held, share, proj, live=None, stacked=None, layer_idx=0)
        assert int(stats["pairs_routed"]) == 11 * mc.num_experts_per_tok
        on_share = (np.asarray(topi) >= first) & (np.asarray(topi) < first + 4)
        assert int(stats["assignments"]) == on_share.sum()
        total = total + out[0]
    shared = proj(jax.nn.silu(proj(m[0], "ws_gate")) * proj(m[0], "ws_up"),
                  "ws_down")
    assert np.abs(np.asarray(total - shared - uncut)).max() < 1e-4
    assert np.abs(np.asarray(uncut)).max() > 0.1


# --------------------------------------------------------------------- #
# the kernel forms, in interpret mode, against the XLA forms
# --------------------------------------------------------------------- #

def _kernel_case(seed, b, pages, lens, s=1):
    """Random absorbed queries, a latent pool and a random selection."""
    mc = get_model_config(MODEL)
    nh, rkv, dr = mc.num_heads, 128, mc.qk_rope_head_dim
    w = 256
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(2, 1 + b * pages, BLOCK, w)),
                       jnp.float32).at[..., rkv + dr:].set(0)
    q = jnp.asarray(rng.normal(size=(b, s, nh, w)), jnp.float32) \
        .at[..., rkv + dr:].set(0)
    tables = _tables(b, pages)
    lens = jnp.asarray(lens, jnp.int32)
    positions = jnp.where(
        lens[:, None] > 0,
        lens[:, None] - s + jnp.arange(s, dtype=jnp.int32)[None], -1)
    j = pages * BLOCK
    keep = jnp.asarray(rng.random((b, s, j)) < 0.3, jnp.float32)
    # a query always attends itself, as a selection over its scores does
    keep = jnp.maximum(keep, (jnp.arange(j)[None, None]
                              == positions[..., None]).astype(jnp.float32))
    return mc, q, pool, tables, positions, lens, keep, rkv, dr


def _xla_absorbed(q, pool, layer, tables, positions, lens, keep, rkv, dr,
                  scale):
    ctx = pool[layer, tables].reshape(tables.shape[0], -1, pool.shape[-1])
    col = jnp.arange(ctx.shape[1])[None, None]
    seen = (col <= positions[..., None]) & (col < lens[:, None, None]) \
        & (keep > 0)
    scores = jnp.einsum("bshw,bjw->bhsj", q, ctx) * scale
    scores = jnp.where(seen[:, None], scores, -1e30)
    p = jnp.where(seen[:, None], jnp.exp(
        scores - scores.max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhsj,bjc->bshc", p, ctx[..., :rkv])


# a scan step's walk over its selected pages, by where the rows' walks end
# against the kernel's groups (16 pages here: two runs of the rolled start
# loop). name: (pages a row holds a kept token in, the rows' lengths)
_W_PAGES, _W_GROUP = 48, 64
WALK_FORMS = {
    "ends_mid_group": ((21, 5, 37), (190, 60, 170)),
    "fills_a_group_exactly": ((16, 32, 16), (100, 192, 65)),
    "spans_several_groups": ((48, 33, 17), (192, 150, 120)),
    "an_idle_row": ((21, 0, 16), (190, 0, 90)),
    "a_row_that_kept_its_own_page_alone": ((1, 0, 40), (3, 0, 180)),
}


def _walk_case(name):
    """Queries, a pool, tables and a selection that holds a token or two
    in the named count of each row's pages (the query's own page among
    them); → the operands, and the pool pages the rows' walks list."""
    kept, lens = WALK_FORMS[name]
    mc, q, pool, tables, positions, lens, _, rkv, dr = _kernel_case(
        54, 3, _W_PAGES, lens)
    rng = np.random.default_rng(7)
    keep = np.zeros((3, 1, _W_PAGES * BLOCK), np.float32)
    walked = set()
    for r, n in enumerate(kept):
        if not n:
            continue
        last = (int(lens[r]) - 1) // BLOCK
        chosen = np.append(rng.choice(last, n - 1, replace=False), last)
        walked.update(np.asarray(tables)[r, chosen].tolist())
        for page in chosen:
            top = min(BLOCK, int(lens[r]) - page * BLOCK)
            keep[r, 0, page * BLOCK + rng.choice(
                top, min(2, top), replace=False)] = 1
        keep[r, 0, int(lens[r]) - 1] = 1     # a query attends itself
    return (q, pool, tables, positions, lens, jnp.asarray(keep), rkv, dr,
            sorted(walked))


@pytest.mark.parametrize("given", ["keep", "walk"])
@pytest.mark.parametrize("name", WALK_FORMS)
def test_the_decode_kernel_walks_the_selected_pages(name, given,
                                                    monkeypatch):
    """``dgi_mla_decode_selected`` in interpret mode, the group narrowed to
    16 pages: rows whose walks end inside a group, fill one exactly, span
    several, and an idle row. A group's pages are started in a rolled loop
    and waited for ONCE a slot; every pool page outside the rows' walks is
    NaN (a page read past a walk's end would reach the output); the result
    is the XLA form's under the same ``keep``, from ``keep`` or from the
    walk a full layer hands the layers that share its selection."""
    monkeypatch.setattr(mla_k, "_WALK_GROUP_TOKENS", _W_GROUP)
    q, pool, tables, positions, lens, keep, rkv, dr, walked = _walk_case(
        name)
    scale = 0.17
    want = _xla_absorbed(q, pool, 1, tables, positions, lens, keep, rkv, dr,
                         scale)
    bad = np.full(pool.shape, np.nan, np.float32)
    bad[:, walked] = np.asarray(pool)[:, walked]
    walk = mla_k.selected_walk(keep, tables, positions[:, 0], lens, BLOCK)
    assert np.asarray(walk.count).tolist() == list(WALK_FORMS[name][0])
    kw = {"keep": keep} if given == "keep" else {"walk": walk}
    # the jitted entry would hand back a trace made at another group width
    got = np.asarray(mla_k.latent_paged_attention.__wrapped__(
        q, jnp.asarray(bad), jnp.int32(1), tables, positions, lens, BLOCK,
        scale=scale, latent=rkv, decode=True, interpret=True, **kw))
    assert np.isfinite(got).all()
    assert np.abs(got - np.asarray(want)).max() < 1e-5
    for r, n in enumerate(WALK_FORMS[name][0]):
        if not n:
            assert np.abs(got[r]).max() == 0


@pytest.mark.parametrize("name", WALK_FORMS)
def test_the_walk_the_kernel_is_handed_ends_in_the_rows_last_fetched_page(
        name, monkeypatch):
    """What the kernel's one wait a slot rests on: the table is whole
    groups of the kernel's OWN width, and past a row's fetched pages it
    holds the last of them again (never a page the selection dropped) with
    ``keep`` zero there, so a group always starts its full count of
    whole-page copies."""
    monkeypatch.setattr(mla_k, "_WALK_GROUP_TOKENS", _W_GROUP)
    q, pool, tables, positions, lens, keep, *_ = _walk_case(name)
    walk = mla_k.selected_walk(keep, tables, positions[:, 0], lens, BLOCK)
    pages, laid, count = map(np.asarray, walk)
    gp = mla_k._pages_per_group(pages.shape[1], BLOCK, True)
    assert gp == _W_GROUP // BLOCK and pages.shape[1] % gp == 0
    assert pages.shape[1] == mla_k.walk_columns(_W_PAGES, BLOCK)
    assert laid.shape == (3, 1, pages.shape[1] * BLOCK)
    for r, n in enumerate(count):
        hit = np.asarray(keep)[r, 0].reshape(_W_PAGES, BLOCK).any(-1)
        assert n == hit.sum()
        assert pages[r, :n].tolist() == np.asarray(tables)[r, hit].tolist()
        assert laid[r, 0, :n * BLOCK].sum() == np.asarray(keep)[r].sum()
        assert not laid[r, 0, n * BLOCK:].any()
        if n:
            assert (pages[r, n:] == pages[r, n - 1]).all()


def test_a_walk_that_is_not_whole_groups_is_refused(monkeypatch):
    """A table the kernel's group does not divide would leave a group's
    copies short of what its one wait draws: refused where it is traced."""
    monkeypatch.setattr(mla_k, "_WALK_GROUP_TOKENS", _W_GROUP)
    q, pool, tables, positions, lens, keep, rkv, *_ = _walk_case(
        "ends_mid_group")
    walk = mla_k.selected_walk(keep, tables, positions[:, 0], lens, BLOCK)
    short = mla_k.SelectedWalk(walk.pages[:, :-3], walk.keep[..., :-3 * BLOCK],
                               walk.count)
    with pytest.raises(ValueError, match="whole groups"):
        mla_k.latent_paged_attention.__wrapped__(
            q, pool, jnp.int32(1), tables, positions, lens, BLOCK,
            scale=0.17, latent=rkv, decode=True, interpret=True, walk=short)


def test_the_round_kernels_mask_each_query_by_its_own_selection(monkeypatch):
    """``dgi_mla_ragged_selected`` in interpret mode: a rectangle of rows,
    and a packed round's tiles, against the XLA form."""
    monkeypatch.setattr(mla_k, "_GROUP_TOKENS", 16)
    mc, q, pool, tables, positions, lens, keep, rkv, dr = _kernel_case(
        1, 2, 12, [40, 23], s=6)
    scale = 0.17
    want = _xla_absorbed(q, pool, 0, tables, positions, lens, keep, rkv, dr,
                         scale)
    got = mla_k.latent_paged_attention(
        q, pool, jnp.int32(0), tables, positions, lens, BLOCK, scale=scale,
        latent=rkv, interpret=True, keep=keep)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    # the same queries as a packed round: row 0's six, then row 1's
    row = jnp.asarray([0] * 6 + [1] * 6 + [2] * 4, jnp.int32)
    col = jnp.asarray(list(range(6)) * 2 + [0] * 4, jnp.int32)
    pos = jnp.concatenate([positions[0], positions[1],
                           jnp.full((4,), -1, jnp.int32)])
    tiles = mla_k.packed_tiles(row, col, pos, 2, 6, mc.num_heads)
    packed_q = jnp.concatenate([q[0], q[1], jnp.zeros((4, *q.shape[2:]))])
    got = mla_k.latent_paged_attention_packed(
        packed_q, tiles, pool, jnp.int32(0), tables, lens, BLOCK,
        scale=scale, latent=rkv, interpret=True,
        keep_tiles=mla_k.keep_for_tiles(keep, tiles, col))
    assert np.abs(np.asarray(got[:12].reshape(2, 6, *got.shape[1:])
                             - want)).max() < 1e-5


def test_forward_chunk_through_the_kernels_matches_the_xla_path(monkeypatch):
    """The whole model with ``kernels_on`` forced and every Pallas call in
    interpret mode: a packed round (a piece beside a decode row), then a
    step, against the XLA forms on the same pools."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(mla_k, "_GROUP_TOKENS", 16)
    monkeypatch.setattr(mla_k, "_WALK_GROUP_TOKENS", 32)
    mc = get_model_config(MODEL, dtype="float32", kv_lora_rank=128)
    params = llama.init_params(mc, jax.random.PRNGKey(2), jnp.float32)
    tables = _tables(2, 16)

    def serve(kernels):
        monkeypatch.setattr(mla, "kernels_on", lambda *a, **kw: kernels)
        common = dict(block_size=BLOCK, pallas=False)
        kv = llama.init_kv_pools(mc, 33, BLOCK, jnp.float32)
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
    assert int(want[2].index_fetched) == int(got[2].index_fetched) > 0


# --------------------------------------------------------------------- #
# through the engine: ragged rounds, scans, the prefix index, refusals
# --------------------------------------------------------------------- #

def _engine(**kw):
    return TPUEngine(
        get_model_config(MODEL, held_experts=SHARE),
        EngineConfig(max_batch_size=4, max_seq_len=128, block_size=16,
                     prefill_buckets=(16, 32, 64), ragged_chunk=32,
                     dtype="float32", **kw), seed=0)


def _serve(eng, prompts, new):
    flying = [eng.submit_chunked_start(InferenceRequest(
        prompt_token_ids=p, sampling=SamplingParams(
            max_new_tokens=new, temperature=0.0, ignore_eos=True)))
        for p in prompts]
    slots = [a.slot for a in flying]
    ragged = {a.slot: [] for a in flying}
    while flying:
        for slot, toks in eng.ragged_round(flying).items():
            ragged[slot] += toks
        flying = [a for a in flying if not a.done]
    scan = eng.decode_multi(new - 1)
    return [(ragged[i], scan[i]) for i in slots]


def test_engine_rounds_follow_the_reference_and_count():
    """Packed ``ragged_round`` (a 40-token prompt enters in two pieces
    beside a 9-token one) then ``decode_multi``: greedy tokens against the
    reference's argmax chain; the layers that scored and that shared."""
    eng = _engine(quantization="int8")
    mc = eng.model_cfg
    assert eng.stats["kv_layout"] == "latent+index"
    assert eng.stats["ragged_kv_path"] == "in_place"
    assert eng.kv[mla.INDEX_KEYS].shape[0] == 3
    assert eng._scan_keys.shape[0] == 3
    cfg = published(mc)
    weights = reference.FromTree(eng.params, reference.dims(cfg))
    prompts, new = [_prompt(40), _prompt(9)], 5
    served = _serve(eng, prompts, new)
    for prompt, (head, rest) in zip(prompts, served):
        seq = list(prompt)
        for step, tok in enumerate(head + rest):
            (want,), _ = reference.forward(cfg, weights, [seq])
            top2 = np.sort(want[0])[-2:]
            if top2[1] - top2[0] > MARGIN:
                assert tok == int(want[0].argmax()), (len(prompt), step)
            seq.append(tok)
    st = eng.stats
    rounds, steps = st["ragged_rounds"], new - 1
    assert rounds == 2
    assert st["index_layers_scored"] == 3 * (rounds + steps)
    assert st["index_layers_shared"] == 6 * (rounds + steps)
    assert st["index_row_steps_scan"] == st["mla_row_steps_scan"] > steps
    assert st["index_fetched_tokens_scan"] > 0
    # both rows pass topk inside the scan: one gather a FULL layer
    assert st["index_key_gathers_scan"] == 3


def test_a_prefix_hit_a_preemption_and_a_resume_bring_the_index_keys():
    """The same 40-token prompt twice on one engine: the second run takes
    its first blocks from the radix index (latent pages and the index keys
    that lie under the same block ids) and decodes the cold run's tokens;
    so does a run preempted between its rounds and resumed."""
    eng = _engine()
    prompt, new = _prompt(40), 6
    (cold,) = _serve(eng, [prompt], new)
    for slot, s in enumerate(eng.slots):
        if s is not None:
            eng.finish_slot(slot)
    assert eng.manager.stats.prefix_hit_tokens == 0
    (warm,) = _serve(eng, [prompt], new)
    assert warm[0] + warm[1] == cold[0] + cold[1]
    assert eng.manager.stats.prefix_hit_tokens >= 32    # two 16-token blocks
    for slot, s in enumerate(eng.slots):
        if s is not None:
            eng.finish_slot(slot)
    # preempt after the first scan steps, resume, and go on
    fresh = _engine()
    req = InferenceRequest(prompt_token_ids=prompt, sampling=SamplingParams(
        max_new_tokens=new, temperature=0.0, ignore_eos=True))
    adm = fresh.submit_chunked_start(req)
    while not adm.done:
        fresh.ragged_round([adm])
    fresh.decode_multi(2)
    slot = fresh.resume(fresh.preempt_slot(adm.slot))
    # the pages the prefix index still holds are a hit, index keys and all
    assert fresh.manager.stats.prefix_hit_tokens >= 32
    while fresh.slots[slot].finish_reason is None:
        fresh.decode_multi(2)
    assert fresh.finish_slot(slot).token_ids == cold[0] + cold[1]


def test_what_cannot_carry_the_selection_refuses_the_model():
    mc = get_model_config(MODEL)

    def make(**kw):
        return TPUEngine(mc, EngineConfig(
            max_batch_size=2, max_seq_len=64, block_size=16,
            prefill_buckets=(16, 32), dtype="float32", **kw), seed=0)

    with pytest.raises(ValueError, match="spill tiers"):
        make(spill_host_blocks=4)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        make(kv_cache_dtype="fp8")
    with pytest.raises(NotImplementedError, match="index-key pool"):
        llama.forward_hidden_chunk(
            mc, {}, jnp.zeros((1, 1, mc.hidden_size)), jnp.zeros((1, 1),
            jnp.int32), {}, _tables(1, 4), jnp.asarray([1]))


def test_the_shared_layer_counters_reach_the_metrics_endpoint():
    from distributed_gpu_inference_tpu.server.observability import (
        MetricsCollector,
    )

    mc = MetricsCollector()
    mc.record_batcher_engine("w1", {
        "kv_layout": "latent+index", "index_pool_bytes": 226492416,
        "index_layers_scored": 270, "index_layers_shared": 540,
        "index_fetched_tokens_scan": 7475200, "index_key_gathers_scan": 60})
    text = mc.metrics.render().decode()
    if "worker_kv_layout" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert 'worker_kv_layout{layout="latent+index",worker="w1"} 1.0' in text
    assert 'worker_kv_layout{layout="latent",worker="w1"} 0.0' in text
    assert 'worker_index_layers_scored_total{worker="w1"} 270.0' in text
    assert 'worker_index_layers_shared_total{worker="w1"} 540.0' in text
    assert 'worker_index_key_gathers_scan_total{worker="w1"} 60.0' in text


def test_a_worker_with_a_handoff_role_drops_the_model():
    from distributed_gpu_inference_tpu.utils.config import WorkerConfig
    from distributed_gpu_inference_tpu.worker.engines import EngineLoadError
    from distributed_gpu_inference_tpu.worker.main import Worker

    cfg = WorkerConfig.model_validate({
        "name": "w", "task_types": ["llm"], "role": "prefill",
        "engines": {"llm": {"model": MODEL, "dtype": "float32", "extra": {
            "max_seq_len": 64, "max_batch_size": 2,
            "prefill_buckets": [16, 32]}}},
    })
    with pytest.raises(EngineLoadError):
        Worker(cfg).load_engines()
