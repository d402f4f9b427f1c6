"""Laguna-S-2.1 on the deployed path: window and full attention mixed layer by
layer with a head count and a rotation per kind, a per-head output gate, a
dense first layer, then a held share of softmax-routed experts beside a
shared one; K/V pages per layer kind with prefix hits that need a window's
pages — held to the benchmark's plain reference
(``benchmark/harness/reference_window_moe.py``, which shares no code with
the program) on ``laguna-tiny`` (10 layers: two periods F,S,S after the
first and an odd end; window 16, four / six query heads over two K/V heads;
8 experts top-3, 2 held).

Tolerances: float32 activations over the same int8 weights differ from the
reference by float32 rounding (measured 7e-6; 1e-3 asserted, where every
planted fault is off by 1e-2 and more)."""

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

from harness import reference_window_moe as reference  # noqa: E402

from distributed_gpu_inference_tpu.models import llama  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    get_model_config,
)
from distributed_gpu_inference_tpu.models.loader import (  # noqa: E402
    init_quantized_streamed,
)
from distributed_gpu_inference_tpu.runtime.engine import (  # noqa: E402
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (  # noqa: E402
    InferenceRequest,
    SamplingParams,
)

MODEL = "laguna-tiny"
TOL = 1e-3
MARGIN = 1e-2
BLOCK = 16


def published(mc):
    """The configuration as the benchmark's file states it."""
    L = mc.num_layers
    factor, original, fast, slow, attention = mc.rope_yarn
    return {
        "hidden_size": mc.hidden_size,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "num_hidden_layers": L, "vocab_size": mc.vocab_size,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "shared_expert_intermediate_size":
            mc.moe_intermediate_size * mc.n_shared_experts,
        "layer_types": [f"{k}_attention" for k in mc.attn_kinds],
        "num_attention_heads_per_layer":
            [mc.heads_of(k) for k in mc.attn_kinds],
        "mlp_layer_types": ["dense" if i < mc.first_k_dense else "sparse"
                            for i in range(L)],
        "sliding_window": mc.sliding_window,
        "num_experts": mc.num_held_experts,
        "expert_share": {"of": mc.num_experts, "first": mc.held_experts[0]},
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "moe_routed_scaling_factor": mc.routed_scaling_factor,
        "gating": "per-head", "rms_norm_eps": mc.rms_norm_eps,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": mc.rope_theta, "rope_type": "yarn",
                "factor": factor,
                "original_max_position_embeddings": original,
                "beta_fast": fast, "beta_slow": slow,
                "attention_factor": attention,
                "partial_rotary_factor": mc.partial_rotary_factor},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": mc.sliding_rope_theta,
                "partial_rotary_factor": 1}},
    }


def _f32(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)


@pytest.fixture(scope="module")
def tiny():
    mc = get_model_config(MODEL)
    params = _f32(init_quantized_streamed(mc, "int8", seed=3))
    return mc, params, reference.SeedStream(published(mc), 3)


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(4, 260, size=n)]


def _pools(mc, rows, pages=8):
    """Pools and the two block tables side by side, a row's pages apart."""
    blocks = 1 + rows * pages
    kv = llama.init_kv_pools(mc, blocks, BLOCK, jnp.float32,
                             window_blocks=blocks)
    one = 1 + np.arange(rows * pages).reshape(rows, pages)
    return kv, jnp.asarray(np.concatenate([one, one], axis=1), jnp.int32)


def _rectangle(prompts, width):
    toks = np.zeros((len(prompts), width), np.int32)
    pos = -np.ones((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
        pos[i, :len(p)] = np.arange(len(p))
    return jnp.asarray(toks), jnp.asarray(pos)


# --------------------------------------------------------------------- #
# the configuration
# --------------------------------------------------------------------- #

def test_registry_and_the_cut():
    mc = get_model_config("laguna-s-2.1-ep4-12l")
    assert (mc.num_layers, mc.num_heads, mc.num_kv_heads, mc.head_dim) \
        == (12, 48, 8, 128)
    assert mc.attn_kinds == ("full", "sliding", "sliding", "sliding") * 3
    assert mc.cache_kinds == (("full", 3, None), ("sliding", 9, 512))
    assert (mc.heads_of("full"), mc.heads_of("sliding")) == (48, 72)
    assert mc.rope_of("full")[:2] == (500000.0, 64)
    assert mc.rope_of("sliding") == (10000.0, 128, None)
    assert (mc.num_experts, mc.num_held_experts, mc.num_experts_per_tok,
            mc.mlp_width, mc.routed_scaling_factor) == (256, 64, 10, 1024, 2.5)
    assert llama.layer_groups(mc) == (
        ("full_dense_layers", 1), ("full_layers", 2), ("layers", 9))
    assert llama.layer_units(mc) == (
        (1, (("full_dense_layers", 1), ("layers", 3))),
        (2, (("full_layers", 1), ("layers", 3))))
    # the issue's bytes: attention 44.2 M / 63.1 M, a routed layer's 64
    # experts of 9.437 M with the shared one and the router
    assert abs(mc.kv_layer_params(4) - (44.2e6 + 0.8e6 + 65 * 9.437e6)) < 1e6
    assert abs(mc.kv_layer_params(1) - mc.kv_layer_params(4) - 18.9e6) < 1e5
    assert 7.70e9 < mc.num_params < 7.75e9
    pools = jax.eval_shape(
        lambda: llama.init_kv_pools(mc, 4, 16, window_blocks=3))
    assert {n: p.shape for n, p in pools.items()} == {
        "k": (3, 4, 8, 16, 128), "v": (3, 4, 8, 16, 128),
        "k_win": (9, 3, 8, 16, 128), "v_win": (9, 3, 8, 16, 128)}
    tiny = get_model_config(MODEL)
    assert llama.layer_units(tiny) == (
        (1, (("full_dense_layers", 1), ("layers", 2))),
        (2, (("full_layers", 1), ("layers", 2))),
        (1, (("full_layers", 1),)))
    # a model-wide window is the case "every layer of the window kind"
    mistral = get_model_config("mistral-7b")
    assert mistral.cache_kinds == (("sliding", 32, 4096),)
    assert llama.layer_units(mistral) == ((1, (("layers", 32),)),)
    assert llama.kind_pools(mistral, "sliding") == ("k", "v")


@pytest.mark.parametrize("fields,match", [
    (dict(index_topk=8, index_num_heads=2, index_head_dim=16),
     "index"),
    (dict(kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=8,
          v_head_dim=16), "latent layers would not read"),
    (dict(sliding_window=None), "needs sliding_window"),
    (dict(layer_types=("full", "sliding")), "for each of the 10 layers"),
    (dict(sliding_num_heads=5), "K/V heads that differ by kind"),
    (dict(layer_types=("sliding",) * 10), "one attention kind"),
    (dict(partial_rotary_factor=0.2), "rotated width"),
    (dict(rope_yarn=(0.5, 64, 32.0, 1.0, 1.0)), "rope_yarn"),
    (dict(attention_bias=True), "described per layer"),
    (dict(qk_norm=True), "described per layer"),
    (dict(router_scoring="tanh"), "router_scoring"),
])
def test_a_combination_that_is_not_built_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(get_model_config(MODEL), **fields)


def test_seed_stream_is_the_programs_init_bit_for_bit(tiny):
    mc, params, weights = tiny
    tree = reference.FromTree(published(mc), params)
    for layer in range(mc.num_layers):
        a, b = weights.layer(layer), tree.layer(layer)
        assert set(a) == set(b)
        for name in a:
            assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), \
                (layer, name)
    assert np.array_equal(np.asarray(weights.head()), np.asarray(tree.head()))
    assert params["layers"]["wq"]["qw"].shape == (6, 64, 6 * 16)
    assert params["full_layers"]["wq"]["qw"].shape == (3, 64, 4 * 16)
    assert params["layers"]["w_hgate"].shape == (6, 64, 6)
    # vectors a misplaced norm would show against
    assert 0.1 < np.asarray(params["layers"]["attn_norm"]).std() < 0.4


def test_yarn_frequencies_are_hugging_faces():
    """``_compute_yarn_parameters`` (truncate on) over the 64 rotated values
    of the published full layer, written out here from its source."""
    import math

    mc = get_model_config("laguna-s-2.1-ep4-12l")
    inv, scale = llama.rope_inv_freq(mc, "full")
    dim, base, factor, original = 64, 500000.0, 128.0, 8192
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(32)), 0)
    high = min(math.ceil(correction_dim(1)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    want = (1 / (factor * pos_freqs)) * (1 - extrapolation_factor) \
        + (1 / pos_freqs) * extrapolation_factor
    assert np.allclose(np.asarray(inv), want, rtol=1e-6)
    assert scale == pytest.approx(0.1 * math.log(factor) + 1.0)
    assert inv.shape == (32,)
    assert np.allclose(np.asarray(llama.rope_inv_freq(mc, "sliding")[0]),
                       1.0 / 10000.0 ** (np.arange(64) / 64), rtol=1e-6)


# --------------------------------------------------------------------- #
# forward_chunk against the reference
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("n", [5, 24, 100])
def test_forward_chunk_matches_the_reference_in_float32(tiny, n):
    mc, params, weights = tiny
    prompts = [_prompt(n), _prompt(max(n // 3, 2), seed=1)]
    want = reference.last_logits(published(mc), weights, prompts)
    kv, tables = _pools(mc, 2)
    toks, pos = _rectangle(prompts, -(-n // 8) * 8)
    out = llama.forward_chunk(
        mc, params, toks, pos, kv, tables,
        jnp.asarray([len(p) for p in prompts]), block_size=BLOCK,
        collect_routing=True)
    for i in range(2):
        assert np.abs(np.asarray(out.logits[i, 0]) - want[i]).max() < TOL
    # nine routed layers of the ten; the dense first one emits nothing
    assert out.routing.shape[0] == mc.num_layers - 1
    assert int(out.moe["layer_calls"]) == mc.num_layers - 1
    assert int(out.moe["pairs_routed"]) == 9 * 3 * sum(map(len, prompts))


def test_pieces_then_decode_through_both_kinds_pools(tiny):
    """A 70-token prompt in pieces of 32 and then six decode steps, the
    sliding layers' pages in a pool of their own whose blocks before the
    window are taken away as the row advances (table entries 0): every
    piece's and step's logits are the reference's over the whole prefix."""
    mc, params, weights = tiny
    cfg = published(mc)
    seq = _prompt(76)
    kv, tables = _pools(mc, 1, pages=8)
    tables = np.array(tables)
    at = [31, 63, 69] + list(range(70, 76))
    (want,), _ = reference.forward(cfg, weights, [seq], at=[at])
    got = []
    cuts = [(0, 32), (32, 64), (64, 70)] + [(i, i + 1) for i in range(70, 76)]
    for lo, hi in cuts:
        # what every query from ``lo`` on is past: the window kind's
        # blocks that end at or before lo - window + 1
        dead = max(lo - mc.sliding_window + 1, 0) // BLOCK
        tables[0, 8:8 + dead] = 0
        width = 32 if hi - lo > 1 else 1
        toks = np.zeros((1, width), np.int32)
        pos = -np.ones((1, width), np.int32)
        toks[0, :hi - lo] = seq[lo:hi]
        pos[0, :hi - lo] = np.arange(lo, hi)
        out = llama.forward_chunk(
            mc, params, jnp.asarray(toks), jnp.asarray(pos), kv,
            jnp.asarray(tables), jnp.asarray([hi]), block_size=BLOCK)
        kv = out.kv
        got.append(np.asarray(out.logits[0, 0]))
    assert np.abs(np.stack(got) - want).max() < TOL


def test_a_packed_round_of_a_piece_beside_decode_rows(tiny):
    """The plain ragged round's form: a 24-token piece of one row and one
    decode token of another packed on one axis."""
    mc, params, weights = tiny
    cfg = published(mc)
    long, short = _prompt(56), _prompt(20, seed=2)
    kv, tables = _pools(mc, 2)
    toks, pos = _rectangle([long[:32], short[:19]], 32)
    kv = llama.forward_chunk(
        mc, params, toks, pos, kv, tables, jnp.asarray([32, 19]),
        block_size=BLOCK, with_logits=False).kv
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
        mc, params, jnp.asarray(tok), jnp.asarray(where), kv, tables,
        jnp.asarray([56, 20]), block_size=BLOCK,
        packing=llama.Packing(jnp.asarray(row), jnp.asarray(col),
                              jnp.asarray([23, 24], jnp.int32), 24))
    want = reference.last_logits(cfg, weights, [long, short])
    for i in range(2):
        assert np.abs(np.asarray(out.logits[i, 0]) - want[i]).max() < TOL


@pytest.mark.parametrize("broken", reference.VARIANTS)
def test_a_block_that_departs_from_the_description_fails(tiny, broken):
    """Each planted fault of the chip's comparison moves the reference's
    logits off the served ones by far more than the tolerance."""
    mc, params, weights = tiny
    prompts = [_prompt(100)]
    (want,), _ = reference.forward(published(mc), weights, prompts,
                                   variant=broken)
    kv, tables = _pools(mc, 1)
    toks, pos = _rectangle(prompts, 104)
    out = llama.forward_chunk(mc, params, toks, pos, kv, tables,
                              jnp.asarray([100]), block_size=BLOCK)
    assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() > 10 * TOL


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    """Guide section 4: the parts of a routed layer's result that the four
    chips' shares give, with the shared expert counted once, add up to what
    the uncut reference layer gives; and the program's expert layer over a
    share is that share's part."""
    mc, params, weights = tiny
    cfg = published(mc)
    s = reference.dims(cfg)
    layer = 6                           # the second full layer with experts
    w = weights.layer(layer)
    lt = reference.layer_type(s, layer)
    x = jax.random.normal(jax.random.PRNGKey(5), (reference.BLOCK, 64))
    whole_cfg = dict(cfg, num_experts=mc.num_experts,
                     expert_share={"of": mc.num_experts, "first": 0})
    whole_s = reference.dims(whole_cfg)
    with jax.default_matmul_precision("highest"):
        zero = jnp.zeros_like(x)
        parts = []
        for first in range(0, mc.num_experts, 2):
            mine = reference.SeedStream(
                dict(cfg, expert_share={"of": mc.num_experts,
                                        "first": first}), 3).layer(layer)
            # every share draws its own experts; stack them for the whole
            parts.append(mine)
        full_w = dict(w)
        for name in ("we_gate", "we_up", "we_down"):
            full_w[name] = jnp.concatenate([p[name] for p in parts])
        whole, _ = reference._mlp(whole_s, lt, full_w, x, None, None, True)
        total = zero
        for n, p in enumerate(parts):
            out, _ = reference._mlp(s, lt, dict(w, **{
                k: p[k] for k in ("we_gate", "we_up", "we_down")}), x, None,
                (2 * n, 2), n == 0)
            total = total + (out - x)
    assert np.abs(np.asarray(total - (whole - x))).max() < 1e-4
    # the program over the held share (0, 2): that share's part
    lp = jax.tree.map(lambda a: a[1], params["full_layers"])
    mlp_in = llama.rms_norm(x[None], lp["mlp_norm"], mc.rms_norm_eps)

    def proj(x_, name):
        return llama.qmm(x_, lp[name], True)

    got, stats, _ = llama.expert_layer(
        mlp_in, lp, mc, proj, live=None, stacked=None, layer_idx=0)
    with jax.default_matmul_precision("highest"):
        want, _ = reference._mlp(s, lt, w, x, None, (0, 2), True)
    assert np.abs(np.asarray(got[0] - (want - x))).max() < TOL
    assert int(stats["pairs_routed"]) == 3 * reference.BLOCK
    assert 0 < int(stats["assignments"]) < int(stats["pairs_routed"])


# --------------------------------------------------------------------- #
# the engine: pieces, rounds beside decode rows, scans, a prefix hit
# --------------------------------------------------------------------- #

def _engine(**kw):
    mc = get_model_config(MODEL)
    cfg = dict(max_batch_size=3, max_seq_len=256, block_size=BLOCK,
               dtype="float32", quantization="int8",
               prefill_buckets=(16, 32, 64), ragged_chunk=32, multi_step=8)
    cfg.update(kw)
    return TPUEngine(mc, EngineConfig(**cfg))


def _req(prompt, new, **kw):
    return InferenceRequest(prompt_token_ids=list(prompt), sampling=SamplingParams(
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
    weights = reference.FromTree(cfg, eng.params)
    seq = list(prompt) + list(generated)
    at = list(range(len(prompt) - 1, len(seq) - 1))
    (want,), _ = reference.forward(cfg, weights, [seq[:-1]], at=[at])
    for row, tok in zip(want, generated):
        top2 = np.sort(row)[-2:]
        if top2[1] - top2[0] > MARGIN:
            assert tok == int(row.argmax())


def test_engine_rounds_and_scans_follow_the_reference_and_count():
    eng = _engine()
    mc = eng.model_cfg
    assert eng.stats["kv_layout"] == "kv+window"
    assert eng.stats["ragged_kv_path"] == "layer_copy"      # the CPU
    assert set(eng.kv) == {"k", "v", "k_win", "v_win"}
    assert eng.kv["k_win"].shape[:2] == (6, 1 + 3 * 8 * 1)
    prompts, new = [_prompt(70), _prompt(5, seed=1)], 9
    slots, first = _admit(eng, prompts, [new, new])
    scan = eng.decode_multi(new - 1)
    for prompt, slot in zip(prompts, slots):
        _follows_the_reference(eng, prompt, first[slot] + scan[slot])
    st = eng.get_stats()
    # the scans: a row-step attends its cache and the token it wrote in a
    # full layer, the last 16 of them in a sliding one
    rows = [(len(p) + len(first[s]) - 1, len(scan[s]))
            for p, s in zip(prompts, slots)]     # (cached before, steps)
    assert st["attn_row_steps_scan"] == sum(n for _, n in rows) > 8
    assert st["attn_full_context_tokens_scan"] == sum(
        before + j for before, n in rows for j in range(1, n + 1))
    assert st["attn_window_context_tokens_scan"] == sum(
        min(before + j, 16) for before, n in rows for j in range(1, n + 1))
    assert 0 < st["kv_window_resident_tokens_scan"] \
        <= 3 * BLOCK * st["attn_row_steps_scan"]
    # the rounds: 32 + 32 + 6 of the long prompt, the short one's 5 and
    # then its decode tokens beside the later pieces
    assert st["attn_pairs_ragged_full"] > st["attn_pairs_ragged_window"] > 0
    kv = st["kv_cache"]
    # a live row holds every block in the full kind and its window's in
    # the other: 79 and 14 tokens are 5 + 1 blocks against 2 + 1
    assert kv["blocks_in_use"] == 6 and kv["window_blocks_in_use"] == 3
    assert kv["window_released_blocks"] == 3 == kv["window_blocks_retained"]


def test_a_request_on_a_prefix_hit_is_the_same_request_served_cold():
    """The second request shares the first one's 96-token document: its
    full-kind pages are a prefix hit and the window kind still holds the
    document's last window, so only the question is prefilled; the reply
    is the one a cold engine gives, and the reference's."""
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
    """A preempted row's pages park in both kinds' caches; the resume is a
    prefix hit that needs the window kind's last window, prefills the rest
    and goes on as the reference does (the tokens before the preemption are
    those of a run that was never preempted)."""
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
    assert eng.manager.stats.prefix_hit_tokens == 64
    assert eng.manager.stats.prefix_hits_cut_by_window == 0
    while eng.slots[slot].finish_reason is None:
        eng.decode_multi(8)
    after = list(eng.slots[slot].generated)
    assert len(after) == 20 and after[:len(got)] == got
    _follows_the_reference(eng, prompt, after)


def test_a_wave_and_long_prompts_in_pieces_share_the_window_pool():
    """``generate`` admits short prompts as one wave (the window kind's
    blocks for the whole prompt at once) and a prompt past the largest
    bucket in pieces (released between them): three rows, one pool."""
    eng = _engine()
    prompts = [_prompt(150), _prompt(20, seed=1), _prompt(40, seed=2)]
    out = eng.generate([_req(p, 6) for p in prompts], use_multi_step=True)
    for prompt, resp in zip(prompts, out):
        _follows_the_reference(eng, prompt, resp.token_ids)
    kv = eng.get_stats()["kv_cache"]
    assert kv["window_blocks_in_use"] == 0 and kv["blocks_in_use"] == 0


@pytest.mark.parametrize("kw,match", [
    (dict(kv_cache_dtype="int8"), "activation dtype"),
    (dict(kv_cache_dtype="fp8"), "activation dtype"),
    (dict(spill_host_blocks=8), "spill tiers"),
    (dict(kv_seq_sharded=True), "sequence axis"),
    (dict(speculative="chain"), "speculative"),
    (dict(mesh=True), "one chip"),
])
def test_what_cannot_carry_pages_per_kind_refuses_the_model(kw, match,
                                                             cpu_devices):
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


def test_a_worker_with_a_handoff_role_drops_the_model():
    from distributed_gpu_inference_tpu.runtime import kv_handoff
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
    with pytest.raises(ValueError, match="pages per layer kind"):
        kv_handoff.require_kv_pages(_engine())
