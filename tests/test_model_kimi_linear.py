"""Kimi-Linear on the deployed path: gated delta-rule (KDA) layers over a
state pool beside the latent pages of its NoPE MLA layers, a dense first
MLP, a selection bias in the router — held to the benchmark's plain
reference (``benchmark/harness/reference_kda_mla_moe.py``, which shares no
code with the program) on ``kimi-linear-tiny``: a dense first layer, the
period K, K, K, M twice over, and the short last period K, K, M.

Tolerances: float32 activations over the same int8 weights differ from the
reference by float32 rounding over fifteen layers (measured 1.2e-4; 1e-3
asserted, where a dropped tail, a dropped bias or a bf16 state is off by
0.01 and more)."""

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

from harness import reference_kda_mla_moe as reference  # noqa: E402

from distributed_gpu_inference_tpu.models import kda, llama, mla  # noqa: E402
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

MODEL = "kimi-linear-tiny"
TOL = 1e-3
MARGIN = 1e-2
BLOCK = 16


def published(mc):
    """The configuration as the benchmark's file states it."""
    first, count = mc.held_experts or (0, mc.num_experts)
    return {
        "hidden_size": mc.hidden_size, "num_attention_heads": mc.num_heads,
        "kv_lora_rank": mc.kv_lora_rank,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim, "v_head_dim": mc.v_head_dim,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "num_hidden_layers": mc.num_layers,
        "first_k_dense_replace": mc.first_k_dense,
        "vocab_size": mc.vocab_size, "num_experts": count,
        "expert_share": {"first": first, "count": count,
                         "of": mc.num_experts},
        "num_shared_experts": mc.n_shared_experts,
        "num_experts_per_token": mc.num_experts_per_tok,
        "moe_renormalize": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "tie_word_embeddings": mc.tie_word_embeddings,
        "rms_norm_eps": mc.rms_norm_eps,
        "linear_attn_config": {
            "full_attn_layers": list(mc.full_attn_layers),
            "head_dim": mc.kda_head_dim, "num_heads": mc.kda_num_heads,
            "short_conv_kernel_size": mc.kda_conv_kernel},
    }


def _f32(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)


@pytest.fixture(scope="module")
def tiny():
    """The tiny model holding 2 of its 8 experts, int8 as served."""
    mc = get_model_config(MODEL)
    params = init_quantized_streamed(mc, "int8", seed=0)
    return mc, _f32(params), reference.SeedStream(published(mc), 0)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed + n)
    return [int(t) for t in rng.integers(4, 260, n)]


def _pools(mc, rows, pages=16):
    tables = jnp.asarray(1 + np.arange(rows * pages).reshape(rows, pages),
                         jnp.int32)
    kv = llama.init_kv_pools(mc, 1 + rows * pages, BLOCK, jnp.float32,
                             state_rows=rows)
    return kv, tables


def _reference_chain(mc, weights, prompts, steps):
    """Per prompt: logits ``[steps + 1, V]`` at the last prompt position and
    at each further one, the reference's own argmax fed back (each from a
    full forward pass), and the tokens fed."""
    fed = [[] for _ in prompts]
    want = [[] for _ in prompts]
    width = max(map(len, prompts)) + steps
    for _ in range(steps + 1):
        logits, _ = reference.forward(
            published(mc), weights, [p + f for p, f in zip(prompts, fed)],
            width=width)
        for r, lg in enumerate(logits):
            want[r].append(lg[0])
            fed[r].append(int(np.argmax(lg[0])))
    return [np.stack(w) for w in want], fed


# --------------------------------------------------------------------- #
# the description and its weights
# --------------------------------------------------------------------- #

def test_registry_and_the_cut():
    mc = get_model_config("kimi-linear-48b-a3b-ep8")
    kinds = mc.layer_kinds
    assert len(kinds) == 27 and kinds.count("mla") == 7
    assert [i + 1 for i, k in enumerate(kinds) if k == "mla"] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert mla.layer_units(mc) == (
        (1, (("kda_dense_layers", 1), ("kda_layers", 2), ("layers", 1))),
        (5, (("kda_layers", 3), ("layers", 1))),
        (1, (("kda_layers", 2), ("layers", 1))))
    # head_dim carries the published 72, which no layer reads
    assert mc.head_dim == 72 and mc.qk_head_dim == 192
    assert mc.num_held_experts == 32 and mc.num_experts == 256
    # the cut, at one byte a matmul weight: 7.2 G parameters here of the
    # published 48 G; a state row is 20 layers x (2.10 + 0.07) MB
    assert 7.1e9 < mc.num_params < 7.4e9
    assert mc.state_bytes_per_row() == 20 * (32 * 128 * 128 * 4
                                             + 3 * 12288 * 2)
    # 7 cache layers for 27 layers: the latent pool's layer axis
    assert mc.kv_bytes_per_token() == 7 * 576 * 2
    pools = jax.eval_shape(lambda: llama.init_kv_pools(
        mc, 9, 16, state_rows=8))
    assert pools[mla.POOL].shape == (7, 9, 16, 640)
    assert pools[kda.STATE].shape == (20, 8, 32, 128, 128)
    assert pools[kda.STATE].dtype == jnp.float32
    assert pools[kda.CONV].shape == (20, 8, 3, 12288)


@pytest.mark.parametrize("fields", [
    dict(full_attn_layers=(2,)),
    dict(kda_num_heads=4, kda_head_dim=16, kda_conv_kernel=4),
    dict(mla_use_nope=True),
    dict(router_selection_bias=True),
], ids=["pattern", "kda-sizes", "nope", "bias"])
def test_a_kv_model_refuses_the_fields_only_the_hybrid_reads(fields):
    with pytest.raises(ValueError, match="only the latent-attention model"):
        get_model_config("llama3-tiny", **fields)


@pytest.mark.parametrize("fields,match", [
    (dict(kda_num_heads=0), "need kda_num_heads"),
    (dict(full_attn_layers=()), "without full_attn_layers"),
    (dict(full_attn_layers=(4, 3)), "not a rising list"),
    (dict(full_attn_layers=(4, 99)), "not a rising list"),
    (dict(sandwich_norm=True), "sandwich"),
    (dict(num_experts=0, first_k_dense=0, held_experts=None),
     "router_selection_bias without experts"),
    (dict(head_dim=32), "no layer reads it"),
], ids=["no-heads", "no-pattern", "falling", "past-the-end", "sandwich",
        "bias-no-experts", "head-dim"])
def test_a_field_no_code_would_read_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        get_model_config(MODEL, **fields)


def test_seed_stream_is_the_programs_init_bit_for_bit(tiny):
    mc, params, weights = tiny
    tree = reference.FromTree(params, reference.dims(published(mc)))
    for layer in (0, 1, 3, 4, 14):
        a, b = weights.layer(layer), tree.layer(layer)
        assert set(a) == set(b)
        for name in a:
            assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), \
                (layer, name)
    assert np.array_equal(np.asarray(weights.head()), np.asarray(tree.head()))
    # the family's draws: decays inside (1, 16), a bias that moves choices
    a_log = np.asarray(params["kda_layers"]["a_log"])
    assert a_log.dtype == np.float32
    assert (np.exp(a_log) >= 1).all() and (np.exp(a_log) <= 16).all()
    bias = np.asarray(params["layers"]["router_bias"])
    assert 0.03 < bias.std() < 0.3


# --------------------------------------------------------------------- #
# forward_chunk against the reference
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("n", [5, 70, 150])
def test_forward_chunk_matches_the_reference_in_float32(tiny, n):
    mc, params, weights = tiny
    prompt = _prompt(n)
    (want,), (routes,) = reference.forward(published(mc), weights, [prompt])
    kv, tables = _pools(mc, 1)
    out = llama.forward_chunk(
        mc, params, jnp.asarray([prompt]), jnp.arange(n)[None], kv, tables,
        jnp.asarray([n]), block_size=BLOCK, collect_routing=True)
    assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() < TOL
    # 14 expert layers, in layer order across the units
    got = np.asarray(out.routing)
    assert got.shape == routes.shape == (14, n, mc.num_experts_per_tok)
    assert (np.sort(got, -1) == np.sort(routes, -1)).mean() > 0.999


def test_pieces_then_decode_through_both_pools(tiny):
    """Four rows of unequal prompts in 48-token pieces (chunk and piece
    boundaries fall apart), then six decode steps: the logits of every
    position against the reference's full forward passes."""
    mc, params, weights = tiny
    prompts = [_prompt(n) for n in (70, 5, 150, 33)]
    want, fed = _reference_chain(mc, weights, prompts, 6)
    rows = len(prompts)
    kv, tables = _pools(mc, rows)
    fwd = jax.jit(lambda t, p, kv, lens: llama.forward_chunk(
        mc, params, t, p, kv, tables, lens, block_size=BLOCK))
    got = [[] for _ in prompts]
    for start in range(0, 150, 48):
        toks = np.zeros((rows, 48), np.int32)
        pos = np.full((rows, 48), -1, np.int32)
        for r, p in enumerate(prompts):
            piece = p[start:start + 48]
            toks[r, :len(piece)] = piece
            pos[r, :len(piece)] = start + np.arange(len(piece))
        out = fwd(toks, pos, kv, (pos.max(1) + 1).clip(min=0))
        kv = out.kv
        for r, p in enumerate(prompts):
            n = len(p[start:start + 48])
            if n and start + n == len(p):
                got[r].append(np.asarray(out.logits[r, 0]))
    for step in range(6):
        toks = np.asarray([[fed[r][step]] for r in range(rows)], np.int32)
        pos = np.asarray([[len(p) + step] for p in prompts], np.int32)
        out = fwd(toks, pos, kv, pos[:, 0] + 1)
        kv = out.kv
        for r in range(rows):
            got[r].append(np.asarray(out.logits[r, 0]))
    for g, w in zip(got, want):
        assert np.abs(np.stack(g) - w).max() < TOL


def test_a_packed_round_of_pieces_beside_decode_rows(tiny):
    """One packed round holds a decoding row (one token from its stored
    state), a second piece (from its row's stored state and tail) and a
    fresh piece: each row's logits are what the rectangle gives it."""
    mc, params, weights = tiny
    prompts = [_prompt(40), _prompt(130), _prompt(20)]
    (want_a, want_b, want_c), _ = reference.forward(
        published(mc), weights, prompts)
    kv, tables = _pools(mc, 4)

    def rect(tokens, starts, kv):
        width = max(map(len, tokens))
        toks = np.zeros((4, width), np.int32)
        pos = np.full((4, width), -1, np.int32)
        for r, (t, s) in enumerate(zip(tokens, starts)):
            toks[r, :len(t)] = t
            pos[r, :len(t)] = s + np.arange(len(t))
        return llama.forward_chunk(
            mc, params, jnp.asarray(toks), jnp.asarray(pos), kv, tables,
            jnp.asarray((pos.max(1) + 1).clip(min=0)), block_size=BLOCK)

    # rows 0 and 1 enter: all but the last token of A, the first 70 of B
    kv = rect([prompts[0][:39], prompts[1][:70], [], []], [0, 0, 0, 0], kv).kv
    # the packed round: A's last token (a decode row), B's second piece,
    # C whole in row 3; row 2 idle; three pads at the end
    segs = [(0, prompts[0][39:], 39), (1, prompts[1][70:], 70),
            (3, prompts[2], 0)]
    tok = np.concatenate([t for _, t, _ in segs] + [np.zeros(3, int)])
    pos = np.concatenate([s + np.arange(len(t)) for _, t, s in segs]
                         + [np.full(3, -1)])
    row = np.concatenate([np.full(len(t), r) for r, t, _ in segs]
                         + [np.full(3, 4)])
    col = np.concatenate([np.arange(len(t)) for _, t, _ in segs]
                         + [np.zeros(3, int)])
    ends = np.cumsum([len(t) for _, t, _ in segs]) - 1
    last = np.zeros(4, np.int32)
    lens = np.zeros(4, np.int32)
    for (r, t, s), e in zip(segs, ends):
        last[r], lens[r] = e, s + len(t)
    out = llama.forward_chunk(
        mc, params, jnp.asarray(tok, jnp.int32), jnp.asarray(pos, jnp.int32),
        kv, tables, jnp.asarray(lens), block_size=BLOCK,
        packing=llama.Packing(jnp.asarray(row, jnp.int32),
                              jnp.asarray(col, jnp.int32),
                              jnp.asarray(last), 64))
    for r, want in ((0, want_a), (1, want_b), (3, want_c)):
        assert np.abs(np.asarray(out.logits[r, 0]) - want[0]).max() < TOL
    # the idle row's state and tail are what they were: zero
    assert not np.asarray(out.kv[kda.STATE][:, 2]).any()
    assert not np.asarray(out.kv[kda.CONV][:, 2]).any()


@pytest.mark.parametrize("broken", ["tail", "bias", "state"])
def test_a_block_that_departs_from_the_description_fails(tiny, broken,
                                                         monkeypatch):
    """The controls of the chip comparison, at the tiny size: conv tails
    dropped at a piece boundary, the selection bias dropped, the state kept
    in bfloat16 — each leaves the tolerance by a wide margin."""
    mc, params, weights = tiny
    prompt = _prompt(100)
    (want,), _ = reference.forward(published(mc), weights, [prompt])
    kv, tables = _pools(mc, 1)
    if broken == "tail":
        monkeypatch.setattr(kda, "read_tails", lambda pool, layer: jnp.zeros(
            pool.shape[1:], pool.dtype))
    elif broken == "bias":
        mc = dataclasses.replace(mc, router_selection_bias=False)
    else:
        kv[kda.STATE] = kv[kda.STATE].astype(jnp.bfloat16)
    for start in (0, 50):
        out = llama.forward_chunk(
            mc, params, jnp.asarray([prompt[start:start + 50]]),
            start + jnp.arange(50)[None], kv, tables,
            jnp.asarray([start + 50]), block_size=BLOCK)
        kv = out.kv
    assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() > 10 * TOL


# --------------------------------------------------------------------- #
# the expert layer: the bias, the shares
# --------------------------------------------------------------------- #

def test_the_bias_moves_selection_and_not_the_weights(tiny):
    mc, params, _ = tiny
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(3), (64, mc.hidden_size))
    bias = lp["router_bias"]
    w_b, e_b = mla.route(mc, x, lp["w_router"], bias)
    w_0, e_0 = mla.route(mc, x, lp["w_router"])
    moved = (np.sort(np.asarray(e_b), -1) != np.sort(np.asarray(e_0), -1))
    assert 0 < moved.any(-1).mean() < 1           # some rows, not all
    scores = np.asarray(jax.nn.sigmoid(x @ lp["w_router"].astype(jnp.float32)))
    kept = np.take_along_axis(scores, np.asarray(e_b), -1)
    want = mc.routed_scaling_factor * kept / kept.sum(-1, keepdims=True)
    assert np.abs(np.asarray(w_b) - want).max() < 1e-6
    # a constant bias moves nothing
    w_c, e_c = mla.route(mc, x, lp["w_router"], jnp.full_like(bias, 0.3))
    assert np.array_equal(np.asarray(e_c), np.asarray(e_0))
    assert np.abs(np.asarray(w_c) - np.asarray(w_0)).max() < 1e-6


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four chips hold two experts each: their routed parts and ONE shared
    expert's add up to the reference's layer over all eight experts."""
    mc = get_model_config(MODEL, held_experts=None)
    params = llama.init_params(mc, jax.random.PRNGKey(1), jnp.float32)
    # layer 1 (0-based) is the first expert layer: a gated delta-rule one
    lp = jax.tree.map(lambda a: a[0], params["kda_layers"])
    m = jax.random.normal(jax.random.PRNGKey(4), (1, 11, mc.hidden_size),
                          jnp.float32)
    cfg = published(mc)
    uncut, _ = reference.expert_layer(
        reference.dims(cfg),
        reference.FromTree(params, reference.dims(cfg)).layer(1), m[0])

    def proj(x, name):
        return x @ lp[name]

    total = 0
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(mc, held_experts=(first, 2))
        held = dict(lp, **{k: lp[k][first:first + 2]
                           for k in ("we_gate", "we_up", "we_down")})
        out, stats, topi = mla._experts(
            m, held, share, proj, live=None, stacked=None, layer_idx=0)
        on_share = (np.asarray(topi) >= first) & (np.asarray(topi) < first + 2)
        assert int(stats["assignments"]) == on_share.sum()
        total = total + out[0]
    shared = proj(jax.nn.silu(proj(m[0], "ws_gate")) * proj(m[0], "ws_up"),
                  "ws_down")
    assert np.abs(np.asarray(total - 3 * shared - uncut)).max() < 1e-4
    assert np.abs(np.asarray(uncut)).max() > 0.1


# --------------------------------------------------------------------- #
# through the engine: both pools in one cache manager
# --------------------------------------------------------------------- #

def _engine(**kw):
    base = dict(max_batch_size=4, max_seq_len=256, block_size=BLOCK,
                prefill_buckets=(16, 32, 64), ragged_chunk=32,
                dtype="float32", quantization="int8")
    base.update(kw)
    return TPUEngine(get_model_config(MODEL), EngineConfig(**base), seed=0)


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


def _state(eng, slot):
    return (np.asarray(eng.kv[kda.STATE][:, slot]),
            np.asarray(eng.kv[kda.CONV][:, slot]))


@pytest.fixture(scope="module")
def chain():
    """A reference run: one 40-token prompt alone, 24 new tokens."""
    eng = _engine()
    resp = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    return resp.token_ids


def test_engine_rounds_follow_the_reference_and_count():
    eng = _engine()
    mc = eng.model_cfg
    assert eng.stats["kv_layout"] == "hybrid"
    assert eng.manager.state_rows == 4 == eng.stats["state_rows"]
    assert eng.stats["state_pool_bytes"] == 4 * mc.state_bytes_per_row(4)
    cfg = published(mc)
    weights = reference.FromTree(eng.params, reference.dims(cfg))
    prompts, new = [_prompt(40), _prompt(9)], 5
    slots, first = _admit(eng, prompts, [new, new])
    scan = eng.decode_multi(new - 1)
    for prompt, slot in zip(prompts, slots):
        seq = list(prompt)
        for step, tok in enumerate(first[slot] + scan[slot]):
            (want,), _ = reference.forward(cfg, weights, [seq])
            top2 = np.sort(want[0])[-2:]
            if top2[1] - top2[0] > MARGIN:
                assert tok == int(want[0].argmax()), (len(prompt), step)
            seq.append(tok)
    st = eng.get_stats()
    # the rounds: 32 of the 40 tokens beside the 9-token prompt, then the
    # last 8 beside the short prompt's first decode token
    assert st["kda_tokens_ragged"] == 32 + 9 + 8 + 1
    assert st["kda_segments_ragged"] == 4 == st["kda_chunks_ragged"]
    # (the short prompt's second token came with that round)
    assert st["kda_row_steps_scan"] == mc.num_kda_layers * sum(
        len(scan[slot]) for slot in slots) == mc.num_kda_layers * 7
    assert st["state_binds"] == 2 and st["prefix_hits_without_state"] == 0


def test_a_reused_slot_starts_from_zero_state(chain):
    eng = _engine()
    (slot,), _ = _admit(eng, [_prompt(70)], [4])
    eng.decode_multi(3)
    assert np.abs(_state(eng, slot)[0]).max() > 0
    eng.finish_slot(slot)
    resp = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    assert eng.slots[slot] is None and resp.token_ids == chain
    assert eng.get_stats()["state_binds"] == 2


@pytest.mark.parametrize("steps", [4, 16])
def test_a_row_that_ends_inside_a_scan_leaves_every_state_exact(chain, steps):
    """Row B's budget ends inside the scan: the masked steps leave its state
    as its last live step left it, and row A's state and tokens are what A
    alone gives."""
    alone = _engine()
    (a0,), f0 = _admit(alone, [_prompt(40)], [24])
    alone.decode_multi(steps)
    both = _engine()
    (a, b), f1 = _admit(both, [_prompt(40), _prompt(23)], [24, 4])
    got = both.decode_multi(steps)
    assert f1[a] + got[a] == chain[:1 + min(steps, 23)]
    # 4 new: one with its piece, one beside A's second piece, two here
    assert len(f1[b]) == 2 == len(got[b])
    # (A's pieces shared their rounds with B's: other shapes, so float32
    # rounding apart, no more)
    for x, y in zip(_state(alone, a0), _state(both, a)):
        assert np.abs(x.astype(np.float32) - y.astype(np.float32)).max() < 1e-3
    # B ran two live steps: a scan of exactly two gives the same state, to
    # the bit
    short = _engine()
    (_, b2), _ = _admit(short, [_prompt(40), _prompt(23)], [24, 4])
    short.decode_multi(2)
    for x, y in zip(_state(short, b2), _state(both, b)):
        assert np.array_equal(x, y)


def test_a_chained_scan_and_an_admission_run_ahead_leave_the_state_exact(
        chain):
    """A scan dispatched behind an unread one in which a row ended, and an
    admission bound to the free slot while a scan is unread: row A's tokens
    are the chain's, and the new row's are what a cold engine gives."""
    eng = _engine()
    (a, b), first = _admit(eng, [_prompt(40), _prompt(23)], [24, 8])
    assert eng.decode_multi(4, ahead=True) == {}
    one = eng.decode_multi(4, ahead=True)        # reads scan 1; B ends in 2
    assert eng.scan_unread
    # the admission runs beside the unread scan, in a slot it does not hold
    adm = eng.submit_chunked_start(_req(_prompt(50), 4))
    assert eng.scan_unread and adm.slot not in (a, b)
    two = eng.collect_scan()
    assert (len(one[b]), len(two[b])) == (4, 2)
    assert eng.slots[b].finish_reason == "length"
    flying, got_c = [adm], []
    while flying:
        out = eng.ragged_round(flying)
        got_c += out.get(adm.slot, [])
        flying = [x for x in flying if not x.done]
    rest = eng.decode_multi(8)
    # A decoded in the two rounds that carried C's pieces as well
    got_a = eng.slots[a].generated
    assert len(got_a) == len(first[a]) + 4 + 4 + 2 + 8
    assert got_a == chain[:len(got_a)]
    cold = _engine()
    want_c = cold.generate([_req(_prompt(50), 4)], use_multi_step=True)[0]
    assert got_c + rest[adm.slot] == want_c.token_ids


def test_preempt_and_resume_continue_token_for_token(chain):
    eng = _engine()
    (slot,), first = _admit(eng, [_prompt(40)], [24])
    got = first[slot] + eng.decode_multi(7)[slot]
    pre = eng.preempt_slot(slot)
    assert eng.slots[slot] is None
    # the resume recomputes from the first token: no state was snapshot,
    # and the pages the prefix index still holds are no hit
    slot = eng.resume(pre)
    assert eng.get_stats()["prefix_hits_without_state"] == 1
    assert eng.slots[slot].cached_tokens == 0
    while eng.slots[slot].finish_reason is None:
        eng.decode_multi(8)
    assert eng.finish_slot(slot).token_ids == chain
    assert got == chain[:8]


def test_a_prefix_the_pages_hold_is_no_hit_without_its_state(chain):
    eng = _engine()
    cold = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    assert eng.manager.stats.prefix_hits_without_state == 0
    warm = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    assert warm.token_ids == cold.token_ids == chain
    assert warm.cached_tokens == 0
    assert eng.manager.stats.prefix_hit_tokens == 0
    assert eng.manager.stats.prefix_hits_without_state == 1


def test_the_submit_path_sends_its_pieces_through_the_packed_round(chain):
    """``submit`` (and so ``resume`` and ``generate``) prefill a hybrid
    model a packed piece at a time in the slot's own row, and a wave
    (``submit_batch``) as a rectangle whose rows are the slots."""
    eng = _engine()
    slots = eng.submit_batch([_req(_prompt(23), 3), _req(_prompt(40), 24)])
    while eng.slots[slots[1]].finish_reason is None:
        eng.decode_multi(8)
    assert eng.finish_slot(slots[1]).token_ids == chain


def test_what_cannot_carry_the_state_refuses_the_model_when_configured():
    from jax.sharding import Mesh

    from distributed_gpu_inference_tpu.runtime import kv_handoff
    from distributed_gpu_inference_tpu.runtime.speculative import (
        SpecDecodeConfig,
    )

    mc = get_model_config(MODEL)
    base = dict(max_batch_size=2, max_seq_len=64, block_size=16,
                prefill_buckets=(16, 32), dtype="float32")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="one chip"):
        TPUEngine(mc, EngineConfig(**base), mesh=mesh)
    with pytest.raises(ValueError, match="spill"):
        TPUEngine(mc, EngineConfig(**base, spill_host_blocks=8))
    with pytest.raises(ValueError, match="multi-token-prediction"):
        TPUEngine(mc, EngineConfig(
            **base, speculative=SpecDecodeConfig(num_draft_tokens=2)))
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        TPUEngine(mc, EngineConfig(**base, kv_cache_dtype="int8"))
    eng = TPUEngine(mc, EngineConfig(**base))
    with pytest.raises(ValueError, match="latent pages"):
        kv_handoff.HandoffReceiver(eng)
    with pytest.raises(ValueError, match="latent pages"):
        kv_handoff.export_slot_kv(eng, 0)
    # a batch row is a state row: a one-row batch over a wider pool is
    # refused at trace time, not served from row 0
    with pytest.raises(ValueError, match="a batch row is a state row"):
        llama.forward_chunk(
            mc, eng.params, jnp.zeros((1, 4), jnp.int32),
            jnp.arange(4)[None], eng.kv, jnp.ones((1, 4), jnp.int32),
            jnp.asarray([4]), block_size=16)
    with pytest.raises(ValueError, match="needs its number of rows"):
        llama.init_kv_pools(mc, 4, 16)


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


def test_the_state_pools_counters_reach_the_metrics_endpoint():
    from distributed_gpu_inference_tpu.server.observability import (
        MetricsCollector,
    )

    mc = MetricsCollector()
    mc.record_batcher_engine("w1", {
        "kv_layout": "hybrid", "state_pool_bytes": 347340800,
        "state_rows": 8, "state_binds": 3, "prefix_hits_without_state": 1,
        "kda_row_steps_scan": 640, "kda_tokens_ragged": 263,
        "kda_segments_ragged": 8, "kda_chunks_ragged": 11})
    text = mc.metrics.render().decode()
    if "worker_kv_layout" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert 'worker_kv_layout{layout="hybrid",worker="w1"} 1.0' in text
    assert 'worker_state_pool_bytes{worker="w1"} 3.473408e+08' in text
    assert 'worker_state_binds_total{worker="w1"} 3.0' in text
    assert 'worker_prefix_hits_without_state_total{worker="w1"} 1.0' in text
    assert 'worker_kda_row_steps_scan_total{worker="w1"} 640.0' in text
    assert 'worker_kda_chunks_ragged_total{worker="w1"} 11.0' in text
