"""Falcon-H1 on the deployed path: in every layer a Mamba-2 (SSD) mixer over
a state pool and GQA attention over K/V pages read one normed input, under
muP multipliers — held to the benchmark's plain reference
(``benchmark/harness/reference_ssd_gqa.py``, which shares no code with the
program) on ``falcon-h1-tiny``: four layers, chunks of 16 so that segments
span chunks, every multiplier != 1.

Tolerances: ``lm_head_multiplier`` 2^-7 shrinks the logits 128-fold, so
every limit is relative: the largest difference over the largest reference
logit. Float32 activations over the same int8 weights differ from the
reference by float32 rounding over four layers (measured 2e-5; 5e-4
asserted, where each planted fault is off by 5e-3 and more)."""

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

from compare_logits_ssd import gate_after_norm  # noqa: E402
from harness import reference_ssd_gqa as reference  # noqa: E402

from distributed_gpu_inference_tpu.models import llama, ssd  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    get_model_config,
)
from distributed_gpu_inference_tpu.models.loader import (  # noqa: E402
    init_quantized_streamed,
)
from distributed_gpu_inference_tpu.ops import ssd_pallas  # noqa: E402
from distributed_gpu_inference_tpu.runtime.engine import (  # noqa: E402
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (  # noqa: E402
    InferenceRequest,
    SamplingParams,
)

MODEL = "falcon-h1-tiny"
TOL = 5e-4
BLOCK = 16


def published(mc):
    """The configuration as the benchmark's file states it."""
    return {
        "hidden_size": mc.hidden_size, "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "intermediate_size": mc.intermediate_size,
        "num_hidden_layers": mc.num_layers, "vocab_size": mc.vocab_size,
        "rms_norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta,
        "mamba_n_heads": mc.ssm_num_heads, "mamba_d_head": mc.ssm_head_dim,
        "mamba_d_state": mc.ssm_state_size,
        "mamba_n_groups": mc.ssm_num_groups,
        "mamba_d_conv": mc.ssm_conv_kernel, "mamba_d_ssm": mc.ssm_inner,
        "mamba_chunk_size": mc.ssm_chunk_size,
        "embedding_multiplier": mc.embedding_multiplier,
        "lm_head_multiplier": mc.lm_head_multiplier,
        "key_multiplier": mc.key_multiplier,
        "attention_in_multiplier": mc.attention_in_multiplier,
        "attention_out_multiplier": mc.attention_out_multiplier,
        "ssm_in_multiplier": mc.ssm_in_multiplier,
        "ssm_out_multiplier": mc.ssm_out_multiplier,
        "mlp_multipliers": list(mc.mlp_multipliers),
        "ssm_multipliers": list(mc.ssm_multipliers),
    }


def _f32(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)


def _rel(got, want):
    """The largest difference over the largest reference value."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def tiny():
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
        logits = reference.forward(
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
    mc = get_model_config("falcon-h1-34b-pp4-18l")
    assert mc.described_per_layer and mc.num_state_layers == 18
    assert llama.layer_groups(mc) == (("layers", 18),)
    assert llama.layer_units(mc) == ((1, (("layers", 18),)),)
    assert (mc.ssm_inner, mc.ssm_conv_dim) == (4096, 5120)
    # the issue's arithmetic: a layer 430.1 M, 18 layers 7.74 G, embedding
    # and head 2 x 65,280 x 5,120
    layer = mc.kv_layer_params(0)
    assert 430.0e6 < layer < 430.4e6
    assert mc.num_params == 18 * layer + 2 * 65280 * 5120 + 5120
    # a state row: 18 layers x (32 x 128 x 256 float32 + a 3 x 5120 tail)
    assert mc.state_bytes_per_row() == 18 * (32 * 128 * 256 * 4
                                             + 3 * 5120 * 2)
    assert mc.kv_bytes_per_token() == 18 * 2 * 4 * 128 * 2
    pools = jax.eval_shape(lambda: llama.init_kv_pools(
        mc, 9, 16, state_rows=8))
    assert pools["k"].shape == (18, 9, 4, 16, 128)
    assert pools[ssd.STATE].shape == (18, 8, 32, 128, 256)
    assert pools[ssd.STATE].dtype == jnp.float32
    assert pools[ssd.CONV].shape == (18, 8, 3, 5120)
    assert pools[ssd.CONV].dtype == jnp.bfloat16
    specs = llama.leaf_specs(mc, "layers")
    assert specs["w_in"][0] == (5120, 9216) and specs["w_in"][2] == "q"
    assert specs["w_dt"][0] == (5120, 32) and specs["w_dt"][2] == "d"
    assert specs["w_out"][0] == (4096, 5120)


@pytest.mark.parametrize("fields,match", [
    (dict(ssm_num_heads=0), "needs ssm_num_heads"),
    (dict(ssm_num_groups=3), "a divisor of the heads"),
    (dict(ssm_conv_kernel=1), "ssm_conv_kernel"),
    (dict(sliding_window=8), "sliding_window is not built"),
    (dict(num_experts=4), "num_experts is not built"),
    (dict(attention_bias=True), "attention_bias is not built"),
    (dict(qk_norm_per_head=True), "qk_norm is not built"),
    (dict(layer_types=("full",) * 4), "layer_types is not built"),
    (dict(ssm_multipliers=(1.0, 1.0)), "z, x, B, C, dt"),
], ids=["no-heads", "groups", "taps", "window", "experts", "bias", "qk-norm",
        "layer-types", "five"])
def test_what_is_not_built_is_refused_at_configuration(fields, match):
    with pytest.raises(ValueError, match=match):
        get_model_config(MODEL, **fields)


@pytest.mark.parametrize("fields,match", [
    (dict(ssm_out_multiplier=0.5), "without a mixer"),
    (dict(ssm_multipliers=(0.5, 1.0, 1.0, 1.0, 1.0)), "without a mixer"),
    (dict(ssm_state_size=16), "needs ssm_num_heads"),
], ids=["out", "five", "sizes"])
def test_a_mixers_field_without_a_mixer_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        get_model_config("llama3-tiny", **fields)


def test_a_latent_model_refuses_the_multipliers():
    with pytest.raises(ValueError, match="no latent layer reads"):
        get_model_config("openpangu-ultra-moe-tiny", key_multiplier=0.5)


def test_seed_stream_is_the_programs_init_bit_for_bit(tiny):
    mc, params, weights = tiny
    tree = reference.FromTree(published(mc), params)
    for layer in (0, 3):
        a, b = weights.layer(layer), tree.layer(layer)
        assert set(a) == set(b)
        for name in a:
            assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), \
                (layer, name)
    assert np.array_equal(np.asarray(weights.head()), np.asarray(tree.head()))
    assert np.array_equal(np.asarray(weights.embedding()),
                          np.asarray(tree.embedding()))
    # the family's initialisation, in float32
    lay = params["layers"]
    assert np.allclose(np.exp(np.asarray(lay["a_log"][0])), [1, 2, 3, 4])
    assert (np.asarray(lay["d_skip"]) == 1).all()
    assert lay["a_log"].dtype == lay["dt_bias"].dtype == jnp.float32
    dt = np.log1p(np.exp(np.asarray(lay["dt_bias"])))
    assert (dt > 9e-4).all() and (dt < 0.11).all()
    # drawn over the multipliers: the keys' matrix 1 / (0.9 x 0.011) wider
    wk = np.asarray(tree.layer(0)["wk"])
    want = mc.hidden_size ** -0.5 / (
        mc.attention_in_multiplier * mc.key_multiplier)
    assert 0.9 * want < wk.std() < 1.1 * want


def test_each_branch_adds_a_comparable_share_to_the_residual(tiny):
    """Under the multipliers, with the weights drawn over them: the mixer,
    attention and the MLP of a layer each add between a tenth and ten
    times what the others add."""
    mc, _, weights = tiny
    s, w = reference.dims(published(mc)), weights.layer(1)
    x = jax.random.normal(jax.random.PRNGKey(5), (48, mc.hidden_size))
    with jax.default_matmul_precision("highest"):
        u = reference._rms_norm(x, w["attn_norm"], s["eps"])
        mix = s["m_sout"] * reference.mixer(s, w, u)
        att = s["m_aout"] * reference.attention(s, w, u)
        mlp = reference.layer_forward(s, w, x) - (x + mix + att)
    norms = [float(jnp.sqrt(jnp.mean(v * v))) for v in (mix, att, mlp)]
    assert max(norms) < 10 * min(norms), norms


# --------------------------------------------------------------------- #
# forward_chunk against the reference
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("n", [5, 40, 100])
def test_forward_chunk_matches_the_reference_in_float32(tiny, n):
    mc, params, weights = tiny
    prompt = _prompt(n)
    (want,) = reference.forward(published(mc), weights, [prompt])
    kv, tables = _pools(mc, 1)
    out = llama.forward_chunk(
        mc, params, jnp.asarray([prompt]), jnp.arange(n)[None], kv, tables,
        jnp.asarray([n]), block_size=BLOCK)
    assert _rel(out.logits[0, 0], want[0]) < TOL


def test_pieces_then_decode_through_pool_and_pages(tiny):
    """Four rows of unequal prompts in 24-token pieces (chunk edges at 16
    and piece edges at 24 fall apart), then six decode steps: the logits of
    every position against the reference's full forward passes."""
    mc, params, weights = tiny
    prompts = [_prompt(n) for n in (70, 5, 100, 33)]
    want, fed = _reference_chain(mc, weights, prompts, 6)
    rows = len(prompts)
    kv, tables = _pools(mc, rows)
    fwd = jax.jit(lambda t, p, kv, lens: llama.forward_chunk(
        mc, params, t, p, kv, tables, lens, block_size=BLOCK))
    got = [[] for _ in prompts]
    for start in range(0, 100, 24):
        toks = np.zeros((rows, 24), np.int32)
        pos = np.full((rows, 24), -1, np.int32)
        for r, p in enumerate(prompts):
            piece = p[start:start + 24]
            toks[r, :len(piece)] = piece
            pos[r, :len(piece)] = start + np.arange(len(piece))
        out = fwd(toks, pos, kv, (pos.max(1) + 1).clip(min=0))
        kv = out.kv
        for r, p in enumerate(prompts):
            n = len(p[start:start + 24])
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
        assert _rel(np.stack(g), w) < TOL


def test_a_packed_round_of_pieces_beside_decode_rows(tiny):
    """One packed round holds a decoding row (one token from its stored
    state), a second piece (from its row's stored state and tail) and a
    fresh piece: each row's logits are what the reference gives it, and the
    idle row's state is untouched."""
    mc, params, weights = tiny
    prompts = [_prompt(40), _prompt(90), _prompt(20)]
    want = reference.forward(published(mc), weights, prompts)
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

    kv = rect([prompts[0][:39], prompts[1][:50], [], []], [0, 0, 0, 0], kv).kv
    segs = [(0, prompts[0][39:], 39), (1, prompts[1][50:], 50),
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
    for r, w in ((0, want[0]), (1, want[1]), (3, want[2])):
        assert _rel(out.logits[r, 0], w[0]) < TOL
    assert not np.asarray(out.kv[ssd.STATE][:, 2]).any()
    assert not np.asarray(out.kv[ssd.CONV][:, 2]).any()


# --------------------------------------------------------------------- #
# the mixer alone: the chunked form, the recurrence, the kernels
# --------------------------------------------------------------------- #

def _mixer_alone(mc, lp, u, cuts, rows=2, row=1, kernels=False,
                 state_dtype=jnp.float32):
    """Layer ``lp``'s mixer over ``u [S, h]`` in row ``row`` of a pool of
    ``rows``, the segment cut at ``cuts`` (a cut of one token goes through
    the step form) → (output ``[S, h]``, the row's state)."""
    kv = ssd.init_state_pools(mc, rows, conv_dtype=jnp.float32,
                              state_dtype=state_dtype)

    def proj(x, name):
        return x @ lp[name]

    outs, start = [], 0
    for end in list(cuts) + [u.shape[0]]:
        n = end - start
        if n == 0:
            continue
        if n == 1:
            x = jnp.zeros((rows, 1, u.shape[1])).at[row, 0].set(u[start])
            pos = jnp.full((rows, 1), -1, jnp.int32).at[row, 0].set(start)
            out, kv = ssd.mixer(mc, x, lp, proj, kv, 0, plan=None,
                                positions=pos, kernels=kernels)
            outs.append(out[row])
        else:
            plan = ssd.make_plan(
                mc, jnp.full((n,), row, jnp.int32),
                jnp.arange(n, dtype=jnp.int32),
                start + jnp.arange(n, dtype=jnp.int32), rows)
            out, kv = ssd.mixer(mc, u[None, start:end], lp, proj, kv, 0,
                                plan=plan, positions=None, kernels=kernels)
            outs.append(out[0])
        start = end
    return jnp.concatenate(outs), kv[ssd.STATE][0, row]


@pytest.mark.parametrize("cut", [15, 16, 17, 31, 32, 33, 46])
def test_the_chunked_form_is_the_recurrence_across_a_cut(tiny, cut):
    """A 50-token segment cut into two pieces at every length around a
    chunk edge (16, 32), and then a step: output and state are the
    recurrence's, token by token."""
    mc, params, weights = tiny
    s, w = reference.dims(published(mc)), weights.layer(2)
    lp = jax.tree.map(lambda a: a[2], params["layers"])
    lp = {k: (v["qw"].astype(jnp.float32) * v["scale"]
              if isinstance(v, dict) else v) for k, v in lp.items()}
    u = jax.random.normal(jax.random.PRNGKey(cut), (50, mc.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = reference.mixer(s, w, u)
        _, _, state = reference.mixer_recurrence(s, w, u)
        got, held = _mixer_alone(mc, lp, u, [cut, 49])
    assert _rel(got, want) < 1e-4
    assert _rel(held, state) < 1e-4


def _step_operands(key, r=4, h=4, p=64, n=32, g=2, layers=3):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (r, h, p))
    b = jax.random.normal(ks[1], (r, g, n))
    c = jax.random.normal(ks[2], (r, g, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (r, h)) - 3.0)
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    pool = jax.random.normal(ks[4], (layers, r, h, p, n))
    return x, b, c, dt, a, pool


def test_a_masked_step_leaves_a_rows_state_to_the_bit():
    x, b, c, dt, a, pool = _step_operands(jax.random.PRNGKey(0))
    live = jnp.asarray([True, False, True, False])
    fresh = jnp.asarray([False, False, True, True])
    for step in (ssd.step_xla,
                 lambda *args: ssd_pallas.ssd_step(*args, interpret=True)):
        _, out = step(x, b, c, dt, a, pool, 1, live, fresh)
        assert np.array_equal(np.asarray(out[1, 1]), np.asarray(pool[1, 1]))
        assert np.array_equal(np.asarray(out[1, 3]), np.asarray(pool[1, 3]))
        assert np.array_equal(np.asarray(out[0]), np.asarray(pool[0]))
        assert not np.array_equal(np.asarray(out[1, 0]),
                                  np.asarray(pool[1, 0]))


def test_the_step_kernel_is_its_xla_form():
    x, b, c, dt, a, pool = _step_operands(jax.random.PRNGKey(1))
    live = jnp.asarray([True, True, True, False])
    fresh = jnp.asarray([False, True, False, False])
    y0, p0 = ssd.step_xla(x, b, c, dt, a, pool, 2, live, fresh)
    y1, p1 = ssd_pallas.ssd_step(x, b, c, dt, a, pool, 2, live, fresh,
                                 interpret=True)
    assert _rel(y1[:3], y0[:3]) < 1e-5 and _rel(p1, p0) < 1e-5


def test_the_chunk_kernel_is_its_xla_form(tiny):
    """A round of a fresh piece over three chunks, a continued piece over
    two, a decode row and an idle row: the kernel's pass over the state
    pool gives the XLA pass's outputs and states."""
    mc, _, _ = tiny
    rows, t = 4, 80
    segs = [(0, 40, 0), (2, 25, 7), (3, 1, 30)]       # row, tokens, start
    row = np.concatenate([np.full(n, r) for r, n, _ in segs]
                         + [np.full(t - 66, rows)])
    col = np.concatenate([np.arange(n) for _, n, _ in segs]
                         + [np.zeros(t - 66, int)])
    pos = np.concatenate([s + np.arange(n) for _, n, s in segs]
                         + [np.full(t - 66, -1)])
    plan = ssd.make_plan(mc, jnp.asarray(row, jnp.int32),
                         jnp.asarray(col, jnp.int32),
                         jnp.asarray(pos, jnp.int32), rows)
    assert int(plan.chunk_first.sum()) == 3 == int(plan.chunk_last.sum())
    h, p, n, g = 4, 64, 32, 2
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (t, h, p))
    b = jax.random.normal(ks[1], (t, g, n))
    c = jax.random.normal(ks[2], (t, g, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (t, h)) - 3.0)
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    pool = jax.random.normal(ks[4], (2, rows, h, p, n))
    ops = ssd.chunk_prepare(x, b, c, dt, a, plan)
    y0, p0 = ssd.chunk_pass_xla(ops, pool, 1, plan)
    y1, p1 = ssd_pallas.ssd_chunk_pass(
        ops, pool, 1, plan.chunk_row, plan.chunk_first, plan.chunk_last,
        plan.chunk_fresh, interpret=True)
    assert _rel(y1, y0) < 1e-5 and _rel(p1, p0) < 1e-5
    assert np.array_equal(np.asarray(p1[0]), np.asarray(pool[0]))
    assert np.array_equal(np.asarray(p1[1, 1]), np.asarray(pool[1, 1]))


# --------------------------------------------------------------------- #
# the comparison's planted faults, at the tiny size
# --------------------------------------------------------------------- #

def test_a_bfloat16_state_fails_the_state_limit(tiny):
    """The control that the logits cannot see at this size: the state
    carried in bfloat16 through 40 steps is off the recurrence's by a
    hundred times the float32 state's error."""
    mc, params, weights = tiny
    s, w = reference.dims(published(mc)), weights.layer(1)
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    lp = {k: (v["qw"].astype(jnp.float32) * v["scale"]
              if isinstance(v, dict) else v) for k, v in lp.items()}
    u = jax.random.normal(jax.random.PRNGKey(7), (60, mc.hidden_size))
    with jax.default_matmul_precision("highest"):
        _, _, state = reference.mixer_recurrence(s, w, u)
        cuts = list(range(20, 60))
        _, exact = _mixer_alone(mc, lp, u, cuts)
        _, rounded = _mixer_alone(mc, lp, u, cuts, state_dtype=jnp.bfloat16)
    assert _rel(exact, state) < 1e-5
    assert _rel(rounded, state) > 1e-3


@pytest.mark.parametrize("broken", [
    "tail", "key_multiplier", "b_multiplier", "no_attention",
    "gate_after_norm"])
def test_a_block_that_departs_from_the_description_fails(tiny, broken,
                                                         monkeypatch):
    """The controls of the chip comparison, at the tiny size: each leaves
    the tolerance by a wide margin, over two 50-token pieces."""
    mc, params, weights = tiny
    prompt = _prompt(100)
    (want,) = reference.forward(published(mc), weights, [prompt])
    kv, tables = _pools(mc, 1)
    if broken == "tail":
        monkeypatch.setattr(ssd, "read_tails", lambda pool, layer: jnp.zeros(
            pool.shape[1:], pool.dtype))
    elif broken == "key_multiplier":
        mc = dataclasses.replace(mc, key_multiplier=1.0)
    elif broken == "b_multiplier":
        mz, mx, _, mc_, mdt = mc.ssm_multipliers
        mc = dataclasses.replace(mc, ssm_multipliers=(mz, mx, 1.0, mc_, mdt))
    elif broken == "no_attention":
        mc = dataclasses.replace(mc, attention_out_multiplier=0.0)
    else:
        monkeypatch.setattr(ssd, "gated_norm", gate_after_norm)
    for start in (0, 50):
        out = llama.forward_chunk(
            mc, params, jnp.asarray([prompt[start:start + 50]]),
            start + jnp.arange(50)[None], kv, tables,
            jnp.asarray([start + 50]), block_size=BLOCK)
        kv = out.kv
    assert _rel(out.logits[0, 0], want[0]) > 10 * TOL


# --------------------------------------------------------------------- #
# through the engine: pool and pages in one cache manager
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
    return (np.asarray(eng.kv[ssd.STATE][:, slot]),
            np.asarray(eng.kv[ssd.CONV][:, slot]))


@pytest.fixture(scope="module")
def chain():
    """A reference run: one 40-token prompt alone, 24 new tokens."""
    eng = _engine()
    resp = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    return resp.token_ids


def test_engine_rounds_follow_the_reference_and_count():
    eng = _engine()
    mc = eng.model_cfg
    assert eng.stats["kv_layout"] == "kv+state"
    assert eng.manager.state_rows == 4 == eng.stats["state_rows"]
    assert eng.stats["state_pool_bytes"] == 4 * mc.state_bytes_per_row(4)
    cfg = published(mc)
    weights = reference.FromTree(cfg, eng.params)
    prompts, new = [_prompt(40), _prompt(9)], 5
    slots, first = _admit(eng, prompts, [new, new])
    scan = eng.decode_multi(new - 1)
    for prompt, slot in zip(prompts, slots):
        seq = list(prompt)
        for step, tok in enumerate(first[slot] + scan[slot]):
            (want,) = reference.forward(cfg, weights, [seq])
            top2 = np.sort(want[0])[-2:]
            if top2[1] - top2[0] > 20 * TOL * np.abs(want[0]).max():
                assert tok == int(want[0].argmax()), (len(prompt), step)
            seq.append(tok)
    st = eng.get_stats()
    # the rounds: 32 of the 40 tokens beside the 9-token prompt, then the
    # last 8 beside the short prompt's first decode token; chunks of 16
    assert st["ssd_tokens_ragged"] == 32 + 9 + 8 + 1
    assert st["ssd_segments_ragged"] == 4
    assert st["ssd_chunks_ragged"] == 2 + 1 + 1 + 1
    assert st["ssd_row_steps_scan"] == mc.num_layers * sum(
        len(scan[slot]) for slot in slots) == mc.num_layers * 7
    assert st["state_binds"] == 2 and st["prefix_hits_without_state"] == 0
    assert "kda_row_steps_scan" not in st


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
    as its last live step left it, and row A's tokens are what A alone
    gives."""
    both = _engine()
    (a, b), f1 = _admit(both, [_prompt(40), _prompt(23)], [24, 4])
    got = both.decode_multi(steps)
    assert f1[a] + got[a] == chain[:1 + min(steps, 23)]
    assert len(f1[b]) == 2 == len(got[b])
    # B ran two live steps: a scan of exactly two gives the same state, to
    # the bit
    short = _engine()
    (_, b2), _ = _admit(short, [_prompt(40), _prompt(23)], [24, 4])
    short.decode_multi(2)
    for x, y in zip(_state(short, b2), _state(both, b)):
        assert np.array_equal(x, y)


def test_a_radix_match_gives_no_cached_tokens_and_is_counted(chain):
    """The prefix cache holds the first request's pages; the second request
    on the same prompt matches them, gets no cached tokens (pages alone
    back them, no state) and generates the same tokens."""
    eng = _engine(enable_prefix_cache=True)
    first = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    assert first.token_ids == chain
    again = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    assert again.token_ids == chain
    st = eng.get_stats()
    assert st["prefix_hits_without_state"] == 1
    assert st["kv_cache"]["prefix_hit_tokens"] == 0
    assert st["state_binds"] == 2


@pytest.mark.parametrize("kw,match", [
    (dict(spill_host_blocks=8), "spill tiers carry K/V pages, not the state"),
    (dict(kv_cache_dtype="int8", block_size=32), "beside a state pool"),
    (dict(kv_cache_dtype="fp8", block_size=32), "beside a state pool"),
    (dict(kv_seq_sharded=True), "sequence axis"),
], ids=["spill", "int8-kv", "fp8-kv", "seq-sharded"])
def test_what_cannot_carry_a_state_row_is_refused_at_configuration(kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(**kw)


def test_a_mesh_and_a_speculative_chain_are_refused_at_configuration():
    from jax.sharding import Mesh

    from distributed_gpu_inference_tpu.runtime.speculative import (
        SpecDecodeConfig,
    )

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with pytest.raises(ValueError, match="served on one chip"):
        TPUEngine(get_model_config(MODEL), EngineConfig(
            max_batch_size=4, max_seq_len=256, dtype="float32"), mesh=mesh)
    with pytest.raises(ValueError, match="speculative decoding"):
        _engine(speculative=SpecDecodeConfig())


def test_the_handoff_wire_refuses_the_engine():
    from distributed_gpu_inference_tpu.runtime import kv_handoff

    eng = _engine()
    with pytest.raises(ValueError, match="not the state row"):
        kv_handoff.require_kv_pages(eng)
    with pytest.raises(ValueError, match="not the state row"):
        kv_handoff.export_slot_kv(eng, 0)
