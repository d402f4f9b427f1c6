"""The routed expert layer's STEP form (a scan step's few rows as one
resident tile, one ``dgi_moe_gmm_step`` call a layer:
``ops/moe_gmm_pallas.py``) against the tiled form it stands beside and
against the dense float32 form, at the three sparse models' tiny presets.

Three ways through the same plan: the XLA twin (``routed_step_layer``: what
the CPU runs), the kernel in interpret mode over int8 weights kept whole
and addressed by layer index (what one chip runs), and the tiled form
(``decode=False``: what a round with a piece keeps). Tolerances are the
ones ``tests/test_model_olmoe.py`` holds the tiled form to: float32
activations, 1e-5 against the dense form."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_gpu_inference_tpu.models import llama, mla
from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.ops import moe_gmm_pallas as moe_gmm
from distributed_gpu_inference_tpu.ops.quantization import (
    dequantize,
    quantize_weight,
)

# preset -> experts stored here (its own share where it holds one; the
# latent preset is cut as its engine tests cut it)
MODELS = {"olmoe-tiny": 8, "openpangu-ultra-moe-tiny": 4,
          "kimi-linear-tiny": 2}
TOL = 1e-5
NAMES = ("we_gate", "we_up", "we_down")


def _layer(model, stored, seed=0):
    """A layer's stored expert weights at the preset's widths, plain and
    (stacked under a zero layer) int8 with their dequantized values."""
    cfg = get_model_config(model, dtype="float32")
    h = cfg.hidden_size
    i = cfg.moe_intermediate_size or cfg.intermediate_size
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = ((stored, h, i), (stored, h, i), (stored, i, h))
    lp = {n: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[1])
          for n, k, s in zip(NAMES, keys, shapes)}
    stacked = {n: jax.tree.map(lambda a: jnp.stack([jnp.zeros_like(a), a]),
                               quantize_weight(lp[n], "int8"))
               for n in NAMES}
    deq = {n: dequantize(jax.tree.map(lambda a: a[1], w))
           for n, w in stacked.items()}
    return cfg, lp, stacked, deq


def _routing(cfg, stored, t, case, seed=1):
    """``(topv, experts, live)`` as ``_moe_mlp`` / ``_experts`` hand them
    to ``_routed_sum``: the router's k of all experts, those stored here
    live."""
    k, e_all = cfg.num_experts_per_tok, cfg.num_experts
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    topv, topi = jax.lax.top_k(jax.random.uniform(keys[0], (t, e_all)), k)
    rows = jnp.ones((t,), bool)
    if case == "finished_rows":
        rows = jnp.arange(t) % 3 != 1
    if case == "one_expert":        # every row's first pair on expert 1
        topi = jnp.concatenate(
            [jnp.ones((t, 1), topi.dtype), stored + topi[:, 1:]], axis=1)
    live = rows[:, None] & (topi < stored)
    if case == "held_elsewhere":    # one pair in the whole step is ours
        live = live & (jnp.arange(t * k).reshape(t, k) == int(
            jnp.argmax(live.reshape(-1))))
    if case == "no_live_pair":
        live = jnp.zeros_like(live)
    if stored == e_all and case in ("all_live", "finished_rows"):
        live = rows         # per token, as _moe_mlp passes it
    return topv, jnp.clip(topi, 0, stored - 1), live


def _dense(x, w, topv, experts, live, act):
    """``sum_k live * topv * down_e(act(gate_e x) * up_e x)``, every expert
    over every row in float32."""
    live = np.broadcast_to(
        np.asarray(live if live.ndim == 2 else live[:, None]), topv.shape)
    mid = act(jnp.einsum("th,ehi->tei", x, w["we_gate"])) \
        * jnp.einsum("th,ehi->tei", x, w["we_up"])
    per = np.asarray(jnp.einsum("tei,eih->teh", mid, w["we_down"]))
    out = np.zeros(x.shape, np.float32)
    for t, j in zip(*np.nonzero(live)):
        out[t] += float(topv[t, j]) * per[t, int(experts[t, j])]
    return out


def _sum(x, lp, routing, stored, act, *, decode, stacked=None, e_all=None):
    topv, experts, live = routing
    t, k = topv.shape
    # the pairs expected here, as _moe_mlp / _experts size the row tiles
    hint = max(t * k * stored // (e_all or stored), 1)
    out, plan = llama._routed_sum(
        x, lp, topv, experts, live, stored, hint, act,
        stacked=stacked, layer_idx=1, decode=decode)
    return np.asarray(out), {
        n: int(v) for n, v in moe_gmm.expert_stats(plan).items()}


@pytest.fixture()
def interpreted(monkeypatch):
    for name in ("routed_step_pallas", "grouped_matmul_pallas"):
        monkeypatch.setattr(moe_gmm, name, functools.partial(
            getattr(moe_gmm, name), interpret=True))


CASES = ["all_live", "finished_rows", "held_elsewhere", "no_live_pair",
         "one_expert"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("model", sorted(MODELS))
def test_step_form_equals_the_tiled_and_the_dense_form(model, case,
                                                       interpreted):
    """8 rows (``T k`` 24-32, over the 2-8 stored experts): the twin and
    the interpreted kernel against the tiled form and the dense one, and
    the counters of the two forms."""
    stored = MODELS[model]
    cfg, lp, stacked, deq = _layer(model, stored)
    act = llama._mlp_act(cfg.activation)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, cfg.hidden_size),
                          jnp.float32)
    routing = _routing(cfg, stored, 8, case)
    both = functools.partial(_sum, x, routing=routing, stored=stored,
                             act=act, e_all=cfg.num_experts)
    twin, stats = both(lp, decode=True)
    tiled, tiled_stats = both(lp, decode=False)
    want = _dense(x, lp, *routing, act)
    assert np.abs(twin - want).max() < TOL
    assert np.abs(twin - tiled).max() < TOL
    kernel, kernel_stats = both({}, decode=True, stacked=stacked)
    tiled_q, _ = both({}, decode=False, stacked=stacked)
    assert np.abs(kernel - _dense(x, deq, *routing, act)).max() < TOL
    assert np.abs(kernel - tiled_q).max() < TOL
    live = np.broadcast_to(np.asarray(
        routing[2] if routing[2].ndim == 2 else routing[2][:, None]),
        routing[0].shape)
    if case == "no_live_pair":
        assert not twin.any() and not kernel.any()
        assert stats["active_experts"] == stats["layer_calls"] == 0
    else:
        assert np.abs(want).max() > 0.01
        assert not twin[~live.any(axis=1)].any()
    if case == "one_expert" and stored < cfg.num_experts:
        assert stats["active_experts"] == 1
    assert stats["assignments"] == live.sum()
    assert stats["active_experts"] == len(
        set(np.asarray(routing[1])[live].tolist()))
    # 8 rows: an expert's pairs fill one 8-row tile of the tiled form
    for name in ("layer_calls", "assignments", "active_experts",
                 "rows_dispatched"):
        assert stats[name] == tiled_stats[name] == kernel_stats[name], name
    assert stats["step_form_calls"] == stats["layer_calls"]
    assert tiled_stats["step_form_calls"] == 0


@pytest.mark.parametrize("t,stored", [(1, 8), (1, 4), (3, 2), (8, 8)],
                         ids=["Tk<E", "Tk<E_share", "Tk>E_share", "Tk>E"])
def test_slots_follow_the_smaller_of_pairs_and_experts(t, stored,
                                                       interpreted):
    """``A_max = min(E_stored, T k)`` on either side, and rows that are no
    multiple of the sublane tile (1 and 3 rows in a tile of 8)."""
    model = {8: "olmoe-tiny", 4: "openpangu-ultra-moe-tiny",
             2: "kimi-linear-tiny"}[stored]
    cfg, lp, stacked, deq = _layer(model, stored, seed=3)
    act = llama._mlp_act(cfg.activation)
    x = jax.random.normal(jax.random.PRNGKey(5), (t, cfg.hidden_size),
                          jnp.float32)
    routing = _routing(cfg, stored, t, "all_live", seed=t)
    plan = moe_gmm.step_plan(routing[1], routing[0], routing[2], stored,
                             moe_gmm.step_rows(t, x.dtype))
    assert plan.slot_expert.shape == (
        min(stored, t * cfg.num_experts_per_tok),)
    used = int(plan.used_slots)
    named = np.asarray(plan.slot_expert)
    assert sorted(set(named[:used].tolist())) == named[:used].tolist()
    assert not named[used:].any()       # never walked: the grid ends
    twin, _ = _sum(x, lp, routing, stored, act, decode=True)
    kernel, _ = _sum(x, {}, routing, stored, act, decode=True,
                     stacked=stacked)
    assert np.abs(twin - _dense(x, lp, *routing, act)).max() < TOL
    assert np.abs(kernel - _dense(x, deq, *routing, act)).max() < TOL


def test_the_kernel_tiles_the_intermediate_axis(monkeypatch):
    """Two intermediate tiles a slot: the accumulator takes each tile's
    share of the down contraction; the grid ends at the experts used."""
    e, h, i, t = 4, 128, 256, 8
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    lp = {n: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[1])
          for n, k, s in zip(NAMES, keys, ((e, h, i), (e, h, i), (e, i, h)))}
    stacked = {n: jax.tree.map(lambda a: a[None],
                               quantize_weight(lp[n], "int8"))
               for n in NAMES}
    deq = {n: dequantize(jax.tree.map(lambda a: a[0], w))
           for n, w in stacked.items()}
    x = jax.random.normal(keys[3], (t, h), jnp.float32)
    topv, experts = jax.lax.top_k(jax.random.uniform(keys[4], (t, e)), 2)
    live = experts != 2                     # expert 2 receives nothing
    plan = moe_gmm.step_plan(experts, topv, live, e, t)
    got = moe_gmm.routed_step_pallas(
        x, stacked, 0, plan, jax.nn.silu, bi=128, interpret=True)
    assert int(plan.used_slots) == 3
    assert np.abs(np.asarray(got) - _dense(
        x, deq, topv, experts, live, jax.nn.silu)).max() < TOL


def _kernel_calls(jaxpr, found):
    """Names of the ``pallas_call`` equations of a jaxpr, nested ones
    (``jit``, ``cond``, ``scan`` bodies) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, found)
    return found


@pytest.mark.parametrize("decode,want", [
    (True, ["dgi_moe_gmm_step"]), (False, ["dgi_moe_gmm"] * 3)],
    ids=["scan_step", "piece"])
def test_a_scan_steps_layer_is_one_kernel_call(decode, want):
    """Traced as one chip traces it (the kernel not interpreted, nothing
    run): a scan step's layer holds ONE ``dgi_moe_gmm_step``, a piece's
    the three grouped matmuls it had."""
    e, h, i, t, k = 4, 128, 256, 8, 2
    w = lambda kk, n: {"qw": jnp.zeros((1, e, kk, n), jnp.int8),  # noqa: E731
                       "scale": jnp.ones((1, e, 1, n), jnp.float32)}
    stacked = {"we_gate": w(h, i), "we_up": w(h, i), "we_down": w(i, h)}

    def layer(x, topv, experts):
        return llama._routed_sum(
            x, {}, topv, experts, jnp.ones((t,), bool), e, t * k,
            jax.nn.silu, stacked=stacked, layer_idx=0, decode=decode)[0]

    traced = jax.make_jaxpr(layer)(
        jnp.zeros((t, h), jnp.bfloat16), jnp.zeros((t, k), jnp.float32),
        jnp.zeros((t, k), jnp.int32))
    assert _kernel_calls(traced.jaxpr, []) == want


@pytest.mark.parametrize("h,i,want", [
    (2048, 1024, 512), (2304, 1024, 512), (7680, 2048, 512),
    (7680, 1536, 512), (16384, 1024, 256), (2048, 640, 128),
    (2048, 1000, None),
])
def test_step_blocks_follow_bytes(h, i, want):
    """``bi`` is the longest multiple of 128 that divides ``I`` inside the
    column cap and the byte budget of the step's three blocks: 512 at the
    three models' widths (the chip's table: PERF.md section 6, PR 43)."""
    assert moe_gmm.step_tile(h, i) == want
    if want:
        assert 3 * h * want <= moe_gmm._STEP_BLOCK_BYTES


def test_only_a_scan_step_of_one_tile_takes_the_step_form():
    """The form follows ``s == 1`` and the row count, nothing else: up to
    one MXU tile of rows; a piece (``s > 1``) keeps the tiled form
    whatever its length; a hidden axis whose three thinnest blocks pass
    the budget keeps it too."""
    assert moe_gmm.takes_step_form(8, jnp.bfloat16, None)
    assert moe_gmm.takes_step_form(128, jnp.bfloat16, None)
    assert not moe_gmm.takes_step_form(129, jnp.bfloat16, None)
    wide = {"we_gate": {"qw": jax.ShapeDtypeStruct(
        (1, 2, 65536, 256), jnp.int8)}}
    assert not moe_gmm.takes_step_form(8, jnp.bfloat16, wide)
    cfg = get_model_config("olmoe-tiny", dtype="float32")
    p = llama.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], p["layers"])
    for shape, step in (((8, 1), True), ((1, 8), False), ((2, 4), False)):
        x = jax.random.normal(jax.random.PRNGKey(2), shape + (64,),
                              jnp.float32)
        _, stats, _ = llama._moe_mlp(x, lp, cfg)
        assert int(stats["layer_calls"]) == 1
        assert int(stats["step_form_calls"]) == int(step), shape


@pytest.mark.parametrize("s", [1, 5])
def test_a_held_share_counts_its_step_form_calls(s):
    """``models/mla._experts`` (openPangu, Kimi): every pair the router
    kept is counted whichever form runs, and the step form is a scan
    step's alone."""
    mc = get_model_config("openpangu-ultra-moe-tiny", dtype="float32",
                          held_experts=(0, 4))
    params = llama.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    m = jax.random.normal(jax.random.PRNGKey(4),
                          (8 if s == 1 else 2, s, mc.hidden_size))
    out, stats, topi = mla._experts(
        m, lp, mc, lambda x, name: x @ lp[name], live=None, stacked=None,
        layer_idx=0)
    tokens = m.shape[0] * s
    assert int(stats["pairs_routed"]) == tokens * mc.num_experts_per_tok
    assert int(stats["assignments"]) == int((np.asarray(topi) < 4).sum())
    assert int(stats["step_form_calls"]) == (int(stats["layer_calls"])
                                             if s == 1 else 0)
    assert out.shape == m.shape
