"""A Mamba-2 (state-space duality, SSD) mixer over a **state pool**: the
token mixer that stands BESIDE attention in every layer of a Falcon-H1-class
model (``models/llama.py`` sums the two), whose past is not pages but one
fixed-size state a sequence.

For a token ``t`` (``u`` the layer's normed input, ``H`` heads of ``P``
channels, a state ``S`` of ``P x N`` float32 a head, ``G`` groups: head
``j`` reads ``B`` / ``C`` of group ``j // (H / G)``)::

    p = ((ssm_in_multiplier * u) W_in) * mup      z | x | B | C | dt
    xBC = silu(conv(xBC) + b_conv)                causal depthwise, 4 taps
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S = exp(dt A) S + dt x (x) B;  y = S C + D x
    y = group_rms(y * silu(z)) * w_norm;  out = y W_out

``W_in`` is held as two leaves: ``w_in`` (``z | x | B | C``, whole
128-column tiles, quantized where the engine quantizes) and ``w_dt`` (the
``H`` step-size columns, under a tile: kept in the activation dtype). What a
sequence carries between calls is ``S`` of every head and the last ``taps -
1`` pre-activation rows of ``xBC`` (the convolution's tail): ``{"ssm_state":
[L, rows, H, P, N] float32, "ssm_conv": [L, rows, taps - 1, P H + 2 G N]}``
beside ``"k"`` / ``"v"`` in the one ``kv`` dict the engine donates through
its round graphs. A row is a batch row of the engine (a slot); a segment
whose first token sits at position 0 starts from a zero state and a zero
tail, which is what binds a row to a new sequence
(``models/state_pool.py``).

Two forms of the same numbers:

- **the recurrence** (:func:`step_xla`, kernel ``dgi_ssd_step``): one token
  a row, a scan step;
- **the chunked form** (:func:`chunk_prepare` + :func:`chunk_pass_xla`,
  kernel ``dgi_ssd_chunk``): a round's packed tokens cut into chunks of
  ``cfg.ssm_chunk_size`` per segment. With ``l_t`` the cumulative ``dt A``
  inside a chunk, ``Y = ((C B^T) o exp(l_t - l_s))_{s<=t} (dt x) + exp(l_t)
  C_t S_prev`` and ``S_end = exp(l_Q) S_prev + sum_s exp(l_Q - l_s) dt_s x_s
  (x) B_s``: only ``exp`` of differences <= 0 is formed. The first term
  needs no state and is computed for all chunks at once, ahead of the pass.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_gpu_inference_tpu.models.configs import ModelConfig
from distributed_gpu_inference_tpu.models import state_pool as _pool
from distributed_gpu_inference_tpu.models.state_pool import Plan

STATE, CONV = "ssm_state", "ssm_conv"
POOLS = (STATE, CONV)
_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def init_state_pools(cfg: ModelConfig, rows: int, conv_dtype=None,
                     state_dtype=F32) -> Dict[str, jax.Array]:
    """The state pool of ``rows`` sequences: zero, as a fresh row is."""
    lyr, h = cfg.num_layers, cfg.ssm_num_heads
    return {
        STATE: jnp.zeros((lyr, rows, h, cfg.ssm_head_dim,
                          cfg.ssm_state_size), state_dtype),
        CONV: jnp.zeros((lyr, rows, cfg.ssm_conv_kernel - 1,
                         cfg.ssm_conv_dim), jnp.dtype(conv_dtype or cfg.dtype)),
    }


def kernels_on(cfg: ModelConfig, state_dtype, pallas: bool = True) -> bool:
    """Trace-time choice of the two kernels (``ops/ssd_pallas``): a TPU
    backend, the caller's ``pallas`` (no mesh), a float32 state of whole
    128-lane tiles."""
    from distributed_gpu_inference_tpu.ops import attention as _attention

    return (pallas and _attention.pallas_backend()
            and jnp.dtype(state_dtype) == jnp.float32
            and cfg.ssm_head_dim % 128 == 0
            and cfg.ssm_state_size % 128 == 0)


def make_plan(cfg: ModelConfig, row, col, positions, num_rows: int) -> Plan:
    return _pool.make_plan(row, col, positions, num_rows,
                           chunk=cfg.ssm_chunk_size)


def chunk_plan(cfg: ModelConfig, packing, packed_positions, positions,
               num_rows: int) -> Optional[Plan]:
    return _pool.chunk_plan(packing, packed_positions, positions, num_rows,
                            chunk=cfg.ssm_chunk_size)


def read_tails(conv_pool: jax.Array, layer) -> jax.Array:
    """A layer's stored tails (``models/state_pool.read_tails``), under
    this module's own name: what a test or a comparison replaces to drop
    them."""
    return _pool.read_tails(conv_pool, layer)


# ---------------------------------------------------------------------------
# the recurrence: one token a row
# ---------------------------------------------------------------------------


def step_xla(x, b, c, dt, a, state_pool, layer, live, fresh):
    """``x [R, H, P]``, ``b, c [R, G, N]``, ``dt [R, H]`` float32, ``a
    [H]``; ``live`` / ``fresh [R]`` → (``S C`` ``[R, H, P]``, the pool). A
    row that is not live keeps its state to the bit."""
    hpg = x.shape[1] // b.shape[1]
    b, c = jnp.repeat(b, hpg, axis=1), jnp.repeat(c, hpg, axis=1)
    s_old = lax.dynamic_index_in_dim(state_pool, layer, 0, keepdims=False)
    s = jnp.where(fresh[:, None, None, None], 0, s_old.astype(F32))
    s = s * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * b[:, :, None, :]
    y = jnp.einsum("rhpn,rhn->rhp", s, c, precision=_HI)
    new = jnp.where(live[:, None, None, None], s.astype(s_old.dtype), s_old)
    return y, lax.dynamic_update_index_in_dim(state_pool, new, layer, 0)


# ---------------------------------------------------------------------------
# the chunked form
# ---------------------------------------------------------------------------


class ChunkOperands(NamedTuple):
    """Per chunk, float32, what the pass over the state takes and what is
    made of its output: with ``S0`` the state a chunk starts from, the pass
    gives ``ys = c S0^T`` and ``S1 = dlast S0 + xdt b``; the chunk's output
    is ``intra + el ys``."""

    c: jax.Array        # [C, G, Q, N]
    b: jax.Array        # [C, G, Q, N]
    xdt: jax.Array      # [C, H, P, Q]  x dt exp(l_Q - l), transposed
    dlast: jax.Array    # [C, H]        exp(l_Q)
    el: jax.Array       # [C, H, Q]     exp(l)
    intra: jax.Array    # [C, H, Q, P]  the in-chunk term


def chunk_prepare(x, b, c, dt, a, plan: Plan) -> ChunkOperands:
    """``x [T, H, P]``, ``b, c [T, G, N]``, ``dt [T, H]`` float32 on the
    flat axis, ``a [H]`` → the chunks' operands. An empty place of a chunk
    neither decays nor writes (all zero)."""
    hpg = x.shape[1] // b.shape[1]

    def lay(v):                                      # [C, ., Q, ...]
        v = jnp.take(v, plan.gather, axis=0, mode="fill", fill_value=0)
        return jnp.moveaxis(v, 2, 1)

    x, b, c = lay(x), lay(b), lay(c)
    dt = lay(dt[..., None])[..., 0]                               # [C, H, Q]
    l = jnp.cumsum(dt * a[None, :, None], axis=-1)                # <= 0
    last = l[..., -1:]
    cb = jnp.einsum("cgqn,cgsn->cgqs", c, b, precision=_HI)       # [C,G,Q,Q]
    q = l.shape[-1]
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(seen, l[..., :, None] - l[..., None, :],
                              -jnp.inf))                          # [C,H,Q,Q]
    m = jnp.repeat(cb, hpg, axis=1) * decay
    intra = jnp.einsum("chqs,chsp->chqp", m, x * dt[..., None],
                       precision=_HI)
    xdt = x * (dt * jnp.exp(last - l))[..., None]
    return ChunkOperands(
        c=c, b=b, xdt=jnp.swapaxes(xdt, -1, -2), dlast=jnp.exp(last[..., 0]),
        el=jnp.exp(l), intra=intra)


def chunk_pass_xla(ops: ChunkOperands, state_pool, layer, plan: Plan):
    """The chunks in order, each segment from its row's stored state →
    (``ys [C, H, Q, P]``, the pool)."""
    r = plan.count.shape[0]
    hpg = ops.xdt.shape[1] // ops.b.shape[1]
    states = lax.dynamic_index_in_dim(state_pool, layer, 0, keepdims=False)

    def body(carry, xs):
        states, s = carry
        c, b, xdt, dlast, row, first, last, fresh = xs
        stored = jnp.take(states, row, axis=0, mode="fill",
                          fill_value=0).astype(F32)
        s = jnp.where(first, jnp.where(fresh, 0.0, stored), s)
        ys = jnp.einsum("hqn,hpn->hqp", jnp.repeat(c, hpg, axis=0), s,
                        precision=_HI)
        s = s * dlast[:, None, None] + jnp.einsum(
            "hpq,hqn->hpn", xdt, jnp.repeat(b, hpg, axis=0), precision=_HI)
        states = states.at[jnp.where(last, row, r)].set(
            s.astype(states.dtype), mode="drop")
        return (states, s), ys

    (states, _), ys = lax.scan(
        body, (states, jnp.zeros(states.shape[1:], F32)),
        (ops.c, ops.b, ops.xdt, ops.dlast, plan.chunk_row, plan.chunk_first,
         plan.chunk_last, plan.chunk_fresh))
    return ys, lax.dynamic_update_index_in_dim(state_pool, states, layer, 0)


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------


def projected(cfg: ModelConfig, xf: jax.Array, proj
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``xf [T, hidden]`` the normed input → (``z [T, d_ssm]``, ``xBC [T,
    conv_dim]`` pre-activation, ``dt [T, H]`` before its bias), each under
    its entry of ``ssm_multipliers``, in the activation dtype."""
    d, gn = cfg.ssm_inner, cfg.ssm_num_groups * cfg.ssm_state_size
    mz, mx, mb, mc, mdt = cfg.ssm_multipliers
    mup = jnp.concatenate([
        jnp.full((n,), m, F32)
        for n, m in ((d, mz), (d, mx), (gn, mb), (gn, mc))])
    xin = xf * jnp.asarray(cfg.ssm_in_multiplier, xf.dtype)
    p = (proj(xin, "w_in").astype(F32) * mup).astype(xf.dtype)
    dt = (proj(xin, "w_dt").astype(F32) * mdt).astype(xf.dtype)
    return p[:, :d], p[:, d:], dt


def gated_norm(cfg: ModelConfig, y: jax.Array, z: jax.Array,
               w_norm: jax.Array) -> jax.Array:
    """``y, z [T, d_ssm]`` → the gate, THEN an RMS norm over each group's
    channels (``mamba_norm_before_gate`` false), times ``w_norm``."""
    t, g = y.shape[0], cfg.ssm_num_groups
    y = (y * jax.nn.silu(z.astype(F32))).reshape(t, g, -1)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                      + cfg.rms_norm_eps)
    return y.reshape(t, -1) * w_norm.astype(F32)


def mixer(
    cfg: ModelConfig, x: jax.Array, lp: Dict[str, Any], proj,
    kv: Dict[str, jax.Array], layer, *, plan: Optional[Plan],
    positions: jax.Array, kernels: bool,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``x [b, s, h]`` the normed input (``[1, T, h]`` for a packed round)
    → (the mixer's output before ``ssm_out_multiplier``, ``kv`` with this
    layer's state and tail advanced). ``plan`` None: one token a row,
    ``positions [R, 1]``."""
    bsz, s, hid = x.shape
    nh, p, n, g = (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
                   cfg.ssm_num_groups)
    d, t = nh * p, bsz * s
    z, pre, dt = projected(cfg, x.reshape(t, hid), proj)
    if plan is None:
        live, fresh = positions[:, 0] >= 0, positions[:, 0] == 0
        conv, conv_pool = _pool.conv_step(
            pre, lp["conv"], kv[CONV], layer, live, fresh, read=read_tails)
    else:
        conv, conv_pool = _pool.conv_segments(
            pre, lp["conv"], kv[CONV], layer, plan, read=read_tails)
    xbc = jax.nn.silu(conv + lp["conv_bias"].astype(F32))
    xs = xbc[:, :d].reshape(t, nh, p)
    b = xbc[:, d:d + g * n].reshape(t, g, n)
    c = xbc[:, d + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"].astype(F32))
    a = -jnp.exp(lp["a_log"].astype(F32))
    state = kv[STATE]
    if kernels:
        from distributed_gpu_inference_tpu.ops import ssd_pallas
    if plan is None:
        if kernels:
            y, state = ssd_pallas.ssd_step(xs, b, c, dt, a, state, layer,
                                           live, fresh)
        else:
            y, state = step_xla(xs, b, c, dt, a, state, layer, live, fresh)
    else:
        with jax.named_scope("dgi_ssd_prepare"):
            ops = chunk_prepare(xs, b, c, dt, a, plan)
        if kernels:
            ys, state = ssd_pallas.ssd_chunk_pass(
                ops, state, layer, plan.chunk_row, plan.chunk_first,
                plan.chunk_last, plan.chunk_fresh)
        else:
            ys, state = chunk_pass_xla(ops, state, layer, plan)
        yc = ops.intra + ops.el[..., None] * ys
        y = jnp.take(jnp.moveaxis(yc, 1, 2).reshape(-1, nh, p), plan.place,
                     axis=0, mode="fill", fill_value=0)
    y = y + lp["d_skip"].astype(F32)[None, :, None] * xs
    y = gated_norm(cfg, y.reshape(t, d), z, lp["ssm_norm"])
    out = proj(y.astype(x.dtype).reshape(bsz, s, d), "w_out")
    return out.astype(x.dtype), {**kv, STATE: state, CONV: conv_pool}
