"""Gated delta-rule linear attention (Kimi Delta Attention, KDA) over a
**state pool**: the layer of a hybrid model whose past is not pages
addressed by position but one fixed-size state a sequence.

For a token ``t`` of a head (``d`` = ``cfg.kda_head_dim``, the state ``S`` a
float32 ``d x d`` matrix, key channels down, value channels across)::

    q, k, v = silu(conv(x W_qkv))              causal depthwise, 4 taps
    q = l2norm(q) / sqrt(d);  k = l2norm(k)
    g = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias)   per key channel
    beta = sigmoid(x W_b)
    S = diag(exp(g)) S;  u = beta (v - k^T S);  S = S + k u^T;  o = q^T S
    out = (rms_d(o) * w_norm * sigmoid((x W_ga) W_gb)) W_o

What a sequence carries between calls is ``S`` of every head and the last
``taps - 1`` pre-activation rows of ``x W_qkv`` (the convolution's tail):
``{"kda_state": [Lk, rows, H, d, d] float32, "kda_conv": [Lk, rows, taps-1,
3 H d]}`` beside the latent pages in the one ``kv`` dict the engine donates
through its round graphs. A row is a batch row of the engine (a slot); no
position enters the layer, and a segment whose first token sits at position
0 starts from a zero state and a zero tail, which is what binds a row to a
new sequence (no dispatch of its own).

Two forms of the same numbers:

- **the recurrence** (:func:`step_xla`, kernel ``dgi_kda_step``): one token
  a row, a scan step;
- **the chunked form** (:func:`chunk_prepare` + :func:`chunk_pass_xla`,
  kernel ``dgi_kda_chunk``): a round's packed tokens cut into chunks of 64
  per segment (a row's tokens in the round: a prompt piece, or one token of
  a decoding row), each segment from its row's stored state. Inside a
  chunk the delta rule is the inverse of a unit lower-triangular matrix;
  decays enter only as ``exp`` of differences of the cumulative ``g`` that
  are <= 0 (16-token sub-blocks, each against the cumulative value at its
  start), so nothing above ``exp(0)`` is formed whatever the decay. A
  chunk of one token (a decoding row beside a piece) is its own row and
  takes no solve.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_gpu_inference_tpu.models.configs import ModelConfig
from distributed_gpu_inference_tpu.models import state_pool as _pool
from distributed_gpu_inference_tpu.models.state_pool import Plan

STATE, CONV = "kda_state", "kda_conv"
CHUNK = 64
_SUB = 16
_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def init_state_pools(cfg: ModelConfig, rows: int, conv_dtype=None
                     ) -> Dict[str, jax.Array]:
    """The state pool of ``rows`` sequences: zero, as a fresh row is."""
    lk, h, d = cfg.num_kda_layers, cfg.kda_num_heads, cfg.kda_head_dim
    return {
        STATE: jnp.zeros((lk, rows, h, d, d), F32),
        CONV: jnp.zeros((lk, rows, cfg.kda_conv_kernel - 1, 3 * h * d),
                        jnp.dtype(conv_dtype or cfg.dtype)),
    }


def kernels_on(cfg: ModelConfig, state_dtype, pallas: bool = True) -> bool:
    """Trace-time choice of the two KDA kernels (``ops/kda_pallas``): a TPU
    backend, the caller's ``pallas`` (no mesh), a float32 state of whole
    128-lane tiles."""
    from distributed_gpu_inference_tpu.ops import attention as _attention

    return (pallas and _attention.pallas_backend()
            and jnp.dtype(state_dtype) == jnp.float32
            and cfg.kda_head_dim % 128 == 0)


# where a round's tokens sit and the convolution's tail a row: shared with
# the state-space mixer (``models/state_pool.py``); the delta rule's chunks
# are 64 tokens
make_plan = functools.partial(_pool.make_plan, chunk=CHUNK)
chunk_plan = functools.partial(_pool.chunk_plan, chunk=CHUNK)


def read_tails(conv_pool: jax.Array, layer) -> jax.Array:
    """A layer's stored tails (``models/state_pool.read_tails``), under
    this module's own name: what a test or a comparison replaces to drop
    them."""
    return _pool.read_tails(conv_pool, layer)


def conv_step(x, w, conv_pool, layer, live, fresh):
    return _pool.conv_step(x, w, conv_pool, layer, live, fresh,
                           read=read_tails)


def conv_segments(x, w, conv_pool, layer, plan: Plan):
    return _pool.conv_segments(x, w, conv_pool, layer, plan, read=read_tails)


# ---------------------------------------------------------------------------
# the recurrence: one token a row
# ---------------------------------------------------------------------------


def step_xla(q, k, v, g, beta, state_pool, layer, live, fresh):
    """``q, k, v, g [R, H, d]`` float32, ``beta [R, H]``; ``live`` /
    ``fresh [R]`` → (``o [R, H, d]``, the pool). A row that is not live
    keeps its state to the bit."""
    s_old = lax.dynamic_index_in_dim(state_pool, layer, 0, keepdims=False)
    s = jnp.where(fresh[:, None, None, None], 0, s_old.astype(F32))
    s = s * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("rhk,rhkv->rhv", k, s,
                                          precision=_HI))
    s = s + k[..., None] * u[..., None, :]
    o = jnp.einsum("rhk,rhkv->rhv", q, s, precision=_HI)
    new = jnp.where(live[:, None, None, None], s.astype(s_old.dtype), s_old)
    return o, lax.dynamic_update_index_in_dim(state_pool, new, layer, 0)


# ---------------------------------------------------------------------------
# the chunked form
# ---------------------------------------------------------------------------


class ChunkOperands(NamedTuple):
    """Per chunk and head, float32, what the pass over the state takes:
    with ``S0`` the state a chunk starts from, ``U = u - w S0``, ``o = qd
    S0 + b U``, ``S1 = diag(dlast) S0 + kd^T U``."""

    w: jax.Array        # [C, H, 64, d]
    u: jax.Array        # [C, H, 64, d]
    qd: jax.Array       # [C, H, 64, d]
    kd: jax.Array       # [C, H, 64, d]
    b: jax.Array        # [C, H, 64, 64]
    dlast: jax.Array    # [C, H, d]


def _decayed_products(lefts, k, gc):
    """``M[s, r] = sum_i left[s, i] k[r, i] exp(gc[s, i] - gc[r, i])`` for
    ``r <= s`` (zero above the diagonal), for each of ``lefts``; ``[...,
    64, d]`` → ``[..., 64, 64]``. Off the 16-token diagonal blocks both
    factors are decays against the cumulative value at the row block's
    start; inside them the pair's own difference."""
    lead, d = gc.shape[:-2], gc.shape[-1]
    nb = CHUNK // _SUB
    blocks = lambda x: x.reshape(*lead, nb, _SUB, d)          # noqa: E731
    gb, kb = blocks(gc), blocks(k)
    ref = jnp.concatenate(
        [jnp.zeros((*lead, 1, d), F32), gb[..., :-1, -1, :]], axis=-2)
    left_decay = jnp.exp(gb - ref[..., None, :])              # <= 1
    right = k[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., :, None, :] - gc[..., None, :, :], 0.0))     # [.., nb, 64, d]
    pair = jnp.exp(jnp.minimum(
        gb[..., :, None, :] - gb[..., None, :, :], 0.0))      # [.., nb,16,16,d]
    a_id = jnp.arange(nb)[:, None, None]
    s_id = jnp.arange(_SUB)[None, :, None]
    r_id = jnp.arange(CHUNK)[None, None, :]
    before = r_id < a_id * _SUB                               # [nb, 16, 64]
    own = (r_id // _SUB == a_id) & (r_id % _SUB <= s_id)
    outs = []
    for left in lefts:
        lb = blocks(left)
        off = jnp.einsum("...asi,...ari->...asr", lb * left_decay, right,
                         precision=_HI)
        diag = jnp.einsum("...asi,...ari,...asri->...asr", lb, kb, pair,
                          precision=_HI)
        diag = jnp.tile(diag, (1,) * (diag.ndim - 1) + (nb,))
        m = jnp.where(before, off, 0.0) + jnp.where(own, diag, 0.0)
        outs.append(m.reshape(*lead, CHUNK, CHUNK))
    return outs


def chunk_prepare(q, k, v, g, beta, plan: Plan) -> ChunkOperands:
    """``q, k, v, g [T, H, d]`` float32 and ``beta [T, H]`` on the flat
    axis → the chunks' operands. An empty place of a chunk neither decays
    nor writes (all zero).

    Most chunks of a round hold ONE token (a decoding row beside a piece),
    and their operands need no solve (:func:`_lone_operands`). The solve
    runs over the ``T // 64 + 1`` chunks a round's pieces mostly fill,
    picked out; a round with more chunks of several tokens than that (many
    short pieces) takes the solve over all of them."""
    c, t = plan.gather.shape[0], q.shape[0]
    few = t // CHUNK + 1
    if few >= c:
        return _solved_operands(q, k, v, g, beta, plan.gather)
    several = plan.gather[:, 1] < t          # a second place is filled
    pick = jnp.argsort(~several, stable=True)[:few]

    def picked(_):
        part = _solved_operands(q, k, v, g, beta, plan.gather[pick])
        lone = _lone_operands(q, k, v, g, beta, plan.gather[:, 0])
        return jax.tree.map(lambda whole, some: whole.at[pick].set(some),
                            lone, part)

    def every(_):
        return _solved_operands(q, k, v, g, beta, plan.gather)

    return lax.cond(jnp.sum(several) <= few, picked, every, None)


def _lone_operands(q, k, v, g, beta, first) -> ChunkOperands:
    """The operands of chunks whose one token is ``first [C]`` on the flat
    axis (``T``: an empty chunk): nothing stands before the token, so the
    solve is the identity and the operands are its own row."""
    def take(x):
        return jnp.take(x, first, axis=0, mode="fill", fill_value=0)

    def row0(x):                                     # [C, H, n] → [.., 64, n]
        return jnp.zeros((*x.shape[:2], CHUNK, x.shape[-1]), F32).at[
            :, :, 0].set(x)

    q, k, v, g = take(q), take(k), take(v), take(g)
    beta = take(beta)[..., None]
    decay = jnp.exp(g)
    qk = jnp.sum(q * k, axis=-1, keepdims=True)
    return ChunkOperands(
        w=row0(k * beta * decay), u=row0(v * beta), qd=row0(q * decay),
        kd=row0(k), b=row0(jnp.pad(qk, ((0, 0), (0, 0), (0, CHUNK - 1)))),
        dlast=decay)


def _solved_operands(q, k, v, g, beta, gather) -> ChunkOperands:
    """The operands of the chunks whose tokens ``gather [C, 64]`` lists on
    the flat axis (``T``: an empty place)."""

    def lay(x):
        x = jnp.take(x, gather, axis=0, mode="fill", fill_value=0)
        return jnp.moveaxis(x, 2, 1)                 # [C, H, 64, ...]

    q, k, v, g = lay(q), lay(k), lay(v), lay(g)
    beta = lay(beta)[..., None]                      # [C, H, 64, 1]
    gc = jnp.cumsum(g, axis=-2)
    kb, vb = k * beta, v * beta
    a, b = _decayed_products((kb, q), k, gc)
    strict = jnp.tril(jnp.ones((CHUNK, CHUNK), bool), -1)
    # (I + A)^-1 for the strictly lower A: with N = -A nilpotent (N^64 = 0)
    # it is (I + N)(I + N^2)(I + N^4) ... (I + N^32), ten small matmuls
    # where a triangular solve is a custom call that cost a round 20 ms
    mm = lambda x, y: jnp.einsum("...ij,...jk->...ik", x, y,     # noqa: E731
                                 precision=_HI)
    n = -jnp.where(strict, a, 0.0)
    inv = jnp.eye(CHUNK, dtype=F32) + n
    for _ in range(5):
        n = mm(n, n)
        inv = inv + mm(inv, n)
    sol = mm(inv, jnp.concatenate([vb, kb * jnp.exp(gc)], axis=-1))
    d = q.shape[-1]
    glast = gc[..., -1:, :]
    return ChunkOperands(
        w=sol[..., d:], u=sol[..., :d], qd=q * jnp.exp(gc),
        kd=k * jnp.exp(glast - gc), b=b, dlast=jnp.exp(glast[..., 0, :]))


def chunk_pass_xla(ops: ChunkOperands, state_pool, layer, plan: Plan):
    """The chunks in order, each segment from its row's stored state →
    (``o [C, H, 64, d]``, the pool)."""
    r = plan.count.shape[0]
    states = lax.dynamic_index_in_dim(state_pool, layer, 0, keepdims=False)

    def body(carry, xs):
        states, s = carry
        op, row, first, last, fresh = xs
        stored = jnp.take(states, row, axis=0, mode="fill",
                          fill_value=0).astype(F32)
        s = jnp.where(first, jnp.where(fresh, 0.0, stored), s)
        u = op.u - jnp.einsum("hsk,hkv->hsv", op.w, s, precision=_HI)
        o = jnp.einsum("hsk,hkv->hsv", op.qd, s, precision=_HI) \
            + jnp.einsum("hsr,hrv->hsv", op.b, u, precision=_HI)
        s = s * op.dlast[..., None] \
            + jnp.einsum("hsk,hsv->hkv", op.kd, u, precision=_HI)
        states = states.at[jnp.where(last, row, r)].set(
            s.astype(states.dtype), mode="drop")
        return (states, s), o

    (states, _), o = lax.scan(
        body, (states, jnp.zeros(states.shape[1:], F32)),
        (ops, plan.chunk_row, plan.chunk_first, plan.chunk_last,
         plan.chunk_fresh))
    return o, lax.dynamic_update_index_in_dim(state_pool, states, layer, 0)


# ---------------------------------------------------------------------------
# the layer's attention sub-block
# ---------------------------------------------------------------------------


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def attention(
    cfg: ModelConfig, x: jax.Array, lp: Dict[str, Any], proj,
    kv: Dict[str, jax.Array], layer, *, plan: Optional[Plan],
    positions: jax.Array, kernels: bool,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``x [b, s, h]`` the normed input (``[1, T, h]`` for a packed round)
    → (the sub-block's output, ``kv`` with this layer's state and tail
    advanced). ``plan`` None: one token a row, ``positions [R, 1]``."""
    b, s, hid = x.shape
    nh, d = cfg.kda_num_heads, cfg.kda_head_dim
    p = nh * d
    t = b * s
    xf = x.reshape(t, hid)
    pre = proj(xf, "wqkv")
    fa = proj(proj(xf, "w_fa"), "w_fb").astype(F32)
    gate = proj(proj(xf, "w_ga"), "w_gb").astype(F32)
    beta = jax.nn.sigmoid(jnp.einsum(
        "th,hn->tn", xf.astype(F32), lp["w_b"].astype(F32), precision=_HI))
    if plan is None:
        live, fresh = positions[:, 0] >= 0, positions[:, 0] == 0
        conv, conv_pool = conv_step(pre, lp["conv"], kv[CONV], layer, live,
                                    fresh)
    else:
        conv, conv_pool = conv_segments(pre, lp["conv"], kv[CONV], layer,
                                        plan)
    qkv = jax.nn.silu(conv).reshape(t, 3, nh, d)
    q = _l2norm(qkv[:, 0]) * (d ** -0.5)
    k, v = _l2norm(qkv[:, 1]), qkv[:, 2]
    g = -jnp.exp(lp["a_log"].astype(F32))[None, :, None] * jax.nn.softplus(
        fa + lp["dt_bias"].astype(F32)).reshape(t, nh, d)
    state = kv[STATE]
    if kernels:
        from distributed_gpu_inference_tpu.ops import kda_pallas
    if plan is None:
        if kernels:
            o, state = kda_pallas.kda_step(q, k, v, g, beta, state, layer,
                                           live, fresh)
        else:
            o, state = step_xla(q, k, v, g, beta, state, layer, live, fresh)
    else:
        with jax.named_scope("dgi_kda_prepare"):
            ops = chunk_prepare(q, k, v, g, beta, plan)
        if kernels:
            oc, state = kda_pallas.kda_chunk_pass(
                ops, state, layer, plan.chunk_row, plan.chunk_first,
                plan.chunk_last, plan.chunk_fresh)
        else:
            oc, state = chunk_pass_xla(ops, state, layer, plan)
        o = jnp.take(jnp.moveaxis(oc, 1, 2).reshape(-1, nh, d), plan.place,
                     axis=0, mode="fill", fill_value=0)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg.rms_norm_eps)
    o = o * lp["o_norm"].astype(F32) * jax.nn.sigmoid(gate).reshape(t, nh, d)
    out = proj(o.astype(x.dtype).reshape(b, s, p), "wo")
    return out.astype(x.dtype), {**kv, STATE: state, CONV: conv_pool}
