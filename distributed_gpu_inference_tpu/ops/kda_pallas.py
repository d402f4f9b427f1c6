"""Pallas TPU kernels over the state pool of the gated delta-rule (KDA)
layers (``models/kda.py`` holds the mathematics and the XLA forms).

The pool is ``[Lk, rows, H, d, d]`` float32: a sequence's state a layer a
head, keys down, values across. Both kernels take the STACKED pool and a
layer index, aliased to their output, as the page kernels do
(``ops/paged_attention_pallas.py`` says why: a layer slice as a custom-call
operand is a copy of the layer), and touch only the rows they are given.

- :func:`kda_step` (``dgi_kda_step``): one token a row, a scan step. A grid
  cell reads eight heads of a row's state in place, decays it, writes the
  delta-rule update, reads the output and writes the state back: 2 x 64 KB a
  head against ~1.5 KB of q / k / v / g, so the state's bytes are the
  kernel's time. A row that is not live is copied through to the bit.
- :func:`kda_chunk_pass` (``dgi_kda_chunk``): a packed round's chunks of 64
  tokens in order, a head at a time. A segment's first chunk loads its
  row's state (or starts from zero), every chunk turns its prepared
  operands (``models/kda.chunk_prepare``: the in-chunk solve is done, batched
  over chunks, before the kernel) into outputs and the next state with five
  matmuls, and a segment's last chunk stores the state. Chunks of other
  segments do not wait for each other's operands, only for the state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# fixed names: the custom calls' names on a device trace's XLA Ops line
STEP_KERNEL_NAME = "dgi_kda_step"
CHUNK_KERNEL_NAME = "dgi_kda_chunk"
_HEADS = 8          # heads a step's grid cell takes: a float32 sublane tile
_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def _column(row: jax.Array) -> jax.Array:
    """``[1, n]`` → ``[n, 1]`` without a relayout: the diagonal of the
    row broadcast down, summed across lanes."""
    n = row.shape[-1]
    eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _step_kernel(layer_ref, live_ref, fresh_ref, q_ref, k_ref, kb_ref,
                 vb_ref, g_ref, s_ref, o_ref, s_out):
    del layer_ref
    r = pl.program_id(0)
    live = live_ref[r] != 0
    fresh = fresh_ref[r] != 0
    for i in range(q_ref.shape[1]):
        old = s_ref[0, 0, i]
        s = jnp.where(fresh, 0.0, old) * _column(jnp.exp(g_ref[0, i:i + 1]))
        u = vb_ref[0, i:i + 1] - jnp.sum(
            s * _column(kb_ref[0, i:i + 1]), axis=0, keepdims=True)
        s = s + _column(k_ref[0, i:i + 1]) * u
        o_ref[0, i:i + 1] = jnp.sum(
            s * _column(q_ref[0, i:i + 1]), axis=0, keepdims=True)
        s_out[0, 0, i] = jnp.where(live, s, old)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step(q, k, v, g, beta, state_pool, layer, live, fresh,
             interpret: bool = False):
    """``models/kda.step_xla`` in place in the pool → (``o [R, H, d]``, the
    pool)."""
    r, h, d = q.shape
    hb = min(_HEADS, h)
    assert h % hb == 0
    row = pl.BlockSpec((1, hb, d), lambda i, j, *_: (i, j, 0))
    state = pl.BlockSpec((1, 1, hb, d, d),
                         lambda i, j, layer_ref, *_: (layer_ref[0], i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(r, h // hb),
        in_specs=[row, row, row, row, row, state],
        out_specs=[row, state],
    )
    o, pool = pl.pallas_call(
        _step_kernel,
        out_shape=[jax.ShapeDtypeStruct((r, h, d), F32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        grid_spec=grid_spec,
        # operands: 3 scalar-prefetch args, five row arrays, the pool (8)
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=STEP_KERNEL_NAME,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), live.astype(jnp.int32),
        fresh.astype(jnp.int32), q, k, k * beta[..., None],
        v * beta[..., None], g, state_pool,
    )
    return o, pool


def _chunk_kernel(layer_ref, row_ref, first_ref, last_ref, fresh_ref,
                  w_ref, u_ref, qd_ref, kdt_ref, b_ref, dl_ref, _pool_in,
                  o_ref, pool_hbm, s_scr, sem):
    h = pl.program_id(0)
    c = pl.program_id(1)
    layer = layer_ref[0]
    row = row_ref[c]
    used = row < pool_hbm.shape[1]
    at = jnp.minimum(row, pool_hbm.shape[1] - 1)

    def copy(load):
        src, dst = pool_hbm.at[layer, at, h], s_scr
        if not load:
            src, dst = dst, src
        return pltpu.make_async_copy(src, dst, sem)

    @pl.when(used & (first_ref[c] != 0) & (fresh_ref[c] == 0))
    def _():
        cp = copy(True)
        cp.start()
        cp.wait()

    @pl.when(used & (fresh_ref[c] != 0))
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    @pl.when(used)
    def _():
        s = s_scr[...]
        dot = functools.partial(jnp.dot, preferred_element_type=F32,
                                precision=_HI)
        u = u_ref[0, 0] - dot(w_ref[0, 0], s)
        o_ref[0, 0] = dot(qd_ref[0, 0], s) + dot(b_ref[0, 0], u)
        s_scr[...] = s * _column(dl_ref[0, 0]) + dot(kdt_ref[0, 0], u)

    @pl.when(jnp.logical_not(used))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(used & (last_ref[c] != 0))
    def _():
        cp = copy(False)
        cp.start()
        cp.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk_pass(ops, state_pool, layer, chunk_row, chunk_first,
                   chunk_last, chunk_fresh, interpret: bool = False):
    """``models/kda.chunk_pass_xla`` in place in the pool → (``o [C, H, 64,
    d]``, the pool). ``ops``: ``models/kda.ChunkOperands``."""
    c, h, n, d = ops.w.shape

    def block(*tail):
        return pl.BlockSpec((1, 1, *tail),
                            lambda i, j, *_: (j, i) + (0,) * len(tail))

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(h, c),
        in_specs=[block(n, d), block(n, d), block(n, d), block(d, n),
                  block(n, n), block(1, d), hbm],
        out_specs=[block(n, d), hbm],
        scratch_shapes=[pltpu.VMEM((d, d), F32), pltpu.SemaphoreType.DMA(())],
    )
    as_i32 = lambda x: x.astype(jnp.int32)                    # noqa: E731
    o, pool = pl.pallas_call(
        _chunk_kernel,
        out_shape=[jax.ShapeDtypeStruct((c, h, n, d), F32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        grid_spec=grid_spec,
        # operands: 5 scalar-prefetch args, six operand arrays, the pool (11)
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=CHUNK_KERNEL_NAME,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), as_i32(chunk_row),
        as_i32(chunk_first), as_i32(chunk_last), as_i32(chunk_fresh),
        ops.w, ops.u, ops.qd, jnp.swapaxes(ops.kd, -1, -2), ops.b,
        ops.dlast[..., None, :], state_pool,
    )
    return o, pool
