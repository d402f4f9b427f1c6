"""Pallas TPU kernels over the state pool of the state-space (Mamba-2, SSD)
mixer (``models/ssd.py`` holds the mathematics and the XLA forms).

The pool is ``[L, rows, H, P, N]`` float32: a sequence's state a layer a
head, the head's channels down, the state's across. Both kernels take the
STACKED pool and a layer index, aliased to their output, as the page
kernels do (``ops/paged_attention_pallas.py`` says why: a layer slice as a
custom-call operand is a copy of the layer), and touch only the rows they
are given.

- :func:`ssd_step` (``dgi_ssd_step``): one token a row, a scan step. A grid
  cell reads eight heads of a row's state in place, decays it, adds ``dt x
  (x) B``, reads ``S C`` and writes the state back: 2 x 128 KB a head at the
  published sizes against ~3 KB of x / B / C, so the state's bytes are the
  kernel's time. A row that is not live is copied through to the bit.
- :func:`ssd_chunk_pass` (``dgi_ssd_chunk``): a packed round's chunks in
  order, a head at a time. A segment's first chunk loads its row's state
  (or starts from zero), every chunk reads ``C S^T`` off the state it
  starts from and advances it by ``exp(l_Q) S + (x dt exp(l_Q - l))^T B``
  (two matmuls), and a segment's last chunk stores the state. The in-chunk
  term needs no state and is computed before the kernel
  (``models/ssd.chunk_prepare``), batched over chunks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_gpu_inference_tpu.ops.kda_pallas import _column

# fixed names: the custom calls' names on a device trace's XLA Ops line
STEP_KERNEL_NAME = "dgi_ssd_step"
CHUNK_KERNEL_NAME = "dgi_ssd_chunk"
_HEADS = 8          # heads a step's grid cell takes: a float32 sublane tile
_HI = lax.Precision.HIGHEST
F32 = jnp.float32


def _row(col: jax.Array) -> jax.Array:
    """``[n, 1]`` → ``[1, n]`` without a relayout, as ``_column`` the other
    way: the diagonal of the column broadcast across, summed down."""
    n = col.shape[0]
    eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _step_kernel(layer_ref, live_ref, fresh_ref, xdt_ref, decay_ref, b_ref,
                 c_ref, s_ref, y_ref, s_out):
    del layer_ref
    r = pl.program_id(0)
    live = live_ref[r] != 0
    fresh = fresh_ref[r] != 0
    b, c = b_ref[0, 0], c_ref[0, 0]                           # [1, N]
    for i in range(xdt_ref.shape[1]):
        old = s_ref[0, 0, i]                                  # [P, N]
        s = jnp.where(fresh, 0.0, old) * _column(decay_ref[0, i:i + 1]) \
            + _column(xdt_ref[0, i:i + 1]) * b
        y_ref[0, i:i + 1] = _row(jnp.sum(s * c, axis=1, keepdims=True))
        s_out[0, 0, i] = jnp.where(live, s, old)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step(x, b, c, dt, a, state_pool, layer, live, fresh,
             interpret: bool = False):
    """``models/ssd.step_xla`` in place in the pool → (``S C`` ``[R, H,
    P]``, the pool). ``b`` / ``c`` ``[R, G, N]``: a group's row serves its
    heads."""
    r, h, p = x.shape
    g, n = b.shape[1:]
    hpg = h // g
    hb = min(_HEADS, hpg)
    assert hpg % hb == 0
    row = pl.BlockSpec((1, hb, p), lambda i, j, *_: (i, j, 0))
    group = pl.BlockSpec((1, 1, 1, n),
                         lambda i, j, *_: (i, (j * hb) // hpg, 0, 0))
    state = pl.BlockSpec((1, 1, hb, p, n),
                         lambda i, j, layer_ref, *_: (layer_ref[0], i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(r, h // hb),
        in_specs=[row, row, group, group, state],
        out_specs=[row, state],
    )
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    y, pool = pl.pallas_call(
        _step_kernel,
        out_shape=[jax.ShapeDtypeStruct((r, h, p), F32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        grid_spec=grid_spec,
        # operands: 3 scalar-prefetch args, four row arrays, the pool (7)
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=STEP_KERNEL_NAME,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), live.astype(jnp.int32),
        fresh.astype(jnp.int32), x * dt[..., None], decay,
        b[:, :, None, :], c[:, :, None, :], state_pool,
    )
    return y, pool


def _chunk_kernel(layer_ref, row_ref, first_ref, last_ref, fresh_ref,
                  c_ref, b_ref, xdt_ref, dl_ref, _pool_in,
                  ys_ref, pool_hbm, s_scr, sem):
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
        s = s_scr[...]                                        # [P, N]
        ys_ref[0, 0] = lax.dot_general(
            c_ref[0, 0], s, (((1,), (1,)), ((), ())),
            preferred_element_type=F32, precision=_HI)        # [Q, P]
        s_scr[...] = s * dl_ref[0, 0] + jnp.dot(
            xdt_ref[0, 0], b_ref[0, 0], preferred_element_type=F32,
            precision=_HI)

    @pl.when(jnp.logical_not(used))
    def _():
        ys_ref[...] = jnp.zeros_like(ys_ref)

    @pl.when(used & (last_ref[c] != 0))
    def _():
        cp = copy(False)
        cp.start()
        cp.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pass(ops, state_pool, layer, chunk_row, chunk_first,
                   chunk_last, chunk_fresh, interpret: bool = False):
    """``models/ssd.chunk_pass_xla`` in place in the pool → (``ys [C, H, Q,
    P]``, the pool). ``ops``: ``models/ssd.ChunkOperands``."""
    c, h, p, q = ops.xdt.shape
    g, n = ops.b.shape[1], ops.b.shape[-1]
    hpg = h // g

    def head(*tail):
        return pl.BlockSpec((1, 1, *tail),
                            lambda i, j, *_: (j, i) + (0,) * len(tail))

    group = pl.BlockSpec((1, 1, q, n), lambda i, j, *_: (j, i // hpg, 0, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(h, c),
        in_specs=[group, group, head(p, q), head(1, n), hbm],
        out_specs=[head(q, p), hbm],
        scratch_shapes=[pltpu.VMEM((p, n), F32), pltpu.SemaphoreType.DMA(())],
    )
    as_i32 = lambda x: x.astype(jnp.int32)                    # noqa: E731
    ys, pool = pl.pallas_call(
        _chunk_kernel,
        out_shape=[jax.ShapeDtypeStruct((c, h, q, p), F32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        grid_spec=grid_spec,
        # operands: 5 scalar-prefetch args, four operand arrays, the pool (9)
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=CHUNK_KERNEL_NAME,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), as_i32(chunk_row),
        as_i32(chunk_first), as_i32(chunk_last), as_i32(chunk_fresh),
        ops.c, ops.b, ops.xdt,
        jnp.broadcast_to(ops.dlast[..., None, None], (c, h, 1, n)),
        state_pool,
    )
    return ys, pool
