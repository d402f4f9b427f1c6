"""Pallas TPU quantized matmul: int8 HBM reads, bf16 MXU compute in-kernel.

Decode is weight-bandwidth-bound: every step streams the full weight tree
through the MXU at trivial arithmetic intensity (M = batch rows). Storing
weights int8 halves the bytes, but the XLA convert-on-read path
(``ops/quantization.matmul``) does not reliably realize the saving — the
converted bf16 operand can be materialized (measured round 2: int8 decode at
~1.2x bf16 instead of the ~1.9x the byte ratio predicts). This kernel closes
the gap by doing the convert AFTER the HBM read, in VMEM:

- **Blocked operands**: weight tiles ``[BK, BN]`` are DMA'd HBM→VMEM as int8
  (half the bytes on the wire), converted to the activation dtype in VMEM,
  and contracted on the MXU with f32 accumulation. The block is a function
  of the weight's shape and item size (``pick_tiles``): the widest column
  tile that divides N, then the longest contraction tile that divides K
  inside a byte budget — 0.9-2 MB a grid step on every width the
  benchmark's cells run (PERF.md section 6, PR 38: the chip's table).
- **Stacked weights + scalar-prefetch layer index**: like the paged-attention
  kernel (``ops/paged_attention_pallas.py``), the kernel takes the whole
  stacked ``[L, K, N]`` weight and a scalar ``layer_idx`` — a custom-call
  operand must be materialized, so passing a per-layer slice (what
  ``lax.scan`` over stacked params produces) would make XLA copy the slice
  every layer, every step, erasing the bandwidth win. The layer scan in
  ``models/llama.py`` closes over the stacked tree and scans the index.
- **Per-output-channel scales** are applied once to the f32 accumulator on
  the final K tile (scale commutes with the K-sum).

Reference analogue: the int8/AWQ CUDA kernels the reference reaches through
vLLM engine flags (``worker/engines/llm_vllm.py:83-87``); here the kernel is
first-party and TPU-shaped.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block rule. BN/BK must divide N/K exactly (no ragged K/N tiles: an
# out-of-bounds K read would contract garbage into real outputs) and both
# are multiples of 128 (the lane dim of the weight block and of the
# activation block). The column tile comes first: the WIDEST multiple of
# 128 that divides N, up to ``_BN_MAX`` — a block's rows are contiguous in
# HBM over its width only, and every column tile re-reads the activation
# block. Then the LONGEST contraction tile that divides K and keeps the
# block inside ``_BLOCK_BYTES``. Both constants are the chip's (TPU v5e;
# PERF.md section 6, PR 38 has the table, GB/s by block for every (K, N)
# the cells run): a 512-wide tile streams at 450-490 GB/s in 256 KB
# blocks and 650-720 in 1-4 MB ones, a 1792-2048-wide tile at 720-740
# from 1 MB on; past 2 MB nothing gains and a small matrix loses (the
# first block's DMA is not overlapped). Not a menu of powers of two: a
# width that is not one (3584, 18944, 7680) falls to its smallest entry.
_BN_MAX = 2048
_BLOCK_BYTES = 2 * 1024 * 1024

# Bandwidth-bound regime bound: above this many activation rows the matmul
# is MXU-bound and XLA's native path (with its better K-parallel scheduling)
# is the right tool; below it the weight stream dominates and int8-on-the-
# wire wins. Decode (M = batch) and tree-verify (M = batch * nodes) qualify.
_MAX_ROWS = 256


def _longest_tile(dim: int, cap: int) -> Optional[int]:
    """The longest multiple of 128 that divides ``dim``, at most ``cap``."""
    top = min(cap, dim) // 128 * 128
    return next((d for d in range(top, 0, -128) if dim % d == 0), None)


def block_tiles(k: int, n: int, block_bytes: int, bn_max: int,
                itemsize: int = 1) -> Optional[tuple]:
    """(bk, bn) of a ``[K, N]`` weight's blocks — the widest column tile up
    to ``bn_max``, then the longest contraction tile inside ``block_bytes``
    — or None if K x N does not tile. ``ops/moe_gmm_pallas.py`` tiles its
    expert matrices by the same rule under constants of its own."""
    bn = _longest_tile(n, bn_max)
    bk = bn and _longest_tile(k, block_bytes // (bn * itemsize))
    return (bk, bn) if bk else None


def pick_tiles(k: int, n: int, itemsize: int = 1) -> Optional[tuple]:
    return block_tiles(k, n, _BLOCK_BYTES, _BN_MAX, itemsize)


# fixed: the Mosaic kernel's name (``kernel_name`` in the lowered text,
# ``ops.attention.pallas_kernels``) and, as the innermost scope, the custom
# call's name on a device trace's XLA Ops line (``dgi_qmm.<n>``)
KERNEL_NAME = "dgi_qmm"


def _qmm_kernel(idx_ref, x_ref, qw_ref, scale_ref, o_ref, acc_ref, *, num_k):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += lax.dot(
        x_ref[...],
        qw_ref[0].astype(x_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == num_k - 1)
    def _():
        # scale [1, BN] broadcasts over the M rows of the f32 accumulator
        o_ref[...] = (acc_ref[...] * scale_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmm_stacked_pallas(
    x: jax.Array,          # [M, K] activations (bf16/f32)
    qw: jax.Array,         # [L, K, N] quantized weights (int8 / float8_e4m3fn)
    scale: jax.Array,      # [L, 1, N] float32 per-output-channel scales
    layer_idx: jax.Array,  # scalar int32 — which layer's weight to use
    *,
    interpret: bool = False,
) -> jax.Array:
    """``x @ dequant(qw[layer_idx])`` with the int8→bf16 convert in VMEM.

    Returns [M, N] in x.dtype. K and N must tile (see ``pick_tiles``); M is
    padded to the sublane tile internally.
    """
    m, k = x.shape
    l, k2, n = qw.shape
    if k != k2:
        raise ValueError(f"x K {k} != weight K {k2}")
    tiles = pick_tiles(k, n, qw.dtype.itemsize)
    if tiles is None:
        raise ValueError(f"untileable qmm shape K={k} N={n}")
    bk, bn = tiles

    sublane = 16 if x.dtype == jnp.bfloat16 else 8
    mp = -(-m // sublane) * sublane
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))

    num_n, num_k = n // bn, k // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_n, num_k),
        in_specs=[
            pl.BlockSpec((mp, bk), lambda ni, ki, idx: (0, ki)),
            pl.BlockSpec((1, bk, bn), lambda ni, ki, idx: (idx[0], ki, ni)),
            pl.BlockSpec((1, 1, bn), lambda ni, ki, idx: (idx[0], 0, ni)),
        ],
        out_specs=pl.BlockSpec((mp, bn), lambda ni, ki, idx: (0, ni)),
        scratch_shapes=[pltpu.VMEM((mp, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, num_k=num_k),
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            # out blocks are revisited across the K walk (accumulator), so K
            # must be sequential; N tiles are independent
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        x,
        qw,
        scale.astype(jnp.float32),
    )
    return out[:m] if mp != m else out


def qmm_rows_ok(m: int) -> bool:
    """True when M rows is in the bandwidth-bound regime this kernel wins."""
    return m <= _MAX_ROWS
