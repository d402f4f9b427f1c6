"""Routed expert layer: dispatch plan + grouped int8 matmul (Pallas TPU).

A sparse-MoE layer with many small experts (OLMoE: top-8 of 64) cannot run
as a dense einsum over the expert axis — that computes and reads every
expert for every token, E/k = 8x the work and a ``[T, E, H]`` combine
tensor. Here the layer is *routed*:

- **Plan** (:func:`route_plan`, plain ``jax.numpy``): the ``T x k`` (token,
  expert) pairs are sorted by expert and laid out in row tiles of ``tm``
  rows that each belong to ONE expert (a group's tail tile is padded).
  Shapes are static: at most ``ceil(T k / tm) + min(E, T k)`` tiles. Dead
  tokens (padding of a packed round, finished rows of a scan step) are
  routed nowhere.
- **Grouped matmul** (:func:`grouped_matmul_pallas`): ``y[r] = x[r] @
  dequant(w[layer, expert_of_tile(r)])`` over the stacked int8 expert
  weights ``[L, E, K, N]`` as stored. The Pallas kernel takes the layer
  index, the tile → expert map and the number of used tiles as scalar
  prefetch, like ``ops/qmm_pallas.py`` takes its layer index: weight
  blocks are DMA'd int8 and converted in VMEM, consecutive tiles of one
  expert reuse the resident block, and an expert that received no token is
  never named by a block index, so it costs no HBM read. Tiles past the
  used count repeat the last block indices and skip the compute.
- Elsewhere (CPU, shapes that do not tile) the same plan runs through an
  XLA gather of each tile's expert weight (:func:`grouped_matmul_layer`).

The caller (``models/llama.py _moe_mlp``) gathers the rows in, applies the
activation between the two matmul stages, and combines the ``k`` rows of a
token in float32.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_gpu_inference_tpu.ops import attention as _attention
from distributed_gpu_inference_tpu.ops.qmm_pallas import block_tiles

# fixed: the Mosaic kernel's name and, as the innermost scope, the custom
# call's name on a device trace's XLA Ops line (``dgi_moe_gmm.<n>``).
# ``decode`` calls (one token a row: a scan step) carry their own name so
# that a trace tells a scan's expert time from a ragged round's.
KERNEL_NAME = "dgi_moe_gmm"
KERNEL_NAME_STEP = "dgi_moe_gmm_step"

_MAX_TILE_ROWS = 128
# an expert matrix this small is one weight block: one DMA and one grid
# step a row tile (OLMoE's [2048, 1024] int8 is 2 MiB; two buffers and the
# bf16 copy of one stay well inside VMEM). Larger ones tile by
# ops/qmm_pallas.py's rule under this kernel's own constants: the chip
# has been asked about column tiles past 512 for ``dgi_qmm`` only.
_WHOLE_BLOCK_BYTES = 2 * 1024 * 1024
_MAX_TILE_COLS = 512


def weight_tiles(k: int, n: int):
    """(bk, bn) of the weight blocks, or None if K x N does not tile. A
    matrix too large for one block takes the widest column tile and then
    the LONGEST contraction tile that keeps the block inside
    ``_WHOLE_BLOCK_BYTES``: a grid step costs the same whether it moves a
    block or skips a tile, and a layer that holds a share of its experts
    skips most of its tiles (7680 x 2048: blocks of 3840 x 512, two steps a
    tile where 512 x 512 took fifteen)."""
    if k % 128 == 0 and n % 128 == 0 and k * n <= _WHOLE_BLOCK_BYTES:
        return k, n
    return block_tiles(k, n, _WHOLE_BLOCK_BYTES, _MAX_TILE_COLS)


class RoutePlan(NamedTuple):
    """Where each (token, expert) pair sits in the tiled row layout."""

    row_token: jax.Array    # [R] int32 token of each tiled row; T = padding
    pair_row: jax.Array     # [T, k] int32 tiled row of each pair; R = dead
    tile_expert: jax.Array  # [n_tiles] int32 expert of each tile
    used_tiles: jax.Array   # [] int32 tiles that hold rows
    active_experts: jax.Array  # [] int32 experts with at least one row
    assignments: jax.Array  # [] int32 live (token, expert) pairs
    tile_rows: int          # tm, static


def tile_rows(pairs: int, num_experts: int, sublane: int) -> int:
    """Rows of one tile: about the mean rows an expert gets, a power of two
    between the dtype's sublane tile and 128 — a few rows an expert in a
    scan step, full MXU tiles in a wide prompt piece."""
    mean = max(pairs // max(num_experts, 1), 1)
    tm = 1 << (mean - 1).bit_length()
    return int(min(max(tm, sublane), _MAX_TILE_ROWS))


def num_tiles(pairs: int, num_experts: int, tm: int) -> int:
    """Static bound on the tiles a plan can need: every group but its last
    tile is full, and at most ``min(E, pairs)`` groups exist."""
    return -(-pairs // tm) + min(num_experts, pairs)


def route_plan(topi: jax.Array, live: jax.Array, num_experts: int,
               tm: int) -> RoutePlan:
    """Tiled layout of the pairs ``topi [T, k]`` (expert of each pair),
    grouped by expert in token order. ``live [T]`` masks tokens that are
    routed at all; ``[T, k]`` masks single pairs (a chip that holds a share
    of the experts routes only the pairs that fall on its own:
    models/mla.py)."""
    t, k = topi.shape
    p, e_n = t * k, num_experts
    n_tiles = num_tiles(p, e_n, tm)
    r = n_tiles * tm
    pair_live = live if live.ndim == 2 else live[:, None]
    expert = jnp.where(pair_live, topi, e_n).reshape(p).astype(jnp.int32)
    onehot = expert[:, None] == jnp.arange(e_n, dtype=jnp.int32)[None, :]
    sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)              # [E]
    safe = jnp.minimum(expert, e_n - 1)
    # rank of a pair inside its group = pairs of the same expert before it
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0, dtype=jnp.int32), safe[:, None], axis=1
    )[:, 0] - 1
    tiles = -(-sizes // tm)
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    used = tile_end[-1]
    pair_row = jnp.where(
        expert < e_n, tile_start[safe] * tm + rank, r
    ).reshape(t, k)
    # tiled row -> token, through the stable sort of the pairs by expert
    # (dead pairs sort last). A tile's rows are consecutive pairs of one
    # group, so every lookup is per TILE (n_tiles of them, not rows):
    # three small gathers and one gather of tm-long slices, no scatter
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    group_start = jnp.cumsum(sizes) - sizes
    tile_ids = jnp.arange(n_tiles, dtype=jnp.int32)
    # tiles past the used count name the last used expert again, so their
    # block indices repeat and the kernel moves nothing for them
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.minimum(tile_ids, used - 1),
                         side="right"),
        e_n - 1,
    ).astype(jnp.int32)
    first = (tile_ids - tile_start[tile_expert]) * tm   # rank of row 0
    held = jnp.where(tile_ids < used, sizes[tile_expert] - first, 0)
    starts = jnp.clip(group_start[tile_expert] + first, 0, p)
    padded = jnp.pad(order, (0, tm))
    src = jax.vmap(
        lambda at: lax.dynamic_slice(padded, (at,), (tm,))
    )(starts)                                                   # [n, tm]
    valid = jnp.arange(tm, dtype=jnp.int32)[None, :] < held[:, None]
    row_token = jnp.where(valid, src // k, t).reshape(r).astype(jnp.int32)
    return RoutePlan(
        row_token=row_token, pair_row=pair_row, tile_expert=tile_expert,
        used_tiles=used, active_experts=jnp.sum(sizes > 0, dtype=jnp.int32),
        assignments=jnp.sum(sizes), tile_rows=tm,
    )


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------


def _gmm_kernel(idx_ref, used_ref, te_ref, x_ref, qw_ref, scale_ref, o_ref,
                acc_ref, *, num_k):
    del idx_ref, te_ref             # consumed by the index maps
    kk = pl.program_id(2)

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        @pl.when(kk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot(
            x_ref[...],
            qw_ref[0, 0].astype(x_ref.dtype),
            preferred_element_type=jnp.float32,
        )

        @pl.when(kk == num_k - 1)
        def _():
            o_ref[...] = (acc_ref[...] * scale_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "name", "interpret"))
def grouped_matmul_pallas(
    x: jax.Array,            # [R, K] tiled rows (R = n_tiles * tm)
    qw: jax.Array,           # [L, E, K, N] int8 / float8_e4m3fn
    scale: jax.Array,        # [L, E, 1, N] float32
    layer_idx: jax.Array,    # scalar int32
    tile_expert: jax.Array,  # [n_tiles] int32
    used_tiles: jax.Array,   # scalar int32
    *,
    tm: int,
    name: str = KERNEL_NAME,
    interpret: bool = False,
) -> jax.Array:
    """``y[tile] = x[tile] @ dequant(qw[layer_idx, tile_expert[tile]])`` for
    the first ``used_tiles`` tiles; rows of the other tiles are not
    written. Returns ``[R, N]`` in ``x.dtype``."""
    r, k = x.shape
    _, _, k2, n = qw.shape
    if k != k2 or r % tm:
        raise ValueError(f"grouped matmul shapes: x {x.shape}, w {qw.shape}, "
                         f"tile {tm}")
    tiles = weight_tiles(k, n) if not interpret else (k, n)
    if tiles is None:
        raise ValueError(f"untileable grouped matmul K={k} N={n}")
    bk, bn = tiles
    num_n, num_m, num_k = n // bn, r // tm, k // bk

    def m_of(mi, used):
        return jnp.maximum(jnp.minimum(mi, used[0] - 1), 0)

    def k_of(mi, ki, used):
        # a skipped tile keeps the block of the step before it
        return jnp.where(mi < used[0], ki, num_k - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_n, num_m, num_k),
        in_specs=[
            pl.BlockSpec((tm, bk), lambda ni, mi, ki, idx, used, te:
                         (m_of(mi, used), k_of(mi, ki, used))),
            pl.BlockSpec((1, 1, bk, bn), lambda ni, mi, ki, idx, used, te:
                         (idx[0], te[mi], k_of(mi, ki, used), ni)),
            pl.BlockSpec((1, 1, 1, bn), lambda ni, mi, ki, idx, used, te:
                         (idx[0], te[mi], 0, ni)),
        ],
        out_specs=pl.BlockSpec((tm, bn), lambda ni, mi, ki, idx, used, te:
                               (m_of(mi, used), ni)),
        scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, num_k=num_k),
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            # skipped tiles revisit the last used out block, and K
            # accumulates: only the N tiles are independent
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.asarray(used_tiles, jnp.int32).reshape(1),
        tile_expert.astype(jnp.int32),
        x, qw, scale.astype(jnp.float32),
    )


def grouped_matmul_layer(x: jax.Array, w, plan: RoutePlan) -> jax.Array:
    """The same contraction without the kernel, over ONE layer's expert
    weight ``[E, K, N]`` (plain or a quantized sub-dict, as the layer scan
    hands it over): each tile's weight is gathered and contracted batched.
    Unused tiles come out zero."""
    from distributed_gpu_inference_tpu.ops.quantization import is_quantized

    xt = x.reshape(-1, plan.tile_rows, x.shape[-1])
    if is_quantized(w):
        out = jnp.einsum("tmk,tkn->tmn", xt,
                         w["qw"][plan.tile_expert].astype(x.dtype))
        out = (out.astype(jnp.float32)
               * w["scale"][plan.tile_expert]).astype(x.dtype)
    else:
        out = jnp.einsum("tmk,tkn->tmn", xt, w[plan.tile_expert])
    keep = jnp.arange(xt.shape[0]) < plan.used_tiles
    return jnp.where(keep[:, None, None], out, 0).reshape(-1, out.shape[-1])


def kernel_ok(layers: Dict[str, Any]) -> bool:
    """Trace-time gate for the Pallas kernel, from a stacked layer tree:
    TPU backend, quantized expert weights, K and N that tile."""
    from distributed_gpu_inference_tpu.ops.quantization import is_quantized

    if not _attention.pallas_backend():
        return False
    for name in ("we_gate", "we_up", "we_down"):
        w = layers.get(name)
        if not is_quantized(w):
            return False
        _, _, k, n = w["qw"].shape
        if (w["qw"].dtype not in (jnp.int8, jnp.float8_e4m3fn)
                or weight_tiles(k, n) is None):
            return False
    return True


def grouped_matmul(x: jax.Array, w: Dict[str, jax.Array], layer_idx,
                   plan: RoutePlan, decode: bool = False) -> jax.Array:
    """``[R, K] -> [R, N]`` through the kernel over the stacked quantized
    expert weight ``w`` (``kernel_ok`` said it can run)."""
    return grouped_matmul_pallas(
        x, w["qw"], w["scale"], layer_idx, plan.tile_expert,
        plan.used_tiles, tm=plan.tile_rows,
        name=KERNEL_NAME_STEP if decode else KERNEL_NAME,
    )


def sublane(dtype) -> int:
    return 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8


def expert_stats(plan: RoutePlan) -> Dict[str, jax.Array]:
    """What one call of the layer did, as int32 scalars the caller sums:
    whether it held a live token, its live (token, expert) pairs, the rows
    the grouped matmul ran (tile padding included), the experts that
    received at least one row."""
    return {
        "layer_calls": (plan.assignments > 0).astype(jnp.int32),
        "assignments": plan.assignments,
        "rows_dispatched": plan.used_tiles * plan.tile_rows,
        "active_experts": plan.active_experts,
    }
