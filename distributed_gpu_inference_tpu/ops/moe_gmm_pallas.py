"""Routed expert layer: dispatch plan + grouped int8 matmul (Pallas TPU).

A sparse-MoE layer with many small experts (OLMoE: top-8 of 64) cannot run
as a dense einsum over the expert axis — that computes and reads every
expert for every token, E/k = 8x the work and a ``[T, E, H]`` combine
tensor. Here the layer is *routed*, in one of two forms of the same sum
``sum_e w_e * down_e(act(gate_e(x)) * up_e(x))``. Which one runs is decided
at trace time from the call's shape alone (``takes_step_form``), never from
a model's name, a config key or the environment:

**The tiled form** — a round with a prompt piece, tree-verify, anything
with more than one token a row or more rows than one MXU tile (128).

- **Plan** (:func:`route_plan`, plain ``jax.numpy``): the ``T x k`` (token,
  expert) pairs are sorted by expert and laid out in row tiles of ``tm``
  rows that each belong to ONE expert (a group's tail tile is padded).
  Shapes are static: at most ``ceil(T k / tm) + min(E, T k)`` tiles. Dead
  tokens (padding of a packed round, finished rows of a scan step) are
  routed nowhere.
- **Grouped matmul** (:func:`grouped_matmul_pallas`, ``dgi_moe_gmm``):
  ``y[r] = x[r] @ dequant(w[layer, expert_of_tile(r)])`` over the stacked
  int8 expert weights ``[L, E, K, N]`` as stored. The Pallas kernel takes
  the layer index, the tile → expert map and the number of used tiles as
  scalar prefetch, like ``ops/qmm_pallas.py`` takes its layer index: weight
  blocks are DMA'd int8 and converted in VMEM, consecutive tiles of one
  expert reuse the resident block, and an expert that received no token is
  never named by a block index, so it costs no HBM read. Tiles past the
  used count repeat the last block indices and skip the compute.
- Elsewhere (CPU, shapes that do not tile) the same plan runs through an
  XLA gather of each tile's expert weight (:func:`grouped_matmul_layer`).
- The caller (``models/llama.py _routed_sum``) gathers the rows in, applies
  the activation between the two matmul stages, and combines the ``k`` rows
  of a token in float32.

**The step form** — a scan step (``s == 1``) of at most 128 padded rows: 8
rows fit ONE row tile, so no row needs gathering and no pair a rank; at 8
rows the tiled form above spent nine tenths of its grid steps on tiles that
moved nothing (PERF.md section 6, PR 43).

- **Plan** (:func:`step_plan`): compares and one cumsum give the dense
  weight of each (row, stored expert) — zero where the pair is dead — and
  the packed list of experts that received a live pair. No sort, no
  ``searchsorted``, no scatter, no gather of rows.
- **One call a layer** (:func:`routed_step_pallas`, ``dgi_moe_gmm_step``):
  the rows stay resident in VMEM; for each listed expert and each tile of
  the intermediate axis the kernel DMAs the gate and up columns and the
  down rows int8 as stored, converts in VMEM, and adds the expert's weighted
  share to a float32 ``[T_pad, H]`` accumulator that is written once. Every
  rounding point of the tiled form stays or moves to float32 (gate, up,
  their product and the down output are never rounded to the activation
  dtype; the down matmul's input is, as there); a row's experts are summed
  in stored-expert order, not top-k order. The grid's first axis is the
  number of experts listed, read at run time: nothing is stepped over.
- Elsewhere the same plan runs through :func:`routed_step_layer` (a gather
  of the listed experts' weights and three einsums).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_gpu_inference_tpu.ops import attention as _attention
from distributed_gpu_inference_tpu.ops.qmm_pallas import (
    _longest_tile, block_tiles,
)

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
# the step form's three weight blocks of one grid step, together (bytes as
# stored): 512 columns at every width the cells run (3 MB a step at 2048,
# 3.5 at 2304, 11.8 at 7680). With the grid as long as the experts used,
# 256-1024 columns read within 3.4 % of one another on the chip; whole
# experts (1024) lose where few experts are used, because the first
# block's DMA is not overlapped (PERF.md section 6, PR 43 has the table).
_STEP_BLOCK_BYTES = 12 * 1024 * 1024
_STEP_VMEM_SLACK = 4 * 1024 * 1024
_STEP_VMEM_FLOOR = 16 * 1024 * 1024


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


def step_tile(h: int, i: int, itemsize: int = 1):
    """Columns of the intermediate axis one grid step of the STEP form
    takes (``bi``), or None if ``H x I`` does not tile: the longest
    multiple of 128 that divides ``I`` and keeps the step's three weight
    blocks (gate and up ``[H, bi]``, down ``[bi, H]``) inside
    ``_STEP_BLOCK_BYTES`` together, ``_MAX_TILE_COLS`` at most —
    ``ops/qmm_pallas.block_tiles``' rule with the whole hidden axis as the
    other side of every block."""
    return _longest_tile(
        i, min(_MAX_TILE_COLS, _STEP_BLOCK_BYTES // (3 * h * itemsize)))


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


# ---------------------------------------------------------------------------
# the step form: a scan step's few rows as ONE resident tile
# ---------------------------------------------------------------------------


class StepPlan(NamedTuple):
    """A scan step's routing with no row layout: every row is in the one
    tile, so all the kernel needs is which experts to walk and what each
    weighs in each row."""

    weight: jax.Array       # [T_pad, E_pad] float32; 0 = pair dead / absent
    slot_expert: jax.Array  # [A_max] int32 experts with a live pair, packed
    used_slots: jax.Array   # [] int32 how many of them
    assignments: jax.Array  # [] int32 live (token, expert) pairs
    rows: int               # T_pad, static


def step_rows(t: int, dtype) -> int:
    """``T`` rows padded to the dtype's sublane tile."""
    sub = sublane(dtype)
    return -(-t // sub) * sub


def takes_step_form(t: int, dtype, stacked: Optional[Dict[str, Any]]) -> bool:
    """Whether ``T`` one-token rows run as the step form: they fit its
    single row tile (one MXU tile at most) and, where the kernel takes the
    weights, a step's three blocks fit its budget (``step_tile``)."""
    if step_rows(t, dtype) > _MAX_TILE_ROWS:
        return False
    if stacked is None or "we_gate" not in stacked:
        return True
    # the thinnest step, 128 columns (that the widths tile at all is
    # ``kernel_ok``'s to say, before this)
    qw = stacked["we_gate"]["qw"]
    return step_tile(qw.shape[2], 128, qw.dtype.itemsize) is not None


def step_plan(experts: jax.Array, topv: jax.Array, live: jax.Array,
              num_experts: int, rows: int) -> StepPlan:
    """The plan of ``experts [T, k]`` (distinct within a row) weighing
    ``topv [T, k]``, ``live`` as in :func:`route_plan`: compares and one
    cumsum over the experts — no sort, no scatter, no gather of rows."""
    t, k = experts.shape
    a_max = min(num_experts, t * k)
    e_pad = -(-num_experts // 128) * 128
    pair_live = live if live.ndim == 2 else live[:, None]
    hit = pair_live[..., None] & (
        experts[..., None] == jnp.arange(e_pad, dtype=jnp.int32))
    weight = jnp.sum(
        jnp.where(hit, topv[..., None].astype(jnp.float32), 0.0), axis=1)
    active = jnp.any(hit, axis=(0, 1))[:num_experts]              # [E]
    slot_of = jnp.cumsum(active, dtype=jnp.int32) - 1
    # slots past the count name expert 0 and are never walked
    slot_expert = jnp.sum(
        jnp.where(active & (slot_of == jnp.arange(
            a_max, dtype=jnp.int32)[:, None]),
                  jnp.arange(num_experts, dtype=jnp.int32), 0),
        axis=1, dtype=jnp.int32)
    return StepPlan(
        weight=jnp.pad(weight, ((0, rows - t), (0, 0))),
        slot_expert=slot_expert, used_slots=slot_of[-1] + 1,
        assignments=jnp.sum(hit, dtype=jnp.int32), rows=rows,
    )


def _step_kernel(idx_ref, used_ref, se_ref, x_ref, w_ref, g_ref, gs_ref,
                 u_ref, us_ref, d_ref, ds_ref, o_ref, *, act):
    del idx_ref                     # consumed by the index maps
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (i == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < used_ref[0])       # false only where no expert is listed
    def _():
        x = x_ref[...]

        def mm(rows, q_ref, scale_ref):
            return lax.dot(
                rows, q_ref[0, 0].astype(x.dtype),
                preferred_element_type=jnp.float32) * scale_ref[0, 0]

        mid = act(mm(x, g_ref, gs_ref)) * mm(x, u_ref, us_ref)
        y = mm(mid.astype(x.dtype), d_ref, ds_ref)              # [T_pad, H]
        # this expert's column of the dense weights: a masked lane sum
        lane = lax.broadcasted_iota(jnp.int32, w_ref.shape, 1)
        col = jnp.sum(jnp.where(lane == se_ref[j], w_ref[...], 0.0),
                      axis=1, keepdims=True)
        # a row the expert does not serve adds nothing, whatever it holds
        o_ref[...] += jnp.where(col != 0.0, col * y, 0.0)


def routed_step_pallas(
    x: jax.Array,               # [T_pad, H] the step's rows
    stacked: Dict[str, Any],    # we_gate / we_up / we_down: qw [L, E, K, N]
    layer_idx: jax.Array,       # scalar int32
    plan: StepPlan,
    act,
    *,
    bi: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """``sum_e weight[:, e] * down_e(act(gate_e(x)) * up_e(x))`` over the
    plan's experts in ONE call → ``[T_pad, H]`` float32. Grid (slot,
    intermediate tile), the slots as many as the plan lists (a bound read
    at run time; one where it lists none, to write the zeros): a step DMAs
    the expert's gate and up columns and down rows int8 as stored,
    converts in VMEM and adds its weighted share to the resident
    accumulator, written once."""
    gate, up, down = (stacked[n] for n in ("we_gate", "we_up", "we_down"))
    t_pad, h = x.shape
    _, _, _, inter = gate["qw"].shape
    if bi is None:
        bi = inter if interpret else step_tile(
            h, inter, gate["qw"].dtype.itemsize)
    if t_pad != plan.rows or not bi or inter % bi:
        raise ValueError(f"step form shapes: x {x.shape}, gate "
                         f"{gate['qw'].shape}, tile {bi}")

    def cols(rows, width):      # a block of gate / up, or of their scales
        return pl.BlockSpec(
            (1, 1, rows, width), lambda j, i, idx, used, se:
            (idx[0], se[j], 0, i))

    whole = lambda *shape: pl.BlockSpec(shape, lambda j, i, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(jnp.maximum(plan.used_slots, 1), inter // bi),
        in_specs=[
            whole(t_pad, h), whole(*plan.weight.shape),
            cols(h, bi), cols(1, bi), cols(h, bi), cols(1, bi),
            pl.BlockSpec((1, 1, bi, h), lambda j, i, idx, used, se:
                         (idx[0], se[j], i, 0)),
            pl.BlockSpec((1, 1, 1, h), lambda j, i, idx, used, se:
                         (idx[0], se[j], 0, 0)),
        ],
        out_specs=whole(t_pad, h),
    )
    # two buffers of the three int8 blocks, their copies in x's dtype, the
    # rows, the weights and the accumulator twice, and room for the rest
    block = 3 * h * bi
    need = block * (2 * gate["qw"].dtype.itemsize + x.dtype.itemsize) \
        + 4 * t_pad * (h + plan.weight.shape[1]) * 4
    f32 = lambda w: w["scale"].astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_step_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((t_pad, h), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            # one accumulator over both axes
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(need + _STEP_VMEM_SLACK, _STEP_VMEM_FLOOR),
        ),
        interpret=interpret,
        name=KERNEL_NAME_STEP,
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.asarray(plan.used_slots, jnp.int32).reshape(1),
        plan.slot_expert,
        x, plan.weight,
        gate["qw"], f32(gate), up["qw"], f32(up), down["qw"], f32(down),
    )


def routed_step_layer(x: jax.Array, lp: Dict[str, Any], plan: StepPlan,
                      act) -> jax.Array:
    """The same sum without the kernel, over ONE layer's expert weights
    ``[E, K, N]`` (plain or quantized sub-dicts): the plan's experts are
    gathered and contracted batched, float32 where the kernel is."""
    from distributed_gpu_inference_tpu.ops.quantization import is_quantized

    se = plan.slot_expert

    def mm(spec, rows, w):
        if not is_quantized(w):
            return jnp.einsum(spec, rows, w[se],
                              preferred_element_type=jnp.float32)
        return jnp.einsum(
            spec, rows, w["qw"][se].astype(x.dtype),
            preferred_element_type=jnp.float32,
        ) * w["scale"][se].astype(jnp.float32)

    mid = act(mm("tk,akn->atn", x, lp["we_gate"])) \
        * mm("tk,akn->atn", x, lp["we_up"])
    y = mm("atk,akn->atn", mid.astype(x.dtype), lp["we_down"])  # [A, T, H]
    used = jnp.arange(se.shape[0]) < plan.used_slots
    weight = jnp.where(
        used, jnp.take(plan.weight, se, axis=1), 0.0).T[..., None]  # [A, T, 1]
    return jnp.sum(jnp.where(weight != 0.0, weight * y, 0.0), axis=0)


def routed_step(x: jax.Array, lp: Dict[str, Any],
                stacked: Optional[Dict[str, Any]], layer_idx, plan: StepPlan,
                act) -> jax.Array:
    """``[T, H]`` rows → their routed sum ``[T, H]`` float32 by the step
    form: the kernel over the stacked quantized weights where
    ``kernel_ok`` kept them whole, else the XLA twin over the layer's."""
    t = x.shape[0]
    rows = jnp.pad(x, ((0, plan.rows - t), (0, 0)))
    if stacked is not None and "we_gate" in stacked:
        return routed_step_pallas(rows, stacked, layer_idx, plan, act)[:t]
    return routed_step_layer(rows, lp, plan, act)[:t]


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


def expert_stats(plan) -> Dict[str, jax.Array]:
    """What one call of the layer did (either plan), as int32 scalars the
    caller sums: whether it held a live token, its live (token, expert)
    pairs, the rows the MXU ran (tile padding included: the used tiles'
    rows, or the step form's one tile once an expert walked), the experts
    that received at least one row, and whether the call took the step
    form."""
    calls = (plan.assignments > 0).astype(jnp.int32)
    if isinstance(plan, StepPlan):
        rows, active, step = plan.used_slots * plan.rows, plan.used_slots, calls
    else:
        rows, active = plan.used_tiles * plan.tile_rows, plan.active_experts
        step = jnp.zeros_like(calls)
    return {
        "layer_calls": calls,
        "assignments": plan.assignments,
        "rows_dispatched": rows,
        "active_experts": active,
        "step_form_calls": step,
    }
