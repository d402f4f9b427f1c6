"""Learned sparse attention's selection: a lightning indexer over paged
index keys (DeepSeek-V3.2's, on a K/V layer: models/llama.py).

Every cached token has ONE index key a layer (``index_head_dim`` wide),
kept in a pool ``[L, N, Bk, lanes]`` beside the K/V pages and addressed by the
same block table. A query token ``t`` scores every cached token ``s <= t``

    I(t, s) = sum_j w[t, j] * relu(qI[t, j] . kI[s])

over the indexer's ``j`` heads, and attends the ``topk`` tokens of largest
score: ``S_t = {s : I(t, s) >= the topk-th largest}``. Ties at that value
are all kept (``lax.top_k`` would cut them by position), and a query with
at most ``topk`` visible tokens keeps them all.

What this module hands the attention kernels is ``keep [B, S, J]`` float32,
1 where query ``(b, s)`` attends context position ``j`` and 0 elsewhere
(``ops/paged_attention_pallas.py`` and ``ops/attention.py`` take it beside
``window`` and ``kv_lens``). Two steps, each with a kernel and an XLA form
of the same arithmetic (the CPU, ``pallas=False``):

- **scores** (``dgi_index_score``): a chunk's ``[B, S, J]`` scores, tile by
  tile, never a ``[B, S, heads, J]`` tensor.
- **threshold** (``dgi_index_threshold``): the topk-th largest of a row by
  bisection over the score's bit pattern — 32 counting passes over a row
  that stays in VMEM, no sort (``lax.top_k`` at k = 2,048 over 24k scores
  lowers to one).

A scan step's two calls (``S == 1``) carry the names with ``_step`` at the
end, so that a device trace tells a scan's selection from a round's.

The score kernels read index keys **in context order**. A round lays its
batch's out of the pool, one layer at a time (``gather_index_keys``). A scan
of several steps lays out every layer's ONCE, before its first step
(``gather_scan_keys`` → ``[L, B, J, lanes]``, "the scan's keys"), carries them
through its steps beside the pools, appends each step's key to them
(``append_scan_keys``; the pool is written as well: the pages stay the
truth) and has ``dgi_index_score_step`` read a layer's out of the carried
array by layer index. What the array holds lives for the scan's call only:
it is derived from the pool at every call, and nothing that owns pages (the
cache manager, the prefix cache, preemption, the handoff) learns of it; its
storage is the engine's, handed from call to call (``runtime/engine.py``
``decode_multi``; ``engine.stats`` ``index_key_gathers_scan`` counts the
layer-gathers the scans issued). A scan no row of which can pass ``topk``
before its last step gathers nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SCORE_KERNEL_NAME = "dgi_index_score"
THRESHOLD_KERNEL_NAME = "dgi_index_threshold"
_INT_MIN = -(2 ** 31)
# query rows and context columns of one score tile of a chunk; columns of a
# scan step's (one query a row: the tile is heads x columns)
_SCORE_ROWS = 128
_SCORE_COLS = 1024
_STEP_COLS = 16 * 1024
# rows of one threshold tile: the tile's scores and its mask, each double-
# buffered, stay under the kernel's VMEM limit at 24k columns
_THRESHOLD_ROWS = 16
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def pool_lanes(index_head_dim: int) -> int:
    """Width of a pool row: the key, padded to whole 128-lane tiles. At 64
    lanes XLA keeps the pool in one layout for the scatter and another for
    the gather and copies it whole between them, every layer of every
    step; a row of whole tiles has one layout."""
    return -(-index_head_dim // 128) * 128


def gather_index_keys(
    ki_pool: jax.Array,       # [L, N, Bk, lanes] stacked index-key pool
    layer_idx: jax.Array,
    block_tables: jax.Array,  # [B, M]
    di: int,                  # the key's width, the row's first lanes
) -> jax.Array:
    """A batch's index keys in context order → ``[B, M * Bk, di]``."""
    b, m = block_tables.shape
    bk = ki_pool.shape[2]
    return ki_pool[layer_idx, block_tables][..., :di].reshape(b, m * bk, di)


def write_index_keys(
    ki_pool: jax.Array,       # [L, N, Bk, lanes]
    new: jax.Array,           # [T, Di] the chunk's index keys, one flat axis
    layer_idx: jax.Array,
    flat_phys: jax.Array,     # [T] page of each token (N = nothing to write)
    flat_slot: jax.Array,     # [T]
) -> jax.Array:
    """Scatter a chunk's index keys into layer ``layer_idx`` of the stacked
    pool, in place in the scan's carry (no kernel takes this pool as an
    operand, so nothing asks XLA for another layout of it)."""
    new = jnp.pad(new.astype(ki_pool.dtype),
                  ((0, 0), (0, ki_pool.shape[3] - new.shape[1])))
    return ki_pool.at[layer_idx, flat_phys, flat_slot].set(new, mode="drop")


def scan_keys_shape(pool_shape: tuple, rows: int, table_width: int,
                    topk: int) -> tuple | None:
    """Shape of a scan's keys, ``[L, B, Jp, lanes]``, for a pool ``[L, N,
    Bk, lanes]`` and ``rows`` block tables ``table_width`` wide (``Jp``: the
    table's positions, padded to whole tiles of the step's score kernel).
    None where the table cannot hold more than ``topk`` (``select`` never
    scores)."""
    l, _, bk, lanes = pool_shape
    j = table_width * bk
    return None if j <= topk else (l, rows, _step_tile(j)[1], lanes)


def gather_scan_keys(
    ki_pool: jax.Array,       # [L, N, Bk, lanes]
    block_tables: jax.Array,  # [B, M]
    most: jax.Array,          # the longest context a row can reach in the scan
    topk: int,
    into: jax.Array,          # [L, B, Jp, lanes] the array's storage
) -> jax.Array:
    """Every layer's index keys of a batch in context order, ``[L, B, Jp,
    lanes]`` (``scan_keys_shape``; the pool's whole rows, the key in their
    first lanes, so that the scatter that appends and the kernel that reads
    agree on one layout: ``pool_lanes``): what a scan carries through its
    steps. ``into`` is storage the caller owns and hands from call to call
    (its contents are never trusted: an array of this size allocated anew
    by every call stalled the device for seconds every few hundred calls).
    Where no row can hold more than ``topk`` tokens inside the scan
    (``most``) no step will score: nothing is gathered, and ``into`` comes
    back as it is, never read."""
    l, n, bk, lanes = ki_pool.shape
    b, m = block_tables.shape
    j = m * bk
    assert into.shape == scan_keys_shape(ki_pool.shape, b, m, topk), (
        into.shape, ki_pool.shape, block_tables.shape)

    def gathered():
        # ONE gather whose result is layer-major as it comes: the layers'
        # pages as rows of one pool, addressed by layer and page at once
        # (indexing the layer axis with a slice gathers page-major and
        # then transposes the whole result)
        pages = block_tables[None] + n * jnp.arange(l, dtype=jnp.int32)[
            :, None, None]
        keys = ki_pool.reshape(l * n, bk, lanes)[pages].reshape(
            l, b, j, lanes)
        if into.shape[2] != j:
            keys = jnp.pad(
                keys, ((0, 0), (0, 0), (0, into.shape[2] - j), (0, 0)))
        return keys

    with jax.named_scope("dgi_index_scan_keys"):
        return lax.cond(most > topk, gathered, lambda: into)


def append_scan_keys(
    scan_keys: jax.Array,     # [L, B, Jp, lanes]
    new: jax.Array,           # [B, Di] a step's index keys, one a row
    layer_idx: jax.Array,
    positions: jax.Array,     # [B] where each lands (-1 = nothing to write)
) -> jax.Array:
    """A step's keys into layer ``layer_idx`` of the scan's keys, in place in
    the scan's carry."""
    new = jnp.pad(new.astype(scan_keys.dtype),
                  ((0, 0), (0, scan_keys.shape[3] - new.shape[1])))
    rows = jnp.arange(positions.shape[0], dtype=jnp.int32)
    at = jnp.where(positions >= 0, positions, scan_keys.shape[2])
    return scan_keys.at[layer_idx, rows, at].set(new, mode="drop")


def _sortable(x: jax.Array) -> jax.Array:
    """float32 → int32 whose signed order is the floats' order."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _kth_largest_key(keys: jax.Array, k: int) -> jax.Array:
    """The k-th largest of each row of int32 ``keys [..., J]`` → ``[...,
    1]``: the largest T with ``count(keys >= T) >= k``, built bit by bit
    from the sign down. A row with fewer than k entries above the smallest
    value gives that smallest value."""

    def enough(t):
        n = jnp.sum((keys >= t).astype(jnp.float32), axis=-1, keepdims=True)
        return n >= k

    base = jnp.where(enough(jnp.int32(0)), jnp.int32(0), jnp.int32(_INT_MIN))

    def bit(i, base):
        cand = base | (jnp.int32(1) << (30 - i))
        return jnp.where(enough(cand), cand, base)

    return lax.fori_loop(0, 31, bit, base)


def keep_from_scores(scores: jax.Array, topk: int) -> jax.Array:
    """``scores [..., J]`` float32 with ``-inf`` at what the query does not
    see → ``keep`` float32 of the same shape (XLA form)."""
    keys = _sortable(scores)
    keep = (keys >= _kth_largest_key(keys, topk)) & (scores > -jnp.inf)
    return keep.astype(jnp.float32)


def _visible(positions: jax.Array, kv_lens: jax.Array, j: int) -> jax.Array:
    col = jnp.arange(j, dtype=jnp.int32)
    return (col[None, None, :] <= positions[:, :, None]) \
        & (col[None, None, :] < kv_lens[:, None, None])


def index_scores_xla(
    qi: jax.Array,         # [B, S, Hi, Di] rotated index queries
    wts: jax.Array,        # [B, S, Hi] float32 head weights (scaled)
    ctx: jax.Array,        # [B, J, Di] index keys in context order
    positions: jax.Array,  # [B, S] (-1 = padding: sees nothing)
    kv_lens: jax.Array,    # [B]
) -> jax.Array:
    """``I(t, s)`` → ``[B, S, J]`` float32, ``-inf`` at what is not seen."""
    dots = jnp.einsum("bshd,bjd->bshj", qi, ctx,
                      preferred_element_type=jnp.float32)
    scores = jnp.sum(wts[..., None] * jnp.maximum(dots, 0.0), axis=2)
    # -0.0 and 0.0 are one score: the bit pattern must say so
    return jnp.where(_visible(positions, kv_lens, ctx.shape[1]),
                     scores + 0.0, -jnp.inf)


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def _score_kernel(
    lens_ref,      # [B] int32 (SMEM)
    qmax_ref,      # [B * S_tiles] int32 largest position of a tile (-1: none)
    q_ref,         # [1, Hi, ts, Di]
    w_ref,         # [1, Hi, ts, 1] float32
    pos_ref,       # [1, ts, 1] int32
    ctx_ref,       # [1, tj, Di]
    out_ref,       # [1, ts, tj] float32
    *,
    s_tiles: int,
):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    heads, ts = q_ref.shape[1], q_ref.shape[2]
    tj = ctx_ref.shape[1]
    kv_len = lens_ref[b]
    qmax = qmax_ref[b * s_tiles + i]
    live = (j * tj <= qmax) & (j * tj < kv_len)

    @pl.when(live)
    def _():
        k = ctx_ref[0]
        acc = jnp.zeros((ts, tj), jnp.float32)
        for h in range(heads):      # static: one [ts, tj] tile a head
            dots = lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + w_ref[0, h] * jnp.maximum(dots, 0.0)
        col = j * tj + lax.broadcasted_iota(jnp.int32, (ts, tj), 1)
        seen = (col <= pos_ref[0]) & (col < kv_len)
        out_ref[0] = jnp.where(seen, acc + 0.0, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[0] = jnp.full((ts, tj), -jnp.inf, jnp.float32)


def _name(kernel: str, step: bool) -> str:
    return kernel + "_step" if step else kernel


def _col_tile(j: int, most: int) -> int:
    """Columns a tile: the largest whole number of 128-lane groups that
    divides the context and is at most ``most`` (a context that is no
    multiple of 128 is padded to one)."""
    groups = -(-j // 128)
    return 128 * max(g for g in range(1, max(most // 128, 1) + 1)
                     if groups % g == 0)


def _step_tile(j: int) -> tuple[int, int]:
    """Columns of a tile of a scan step's score kernel over a context of
    ``j`` positions, and the context padded to whole tiles."""
    tj = _col_tile(j, _STEP_COLS)
    return tj, -(-j // tj) * tj


def _step_score_kernel(
    lens_ref,      # [B] int32 (SMEM)
    pos_ref,       # [B] int32 the row's query position (-1: none)
    layer_ref,     # [1] int32 the layer of ``ctx`` the call reads
    q_ref,         # [1, Hi, Di]
    w_ref,         # [1, Hi, 1] float32
    ctx_ref,       # [1, 1, tj, Di or more lanes: the key is the first Di]
    out_ref,       # [1, 1, tj] float32
):
    del layer_ref       # the index maps read it
    b, j = pl.program_id(0), pl.program_id(1)
    tj = ctx_ref.shape[2]
    kv_len, pos = lens_ref[b], pos_ref[b]
    live = (j * tj <= pos) & (j * tj < kv_len)

    @pl.when(live)
    def _():
        # the heads are the rows of ONE matmul against the tile's keys
        dots = lax.dot_general(
            q_ref[0], ctx_ref[0, 0][:, :q_ref.shape[2]],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [Hi, tj]
        score = jnp.sum(w_ref[0] * jnp.maximum(dots, 0.0), axis=0,
                        keepdims=True)
        col = j * tj + lax.broadcasted_iota(jnp.int32, (1, tj), 1)
        seen = (col <= pos) & (col < kv_len)
        out_ref[0] = jnp.where(seen, score + 0.0, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[0] = jnp.full((1, tj), -jnp.inf, jnp.float32)


def _step_scores_pallas(qi, wts, ctx, layer_idx, positions, kv_lens, j,
                        interpret):
    """A scan step's scores: one query a row, ``[B, 1, j]``. ``ctx [L, B,
    Jp, W]`` holds whole tiles (``_step_tile``), a key in the first ``Di``
    of a row's ``W`` values, and the call reads its layer ``layer_idx`` in
    place: the index maps take the layer from a prefetched scalar, as the
    attention kernels address the stacked pools."""
    b, _, heads, di = qi.shape
    tj, j_pad = _step_tile(j)
    width = ctx.shape[3]
    assert ctx.shape[2] == j_pad, (ctx.shape, j_pad)
    out = pl.pallas_call(
        _step_score_kernel,
        out_shape=jax.ShapeDtypeStruct((b, 1, j_pad), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, j_pad // tj),
            in_specs=[
                pl.BlockSpec((1, heads, di), lambda b_, j_, *_: (b_, 0, 0)),
                pl.BlockSpec((1, heads, 1), lambda b_, j_, *_: (b_, 0, 0)),
                pl.BlockSpec((1, 1, tj, width),
                             lambda b_, j_, lens, pos, layer:
                             (layer[0], b_, j_, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, tj), lambda b_, j_, *_: (b_, 0, j_)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=_name(SCORE_KERNEL_NAME, True),
    )(
        kv_lens.astype(jnp.int32), positions[:, 0].astype(jnp.int32),
        jnp.reshape(layer_idx, (1,)).astype(jnp.int32),
        qi[:, 0], wts[:, 0, :, None], ctx,
    )
    return out[:, :, :j]


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores_pallas(
    qi: jax.Array, wts: jax.Array, ctx: jax.Array, positions: jax.Array,
    kv_lens: jax.Array, interpret: bool = False,
) -> jax.Array:
    """:func:`index_scores_xla`, tile by tile: a tile no query of which
    sees a column (a decode row's padding in the rectangle, columns past a
    row's context) is filled and not computed. A scan step (one query a
    row) takes a form of its own, the heads as the rows of one matmul."""
    b, s, heads, di = qi.shape
    j = ctx.shape[1]
    if s == 1:
        j_pad = _step_tile(j)[1]
        if j_pad != j:
            ctx = jnp.pad(ctx, ((0, 0), (0, j_pad - j), (0, 0)))
        return _step_scores_pallas(qi, wts, ctx[None], jnp.int32(0),
                                   positions, kv_lens, j, interpret)
    ts = min(_SCORE_ROWS, -(-s // 8) * 8)
    tj = _col_tile(j, _SCORE_COLS)
    s_pad, j_pad = -(-s // ts) * ts, -(-j // tj) * tj
    if s_pad != s:
        qi = jnp.pad(qi, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        wts = jnp.pad(wts, ((0, 0), (0, s_pad - s), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, s_pad - s)),
                            constant_values=-1)
    if j_pad != j:
        ctx = jnp.pad(ctx, ((0, 0), (0, j_pad - j), (0, 0)))
    s_tiles = s_pad // ts
    positions = positions.astype(jnp.int32)
    qmax = jnp.max(positions.reshape(b, s_tiles, ts), axis=2).reshape(-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, s_tiles, j_pad // tj),
        in_specs=[
            pl.BlockSpec((1, heads, ts, di), lambda b_, i, j_, *_: (b_, 0, i, 0)),
            pl.BlockSpec((1, heads, ts, 1), lambda b_, i, j_, *_: (b_, 0, i, 0)),
            pl.BlockSpec((1, ts, 1), lambda b_, i, j_, *_: (b_, i, 0)),
            pl.BlockSpec((1, tj, di), lambda b_, i, j_, *_: (b_, j_, 0)),
        ],
        out_specs=pl.BlockSpec((1, ts, tj), lambda b_, i, j_, *_: (b_, i, j_)),
    )
    out = pl.pallas_call(
        functools.partial(_score_kernel, s_tiles=s_tiles),
        out_shape=jax.ShapeDtypeStruct((b, s_pad, j_pad), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=SCORE_KERNEL_NAME,
    )(
        kv_lens.astype(jnp.int32), qmax,
        qi.transpose(0, 2, 1, 3), wts.transpose(0, 2, 1)[..., None],
        positions[..., None], ctx,
    )
    return out[:, :s, :j]


def _threshold_kernel(live_ref, scores_ref, keep_ref, *, topk: int):
    r = pl.program_id(0)

    @pl.when(live_ref[r] > 0)
    def _():
        scores = scores_ref[...]
        keys = _sortable(scores)
        keep = (keys >= _kth_largest_key(keys, topk)) & (scores > -jnp.inf)
        keep_ref[...] = keep.astype(jnp.float32)

    @pl.when(live_ref[r] <= 0)
    def _():
        keep_ref[...] = jnp.zeros(keep_ref.shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("topk", "interpret", "step"))
def keep_from_scores_pallas(
    scores: jax.Array,     # [R, J] float32, -inf = not seen
    live: jax.Array,       # [R] bool: rows that see anything
    topk: int, interpret: bool = False,
    step: bool = False,    # a scan step's rows: the kernel's name says so
) -> jax.Array:
    """:func:`keep_from_scores` over row tiles that stay in VMEM through
    the 32 counting passes; a tile with no live row is zeroed unread."""
    r, j = scores.shape
    tr = min(_THRESHOLD_ROWS, -(-r // 8) * 8)
    r_pad = -(-r // tr) * tr
    if r_pad != r:
        scores = jnp.pad(scores, ((0, r_pad - r), (0, 0)),
                         constant_values=-jnp.inf)
        live = jnp.pad(live, (0, r_pad - r))
    tiles = r_pad // tr
    tile_live = jnp.any(live.reshape(tiles, tr), axis=1).astype(jnp.int32)
    block = pl.BlockSpec((tr, j), lambda i, *_: (i, 0))
    keep = pl.pallas_call(
        functools.partial(_threshold_kernel, topk=topk),
        out_shape=jax.ShapeDtypeStruct((r_pad, j), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,),
            in_specs=[block], out_specs=block,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=_name(THRESHOLD_KERNEL_NAME, step),
    )(tile_live, scores)
    return keep[:r]


# --------------------------------------------------------------------------
# the selection
# --------------------------------------------------------------------------

def select(
    qi: jax.Array,            # [B, S, Hi, Di]
    wts: jax.Array,           # [B, S, Hi] float32
    ki_pool: jax.Array,       # [L, N, Bk, lanes], this chunk's keys written
    layer_idx: jax.Array,
    block_tables: jax.Array,  # [B, M]
    positions: jax.Array,     # [B, S] (-1 = padding)
    kv_lens: jax.Array,       # [B] context after this chunk
    topk: int,
    kernels: bool,            # the Pallas forms (a TPU, no mesh)
    interpret: bool = False,
    scan_keys: jax.Array | None = None,
                              # [L, B, Jp, lanes] a scan's keys in context
                              # order, this step's appended (S == 1)
) -> jax.Array:
    """``keep [B, S, J]`` float32: what each query of the chunk attends.
    While no row holds more than ``topk`` tokens nothing is gathered or
    scored: every query keeps what it sees. With ``scan_keys`` nothing is
    gathered at all: the scores are taken from the layer's keys there."""
    b, s = positions.shape
    j = block_tables.shape[1] * ki_pool.shape[2]

    def dense():
        return _visible(positions, kv_lens, j).astype(jnp.float32)

    def threshold(scores):
        return keep_from_scores_pallas(
            scores.reshape(b * s, j), (positions >= 0).reshape(-1), topk,
            interpret=interpret, step=s == 1,
        ).reshape(b, s, j)

    def sparse():
        if scan_keys is None:
            ctx = gather_index_keys(ki_pool, layer_idx, block_tables,
                                    qi.shape[3])
        elif kernels:       # the layer's keys read where they lie
            return threshold(_step_scores_pallas(
                qi, wts, scan_keys, layer_idx, positions, kv_lens, j,
                interpret))
        else:
            ctx = lax.dynamic_index_in_dim(
                scan_keys, layer_idx, 0, keepdims=False)[:, :j, :qi.shape[3]]
        if not kernels:
            return keep_from_scores(
                index_scores_xla(qi, wts, ctx, positions, kv_lens), topk)
        return threshold(index_scores_pallas(
            qi, wts, ctx, positions, kv_lens, interpret=interpret))

    if j <= topk:       # static: the table cannot hold more than topk
        return dense()
    with jax.named_scope("dgi_index_select"):
        return lax.cond(jnp.max(kv_lens) > topk, sparse, dense)
