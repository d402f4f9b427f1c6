"""Pallas TPU kernels over the latent (MLA) paged pool.

The pool is ``[L, N, Bk, W]``: a token's normed KV latent (``latent`` wide)
and its shared rotated rope key side by side, no head axis
(``models/mla.py``). Both kernels take the STACKED pool and a layer index,
as the K/V kernels do (``ops/paged_attention_pallas.py`` says why: a layer
slice as a custom-call operand is a copy of the layer).

- :func:`write_latent_pages_in_place` (``dgi_mla_write``): a chunk's rows
  into their pages, the pool aliased to the output. The K/V page write with
  one pool; the plan is the same ``page_write_plan``.
- :func:`latent_paged_attention` (``dgi_mla_decode`` for one token a row:
  a scan step; ``dgi_mla_ragged`` for a round's mixed rows): **absorbed**
  attention. Every head's query, already folded into the latent space and
  concatenated with its rope half, meets the ONE ``W``-wide key of a cached
  token, so the heads are the rows of a single matmul against a page group
  and a page is read once for all of them; the value is the key's first
  ``latent`` lanes. 2 x heads x (W + latent) FLOP a cached token against
  ``W x 2`` bytes: about the chip's ridge at 128 heads, neither the
  memory-bound decode kernel nor the compute-bound prefill kernel.
  The walk over live page groups, the double-buffered page DMAs and the
  per-query causal mask are the ragged K/V kernel's.
- **Under a learned selection** (``keep``: ``ops/index_select.py``) the same
  kernel masks a cached token by its query's ``keep`` as well. A scan step
  (``dgi_mla_decode_selected``) walks a row's SELECTED pages only: the
  caller lays them out once for a layer that computes a selection
  (:func:`selected_walk`: the K/V decode kernel's ``_selected_pages``) and
  hands the same walk to every layer that shares it, so the kernel reads
  the pages that hold a selected token and not the row's whole cache. A
  round (``dgi_mla_ragged_selected``) walks the rows' pages as it does
  without one, every query of a tile under its own ``keep``.
- **The step's selected walk has a form of its own** (``walk``, a static
  flag of the one kernel body; ``ops/paged_attention_pallas._decode_kernel``
  has the same under a selection). Its groups are ``_WALK_GROUP_TOKENS``
  (2,048) wide, the one constant the table's padding reads too: a row's
  ~900 scattered pages are 8 grid steps where 512-token groups take 29. A
  group's copies all signal ONE semaphore of their slot and the kernel
  waits once a slot, for the slot's bytes: safe because the table is whole
  groups wide and repeats the row's last fetched page past its end
  (``_selected_pages``), so a group always starts exactly its full count of
  whole-page copies and a start clamps nothing. The starts sit in a rolled
  loop over runs of ``_SELECTED_UNROLL``: a worker re-traces and re-lowers
  every round graph at every start, and 128 starts a site unrolled in
  Python are set-up time every cell with this kernel would pay. The dense
  forms and the round's are deliberately left as they were (a wait and a
  start a page of a 32-page group): openPangu's and Kimi-Linear's graphs
  trace this body too, a faster dense walk bought them nothing end to end
  and cost their ``setup_s`` a bound (PERF.md section 6, PRs 53-54).
- **A sliding latent layer** (``window``: a model of two attention kinds,
  ``models/mla.py``) runs the same body over ITS pool (a wider row, its own
  head count) under the window kind's block table, with the window as a
  rule of the walk: a tile's first group is the one that holds ``qmin -
  window + 1`` (an eighth scalar operand, the tile's lowest query position),
  and a cached position at or under ``p - window`` is masked. Its calls
  carry names of their own (``dgi_mla_window_decode`` /
  ``dgi_mla_window_ragged``), so that a device trace tells them from the
  full layers'. With ``window`` None the kernel's equations and operands
  are what they were.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
    _SELECTED_UNROLL,
    PageWritePlan,
)

_NEG_INF = -1e30
# fixed names: the custom calls' names on a device trace's XLA Ops line
DECODE_KERNEL_NAME = "dgi_mla_decode"
RAGGED_KERNEL_NAME = "dgi_mla_ragged"
WRITE_KERNEL_NAME = "dgi_mla_write"
# the same kernels under a selection: a device trace tells the selected
# walk from the dense one
_SELECTED = "_selected"
# a sliding latent layer's calls (the window kind's pool and table)
WINDOW_DECODE_KERNEL_NAME = "dgi_mla_window_decode"
WINDOW_RAGGED_KERNEL_NAME = "dgi_mla_window_ragged"
# ceiling on (heads) x (query tile): the rows of the score tile and of the
# float32 accumulator a grid cell carries (1024 x 512 x 4 B = 2 MiB)
_HEAD_ROWS = 1024
# a full tile's blocks, score tile and accumulator take about 18 MiB: over
# the compiler's default scoped limit (16 MiB), a fraction of the 128 MiB a
# v5e core has
_VMEM_LIMIT_BYTES = 40 * 1024 * 1024
# tokens a page group stages (two slots of [group, W] in the pool dtype)
_GROUP_TOKENS = 512
# tokens of a group of a scan step's walk over its selected pages: the one
# width both :func:`walk_columns` (the table's padding) and the kernel (the
# copies a group starts) read, so the two cannot disagree
_WALK_GROUP_TOKENS = 2048


def _page_write_kernel(page_ref, kind_ref, slots_ref, layer_ref,
                       new_ref, _pool_in, pool_hbm, stage, sems, *,
                       tile: int, words: int):
    """One grid step writes ``tile`` cells (pages a row's span touches): a
    page written whole goes out as its update block, a page written in part
    is staged, blended by slot mask and written back whole
    (``ops/paged_attention_pallas._page_write_kernel``, one pool)."""
    base = pl.program_id(0) * tile
    layer = layer_ref[0]
    _, bk, w = stage.shape

    def copy(j, read):
        src, dst = pool_hbm.at[layer, page_ref[base + j]], stage.at[j]
        if not read:
            src, dst = dst, src
        return pltpu.make_async_copy(src, dst, sems.at[j])

    def start_reads(j, carry):
        @pl.when(kind_ref[base + j] == 1)
        def _():
            copy(j, True).start()

        return carry

    def blend(j, carry):
        kind = kind_ref[base + j]

        @pl.when(kind == 1)
        def _():
            copy(j, True).wait()

        @pl.when(kind != 0)
        def _():
            slot = lax.broadcasted_iota(jnp.int32, (bk, w), 0)
            sel = None
            for wd in range(words):
                hit = (jnp.right_shift(
                    slots_ref[(base + j) * words + wd], (slot - 32 * wd) & 31
                ) & 1) == 1
                if words > 1:
                    hit &= (slot >= 32 * wd) & (slot < 32 * (wd + 1))
                sel = hit if sel is None else sel | hit
            stage[j] = jnp.where(sel, new_ref[j], stage[j])
            copy(j, False).start()

        return carry

    def wait_writes(j, carry):
        @pl.when(kind_ref[base + j] != 0)
        def _():
            copy(j, False).wait()

        return carry

    lax.fori_loop(0, tile, start_reads, 0)
    lax.fori_loop(0, tile, blend, 0)
    lax.fori_loop(0, tile, wait_writes, 0)


def write_latent_pages_in_place(
    new_rows: jax.Array,      # [T, W] the chunk's rows, one flat axis
    pool: jax.Array,          # [L, N, Bk, W] stacked latent pool
    layer_idx: jax.Array,     # scalar int32
    plan: PageWritePlan,
    interpret: bool = False,
) -> jax.Array:
    """Write a chunk's rows into layer ``layer_idx`` of the stacked pool, in
    place → pool. The bytes after the call are what a scatter of the rows
    leaves: other layers, other pages and the unwritten slots of written
    pages keep theirs."""
    _, _, bk, w = pool.shape
    cells = plan.page.shape[0]
    tile = plan.tile
    words = plan.slots.shape[0] // cells
    pages = jnp.take(new_rows, plan.src, axis=0, mode="fill", fill_value=0) \
        .reshape(cells, bk, w).astype(pool.dtype)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(cells // tile,),
        in_specs=[
            pl.BlockSpec((tile, bk, w), lambda i, *_refs: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            hbm,
        ],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((tile, bk, w), pool.dtype),
            pltpu.SemaphoreType.DMA((tile,)),
        ],
    )
    # operands: 4 scalar-prefetch args, the update array, the pool (idx 5)
    return pl.pallas_call(
        functools.partial(_page_write_kernel, tile=tile, words=words),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        grid_spec=grid_spec,
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=WRITE_KERNEL_NAME,
    )(
        plan.page, plan.kind, plan.slots,
        jnp.asarray(layer_idx, jnp.int32).reshape(1), pages, pool,
    )


def _attention_kernel(
    # scalar prefetch (SMEM; bidx/init persist across the sequential grid)
    bt_ref,        # [B, M] int32 per-sequence block tables
    lens_ref,      # [B] int32 kv length per sequence
    seq_ref,       # [R] int32 the sequence each query tile belongs to
    qmax_ref,      # [R] int32 max valid query position (-1 = inactive tile)
    layer_ref,     # [1] int32 layer index into the stacked pool
    bidx_ref,      # [1] int32 current double-buffer slot
    init_ref,      # [1] int32 1 until the first live chunk issues its DMA
    # blocked operands
    q_ref,         # [1, Nh*T, W] this tile's absorbed queries, head-major
    pos_ref,       # [1, Nh*T, 1] int32 per-query positions (-1 = pad)
    pool_hbm,      # [L, N, Bk, W]
    *rest,         # (``window``: the whole operand list is shifted by one
                   # more scalar, ``qmin_ref [R]``, behind ``init_ref``:
                   # read below)
                   # [keep_ref [1, T, gsz] float32 (> 0: attended),]
                   # out_ref [1, Nh*T, latent], buf [2, G, Bk, W] page
                   # staging, sems DMA [2, G] ([2] in the ``walk`` form),
                   # m_scr, l_scr [Nh*T, 1] float32 softmax state, acc_scr
                   # [Nh*T, latent] float32
    rows: int, block_size: int, pages_per_group: int,
    max_pages: int, scale: float, latent: int, selected: bool, heads: int,
    walk: bool, window: int | None = None,
):
    """``walk``: ``bt_ref`` is a scan step's table of selected pages, padded
    to whole groups (:func:`selected_walk`). Static, and read in Python
    only: with it off the kernel's equations are what they were before the
    flag (every other configuration's graphs trace this body too)."""
    qmin_ref = None
    if window is not None:
        # an eighth scalar operand: a tile's lowest valid query position
        qmin_ref, q_ref, pos_ref, pool_hbm, *rest = (
            q_ref, pos_ref, pool_hbm, *rest)
    keep_ref = None
    if selected:
        keep_ref, *rest = rest
    out_ref, buf, sems, m_scr, l_scr, acc_scr = rest
    r = pl.program_id(0)
    i = pl.program_id(1)
    gp = pages_per_group
    gsz = gp * block_size
    layer = layer_ref[0]
    max_groups = pl.num_programs(1)

    def num_groups(s_):
        s_ = jnp.clip(s_, 0, rows - 1)
        needed = jnp.minimum(qmax_ref[s_] + 1, lens_ref[seq_ref[s_]])
        # clamped to the grid: a length past the table must not leave a
        # prefetched DMA un-waited at kernel exit
        return jnp.minimum(pl.cdiv(needed, gsz), max_groups)

    def start_group(s_):
        """(``window`` only) the group that holds the tile's first visible
        key, ``max(0, qmin - window + 1)``."""
        s_ = jnp.clip(s_, 0, rows - 1)
        return jnp.maximum(qmin_ref[s_] - window + 1, 0) // gsz

    ng_r = num_groups(r)
    if window is None:
        live = i < ng_r
    else:
        start_r = start_group(r)
        live = (i >= start_r) & (i < ng_r)

    if walk:
        # ``ops/paged_attention_pallas._decode_kernel``'s walk under a
        # selection. The table is whole groups wide and holds, past a row's
        # fetched pages, the last of them again, so a start reads its page
        # and clamps nothing, and a group always starts exactly ``gp``
        # whole-page copies, each signalling the ONE semaphore of its slot
        run = _SELECTED_UNROLL if gp % _SELECTED_UNROLL == 0 else gp

        def start_dma(s_, j, slot):
            row = seq_ref[jnp.clip(s_, 0, rows - 1)]
            base = j * gp

            # a rolled loop over runs of a few starts: unrolled whole, 128
            # descriptors a site are traced and lowered in Python for every
            # scan graph at every start of a worker (PERF.md section 6)
            def body(c, carry):
                for p in range(run):
                    pltpu.make_async_copy(
                        pool_hbm.at[layer, bt_ref[row, base + c * run + p]],
                        buf.at[slot, c * run + p], sems.at[slot]).start()
                return carry

            lax.fori_loop(0, gp // run, body, 0)

        def wait_dma(s_, j, slot):
            # ONE wait: it draws the byte count of its destination, the
            # whole slot (the source only shapes the descriptor), which is
            # what the group's ``gp`` copies signalled. A wait for more
            # bytes than were signalled hangs the chip
            pltpu.make_async_copy(
                pool_hbm.at[0, pl.ds(0, gp)], buf.at[slot],
                sems.at[slot]).wait()
    else:
        def page_copy(s_, j, slot, p):
            idx = jnp.minimum(j * gp + p, max_pages - 1)
            page = bt_ref[seq_ref[jnp.clip(s_, 0, rows - 1)], idx]
            return pltpu.make_async_copy(
                pool_hbm.at[layer, page], buf.at[slot, p], sems.at[slot, p])

        def start_dma(s_, j, slot):
            def body(p, carry):
                page_copy(s_, j, slot, p).start()
                return carry

            lax.fori_loop(0, gp, body, 0, unroll=True)

        def wait_dma(s_, j, slot):
            def body(p, carry):
                page_copy(s_, j, slot, p).wait()
                return carry

            lax.fori_loop(0, gp, body, 0, unroll=True)

    def next_chunk(s_, j):
        """Grid-order successor of live chunk (s_, j): the next group of the
        tile, else the first group of the next tile that has any."""

        def advance_row():
            def step(_, ss):
                return jnp.where(
                    (ss < rows) & (num_groups(ss) == 0), ss + 1, ss)

            ns = lax.fori_loop(0, rows, step, s_ + 1)
            if window is None:
                return ns, jnp.int32(0)
            return ns, jnp.where(ns < rows, start_group(ns), 0)

        return lax.cond(
            j + 1 < num_groups(s_), lambda: (s_, j + 1), advance_row)

    @pl.when((ng_r == 0) & (i == 0))
    def _():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    @pl.when(live)
    def _():
        slot = bidx_ref[0]

        @pl.when(init_ref[0] == 1)
        def _():
            start_dma(r, i, slot)

        init_ref[0] = 0
        nr, ni = next_chunk(r, i)

        @pl.when(nr < rows)
        def _():
            start_dma(nr, ni, 1 - slot)

        bidx_ref[0] = 1 - slot
        wait_dma(r, i, slot)

        @pl.when(i == (0 if window is None else start_r))
        def _():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        kv_len = lens_ref[seq_ref[r]]
        kv = buf[slot].reshape(gsz, buf.shape[-1])            # [gsz, W]
        # every head against the one key: [Nh*T, W] x [gsz, W]^T
        scores = lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        col = i * gsz + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        pos = pos_ref[0]                                       # [Nh*T, 1]
        valid = (col < kv_len) & (col <= pos)
        if window is not None:
            valid &= col > pos - window
        if selected:
            # the tile's T queries repeat once a head, as their positions do
            kept = keep_ref[0] > 0                              # [T, gsz]
            if kept.shape[0] == 1:
                kept = jnp.broadcast_to(kept, valid.shape)
            elif heads > 1:
                kept = jnp.concatenate([kept] * heads, axis=0)
            valid &= kept
        scores = jnp.where(valid, scores, _NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        # the value is the key's latent lanes (a lane-aligned slice)
        acc_new = acc_scr[...] * alpha + lax.dot_general(
            probs.astype(kv.dtype), kv[:, :latent],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

        @pl.when(i == ng_r - 1)
        def _():
            out = jnp.where(
                l_new > 0, acc_new / jnp.where(l_new > 0, l_new, 1.0), 0.0)
            out_ref[0] = out.astype(out_ref.dtype)


def _q_tile(s: int, nh: int) -> int:
    t = max(1, min(s, _HEAD_ROWS // max(nh, 1)))
    return 1 << (t.bit_length() - 1)


def _attend_tiles(q_tiles, pos_tiles, tile_seq, pool, layer_idx,
                  block_tables, kv_lens, *, block_size, scale, latent, name,
                  interpret, keep_tiles=None, walk=False, window=None):
    """The kernel over query tiles: ``q_tiles [R, T, Nh, W]`` (``T``
    consecutive queries of ONE sequence a tile, ``tile_seq [R]`` says
    which), ``pos_tiles [R, T]`` their positions (-1 = no query) →
    ``[R, T, Nh, latent]``. ``keep_tiles [R, T, J]`` float32: a query
    attends only the columns of its tile's walk where it is > 0. ``walk``:
    ``block_tables`` is a :class:`SelectedWalk`'s ``pages``. ``window``: a
    query attends the ``window`` positions up to its own."""
    if window is not None and (walk or keep_tiles is not None):
        raise ValueError("a windowed latent layer attends no selection")
    rows, t, nh, w = q_tiles.shape
    m = block_tables.shape[1]
    # [R, T, Nh, W] → [R, Nh*T, W], the query index fastest inside a head
    q_r = q_tiles.transpose(0, 2, 1, 3).reshape(rows, nh * t, w) \
        .astype(pool.dtype)
    pos_r = pos_tiles.astype(jnp.int32)
    pos_q = jnp.tile(pos_r, (1, nh))[:, :, None]
    gp = _pages_per_group(m, block_size, walk)
    if walk and m % gp:
        raise ValueError(
            f"a walk of {m} columns is not whole groups of {gp} pages")

    def tile_spec(width):
        return pl.BlockSpec((1, nh * t, width), lambda i, j, *_refs: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    max_groups = -(-m // gp)
    gsz = gp * block_size
    in_specs = [tile_spec(w), tile_spec(1),
                pl.BlockSpec(memory_space=pltpu.HBM)]
    operands = [q_r, pos_q, pool]
    selected = keep_tiles is not None
    if selected:
        keep_r = jnp.pad(keep_tiles.astype(jnp.float32), (
            (0, 0), (0, 0), (0, max_groups * gsz - keep_tiles.shape[2])))

        def keep_block(i, j, _bt, lens, seq, qmax, *_refs):
            # a cell past the tile's last live group names that group's
            # block again, so nothing of a dead cell is fetched
            needed = jnp.minimum(qmax[i] + 1, lens[seq[i]])
            live = jnp.minimum(pl.cdiv(needed, gsz), max_groups)
            return i, 0, jnp.clip(j, 0, jnp.maximum(live - 1, 0))

        in_specs.append(pl.BlockSpec((1, t, gsz), keep_block,
                                     memory_space=pltpu.VMEM))
        operands.append(keep_r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7 if window is None else 8,
        grid=(rows, max_groups),
        in_specs=in_specs,
        out_specs=tile_spec(latent),
        scratch_shapes=[
            pltpu.VMEM((2, gp, block_size, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,) if walk else (2, gp)),
            pltpu.VMEM((nh * t, 1), jnp.float32),
            pltpu.VMEM((nh * t, 1), jnp.float32),
            pltpu.VMEM((nh * t, latent), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _attention_kernel, rows=rows, block_size=block_size,
        pages_per_group=gp, max_pages=m, scale=scale, latent=latent,
        selected=selected, heads=nh, walk=walk, window=window,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, nh * t, latent), q_tiles.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name + _SELECTED if selected else name,
    )(
        block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
        tile_seq.astype(jnp.int32), jnp.max(pos_r, axis=1),
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
        *(() if window is None else (_tile_qmin(pos_r),)),
        *operands,
    )
    return out.reshape(rows, nh, t, latent).transpose(0, 2, 1, 3)


def _tile_qmin(pos_tiles: jax.Array) -> jax.Array:
    """A tile's lowest valid query position (a tile with none: 0; its walk
    is empty anyway, ``qmax`` -1)."""
    big = jnp.iinfo(jnp.int32).max
    qmin = jnp.min(jnp.where(pos_tiles >= 0, pos_tiles, big), axis=1)
    return jnp.where(qmin == big, 0, qmin)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "latent", "decode", "interpret",
                     "window"),
)
def latent_paged_attention(
    q: jax.Array,             # [B, S, Nh, W] absorbed queries (q~ ; q_r)
    pool: jax.Array,          # [L, N, Bk, W] stacked latent pool
    layer_idx: jax.Array,     # scalar int32
    block_tables: jax.Array,  # [B, M] int32
    positions: jax.Array,     # [B, S] int32 (-1 = pad)
    kv_lens: jax.Array,       # [B] int32
    block_size: int = 16,
    *,
    scale: float,
    latent: int,
    decode: bool = False,
    interpret: bool = False,
    keep: jax.Array | None = None,    # [B, S, M * Bk] float32 > 0: the
                                      # cached tokens each query attends
    walk: "SelectedWalk | None" = None,
                                      # S == 1: the rows' selected pages
                                      # (:func:`selected_walk`), in place
                                      # of ``keep``
    window: int | None = None,        # a sliding layer: the positions a
                                      # query attends, itself among them
) -> jax.Array:
    """Absorbed attention of ``S`` queries a row against the row's cached
    latents → ``[B, S, Nh, latent]`` (the caller lifts it through ``W_UV``).
    Masking is the XLA form's (``models/mla.latent_attention_xla``): a query
    at position p sees cached positions ``j <= p`` inside ``kv_lens``, a
    padded query gives zeros. ``decode`` only names the kernel. Under a
    selection a one-token row walks its selected pages alone (``walk``, or
    built here from ``keep``); a longer row walks its pages under ``keep``."""
    b, s, nh, w = q.shape
    _check(pool, block_size, w, latent, interpret)
    if s == 1 and (keep is not None or walk is not None):
        if walk is None:
            walk = selected_walk(keep, block_tables, positions[:, 0],
                                 kv_lens, block_size)
        # the walk IS the table: ``count`` pages, every column of which
        # the query sees unless ``keep`` says otherwise
        fetched = walk.count * block_size
        out = _attend_tiles(
            q.reshape(b, 1, nh, w),
            jnp.where(positions >= 0, fetched[:, None] - 1, -1),
            jnp.arange(b, dtype=jnp.int32), pool, layer_idx, walk.pages,
            fetched, block_size=block_size, scale=scale, latent=latent,
            interpret=interpret, keep_tiles=walk.keep, walk=True,
            window=window,
            name=DECODE_KERNEL_NAME if decode else RAGGED_KERNEL_NAME,
        )
        return out.reshape(b, 1, nh, latent)
    t = _q_tile(s, nh)
    s_pad = -(-s // t) * t
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        positions = jnp.pad(
            positions, ((0, 0), (0, s_pad - s)), constant_values=-1)
        if keep is not None:
            keep = jnp.pad(keep, ((0, 0), (0, s_pad - s), (0, 0)))
    qt = s_pad // t
    out = _attend_tiles(
        q.reshape(b * qt, t, nh, w), positions.reshape(b * qt, t),
        jnp.arange(b * qt, dtype=jnp.int32) // qt, pool, layer_idx,
        block_tables, kv_lens, block_size=block_size, scale=scale,
        latent=latent, interpret=interpret,
        name=_kernel_name(decode, window),
        keep_tiles=None if keep is None
        else keep.reshape(b * qt, t, keep.shape[2]),
        window=window,
    )
    return out.reshape(b, s_pad, nh, latent)[:, :s]


def _kernel_name(decode: bool, window) -> str:
    if window is not None:
        return WINDOW_DECODE_KERNEL_NAME if decode \
            else WINDOW_RAGGED_KERNEL_NAME
    return DECODE_KERNEL_NAME if decode else RAGGED_KERNEL_NAME


class SelectedWalk(NamedTuple):
    """A scan step's rows under a selection, laid out for the kernel
    (:func:`selected_walk`): built once by a layer that computes a
    selection and walked again, as it is, by the layers that share it."""

    pages: jax.Array    # [B, columns] int32 the row's selected pages, in
                        # context order, the last of them repeated
    keep: jax.Array     # [B, 1, columns x Bk] float32 in that order
    count: jax.Array    # [B] int32 pages to fetch


def _pages_per_group(table_width: int, block_size: int, walk: bool) -> int:
    """Pages a group stages of a table ``table_width`` pages wide: the
    rows' block tables, or (``walk``) a scan step's selected pages."""
    tokens = _WALK_GROUP_TOKENS if walk else _GROUP_TOKENS
    return max(1, min(tokens // block_size, table_width))


def walk_columns(table_width: int, block_size: int) -> int:
    """Columns of a walk over a table ``table_width`` pages wide: the
    kernel's whole page groups."""
    gp = _pages_per_group(table_width, block_size, True)
    return -(-table_width // gp) * gp


def selected_walk(keep: jax.Array, block_tables: jax.Array,
                  positions: jax.Array, kv_lens: jax.Array,
                  block_size: int) -> SelectedWalk:
    """``keep [B, 1, J]`` of one-token rows (``positions [B]``) → the pages
    that hold a token a row's query attends, and ``keep`` in their order
    (``ops/paged_attention_pallas._selected_pages``: one stable sort of
    ``[B, M]`` words)."""
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        _selected_pages,
    )

    return SelectedWalk(*_selected_pages(
        keep, block_tables, positions, kv_lens, block_size, None,
        walk_columns(block_tables.shape[1], block_size)))


def _check(pool, block_size, w, latent, interpret):
    if pool.shape[2] != block_size or pool.shape[3] != w:
        raise ValueError(f"pool {pool.shape} against queries {w} wide, "
                         f"block {block_size}")
    if latent % 128 and not interpret:
        raise ValueError(f"latent width {latent} is not lane-aligned")


class PackedTiles(NamedTuple):
    """A packed round's tokens as query tiles, the same for every layer
    (:func:`packed_tiles`): ``T`` consecutive tokens of one sequence a
    tile, a sequence's last tile padded. ``ceil(Tp / T) + B`` tiles hold
    any round, where the ``[B, S]`` rectangle takes ``B x S / T``."""

    token: jax.Array    # [R, T] int32 packed index of each query; Tp = none
    seq: jax.Array      # [R] int32 sequence of each tile
    pos: jax.Array      # [R, T] int32 position of each query (-1 = none)
    slot: jax.Array     # [Tp] int32 where each packed token sits, R*T flat


def packed_tiles(row: jax.Array, col: jax.Array, positions: jax.Array,
                 num_seqs: int, width: int, heads: int) -> PackedTiles:
    """Tiles of a packed round (``models/llama.Packing``): token ``i`` is
    query ``col[i]`` of sequence ``row[i]`` (``num_seqs`` = padding), a
    sequence's tokens lie side by side with ``col`` counting from 0."""
    tp = row.shape[0]
    t = _q_tile(width, heads)
    n_tiles = -(-tp // t) + num_seqs
    live = row < num_seqs
    count = jnp.zeros((num_seqs + 1,), jnp.int32).at[row].max(
        jnp.where(live, col + 1, 0))[:num_seqs]
    tiles_of = -(-count // t)
    base = jnp.cumsum(tiles_of) - tiles_of                       # [B]
    safe = jnp.minimum(row, num_seqs - 1)
    slot = jnp.where(live, (base[safe] + col // t) * t + col % t,
                     n_tiles * t)
    token = jnp.full((n_tiles * t,), tp, jnp.int32).at[slot].set(
        jnp.arange(tp, dtype=jnp.int32), mode="drop")
    pos = jnp.full((n_tiles * t,), -1, jnp.int32).at[slot].set(
        positions.astype(jnp.int32), mode="drop")
    seq = jnp.zeros((n_tiles,), jnp.int32).at[slot // t].set(
        safe.astype(jnp.int32), mode="drop")
    return PackedTiles(token.reshape(n_tiles, t), seq,
                       pos.reshape(n_tiles, t), slot.astype(jnp.int32))


def latent_paged_attention_packed(
    q: jax.Array,             # [Tp, Nh, W] absorbed queries, packed axis
    tiles: PackedTiles,
    pool: jax.Array,
    layer_idx: jax.Array,
    block_tables: jax.Array,  # [B, M]
    kv_lens: jax.Array,       # [B]
    block_size: int = 16,
    *,
    scale: float,
    latent: int,
    interpret: bool = False,
    keep_tiles: jax.Array | None = None,   # [R, T, M * Bk] float32 > 0:
                                           # what each tile's queries attend
    window: int | None = None,             # a sliding layer's window
) -> jax.Array:
    """:func:`latent_paged_attention` for a packed round, without the
    rectangle: the queries are gathered straight into their tiles and the
    result back onto the packed axis → ``[Tp, Nh, latent]``. At ``Tp`` 264
    on 8 sequences of width 256 that is 41 tiles where the rectangle has
    256, most of them empty. ``keep_tiles``: a selection, a row a query of
    a tile (:func:`keep_for_tiles`)."""
    tp, nh, w = q.shape
    _check(pool, block_size, w, latent, interpret)
    rows, t = tiles.token.shape
    q_tiles = jnp.take(q, tiles.token.reshape(-1), axis=0, mode="fill",
                       fill_value=0).reshape(rows, t, nh, w)
    out = _attend_tiles(
        q_tiles, tiles.pos, tiles.seq, pool, layer_idx, block_tables,
        kv_lens, block_size=block_size, scale=scale, latent=latent,
        name=_kernel_name(False, window), interpret=interpret,
        keep_tiles=keep_tiles, window=window,
    )
    return jnp.take(out.reshape(rows * t, nh, latent), tiles.slot, axis=0,
                    mode="fill", fill_value=0)


def keep_for_tiles(keep: jax.Array, tiles: PackedTiles, col: jax.Array
                   ) -> jax.Array:
    """A round's selection ``keep [B, S, J]`` (the rectangle's) as the
    packed kernel takes it, ``[R, T, J]``: the row of each tile's query
    (``col [Tp]`` its column in the rectangle); a tile position that holds
    no query attends nothing."""
    tp = col.shape[0]
    safe = jnp.minimum(tiles.token, tp - 1)
    rows = keep[tiles.seq[:, None], col[safe]]              # [R, T, J]
    return jnp.where((tiles.token < tp)[..., None], rows, 0.0)
