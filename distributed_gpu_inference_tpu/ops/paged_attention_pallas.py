"""Pallas TPU paged-attention decode kernel (fused KV-write + attention).

First-party replacement for vLLM's PagedAttention CUDA kernel (SURVEY §2.3).
Decode (S = 1) is HBM-bandwidth-bound; this kernel owns the WHOLE per-layer
decode KV path:

- **Fused token write**: the new K/V rows for the step are DMA'd into their
  page slots inside the kernel (pools are input/output-aliased), replacing
  the XLA scatter. Round-2 profiling showed the scatter forced a
  scatter-preferred pool layout inside the decode loop while the kernel
  required the natural layout — XLA reconciled them by COPYING both pools
  every step (~10-20 ms/step at serving pool sizes, scaling with pool size).
- **Full-pool operands + layer index**: the kernel takes the stacked
  ``[L, N, Hkv, Bk, D]`` pools and a scalar ``layer_idx`` instead of a
  per-layer slice — a custom-call operand must be materialized, so the old
  single-layer API made XLA copy the layer slice (pool_bytes/L per layer per
  pool per step) just to pass it in.
- Walks only the **live** page groups of each sequence — the grid is
  ``(B, max_groups)`` and dead cells skip in a few cycles; under a learned
  selection (``keep``) only the pages that hold a token the query attends,
  which the wrapper sorts to the front of a table of their own
  (``_selected_pages``). The scalar core issues the DMA descriptors and the
  vector work from ONE instruction stream, so what it does a page is time
  the compute does not get: a page is the unit, and a page costs the kernel
  one SMEM read and a descriptor a pool. Nothing is tested or clamped a
  page (the wrapper pads the walk's table to whole groups), and a group's
  pages are waited for ONCE a pool: every copy of a buffer slot signals one
  semaphore, and one wait draws the slot's byte count,
- DMAs each KV page HBM→VMEM exactly once (whole ``[Hkv, Bk, D]`` pages stay
  contiguous) and runs flash-style online softmax per page group,
- **Pipelines DMA across the whole (sequence, group) walk** — while group g
  of sequence b computes, the next live group's pages (even of sequence
  b+1) are in flight into the other buffer slot (mutable scalar
  ``buffer_index``/``init_flag``, the standard TPU pattern, cf.
  jax.experimental.pallas.ops.tpu.paged_attention). Round-1's kernel
  double-buffered only within one sequence, so short contexts ran DMA and
  compute serialized and lost to the XLA gather path (ADVICE r1 #3),
- sizes page groups by a VMEM byte budget instead of a fixed token count
  (ADVICE r1 #2: Gemma-7B-geometry pages are 16x llama pages),
- computes every (kv-head, GQA-query-group) in one batched MXU contraction
  per group, in the pool dtype (bf16 in, f32 accumulation) — converting
  staged pages to f32 was a VPU-bound relayout that dominated large-batch
  steps.

Write/read ordering: all token writes are issued AND waited in the first
grid cell, before any read DMA is issued (read prefetches only start in live
cells, which come later in the sequential grid), so a step's written token is
visible to its own attention (its position is within ``kv_lens``).

Correctness contract is identical to ``paged_attention_xla`` over the
written pool (same masking semantics, including window and padded-query
handling); parametrized parity tests drive both through the same cases
(CPU: interpret mode).

A multi-token chunk (S > 1: the ragged round) has the same KV path in two
kernels, further down: ``write_kv_pages_in_place`` (``dgi_paged_write``)
puts the chunk's rows into the stacked pools by layer index, and
``ragged_paged_attention(..., layer_idx=)`` reads them there.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# fixed names (see ops/qmm_pallas.py KERNEL_NAME): ``dgi_paged_decode.<n>``
# on a device trace's XLA Ops line where the fused decode kernel used to
# show as the enclosing ``closed_call.<n>``
DECODE_KERNEL_NAME = "dgi_paged_decode"
RAGGED_KERNEL_NAME = "dgi_ragged_attention"
WRITE_KERNEL_NAME = "dgi_paged_write"
# VMEM budget for the four KV staging buffers (2 pools x 2 slots); the rest
# of VMEM stays free for q/out blocks and compute temporaries.
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024
# tokens of a page group: of a dense walk, and of a walk under a learned
# selection, which is over the pages that hold a selected token and takes
# the width the kernel alone ran fastest at (PERF.md section 6, PR 46)
_GROUP_TOKENS = 512
_SELECTED_GROUP_TOKENS = 2048
# page starts of a group's DMA loop unrolled together under a selection. A
# wide group's loop unrolled whole (twice 120 descriptor pairs) runs the
# kernel 4 % faster, its starts' offsets being constants, and costs every
# start of the worker a quarter more set-up: the lowering is Python's
# (PERF.md section 6, PR 50)
_SELECTED_UNROLL = 8


def _pages_per_group(
    block_size: int, hkv: int, head_dim: int, itemsize: int, max_pages: int,
    staging_pages: int = 0, scale_page_bytes: int = 0,
    selected: bool = False,
) -> int:
    """Pages DMA'd per loop iteration.

    Target ~512-token groups (the grid step has a fixed cost of ~2us on
    v5e, amortized against ~0.6us/128-token HBM transfer), but scale DOWN so
    2 slots x G pages x 2 pools — plus ``staging_pages`` write-staging pages
    — fits the VMEM budget regardless of page geometry, and never exceed the
    static table width. ``scale_page_bytes``: per-page bytes of the int8
    path's bf16 scale buffers ([Bk, D] per page, staged AND double-buffered
    alongside the data pages) — at MQA-ish hkv they rival the int8 data
    pages, so they must count against the same budget. ``selected``: the
    walk is over the pages a selection kept a token of, in wider groups."""
    page_bytes = hkv * block_size * head_dim * itemsize + scale_page_bytes
    budget = _VMEM_BUDGET_BYTES - staging_pages * page_bytes
    g = max(1, budget // (4 * page_bytes))
    tokens = _SELECTED_GROUP_TOKENS if selected else _GROUP_TOKENS
    g = min(g, max(tokens // block_size, 1), max_pages)
    if selected:
        # a group's slice of the selection is a block of the grid: whole
        # 128-lane tiles where the budget, not the target, set the width
        lanes = 128 // math.gcd(128, block_size)
        if g > lanes:
            g -= g % lanes
    return max(g, 1)


def fetched_tokens(keep: jax.Array, block_size: int) -> jax.Array:
    """Tokens the decode kernel fetches of one layer's pool for ``keep [B,
    1, J]`` (int32 scalar): the pages that hold a token a row's query
    attends, whole."""
    b, _, j = keep.shape
    hit = jnp.max(keep.reshape(b, j // block_size, block_size), axis=-1) > 0
    return block_size * jnp.sum(hit, dtype=jnp.int32)


def _selected_pages(
    keep: jax.Array,          # [B, 1, J] float32 > 0: what the query attends
    block_tables: jax.Array,  # [B, M]
    positions: jax.Array,     # [B]
    kv_lens: jax.Array,       # [B]
    block_size: int,
    window: Optional[int],
    columns: int,             # >= M: the walk's whole groups, in pages
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A row's walk under a selection, laid out for the decode kernel: the
    pages that hold a token the row's query attends, in context order, then
    the last of them again in every column up to ``columns``. → (``pages [B,
    columns]`` int32 physical ids; ``keep [B, 1, columns x Bk]`` float32 in
    that order, with what ``positions``, ``kv_lens`` and ``window`` hide
    already taken out; ``count [B]``: the pages to fetch). One stable sort
    of ``[B, M]`` words a call (a page's ``keep`` rides it as a bit mask):
    the kernel then walks ``count`` pages with no test of its own a page — a
    page DMA costs the scalar core what a branch does — and masks a token's
    score by ``keep`` alone. The tail is what lets the kernel start a whole
    group of copies wherever the row's pages end (its one wait a group
    counts on that) without ever reading a page the selection dropped."""
    b, m = block_tables.shape
    if block_size > 32:
        raise ValueError(
            f"block_size {block_size}: a page's selection is one 32-bit word")
    col = jnp.arange(m * block_size, dtype=jnp.int32)[None, :]
    seen = (col <= positions[:, None]) & (col < kv_lens[:, None])
    if window is not None:
        seen &= col > positions[:, None] - window
    keep = jnp.pad(keep[:, 0], ((0, 0), (0, m * block_size - keep.shape[2])))
    kept = ((keep > 0) & seen).reshape(b, m, block_size)
    slots = jnp.arange(block_size, dtype=jnp.uint32)
    bits = jnp.sum(kept.astype(jnp.uint32) << slots, axis=-1,
                   dtype=jnp.uint32)
    _, pages, bits = lax.sort(
        ((bits == 0).astype(jnp.int32), block_tables.astype(jnp.int32), bits),
        dimension=1, is_stable=True, num_keys=1,
    )
    count = jnp.sum(bits != 0, axis=1, dtype=jnp.int32)
    last = jnp.take_along_axis(
        pages, jnp.maximum(count - 1, 0)[:, None], axis=1)
    tail = ((0, 0), (0, columns - m))
    pages = jnp.where(
        jnp.arange(columns, dtype=jnp.int32)[None, :] < count[:, None],
        jnp.pad(pages, tail), last)
    keep = ((jnp.pad(bits, tail)[:, :, None] >> slots) & 1).astype(jnp.float32)
    return pages, keep.reshape(b, 1, columns * block_size), count


def _quantize_token_rows(x: jax.Array, axes) -> Tuple[jax.Array, jax.Array]:
    """THE scalar int8-KV quantization contract, shared by the host-side
    pool quantizer (:func:`quantize_kv_pool`) and the kernel's fused token
    write so the two can never drift: one scale per token over every
    (head, channel) element — amax over ``axes`` floored at 1e-6, /127,
    ROUNDED TO bf16 BEFORE quantizing (the stored int8 must match the
    stored bf16 scale exactly) — real = int * scale. Returns (int8 like x,
    f32 scale with ``axes`` kept as size-1 dims)."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = (jnp.maximum(amax, 1e-6) / 127.0).astype(jnp.bfloat16).astype(
        jnp.float32
    )
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _decode_kernel(
    # scalar prefetch (SMEM; bidx/init are MUTABLE and persist across the
    # sequential grid — they carry the DMA pipeline state)
    bt_ref,        # [B, M] int32 block tables
    lens_ref,      # [B] int32 kv lengths (incl. the token written this step)
    pos_ref,       # [B] int32 query positions (kv_len - 1; <0 = inactive)
    wpos_ref,      # [B] int32 write positions (<0 = no write for this row)
    layer_ref,     # [1] int32 layer index into the stacked pools
    bidx_ref,      # [1] int32 current double-buffer slot
    init_ref,      # [1] int32 1 until the first live chunk issues its DMA
    *rest,         # [pages_ref,] then the blocked operands:
                   # q_ref [1, 1, Nh, D] — this sequence's query heads
                   # newk_ref, newv_ref [B, Hkv, D] new rows (VMEM; whole
                   #   batch)
                   # k_hbm, v_hbm [L, N, Hkv, Bk, D] full stacked pools (HBM,
                   #   aliased)
                   # [keep_ref,] [ks_hbm, vs_hbm,] out_ref, ko_hbm, vo_hbm,
                   # scratch...
    batch: int,
    block_size: int,
    pages_per_group: int,
    max_pages: int,
    window: Optional[int],
    scale: float,
    fused_write: bool,
    quantized: bool,
    selected: bool = False,
):
    # a learned selection (ops/index_select.py), as ``_selected_pages`` lays
    # it out: scalar prefetch [B, M] int32, the row's pages that hold a
    # token its query attends (``lens_ref`` counts their tokens), and [1,
    # 1, group] float32 > 0 at the tokens of this group's pages it attends
    pages_ref = keep_ref = None
    if selected:
        pages_ref, *rest = rest
    q_ref, newk_ref, newv_ref, k_hbm, v_hbm, *rest = rest
    if selected:
        keep_ref, *rest = rest
    # int8 pools carry per-(page, token) scale pages ([L, N, Bk, D] bf16,
    # lane-replicated): staged tiles dequantize IN PAGE LAYOUT during the
    # upcast — int8→bf16 is a native VPU convert (unlike fp8, which v5e
    # emulates in software: the round-3 2.2x loss) and the scale multiply
    # rides the same elementwise pass before the bf16 MXU dot
    if quantized:
        (_ks_in, _vs_in, out_ref, ko_hbm, vo_hbm, kso_hbm, vso_hbm,
         kbuf, vbuf, ksbuf, vsbuf, sems, ssems, wsems,
         wk_stage, wv_stage, wks_stage, wvs_stage,
         m_scr, l_scr, acc_scr) = rest
    else:
        (out_ref, ko_hbm, vo_hbm,
         kbuf, vbuf, sems, wsems,
         wk_stage, wv_stage, m_scr, l_scr, acc_scr) = rest
        kso_hbm = vso_hbm = ksbuf = vsbuf = ssems = None
        wks_stage = wvs_stage = None
    b = pl.program_id(0)
    i = pl.program_id(1)
    gp = pages_per_group
    gsz = gp * block_size
    nh, d = q_ref.shape[2], q_ref.shape[3]
    hkv = k_hbm.shape[2]
    qpk = nh // hkv
    layer = layer_ref[0]
    max_groups = pl.num_programs(1)

    def num_groups(s):
        s = jnp.clip(s, 0, batch - 1)
        # clamp to the grid bound: a kv_len beyond the table capacity (caller
        # bug) must not leave a prefetched DMA un-waited at kernel exit —
        # that wedges the chip with a hung semaphore instead of just
        # returning garbage for the out-of-range tail
        return jnp.minimum(pl.cdiv(lens_ref[s], gsz), max_groups)

    def start_group(s):
        if window is None:
            return jnp.int32(0)
        s = jnp.clip(s, 0, batch - 1)
        # first visible key = max(0, pos - window + 1) → its group
        return jnp.maximum(pos_ref[s] - window + 1, 0) // gsz

    ng_b = num_groups(b)
    start_b = start_group(b)
    live = (i >= start_b) & (i < ng_b)

    if fused_write:
        # ---- token writes: ALL rows handled in the FIRST grid cell,
        # strictly before any read DMA is issued (reads start in live cells,
        # which are at or after (0,0) in the sequential grid). The HBM pool
        # is (8,128)-tiled on its last two dims, so a single token slot is
        # not DMA-addressable — each row's page is staged whole into VMEM,
        # the slot row is blended in with a vectorized select (no dynamic
        # sublane store), and the page is written back whole. All four DMA
        # phases are issued batch-wide before being waited, so latency is
        # paid ~twice, not 4B times. Distinct rows never share a page (each
        # sequence owns its block chain and CoW gives writers exclusive
        # pages), so whole-page write-back cannot clobber a sibling write.
        n_stage = wk_stage.shape[0]

        def row_page(r):
            wpos = wpos_ref[r]
            safe = jnp.maximum(wpos, 0)
            page = bt_ref[r, jnp.minimum(safe // block_size, max_pages - 1)]
            return wpos >= 0, page, safe % block_size

        def stage_copies(st, page, dst_first):
            def cp(hbm, stage, sem):
                return pltpu.make_async_copy(
                    hbm.at[layer, page], stage.at[st], sem
                ) if dst_first else pltpu.make_async_copy(
                    stage.at[st], hbm.at[layer, page], sem
                )

            copies = [cp(ko_hbm, wk_stage, wsems.at[0, st]),
                      cp(vo_hbm, wv_stage, wsems.at[1, st])]
            if quantized:
                copies += [cp(kso_hbm, wks_stage, wsems.at[2, st]),
                           cp(vso_hbm, wvs_stage, wsems.at[3, st])]
            return copies

        def each_row(c0, phase):
            """One phase of the write over the chunk of rows from ``c0``:
            ``phase(row, staging page, page, slot)``, a row with a token to
            write under its own ``pl.when``. The body is traced ONCE, the
            row an index, and unrolled at lowering, where the index is a
            constant again: Mosaic gets the program a Python loop over the
            rows gives it, and Python traces an eighth of it at eight rows.
            With ``group_dma``'s page starts these loops were most of the
            seconds a decode graph took to trace (PERF.md section 6, PR
            56)."""

            def row(st, carry):
                valid, page, slot = row_page(c0 + st)
                pl.when(valid)(lambda: phase(c0 + st, st, page, slot))
                return carry

            lax.fori_loop(0, min(n_stage, batch - c0), row, 0, unroll=True)

        def copies_phase(dst_first, wait):
            def phase(_r, st, page, _slot):
                for c in stage_copies(st, page, dst_first):
                    c.wait() if wait else c.start()

            return phase

        def blend(r, st, _page, slot):
            sel = lax.broadcasted_iota(
                jnp.int32, (hkv, block_size, d), 1) == slot
            if quantized:
                # quantize the new rows IN-KERNEL through the shared
                # contract: one scale over the token's whole (Hkv, D) row
                # block
                newk = newk_ref[r].astype(jnp.float32)
                newv = newv_ref[r].astype(jnp.float32)
                ki, sk = _quantize_token_rows(newk, (0, 1))
                vi, sv = _quantize_token_rows(newv, (0, 1))
                sk, sv = sk[0, 0], sv[0, 0]
                wk_stage[st] = jnp.where(sel, ki[:, None, :], wk_stage[st])
                wv_stage[st] = jnp.where(sel, vi[:, None, :], wv_stage[st])
                sel_s = lax.broadcasted_iota(
                    jnp.int32, (block_size, d), 0) == slot
                wks_stage[st] = jnp.where(
                    sel_s, sk.astype(jnp.bfloat16), wks_stage[st])
                wvs_stage[st] = jnp.where(
                    sel_s, sv.astype(jnp.bfloat16), wvs_stage[st])
            else:
                wk_stage[st] = jnp.where(
                    sel, newk_ref[r][:, None, :], wk_stage[st])
                wv_stage[st] = jnp.where(
                    sel, newv_ref[r][:, None, :], wv_stage[st])

        @pl.when((b == 0) & (i == 0))
        def _():
            # rows are processed in chunks of n_stage staging pages so the
            # scratch footprint stays within the VMEM budget at any
            # batch x page geometry; within a chunk the four DMA phases are
            # issued batch-wide before being waited
            for c0 in range(0, batch, n_stage):
                each_row(c0, copies_phase(dst_first=True, wait=False))
                each_row(c0, copies_phase(dst_first=True, wait=True))
                each_row(c0, blend)
                each_row(c0, copies_phase(dst_first=False, wait=False))
                each_row(c0, copies_phase(dst_first=False, wait=True))

    # the table the walk reads, a page a column in the order of the walk: the
    # block table, or under a selection the pages that hold a token the
    # query attends. The wrapper pads it to whole groups with the page the
    # walk repeats past its end (the table's last column, masked by
    # ``kv_lens``; the row's last fetched page, where ``keep`` holds zeros),
    # so a start reads its page and clamps nothing
    walk_ref = pages_ref if selected else bt_ref

    def group_dma(s, j, slot, wait):
        """Start the page DMAs of group j of sequence s into buffer slot
        (the next ``gp`` pages of its walk), or wait for the slot to be
        whole. Reads go through the ALIASED output refs so they observe the
        token writes above (the written scales of an int8 pool like its
        data pages)."""
        pools = [(ko_hbm, kbuf, sems.at[0, slot]),
                 (vo_hbm, vbuf, sems.at[1, slot])]
        if quantized:
            pools += [(kso_hbm, ksbuf, ssems.at[0, slot]),
                      (vso_hbm, vsbuf, ssems.at[1, slot])]
        if wait:
            # ONE wait a pool: every copy of the group signals the pool's
            # semaphore of this slot, and a wait draws the byte count of its
            # destination, here the whole slot (the source only gives the
            # descriptor its shape). That count is what was signalled
            # BECAUSE a group always starts exactly ``gp`` whole-page copies
            # a pool, whatever the row holds (``walk_ref`` above): a wait for
            # more bytes than were signalled hangs the chip
            for hbm, buf, sem in pools:
                pltpu.make_async_copy(
                    hbm.at[0, pl.ds(0, gp)], buf.at[slot], sem).wait()
            return
        row = jnp.clip(s, 0, batch - 1)
        base = j * gp

        def start(p):
            page = walk_ref[row, base + p]
            # whole-page slice [Hkv, Bk, D]: contiguous, tiling-safe
            for hbm, buf, sem in pools:
                pltpu.make_async_copy(
                    hbm.at[layer, page], buf.at[slot, p], sem).start()

        # G page starts a pool. Under a selection, whose group is up to 128
        # pages wide, a rolled loop over runs of a few
        step = _SELECTED_UNROLL if selected and gp % _SELECTED_UNROLL == 0 \
            else gp

        def run(c, carry):
            for p in range(step):
                start(c * step + p)
            return carry

        def one(p, carry):
            start(p)
            return carry

        if step == gp:
            # unrolled whole, at lowering: traced once, as the write's rows
            lax.fori_loop(0, gp, one, 0, unroll=True)
        else:
            lax.fori_loop(0, gp // step, run, 0)

    def next_chunk(s, j):
        """Grid-order successor of live chunk (s, j): (s, j+1) within the
        sequence, else the first live group of the next non-empty sequence;
        (batch, 0) when the walk is done."""

        def advance_seq():
            def step(_, ss):
                return jnp.where(
                    (ss < batch) & (num_groups(ss) == 0), ss + 1, ss
                )

            ns = lax.fori_loop(0, batch, step, s + 1)
            return ns, jnp.where(ns < batch, start_group(ns), 0)

        return lax.cond(
            j + 1 < num_groups(s), lambda: (s, j + 1), advance_seq
        )

    # inactive sequence: its output block must still be written once
    @pl.when((ng_b == 0) & (i == 0))
    def _():
        out_ref[0, 0] = jnp.zeros((nh, d), out_ref.dtype)

    @pl.when(live)
    def _():
        slot = bidx_ref[0]

        # very first live chunk of the whole walk: nothing prefetched it
        @pl.when(init_ref[0] == 1)
        def _():
            group_dma(b, i, slot, wait=False)

        init_ref[0] = 0

        # pipeline: issue the NEXT live chunk (possibly of the next
        # sequence) into the other slot before waiting on this one
        nb, ni = next_chunk(b, i)

        @pl.when(nb < batch)
        def _():
            group_dma(nb, ni, 1 - slot, wait=False)

        bidx_ref[0] = 1 - slot

        group_dma(b, i, slot, wait=True)

        @pl.when(i == start_b)
        def _():
            m_scr[...] = jnp.full((hkv, qpk), _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros((hkv, qpk), jnp.float32)
            acc_scr[...] = jnp.zeros((hkv, qpk, d), jnp.float32)

        kv_len = lens_ref[b]
        pos = pos_ref[b]
        # [Hkv, qpk, D] — GQA head h = g*qpk + j belongs to kv head g.
        # The dot runs in the pool dtype when it is MXU-native (bf16 with
        # f32 accumulation; converting the staged K/V pages to f32 in VMEM
        # is a VPU-bound relayout of megabytes per grid cell that dominated
        # the kernel at large batch). An fp8 pool (kv_cache_dtype="fp8") is
        # NOT MXU-native on v5e — pages are upcast to bf16 in VMEM right at
        # the dot operand, so HBM still only saw the fp8 bytes. The softmax
        # scale is applied to the f32 scores so q carries no extra rounding.
        cdt = jnp.bfloat16 if kbuf.dtype.itemsize == 1 else kbuf.dtype
        qf = q_ref[0, 0].reshape(hkv, qpk, d).astype(cdt)

        # [G, Hkv, Bk, D] → [Hkv, G*Bk, D] (leading-dim relabel, no relayout)
        if quantized:
            # dequantize in the page layout during the upcast: the int8→bf16
            # convert is a native VPU op (unlike fp8, which v5e emulates) and
            # the scale rides the same elementwise pass. Scale pages store
            # one per-(page, token) scale LANE-REPLICATED as [Bk, D] bf16 —
            # the only layout that is both HBM-DMA-sliceable (last dim 128)
            # and broadcastable over the Hkv sublane dim without a Mosaic
            # relayout (a packed [Hkv, Bk] tile is neither).
            kq = kbuf[slot].astype(cdt) * ksbuf[slot][:, None, :, :]
            vq = vbuf[slot].astype(cdt) * vsbuf[slot][:, None, :, :]
            k = kq.transpose(1, 0, 2, 3).reshape(hkv, gsz, d)
            v = vq.transpose(1, 0, 2, 3).reshape(hkv, gsz, d)
        else:
            k = kbuf[slot].transpose(1, 0, 2, 3).reshape(hkv, gsz, d).astype(cdt)
            v = vbuf[slot].transpose(1, 0, 2, 3).reshape(hkv, gsz, d).astype(cdt)
        scores = lax.dot_general(
            qf, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                         # [Hkv, qpk, gsz]
        if selected:
            # the group's pages lie in the order of ``keep``, which has the
            # position, the length and the window in it already
            valid = jnp.broadcast_to(keep_ref[...] > 0, (hkv, qpk, gsz))
        else:
            col = i * gsz + lax.broadcasted_iota(
                jnp.int32, (hkv, qpk, gsz), 2)
            valid = (col < kv_len) & (col <= pos)
            if window is not None:
                valid &= col > pos - window
        scores = jnp.where(valid, scores, _NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))   # [Hkv, qpk]
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new[..., None])
        probs = jnp.where(valid, probs, 0.0)
        l_new = l_prev * alpha + jnp.sum(probs, axis=-1)
        # P·V in the pool dtype (f32 accumulation): bf16 probs is the
        # standard flash-attention trade — error is bounded by the softmax
        # normalization and the parity tests hold at bf16 tolerance
        acc_new = acc_scr[...] * alpha[..., None] + lax.dot_general(
            probs.astype(cdt), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                 # [Hkv, qpk, D]
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

        # last live group of this sequence: normalize and emit
        @pl.when(i == ng_b - 1)
        def _():
            safe_l = jnp.where(l_new > 0, l_new, 1.0)[..., None]
            # minor-dim insertion on i1 vectors is unsupported by Mosaic —
            # expand the f32 operand and compare after
            out = jnp.where(safe_l > 0, acc_new / safe_l, 0.0)
            out = jnp.where(l_new[..., None] > 0, out, 0.0)
            out_ref[0, 0] = out.reshape(nh, d).astype(out_ref.dtype)


def _call_decode_kernel(
    q: jax.Array,             # [B, 1, Nh, D]
    new_k: jax.Array,         # [B, Hkv, D]
    new_v: jax.Array,
    k_pool: jax.Array,        # [L, N, Hkv, Bk, D] stacked pools
    v_pool: jax.Array,
    layer_idx: jax.Array,     # scalar int32
    block_tables: jax.Array,  # [B, M] int32
    positions: jax.Array,     # [B] int32 query positions (-1 = inactive)
    write_positions: jax.Array,  # [B] int32 (-1 = no write)
    kv_lens: jax.Array,       # [B] int32
    block_size: int,
    window: Optional[int],
    fused_write: bool,
    interpret: bool,
    k_scale: Optional[jax.Array] = None,   # [L, N, Bk, D] bf16 lane-replicated
    v_scale: Optional[jax.Array] = None,   # (int8 pools; see paged_attention_pallas)
    keep: Optional[jax.Array] = None,      # [B, 1, M * Bk] float32 selection
) -> Tuple[jax.Array, ...]:
    # → (out, k_pool, v_pool) — plus (k_scale, v_scale) when quantized
    b, s, nh, d = q.shape
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "int8-KV pools need BOTH k_scale and v_scale (or neither): a "
            "lone scale would silently treat the other pool's raw int8 "
            "codes as real values"
        )
    quantized = k_scale is not None
    if s != 1:
        raise ValueError("pallas paged attention is the decode (S=1) kernel")
    if d % 128 != 0 and not interpret:
        # XLA:TPU pads HBM arrays to 128 lanes; a page slice of a narrower
        # head_dim is not expressible without relayout — dispatch keeps such
        # models on the XLA path (ops/attention.py impl="auto")
        raise ValueError(f"pallas decode kernel needs head_dim % 128 == 0, got {d}")
    L, n, hkv, bk, _ = k_pool.shape
    if bk != block_size:
        raise ValueError(f"pool block dim {bk} != block_size {block_size}")
    m = block_tables.shape[1]
    # write staging: up to `b` pages per pool, capped so 2 pools of staging
    # never take more than half the VMEM budget (rows are chunked through
    # the staging pages when b exceeds the cap). int8 pools stage a bf16
    # [Bk, D] scale page per data page (buffers AND staging), which at
    # MQA-ish hkv rivals the int8 page itself — count it.
    scale_page_bytes = block_size * d * 2 if quantized else 0
    page_bytes = hkv * block_size * d * k_pool.dtype.itemsize \
        + scale_page_bytes
    if fused_write:
        n_stage = max(1, min(b, _VMEM_BUDGET_BYTES // 2 // (2 * page_bytes)))
    else:
        n_stage = 1
    selected = keep is not None
    # a group is never wider than the pool: the kernel's one wait a group
    # takes its shape from a group's worth of the pool's pages
    gp = _pages_per_group(
        block_size, hkv, d, k_pool.dtype.itemsize, min(m, n),
        staging_pages=2 * n_stage, scale_page_bytes=scale_page_bytes,
        selected=selected,
    )
    max_groups = -(-m // gp)
    # the table the kernel walks is whole groups wide, its tail the page a
    # group's copies repeat past the row's end: the kernel starts ``gp``
    # copies a group whatever the row holds and clamps no index
    columns = max_groups * gp
    lens = kv_lens
    block_tables = block_tables.astype(jnp.int32)
    if selected:
        # the walk is over the pages the selection kept a token of
        pages, keep, count = _selected_pages(
            keep, block_tables, positions, kv_lens, block_size, window,
            columns)
        lens = count * block_size
    else:
        block_tables = jnp.pad(
            block_tables, ((0, 0), (0, columns - m)), mode="edge")

    in_specs = [
        pl.BlockSpec(
            (1, 1, nh, d),
            lambda i, j, *_refs: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(memory_space=pltpu.VMEM),   # new_k (whole array)
        pl.BlockSpec(memory_space=pltpu.VMEM),   # new_v
        # pools must STAY in HBM (left to the compiler, the whole pool can
        # land in VMEM, where the padded lane dim breaks page slices)
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
    ]
    scratch = [
        pltpu.VMEM((2, gp, hkv, block_size, d), k_pool.dtype),
        pltpu.VMEM((2, gp, hkv, block_size, d), v_pool.dtype),
    ]
    out_specs = [
        pl.BlockSpec(
            (1, 1, nh, d),
            lambda i, j, *_refs: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
    ]
    scalars = [
        block_tables,
        lens.astype(jnp.int32),
        positions.astype(jnp.int32),
        write_positions.astype(jnp.int32),
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),   # buffer_index
        jnp.ones((1,), jnp.int32),    # init_flag
    ]
    if selected:
        # the pages in the order of the walk: a second table in SMEM beside
        # the block table, which the fused write still reads
        scalars.append(pages)
        # a group's slice of the row's selection rides the grid like q
        in_specs.append(pl.BlockSpec(
            (1, 1, gp * block_size), lambda i, j, *_refs: (i, 0, j),
            memory_space=pltpu.VMEM,
        ))
    if quantized:
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.HBM),   # k_scale
            pl.BlockSpec(memory_space=pltpu.HBM),   # v_scale
        ]
        out_specs += [
            pl.BlockSpec(memory_space=pltpu.HBM),   # k_scale (aliased)
            pl.BlockSpec(memory_space=pltpu.HBM),   # v_scale (aliased)
        ]
        scratch += [
            pltpu.VMEM((2, gp, block_size, d), jnp.bfloat16),    # ksbuf
            pltpu.VMEM((2, gp, block_size, d), jnp.bfloat16),    # vsbuf
        ]
    # a semaphore a pool and slot: every copy of a group signals it
    scratch += [pltpu.SemaphoreType.DMA((2, 2))]                 # sems
    if quantized:
        scratch += [pltpu.SemaphoreType.DMA((2, 2))]             # ssems
    scratch += [
        pltpu.SemaphoreType.DMA((4 if quantized else 2, b)),     # wsems
        pltpu.VMEM((n_stage, hkv, block_size, d), k_pool.dtype),
        pltpu.VMEM((n_stage, hkv, block_size, d), v_pool.dtype),
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((n_stage, block_size, d), jnp.bfloat16),  # wks_stage
            pltpu.VMEM((n_stage, block_size, d), jnp.bfloat16),  # wvs_stage
        ]
    scratch += [
        pltpu.VMEM((hkv, nh // hkv), jnp.float32),
        pltpu.VMEM((hkv, nh // hkv), jnp.float32),
        pltpu.VMEM((hkv, nh // hkv, d), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, max_groups),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _decode_kernel,
        batch=b,
        block_size=block_size,
        pages_per_group=gp,
        max_pages=m,
        window=window,
        scale=d**-0.5,
        fused_write=fused_write,
        quantized=quantized,
        selected=selected,
    )
    operands = [*scalars, q, new_k, new_v, k_pool, v_pool]
    if selected:
        operands.append(keep)
    out_shape = [
        jax.ShapeDtypeStruct((b, 1, nh, d), q.dtype),
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    ]
    # operand order: the scalar-prefetch args (7, and the selection's
    # pages), then q, new_k, new_v, k_pool, v_pool → the pools aliased to
    # outputs 1, 2; quantized adds scale pools (after the selection, where
    # there is one) aliased to outputs 3, 4 so the fused write's
    # quantization scales land in place
    aliases = {len(scalars) + 3: 1, len(scalars) + 4: 2}
    if quantized:
        first = len(operands)       # after the selection, where there is one
        operands += [k_scale.astype(jnp.bfloat16),
                     v_scale.astype(jnp.bfloat16)]
        out_shape += [
            jax.ShapeDtypeStruct(k_scale.shape, jnp.bfloat16),
            jax.ShapeDtypeStruct(v_scale.shape, jnp.bfloat16),
        ]
        aliases.update({first: 3, first + 1: 4})
    results = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=DECODE_KERNEL_NAME,
    )(*operands)
    return results  # (out, k, v[, k_scale, v_scale])


def paged_decode_attention_fused(
    q: jax.Array,             # [B, 1, Nh, D]
    new_k: jax.Array,         # [B, 1, Hkv, D] this step's K rows
    new_v: jax.Array,
    k_pool: jax.Array,        # [L, N, Hkv, Bk, D] stacked pools
    v_pool: jax.Array,
    layer_idx: jax.Array,     # scalar int32
    block_tables: jax.Array,  # [B, M] int32
    positions: jax.Array,     # [B, 1] int32 (-1 = inactive); ALSO the write
                              # position of the new row
    kv_lens: jax.Array,       # [B] int32, INCLUDING the written token
    block_size: int = 16,
    window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,   # [L, N, Bk, D] bf16 (int8 pools)
    v_scale: Optional[jax.Array] = None,
    keep: Optional[jax.Array] = None,      # [B, 1, M * Bk] float32 > 0: the
                                           # context the row's query attends
):
    """The per-layer decode step: write this step's K/V rows into their page
    slots AND attend over the updated paged context, in one kernel with the
    pools aliased in place. → (attn [B, 1, Nh, D], k_pool, v_pool) — plus
    (k_scale, v_scale) when the pools are int8 (the kernel quantizes the
    new rows in place and the step's scales ride the aliased scale
    pools)."""
    pos = positions[:, 0]
    return _call_decode_kernel(
        q, new_k[:, 0], new_v[:, 0], k_pool, v_pool, layer_idx,
        block_tables, pos, pos, kv_lens, block_size, window,
        fused_write=True, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale, keep=keep,
    )


def quantize_kv_pool(pool: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """bf16/f32 pool [N, Hkv, Bk, D] → (int8 pool, [N, Bk, D] bf16 scales).

    The STORAGE layout of the int8-KV kernel path (tests and benchmarks
    import it so it cannot drift): one scale per (page, token), amax over
    (Hkv, D) shared across KV heads, stored lane-replicated over D as
    bf16; real = int * scale. The scalar contract itself lives in
    ``_quantize_token_rows`` — shared with the kernel's fused token
    write."""
    n, _, bk, d = pool.shape
    q, scale = _quantize_token_rows(pool.astype(jnp.float32), (1, 3))
    return q, jnp.broadcast_to(
        scale[:, 0, :, 0, None].astype(jnp.bfloat16), (n, bk, d)
    )


# --------------------------------------------------------------------------
# In-place page write: a multi-token chunk's K/V rows go into the STACKED
# pools where they lie, as the decode kernel's fused write does for one
# token a row. The XLA scatter it replaces needs one layer's pool as its
# operand: sliced out of the stack, copied into the layout the scatter
# prefers and back, written back into the stack — about five passes over
# the whole pool in every multi-token round, whatever the round held
# (PERF.md section 5).
# --------------------------------------------------------------------------

# VMEM the write kernel's page buffers may take: the K and V update blocks
# (double-buffered by the pipeline) and the two staging buffers, six
# page-tiles in all. A row's span that needs more is split into tiles.
_WRITE_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


class PageWritePlan(NamedTuple):
    """Where a chunk's rows land in the paged pool, page by page. The same
    for every layer: built once a forward pass (:func:`page_write_plan`),
    outside the layer scan. A *cell* is one page a row of the chunk can
    touch: ``cells`` of them a row (the pages a span of S tokens covers at
    any offset, rounded up to whole tiles), row-major."""

    page: jax.Array   # [B * cells] int32 physical page of each cell
    kind: jax.Array   # [B * cells] int32 0 = nothing written to the page,
                      # 1 = some of its slots (read-modify-write), 2 = all
    slots: jax.Array  # [B * cells * words] int32 bit s of word w: slot
                      # 32 w + s of the page is written
    src: jax.Array    # [B * cells * Bk] int32 the chunk token every slot
                      # takes, on the caller's flat token axis (its length
                      # = none)
    tile: int         # cells a grid step handles (static)


def page_write_plan(
    block_tables: jax.Array,     # [B, M] int32
    write_positions: jax.Array,  # [B, S] int32 (-1 = nothing to write)
    block_size: int,
    page_bytes: int,             # one page of one pool: Hkv * Bk * D * size
    token_index: Optional[jax.Array] = None,  # [B, S] where each position
                                 # of the rectangle lies on the caller's
                                 # flat token axis (a packed round's
                                 # ``to_rect``); None: row-major, b * S + s
    num_tokens: Optional[int] = None,         # length of that axis
) -> PageWritePlan:
    """The chunk's own view of the page write, in the semantics of the
    scatter it replaces (``models/llama._page_scatter_indices``): token
    ``(b, s)`` with ``write_positions[b, s] >= 0`` lands in slot ``pos %
    Bk`` of page ``block_tables[b, pos // Bk]``, a negative position writes
    nothing. One restriction: a row's written positions lie within S of
    each other (every caller writes a span; positions further than the
    pages counted from the row's first are dropped)."""
    b, s = write_positions.shape
    m = block_tables.shape[1]
    if block_size > 32 and block_size % 32:
        raise ValueError(f"block_size {block_size}: want <= 32 or whole words")
    if token_index is None:
        token_index = jnp.arange(b * s, dtype=jnp.int32).reshape(b, s)
        num_tokens = b * s
    # pages a span of s tokens touches when it starts at a page's last slot
    need = (s + 2 * block_size - 2) // block_size
    tiles = -(-need // max(1, _WRITE_VMEM_BUDGET_BYTES // (6 * page_bytes)))
    tile = -(-need // tiles)
    cells = tiles * tile
    valid = write_positions >= 0
    # the row's first written page (a row with nothing to write: far past
    # any table, so none of its cells is live)
    first = jnp.min(
        jnp.where(valid, write_positions, jnp.int32(2**30)), axis=1,
        keepdims=True,
    ) // block_size                                            # [B, 1]
    # slot of the row's span each token takes; pads go out of range
    rel = jnp.where(
        valid, write_positions - first * block_size, cells * block_size
    )
    src = jnp.full((b, cells * block_size), num_tokens, jnp.int32).at[
        jnp.arange(b, dtype=jnp.int32)[:, None], rel
    ].set(token_index.astype(jnp.int32), mode="drop")
    logical = first + jnp.arange(cells, dtype=jnp.int32)       # [B, cells]
    # a page past the row's table is never written (the scatter drops it)
    live = (src < num_tokens).reshape(b, cells, block_size) \
        & (logical < m)[:, :, None]
    count = jnp.sum(live, axis=2, dtype=jnp.int32)
    kind = (count > 0).astype(jnp.int32) + (count == block_size)
    width = min(block_size, 32)                 # slots a mask word holds
    slots = jnp.sum(
        live.reshape(b, -1, width).astype(jnp.int32)
        << jnp.arange(width, dtype=jnp.int32), axis=2, dtype=jnp.int32,
    )                       # bit 31 wraps into the sign: two's complement
    page = jnp.take_along_axis(
        block_tables.astype(jnp.int32), jnp.minimum(logical, m - 1), axis=1
    )
    return PageWritePlan(
        page=page.reshape(-1), kind=kind.reshape(-1),
        slots=slots.reshape(-1), src=src.reshape(-1), tile=tile,
    )


def _page_write_kernel(
    # scalar prefetch (SMEM)
    page_ref,      # [C] int32 physical page of each cell
    kind_ref,      # [C] int32 0 untouched / 1 partly written / 2 whole
    slots_ref,     # [C * words] int32 written-slot bit masks
    layer_ref,     # [1] int32 layer index into the stacked pools
    # blocked operands
    newk_ref,      # [tile, Hkv, Bk, D] this step's cells' new rows, page-
    newv_ref,      # shaped (VMEM; slots nothing is written to hold zeros)
    _k_in,         # [L, N, Hkv, Bk, D] stacked pools (HBM), aliased to the
    _v_in,         # outputs: every access goes through ko_hbm / vo_hbm
    ko_hbm,
    vo_hbm,
    stage_k,       # [tile, Hkv, Bk, D] VMEM
    stage_v,
    sems,          # DMA [2, tile]
    *,
    tile: int,
    words: int,
):
    """One grid step writes ``tile`` cells. The HBM pool is (8, 128)-tiled
    on its last two dims, so a token slot is not DMA-addressable: a page is
    the unit. A page written whole goes out as its update block; a page
    written in part is staged into VMEM first, the written slots are
    blended in with a vector select (no dynamic sublane store) and the
    page goes back whole, so its other slots keep their bytes. Cells of
    one call never share a page (a sequence owns its block chain, and a
    row's cells are distinct entries of its table), so whole-page
    write-back cannot clobber a sibling's write."""
    base = pl.program_id(0) * tile
    layer = layer_ref[0]
    _, hkv, bk, d = stage_k.shape

    def copies(j, read):
        """Cell j's two page DMAs, K and V: HBM → staging, or back."""
        page = page_ref[base + j]
        out = []
        for c, (hbm, stage) in enumerate(((ko_hbm, stage_k),
                                          (vo_hbm, stage_v))):
            src, dst = hbm.at[layer, page], stage.at[j]
            if not read:
                src, dst = dst, src
            out.append(pltpu.make_async_copy(src, dst, sems.at[c, j]))
        return out

    def start_reads(j, carry):
        @pl.when(kind_ref[base + j] == 1)
        def _():
            for c in copies(j, read=True):
                c.start()

        return carry

    def blend(j, carry):
        kind = kind_ref[base + j]

        @pl.when(kind == 1)
        def _():
            for c in copies(j, read=True):
                c.wait()

        @pl.when(kind != 0)
        def _():
            slot = lax.broadcasted_iota(jnp.int32, (hkv, bk, d), 1)
            sel = None
            for w in range(words):      # static: one word a 32 slots
                mask = jnp.right_shift(
                    slots_ref[(base + j) * words + w], (slot - 32 * w) & 31
                ) & 1
                hit = mask == 1
                if words > 1:
                    hit &= (slot >= 32 * w) & (slot < 32 * (w + 1))
                sel = hit if sel is None else sel | hit
            # a whole page was not staged: every slot selects the new row
            stage_k[j] = jnp.where(sel, newk_ref[j], stage_k[j])
            stage_v[j] = jnp.where(sel, newv_ref[j], stage_v[j])
            for c in copies(j, read=False):
                c.start()

        return carry

    def wait_writes(j, carry):
        @pl.when(kind_ref[base + j] != 0)
        def _():
            for c in copies(j, read=False):
                c.wait()

        return carry

    # three passes, each over every cell of the tile, so the page DMAs of a
    # phase are in flight together and their latency is paid once a phase
    lax.fori_loop(0, tile, start_reads, 0)
    lax.fori_loop(0, tile, blend, 0)
    lax.fori_loop(0, tile, wait_writes, 0)


def write_kv_pages_in_place(
    new_k: jax.Array,         # [T, Hkv, D] the chunk's K rows, one flat axis
    new_v: jax.Array,
    k_pool: jax.Array,        # [L, N, Hkv, Bk, D] stacked pools
    v_pool: jax.Array,
    layer_idx: jax.Array,     # scalar int32
    plan: PageWritePlan,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Write a chunk's K/V rows into layer ``layer_idx`` of the stacked
    pools, in place (the pools are aliased to the outputs) → (k_pool,
    v_pool). What lands where is ``plan``'s; the bytes after the call are
    what ``_write_kv_pages`` into the sliced layer and the write-back
    leave, other layers, other pages and the unwritten slots of written
    pages included. XLA gathers the tokens into page-shaped updates (a few
    hundred KB, tokens only); the kernel moves whole pages by DMA."""
    _, _, hkv, bk, d = k_pool.shape
    if d % 128 != 0 and not interpret:
        raise ValueError(f"in-place page write needs head_dim % 128 == 0, got {d}")
    cells = plan.page.shape[0]
    tile = plan.tile
    words = plan.slots.shape[0] // cells

    def pages(x, pool):
        g = jnp.take(x, plan.src, axis=0, mode="fill", fill_value=0)
        return g.reshape(cells, bk, hkv, d).transpose(0, 2, 1, 3) \
            .astype(pool.dtype)

    block = pl.BlockSpec(
        (tile, hkv, bk, d), lambda i, *_refs: (i, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(cells // tile,),
        in_specs=[block, block, hbm, hbm],
        out_specs=[hbm, hbm],
        scratch_shapes=[
            pltpu.VMEM((tile, hkv, bk, d), k_pool.dtype),
            pltpu.VMEM((tile, hkv, bk, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, tile)),
        ],
    )
    # operand order: 4 scalar-prefetch args, the two update arrays, then
    # k_pool (idx 6), v_pool (idx 7) → aliased to outputs 0, 1
    return tuple(pl.pallas_call(
        functools.partial(_page_write_kernel, tile=tile, words=words),
        out_shape=[
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        grid_spec=grid_spec,
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name=WRITE_KERNEL_NAME,
    )(
        plan.page, plan.kind, plan.slots,
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        pages(new_k, k_pool), pages(new_v, v_pool), k_pool, v_pool,
    ))


# --------------------------------------------------------------------------
# Ragged paged attention: one kernel invocation over a flattened row batch
# where decode rows (q_len = 1), speculative verify rows (q_len = 2..K+1)
# and prefill chunk rows (q_len up to the chunk width) coexist — the
# serving-side unification that lets admission append rows to a decode
# round instead of scheduling a competing prefill dispatch (Ragged Paged
# Attention, PAPERS.md). Since round 8 the verify-row shape is a SERVING
# path, not just a tested one: a spec-integrated engine's ragged_round
# dispatches its draft chains here as q_len = K+1 rows (contiguous
# positions lens..lens+K, per-row in-length bound lens+K+1), mixed with
# chunk rows — int8 pools dequant in-kernel on the same read, which is
# what lifted the models/llama.py int8 verify fence.
# --------------------------------------------------------------------------

# ceiling on (GQA queries per KV head) x (query tile) per grid cell: bounds
# the f32 score tile [Hkv, qpk*T, group] and the accumulator scratch so a
# wide prefill chunk never blows VMEM. Rows longer than the tile split into
# independent q-tiles (softmax state is per query, so tiles never talk);
# pages re-stage once per TILE, not once per query — the fix for the old
# multi-query path's per-query re-staging that capped it at q_len <= 8.
_RAGGED_QPK_TILE = 256
# ceiling on (query heads) x (query tile): the q / out blocks, the
# accumulator and the score tile all carry every head of the cell, so at
# many KV heads (MHA: OLMoE's 16 of 128) the per-KV-head ceiling alone
# lets them outgrow VMEM. 2048 is what the GQA models reach (Mistral:
# 8 KV heads x 256), so their tiles are as they were.
_RAGGED_HEAD_ROWS = 2048
# ceiling on the ragged kernel's four KV staging buffers: what the GQA
# models use at 512-token groups (8 KV heads: 4 MiB). More KV heads take
# fewer pages a group instead of more VMEM.
_RAGGED_KV_STAGING_BYTES = 4 * 1024 * 1024


def _ragged_q_tile(s: int, qpk: int, hkv: int = 1) -> int:
    t = max(1, min(s, _RAGGED_QPK_TILE // max(qpk, 1),
                   _RAGGED_HEAD_ROWS // max(qpk * hkv, 1)))
    return 1 << (t.bit_length() - 1)     # power of two so buckets divide


def _ragged_kernel(
    # scalar prefetch (SMEM; bidx/init persist across the sequential grid)
    bt_ref,        # [B, M] int32 per-SEQUENCE block tables (q-tile rows of
                   # one sequence share its table: row // q_tiles indexes it
                   # — repeating the table per tile would multiply the SMEM
                   # footprint by the tile count, which at long-context
                   # table widths (32k = 2048 pages) is the difference
                   # between fitting and not)
    lens_ref,      # [B] int32 effective kv length per sequence
    qmax_ref,      # [R] int32 max valid query position (-1 = inactive row)
    qmin_ref,      # [R] int32 min valid query position (0 when inactive)
    layer_ref,     # [1] int32 layer index into the stacked pools
    bidx_ref,      # [1] int32 current double-buffer slot
    init_ref,      # [1] int32 1 until the first live chunk issues its DMA
    # blocked operands
    q_ref,         # [1, Hkv, qpk*T, D] — this row's query tile, GQA-grouped
    pos_ref,       # [1, qpk*T, 1] int32 per-query positions (-1 = pad),
                   # tiled over the GQA slots in q_ref's row order
    k_hbm,         # [L, N, Hkv, Bk, D] full stacked pool (HBM, read in
    v_hbm,         # place: a page DMA is k_hbm.at[layer, page])
    *rest,         # [keep_ref,] [ks_hbm, vs_hbm,] out_ref, kbuf, vbuf,
                   # [ksbuf, vsbuf,] sems, [ssems,] m_scr, l_scr, acc_scr
    rows: int,
    q_tiles: int,
    q_tile: int,
    block_size: int,
    pages_per_group: int,
    max_pages: int,
    window: Optional[int],
    scale: float,
    quantized: bool,
    selected: bool = False,
):
    # a learned selection (ops/index_select.py): [1, T, group] float32 > 0
    # at the context positions of this group each query of the tile attends
    keep_ref = None
    if selected:
        keep_ref, *rest = rest
    if quantized:
        (_ks_in, _vs_in, out_ref, kbuf, vbuf, ksbuf, vsbuf,
         sems, ssems, m_scr, l_scr, acc_scr) = rest
        ks_hbm, vs_hbm = _ks_in, _vs_in
    else:
        (out_ref, kbuf, vbuf, sems, m_scr, l_scr, acc_scr) = rest
        ks_hbm = vs_hbm = ksbuf = vsbuf = ssems = None
    r = pl.program_id(0)
    i = pl.program_id(1)
    gp = pages_per_group
    gsz = gp * block_size
    hkv = k_hbm.shape[2]
    d = q_ref.shape[3]
    qpk = q_ref.shape[2] // q_tile
    layer = layer_ref[0]
    max_groups = pl.num_programs(1)

    def num_groups(s_):
        s_ = jnp.clip(s_, 0, rows - 1)
        # a padded/inactive q-tile (qmax < 0) has zero live groups and its
        # grid cells skip in a few cycles — dead tiles of a short row in a
        # wide ragged batch cost nothing but the grid step
        needed = jnp.minimum(qmax_ref[s_] + 1, lens_ref[s_ // q_tiles])
        return jnp.minimum(pl.cdiv(needed, gsz), max_groups)

    def start_group(s_):
        if window is None:
            return jnp.int32(0)
        s_ = jnp.clip(s_, 0, rows - 1)
        return jnp.maximum(qmin_ref[s_] - window + 1, 0) // gsz

    ng_r = num_groups(r)
    start_r = start_group(r)
    live = (i >= start_r) & (i < ng_r)

    def group_copies(s_, j, slot, p):
        """The page DMAs of page p of group j of row s_ into buffer slot."""
        idx = jnp.minimum(j * gp + p, max_pages - 1)
        page = bt_ref[jnp.clip(s_, 0, rows - 1) // q_tiles, idx]
        out = [
            pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[slot, p], sems.at[0, slot, p]),
            pltpu.make_async_copy(
                v_hbm.at[layer, page], vbuf.at[slot, p], sems.at[1, slot, p]),
        ]
        if quantized:
            out += [
                pltpu.make_async_copy(
                    ks_hbm.at[layer, page], ksbuf.at[slot, p],
                    ssems.at[0, slot, p]),
                pltpu.make_async_copy(
                    vs_hbm.at[layer, page], vsbuf.at[slot, p],
                    ssems.at[1, slot, p]),
            ]
        return out

    # G paired page DMAs a group, unrolled where the kernel is lowered: the
    # body is traced once and not G times (a ragged graph's trace was
    # mostly these loops: PERF.md PR 28)
    def start_dma(s_, j, slot):
        def body(p, carry):
            for c in group_copies(s_, j, slot, p):
                c.start()
            return carry

        lax.fori_loop(0, gp, body, 0, unroll=True)

    def wait_dma(s_, j, slot):
        def body(p, carry):
            for c in group_copies(s_, j, slot, p):
                c.wait()
            return carry

        lax.fori_loop(0, gp, body, 0, unroll=True)

    def next_chunk(s_, j):
        """Grid-order successor of live chunk (s_, j) — same walk as the
        decode kernel, over ragged rows instead of sequences."""

        def advance_row():
            def step(_, ss):
                return jnp.where(
                    (ss < rows) & (num_groups(ss) == 0), ss + 1, ss
                )

            ns = lax.fori_loop(0, rows, step, s_ + 1)
            return ns, jnp.where(ns < rows, start_group(ns), 0)

        return lax.cond(
            j + 1 < num_groups(s_), lambda: (s_, j + 1), advance_row
        )

    # inactive row (fully padded q-tile): its output block still writes once
    @pl.when((ng_r == 0) & (i == 0))
    def _():
        out_ref[0] = jnp.zeros((hkv, qpk * q_tile, d), out_ref.dtype)

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

        @pl.when(i == start_r)
        def _():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        kv_len = lens_ref[r // q_tiles]
        # the dot runs in the pool dtype (bf16 in, f32 accumulation) — the
        # same MXU contract as the decode kernel; int8 pages dequantize in
        # page layout during the upcast
        cdt = jnp.bfloat16 if kbuf.dtype.itemsize == 1 else kbuf.dtype
        qf = q_ref[0].astype(cdt)                         # [Hkv, qpk*T, D]
        if quantized:
            kq = kbuf[slot].astype(cdt) * ksbuf[slot][:, None, :, :]
            vq = vbuf[slot].astype(cdt) * vsbuf[slot][:, None, :, :]
            k = kq.transpose(1, 0, 2, 3).reshape(hkv, gsz, d)
            v = vq.transpose(1, 0, 2, 3).reshape(hkv, gsz, d)
        else:
            k = kbuf[slot].transpose(1, 0, 2, 3).reshape(hkv, gsz, d).astype(cdt)
            v = vbuf[slot].transpose(1, 0, 2, 3).reshape(hkv, gsz, d).astype(cdt)
        scores = lax.dot_general(
            qf, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                   # [Hkv, qpk*T, gsz]
        # per-query causal/in-length mask in the flattened [qpk*T, gsz]
        # layout the scores already have: positions arrive pre-tiled per
        # (GQA slot, query) as a [qpk*T, 1] column, so the mask is one lane
        # broadcast — no reshape of the score tile (Mosaic has no shape
        # cast that splits the sublane axis or inserts a minor dim). THE
        # per-row path selection: a decode row (q_len = 1) and a prefill
        # chunk row differ only in this mask and in how many groups the
        # walk gave them.
        col = i * gsz + lax.broadcasted_iota(
            jnp.int32, (1, qpk * q_tile, gsz), 2
        )
        pos_b = pos_ref[0][None]                    # [1, qpk*T, 1]
        valid = (col < kv_len) & (col <= pos_b)
        if window is not None:
            valid &= col > pos_b - window
        if selected:
            # the tile's T rows repeat once a GQA slot, as its positions do
            kept = keep_ref[0] > 0                  # [T, gsz]
            if qpk > 1:
                kept = jnp.concatenate([kept] * qpk, axis=0)
            valid &= kept[None]
        scores = jnp.where(valid, scores, _NEG_INF)

        # softmax state keeps a size-1 minor dim ([Hkv, qpk*T, 1]) so every
        # update below is a lane broadcast against the score tile
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(
            m_prev, jnp.max(scores, axis=-1, keepdims=True)
        )
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        acc_new = acc_scr[...] * alpha + lax.dot_general(
            probs.astype(cdt), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                           # [Hkv, qpk*T, D]
        m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

        @pl.when(i == ng_r - 1)
        def _():
            # fully-masked queries (padding inside a live tile) → exact 0,
            # the XLA-path contract
            out = jnp.where(
                l_new > 0, acc_new / jnp.where(l_new > 0, l_new, 1.0), 0.0
            )
            out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "window", "interpret"),
)
def ragged_paged_attention(
    q: jax.Array,             # [B, S, Nh, D] — per-row spans padded to S
    k_pool: jax.Array,        # [N, Hkv, Bk, D] one layer's pool (head-major
    v_pool: jax.Array,        # pages), or with ``layer_idx`` the stacked
                              # [L, N, Hkv, Bk, D] pool, read in place
    block_tables: jax.Array,  # [B, M] int32
    positions: jax.Array,     # [B, S] int32 (-1 = pad)
    kv_lens: jax.Array,       # [B] int32 effective context per row
    block_size: int = 16,
    window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,   # [N, Bk, D] bf16 lane-replicated
    v_scale: Optional[jax.Array] = None,   # ([L, N, Bk, D] with layer_idx)
    layer_idx: Optional[jax.Array] = None,  # scalar int32: the pools are
                              # the stacked ones and this is the layer
    keep: Optional[jax.Array] = None,       # [B, S, M * Bk] float32 > 0:
                              # the context each query attends
) -> jax.Array:
    """Ragged paged attention: ONE kernel invocation over a flattened token
    batch in which each row carries its own (block table, query-span
    length, effective KV length). Decode rows (one valid query), spec
    verify rows (2..K+1) and prefill chunk rows (up to S) coexist in one
    grid; per-row bounds select each row's path inside the kernel — group
    walk length from ``min(max_pos + 1, kv_len)``, window start from the
    row's min position, causal masking per query. Masking semantics
    (causal, in-length, window, padded queries → exact zeros) are
    identical to ``paged_attention_xla`` over the same batch.

    Rows are split host-side into independent query tiles (softmax state
    is per query) sized so the f32 score tile stays inside VMEM; pages
    re-stage once per TILE — this replaces the old multi-query path, which
    re-staged pages once per QUERY and therefore capped q_len at 8.

    ``layer_idx``: the pools are the model's stacked ``[L, N, Hkv, Bk, D]``
    pools and the kernel reads layer ``layer_idx`` of them where they lie
    (one more scalar-prefetch operand; a page DMA is ``pool.at[layer,
    page]``). A caller inside the layer scan must pass them so: a
    ``dynamic_slice`` of the layer is a custom-call operand XLA has to
    materialise, pool bytes / L a layer a pool. Without it the pools are
    one layer's, as the bare-read callers hold them."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "int8-KV pools need BOTH k_scale and v_scale (or neither)"
        )
    quantized = k_scale is not None
    if layer_idx is None:
        # a leading axis of one is a relabel, not a copy
        layer_idx = 0
        k_pool, v_pool = k_pool[None], v_pool[None]
        if quantized:
            k_scale, v_scale = k_scale[None], v_scale[None]
    b, s, nh, d = q.shape
    _, n, hkv, bk, _ = k_pool.shape
    if bk != block_size:
        raise ValueError(f"pool block dim {bk} != block_size {block_size}")
    if d % 128 != 0 and not interpret:
        raise ValueError(
            f"ragged paged attention needs head_dim % 128 == 0, got {d}"
        )
    qpk = nh // hkv
    m = block_tables.shape[1]
    t = _ragged_q_tile(s, qpk, hkv)
    s_pad = -(-s // t) * t
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        positions = jnp.pad(
            positions, ((0, 0), (0, s_pad - s)), constant_values=-1
        )
        if keep is not None:
            keep = jnp.pad(keep, ((0, 0), (0, s_pad - s), (0, 0)))
    qt = s_pad // t
    rows = b * qt
    # [B, S, Nh, D] → [R, Hkv, qpk*T, D] with the query index t fastest
    # inside each (kv-head, GQA-slot) group — the layout the kernel's one
    # batched MXU contraction per page group wants
    q_r = q.reshape(b, qt, t, hkv, qpk, d).transpose(0, 1, 3, 4, 2, 5) \
        .reshape(rows, hkv, qpk * t, d)
    pos_r = positions.reshape(rows, t).astype(jnp.int32)
    qmax_r = jnp.max(pos_r, axis=1)
    qmin_r = jnp.min(jnp.where(pos_r >= 0, pos_r, jnp.int32(2**30)), axis=1)
    qmin_r = jnp.where(qmax_r >= 0, qmin_r, 0)
    # one position per score-tile row: q_r's row index is slot * T + query,
    # so the tile's T positions repeat once per GQA slot
    pos_q = jnp.tile(pos_r, (1, qpk))[:, :, None]

    scale_page_bytes = block_size * d * 2 if quantized else 0
    gp = _pages_per_group(
        block_size, hkv, d, k_pool.dtype.itemsize, m,
        scale_page_bytes=scale_page_bytes,
    )
    gp = max(1, min(gp, _RAGGED_KV_STAGING_BYTES // (
        4 * (hkv * block_size * d * k_pool.dtype.itemsize
             + scale_page_bytes))))
    max_groups = -(-m // gp)

    in_specs = [
        pl.BlockSpec(
            (1, hkv, qpk * t, d),
            lambda i, j, *_refs: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (1, qpk * t, 1), lambda i, j, *_refs: (i, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(memory_space=pltpu.HBM),   # k_pool
        pl.BlockSpec(memory_space=pltpu.HBM),   # v_pool
    ]
    selected = keep is not None
    if selected:
        gsz = gp * block_size
        keep_r = jnp.pad(keep.astype(jnp.float32), (
            (0, 0), (0, 0), (0, max_groups * gsz - keep.shape[2]))
        ).reshape(rows, t, max_groups * gsz)

        def keep_block(i, j, _bt, lens, qmax, *_refs):
            # a cell past the tile's last live group names that group's
            # block again, so nothing of a dead cell is fetched
            needed = jnp.minimum(qmax[i] + 1, lens[i // qt])
            live = jnp.minimum(pl.cdiv(needed, gsz), max_groups)
            return i, 0, jnp.clip(j, 0, jnp.maximum(live - 1, 0))

        in_specs.append(pl.BlockSpec(
            (1, t, gsz), keep_block, memory_space=pltpu.VMEM))
    if quantized:
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.HBM),   # k_scale
            pl.BlockSpec(memory_space=pltpu.HBM),   # v_scale
        ]
    out_specs = pl.BlockSpec(
        (1, hkv, qpk * t, d),
        lambda i, j, *_refs: (i, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    scratch = [
        pltpu.VMEM((2, gp, hkv, block_size, d), k_pool.dtype),
        pltpu.VMEM((2, gp, hkv, block_size, d), v_pool.dtype),
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((2, gp, block_size, d), jnp.bfloat16),    # ksbuf
            pltpu.VMEM((2, gp, block_size, d), jnp.bfloat16),    # vsbuf
        ]
    scratch += [pltpu.SemaphoreType.DMA((2, 2, gp))]             # sems
    if quantized:
        scratch += [pltpu.SemaphoreType.DMA((2, 2, gp))]         # ssems
    scratch += [
        pltpu.VMEM((hkv, qpk * t, 1), jnp.float32),              # m
        pltpu.VMEM((hkv, qpk * t, 1), jnp.float32),              # l
        pltpu.VMEM((hkv, qpk * t, d), jnp.float32),              # acc
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(rows, max_groups),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _ragged_kernel,
        rows=rows,
        q_tiles=qt,
        q_tile=t,
        block_size=block_size,
        pages_per_group=gp,
        max_pages=m,
        window=window,
        scale=d**-0.5,
        quantized=quantized,
        selected=selected,
    )
    # block tables and kv lens stay per-SEQUENCE ([B, M] / [B]): q-tile
    # rows index them via row // q_tiles inside the kernel. Repeating them
    # per tile (the old layout) multiplied the SMEM scalar-prefetch
    # footprint by the tile count — at 32k contexts (M = 2048 pages,
    # 2048-wide chunks → 32+ tiles) that is megabytes of SMEM tables for
    # kilobytes of real data
    operands = [
        block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
        qmax_r, qmin_r,
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.zeros((1,), jnp.int32),   # buffer_index
        jnp.ones((1,), jnp.int32),    # init_flag
        q_r, pos_q, k_pool, v_pool,
    ]
    if selected:
        operands.append(keep_r)
    if quantized:
        operands += [k_scale.astype(jnp.bfloat16),
                     v_scale.astype(jnp.bfloat16)]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, hkv, qpk * t, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=RAGGED_KERNEL_NAME,
    )(*operands)
    out = out.reshape(b, qt, hkv, qpk, t, d).transpose(0, 1, 4, 2, 3, 5) \
        .reshape(b, s_pad, nh, d)
    return out[:, :s]


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "window", "interpret"),
)
def paged_attention_pallas_multiquery(
    q: jax.Array,             # [B, S, Nh, D], small S (spec verify windows)
    k_pool: jax.Array,        # [N, Hkv, Bk, D] (head-major pages, 1 layer)
    v_pool: jax.Array,
    block_tables: jax.Array,  # [B, M] int32
    positions: jax.Array,     # [B, S] int32 (-1 = pad)
    kv_lens: jax.Array,       # [B] int32
    block_size: int = 16,
    window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,   # [N, Bk, D] bf16 lane-replicated
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Small-q paged attention (speculative verify windows) — since round 6
    a thin alias of :func:`ragged_paged_attention` with uniform spans. The
    old implementation flattened every query into its own decode-kernel
    row, re-staging pages once per query, which capped q_len at 8; the
    ragged kernel stages pages once per query TILE, so the cap (and the
    separate dispatch path) is gone."""
    return ragged_paged_attention(
        q, k_pool, v_pool, block_tables, positions, kv_lens, block_size,
        window=window, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "window", "interpret"),
)
def paged_attention_pallas(
    q: jax.Array,             # [B, 1, Nh, D]
    k_pool: jax.Array,        # [N, Hkv, Bk, D] (head-major pages, 1 layer)
    v_pool: jax.Array,
    block_tables: jax.Array,  # [B, M] int32
    positions: jax.Array,     # [B, 1] int32 (-1 = inactive)
    kv_lens: jax.Array,       # [B] int32
    block_size: int = 16,
    window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,   # [N, Bk, D] bf16 lane-replicated
    v_scale: Optional[jax.Array] = None,
    keep: Optional[jax.Array] = None,      # [B, 1, M * Bk] float32 selection
) -> jax.Array:
    """Read-only single-layer variant (micro-benchmarks, parity tests, and
    callers that manage KV writes themselves).

    ``k_scale``/``v_scale`` activate the int8-KV path: pools hold int8 rows
    with one scale per (page, token) — shared across KV heads, stored
    LANE-REPLICATED over D as bf16 (real = int * scale). That layout is
    what HBM DMA slicing and the Mosaic broadcast both accept; it costs
    +25% over pure int8 bytes, i.e. HBM sees ~62% of the bf16 bytes per
    token and page capacity is ~1.6x at equal pool bytes (VERDICT r3 #4)."""
    b, _, nh, d = q.shape
    hkv = k_pool.shape[1]
    zeros = jnp.zeros((b, hkv, d), jnp.bfloat16)
    results = _call_decode_kernel(
        q, zeros, zeros, k_pool[None], v_pool[None], jnp.int32(0),
        block_tables, positions[:, 0],
        jnp.full((b,), -1, jnp.int32),   # no writes
        kv_lens, block_size, window,
        fused_write=False, interpret=interpret,
        k_scale=None if k_scale is None else k_scale[None],
        v_scale=None if v_scale is None else v_scale[None],
        keep=keep,
    )
    return results[0]
