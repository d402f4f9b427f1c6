"""What the layers whose past is a **state row** share, whatever their
mathematics (``models/kda.py``: gated delta-rule linear attention beside
latent pages; ``models/ssd.py``: a Mamba-2 mixer beside K/V pages): where a
packed round's tokens sit as segments and chunks (:class:`Plan`), and the
short causal convolution with its tail a row.

A row is a batch row of the engine (a slot). A segment is a row's tokens in
a call, contiguous and in order on the flat axis; it is cut into chunks of
``chunk`` tokens (64 for the delta rule, 128 for the state-space form), and
a segment whose first token sits at position 0 starts from a zero state and
a zero tail, which is what binds a row to a new sequence.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


# ---------------------------------------------------------------------------
# where a round's tokens sit: segments, chunks
# ---------------------------------------------------------------------------


class Plan(NamedTuple):
    """A multi-token call's tokens on one flat axis of ``T`` entries, as
    segments (a state row's tokens, contiguous, in order) cut into chunks of
    ``chunk`` tokens. Built once a forward pass, for all its state layers."""

    row: jax.Array          # [T] state row of each token; R = padding
    col: jax.Array          # [T] its place in its segment
    count: jax.Array        # [R] tokens of each row's segment
    first: jax.Array        # [R] flat index of a segment's first token
    fresh: jax.Array        # [R] bool: the segment starts at position 0
    gather: jax.Array       # [C, chunk] flat token index; T = an empty place
    place: jax.Array        # [T] where a token sits in [C * chunk]
    chunk_row: jax.Array    # [C] state row of a chunk; R = an unused chunk
    chunk_first: jax.Array  # [C] bool: loads its row's state
    chunk_last: jax.Array   # [C] bool: stores it
    chunk_fresh: jax.Array  # [C] bool: a first chunk that starts from zero


def make_plan(row: jax.Array, col: jax.Array, positions: jax.Array,
              num_rows: int, *, chunk: int) -> Plan:
    """``row`` / ``col`` / ``positions`` ``[T]`` (a pad: position -1). The
    number of chunks is static: a segment of n tokens takes ceil(n /
    chunk), so ``num_rows + T // chunk`` hold any split of ``T`` tokens."""
    t = row.shape[0]
    r = num_rows
    c = r + t // chunk
    valid = positions >= 0
    row = jnp.where(valid, row, r).astype(jnp.int32)
    idx = jnp.arange(t, dtype=jnp.int32)
    count = jnp.zeros((r,), jnp.int32).at[row].add(1, mode="drop")
    head = valid & (col == 0)
    first = jnp.zeros((r,), jnp.int32).at[
        jnp.where(head, row, r)].set(idx, mode="drop")
    fresh = jnp.zeros((r,), bool).at[
        jnp.where(head & (positions == 0), row, r)].set(True, mode="drop")
    chunks = -(-count // chunk)
    base = jnp.cumsum(chunks) - chunks
    ends = base + chunks                                     # [R]
    tok_chunk = jnp.take(base, row, mode="fill", fill_value=c) + col // chunk
    place = jnp.where(valid, tok_chunk * chunk + col % chunk, c * chunk)
    gather = jnp.full((c * chunk,), t, jnp.int32).at[place].set(
        idx, mode="drop").reshape(c, chunk)
    cid = jnp.arange(c, dtype=jnp.int32)
    # the row a chunk belongs to: the first whose chunks end after it
    chunk_row = jnp.sum(cid[:, None] >= ends[None, :], axis=1,
                        dtype=jnp.int32)
    used = cid < ends[-1]
    chunk_row = jnp.where(used, chunk_row, r)
    row_base = jnp.take(base, chunk_row, mode="fill", fill_value=-1)
    row_end = jnp.take(ends, chunk_row, mode="fill", fill_value=-1)
    chunk_first = used & (cid == row_base)
    return Plan(
        row=row, col=col.astype(jnp.int32), count=count, first=first,
        fresh=fresh, gather=gather, place=place, chunk_row=chunk_row,
        chunk_first=chunk_first, chunk_last=used & (cid == row_end - 1),
        chunk_fresh=chunk_first & jnp.take(fresh, chunk_row, mode="fill",
                                           fill_value=False),
    )


def chunk_plan(packing, packed_positions, positions: jax.Array,
               num_rows: int, *, chunk: int) -> Optional[Plan]:
    """The plan of a forward pass's chunk, whichever form it has: a packed
    round (``packing``: ``models/llama.Packing``, its tokens' positions
    ``packed_positions [T]``), a ``[B, S]`` rectangle of ``positions`` (row
    by row on the flat axis), or one token a row, which takes the step form
    and no plan (None)."""
    if packing is not None:
        return make_plan(packing.row, packing.col, packed_positions,
                         num_rows, chunk=chunk)
    b, s = positions.shape
    if s == 1:
        return None
    return make_plan(
        jnp.repeat(jnp.arange(b, dtype=jnp.int32), s),
        jnp.tile(jnp.arange(s, dtype=jnp.int32), b),
        positions.reshape(-1), num_rows, chunk=chunk)


# ---------------------------------------------------------------------------
# the short convolution and its tail
# ---------------------------------------------------------------------------


def read_tails(conv_pool: jax.Array, layer) -> jax.Array:
    """A layer's stored tails ``[R, taps - 1, P]``, oldest row first."""
    return lax.dynamic_index_in_dim(conv_pool, layer, 0, keepdims=False)


def conv_step(x: jax.Array, w: jax.Array, conv_pool: jax.Array, layer,
              live: jax.Array, fresh: jax.Array, read=read_tails
              ) -> Tuple[jax.Array, jax.Array]:
    """One token a row: ``x [R, P]`` pre-activation (``P`` channels) →
    (convolved ``[R, P]`` float32, the pool). A row that is not live keeps
    its tail. ``read``: the caller's ``read_tails`` (a recipe's module holds
    its own name for it, which a test or a comparison's control replaces)."""
    tails = read(conv_pool, layer)
    old = jnp.where(fresh[:, None, None], 0, tails)
    window = jnp.concatenate([old, x[:, None].astype(old.dtype)], axis=1)
    y = jnp.einsum("rtp,tp->rp", window.astype(F32), w.astype(F32))
    new = jnp.where(live[:, None, None], window[:, 1:], tails)
    return y, lax.dynamic_update_index_in_dim(conv_pool, new, layer, 0)


def conv_segments(x: jax.Array, w: jax.Array, conv_pool: jax.Array, layer,
                  plan: Plan, read=read_tails
                  ) -> Tuple[jax.Array, jax.Array]:
    """A round's tokens ``x [T, P]``: each token's window is the rows
    before it in its segment and, at a segment's start, its row's stored
    tail (zero for a fresh row). A segment is contiguous on the flat axis,
    so the rows before a token are the rows before it there (shifts, no
    gather); only a segment's first ``taps - 1`` tokens reach into the
    tail, and what it adds to them is scattered in (``R x (taps - 1)``
    rows)."""
    t, taps = x.shape[0], w.shape[0]
    r = plan.count.shape[0]
    tails = read(conv_pool, layer)
    old = jnp.where(plan.fresh[:, None, None], 0, tails)       # [R, 3, P]
    xe, wf = x.astype(old.dtype), w.astype(F32)
    live = plan.row < r
    y = jnp.zeros(x.shape, F32)
    for back in range(taps):            # tap taps-1 multiplies the token
        rows = xe if back == 0 else jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), xe.dtype), xe[:-back]])
        inside = live & (plan.col >= back)
        y = y + jnp.where(inside[:, None], rows, 0).astype(F32) \
            * wf[taps - 1 - back]
    # token c of a segment (c < taps - 1) sees tail rows c .. taps - 2
    # under taps 0 .. taps - 2 - c
    oldf = old.astype(F32)
    reach = jnp.stack([
        sum(oldf[:, j] * wf[j - c] for j in range(c, taps - 1))
        for c in range(taps - 1)], axis=1)                     # [R, 3, P]
    keep = jnp.arange(taps - 1, dtype=jnp.int32)[None, :]       # [1, 3]
    to = jnp.where(keep < plan.count[:, None], plan.first[:, None] + keep, t)
    y = y.at[to.reshape(-1)].add(reach.reshape(-1, x.shape[1]), mode="drop")
    # what a segment leaves: its last taps-1 rows, through the old tail
    # where it is shorter than that
    at = plan.count[:, None] - (taps - 1) + keep                # [R, 3]
    mine = jnp.take(xe, jnp.clip(plan.first[:, None] + at, 0, t - 1), axis=0)
    before = jnp.take_along_axis(
        old, jnp.clip(at + (taps - 1), 0, taps - 2)[..., None], axis=1)
    new = jnp.where((at >= 0)[..., None], mine, before)
    new = jnp.where((plan.count > 0)[:, None, None], new, tails)
    return y, lax.dynamic_update_index_in_dim(conv_pool, new, layer, 0)
