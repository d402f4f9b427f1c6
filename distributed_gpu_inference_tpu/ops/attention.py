"""Attention over paged KV: XLA gather-based implementation + dense reference.

This is the correctness-first fallback path (SURVEY §7 "needs a pure-XLA
fallback (gather-based) for correctness testing"); the Pallas TPU kernel in
``ops/paged_attention_pallas.py`` is selected automatically on TPU backends
for the hot decode path.

Semantics shared by every implementation:

- KV lives in a paged pool ``[num_blocks, n_kv_heads, block_size, head_dim]``
  per layer (head-major pages — a (page, head) slice is one contiguous
  [Bk, D] tile, the layout the Pallas kernel DMAs); a sequence's context is
  the concatenation of its block table's pages, valid up to ``kv_lens[b]``
  tokens.
- Queries carry explicit ``positions`` (``-1`` = padding); causal masking is
  positional: query at position p attends to context positions ``j <= p``.
- GQA: ``n_heads`` queries share ``n_kv_heads`` KV heads.
"""

from __future__ import annotations

import functools
import os
import re
from typing import Optional, Set

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def pallas_backend() -> bool:
    """THE backend gate of kernel dispatch (attention here, the int8 matmul
    in ``ops/quantization.py``): Pallas TPU kernels are eligible when the
    default backend is a TPU. A backend that cannot be read raises — that
    is an error, not a reason to serve from another path. What this gate
    cannot see is partitioning: a caller whose operands are GSPMD-sharded
    over a mesh must ask for the XLA path itself (``forward_chunk``'s
    ``pallas=False``), because a bare ``pallas_call`` has no partitioning
    rule, or run the kernel a shard a chip inside ``jax.shard_map``
    (``forward_chunk``'s ``heads``: the attention kernels)."""
    if os.environ.get("DGI_DISABLE_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


def pallas_kernels(lowered) -> Set[str]:
    """Names of the Pallas kernels a lowered program (``jit(f).lower(...)``)
    calls — what dispatch resolved to, read from the program itself rather
    than inferred from the backend."""
    return set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))


# Measured model-level crossover on v5e (llama3-3b, batch 8, round 2): the
# XLA gather path wins below ~450 padded context tokens (one fused
# gather+einsum beats per-layer pallas_call launch overhead when the whole
# table is a few pages); the Pallas kernel wins from ~650 up and by 1.3x+ at
# 1400+. The threshold is on the STATIC padded table width, so dispatch is
# trace-time and costs nothing.
_PALLAS_MIN_PADDED_CTX = 512
# Measured row-count crossover of the BARE (non-fused) decode read kernel
# vs the XLA gather (r5 wedge table, v5e): the kernel wins 3.4x at batch 8
# mixed lengths, loses 2-4x by batch 32 — per-row page staging scales with
# rows while one gather amortizes. 16 is the conservative boundary between
# the measured points. Serving's decode path never sees this (it reads
# through the FUSED write+attention kernel, whose staging the write pass
# already pays); only bare paged_attention() reads — adopted pools, parity
# checks — cross over. resolve_impl applies it from the static row count.
# Not re-measured on the current chip (PERF.md section 7).
_MICRO_READ_XLA_MIN_BATCH = 16


def micro_read_xla_min_batch() -> int:
    """The bare-read row-count crossover — the measured default, with the
    ``MICRO_READ_XLA_MIN_BATCH`` env var kept as an OVERRIDE only (re-tuning
    on new chip generations without a code change)."""
    raw = os.environ.get("MICRO_READ_XLA_MIN_BATCH", "")
    try:
        return max(1, int(raw))
    except ValueError:
        return _MICRO_READ_XLA_MIN_BATCH


def resolve_impl(
    q_seq: int,
    head_dim: int,
    padded_ctx: int,
    backend_is_tpu: Optional[bool] = None,
    rows: Optional[int] = None,
    fused: bool = True,
) -> str:
    """The implementation ``impl="auto"`` will select, from static shape
    facts alone: q_seq (chunk length), head_dim, the padded context
    capacity ``block_tables.shape[1] * block_size``, and the batch row
    count. Exposed so callers (engines, tests) can ASSERT the Pallas
    kernel is in the measured path instead of discovering a silent
    fallback after the fact (VERDICT r1 weak #1).

    q_seq > 1 resolves to ``ragged`` — the ragged paged-attention kernel
    serving mixed prefill-chunk / spec-verify / decode rows in ONE
    invocation (it replaced the q_len <= 8 ``pallas_mq`` path in round 6;
    per-row bounds select each row's path inside the kernel, so there is
    no small-q cap anymore).

    ``fused``: the caller reads through the fused write+attention decode
    kernel (the serving path) — row count never flips it. ``fused=False``
    is the bare read (micro-benches, externally-written pools): there the
    measured row-count crossover applies and ``rows`` at or above
    :func:`micro_read_xla_min_batch` falls back to the one-gather XLA path.
    """
    if backend_is_tpu is None:
        backend_is_tpu = pallas_backend()
    if (
        backend_is_tpu
        and head_dim % 128 == 0
        and padded_ctx >= _PALLAS_MIN_PADDED_CTX
    ):
        if q_seq == 1:
            if (
                not fused
                and rows is not None
                and rows >= micro_read_xla_min_batch()
            ):
                return "xla"
            return "pallas"
        return "ragged"
    return "xla"


def paged_attention(
    q: jax.Array,             # [B, S, Nh, D]
    k_pool: jax.Array,        # [N, Hkv, Bk, D] (single layer)
    v_pool: jax.Array,        # [N, Hkv, Bk, D]
    block_tables: jax.Array,  # [B, M] int32
    positions: jax.Array,     # [B, S] int32, -1 = pad
    kv_lens: jax.Array,       # [B] int32
    block_size: int = 16,
    impl: str = "auto",
    window: Optional[int] = None,  # Mistral sliding window (None = full causal)
    k_scale: Optional[jax.Array] = None,   # [N, Bk, D] bf16 — int8 pools
    v_scale: Optional[jax.Array] = None,
    keep: Optional[jax.Array] = None,      # [B, S, M * Bk] float32 > 0: the
                                           # context positions a query
                                           # attends (ops/index_select.py)
) -> jax.Array:
    """Attention of a chunk of queries against paged context. → [B, S, Nh, D].

    ``impl``: "auto" (pallas on TPU for decode, ragged for multi-token
    spans, else xla), "xla", "pallas", "ragged" ("pallas_mq" accepted as a
    legacy alias of "ragged").
    ``window``: query at position p sees context positions (p-window, p].
    ``k_scale``/``v_scale``: int8 pools' per-(page, token) scales — both
    impls dequantize context-sized (Pallas in VMEM, XLA at the gather).
    ``keep``: a per-query selection of the context (learned sparse
    attention), applied on top of the causal / in-length mask by every
    implementation; None: a query attends all it sees.
    """
    if impl == "auto":
        # the Pallas decode kernel needs lane-aligned pages: XLA:TPU stores
        # HBM arrays padded to 128 lanes, so a head_dim that isn't a
        # multiple of 128 cannot be page-DMA'd without relayout. All the
        # production geometries (Llama-3 8B/70B, Qwen-7B, Mistral, Gemma)
        # have D ∈ {128, 256}; CI-scale minis fall back to XLA. Small padded
        # tables also stay on XLA (see resolve_impl / the measured
        # crossover note above). This is the BARE read path (the fused
        # write+attention kernel dispatches inside models/llama.py), so the
        # row-count crossover applies.
        impl = resolve_impl(
            q_seq=q.shape[1],
            head_dim=q.shape[3],
            padded_ctx=block_tables.shape[1] * block_size,
            rows=q.shape[0],
            fused=False,
        )
    if impl == "pallas":
        from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
            paged_attention_pallas,
        )

        return paged_attention_pallas(
            q, k_pool, v_pool, block_tables, positions, kv_lens, block_size,
            window=window, k_scale=k_scale, v_scale=v_scale, keep=keep,
        )
    if impl in ("ragged", "pallas_mq"):
        # "pallas_mq" is the pre-round-6 name of the small-q path, kept as
        # an alias: the ragged kernel serves those shapes (and every other
        # mixed-span batch) without the old q_len <= 8 cap
        from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
            ragged_paged_attention,
        )

        return ragged_paged_attention(
            q, k_pool, v_pool, block_tables, positions, kv_lens, block_size,
            window=window, k_scale=k_scale, v_scale=v_scale, keep=keep,
        )
    return paged_attention_xla(
        q, k_pool, v_pool, block_tables, positions, kv_lens, block_size,
        window=window, k_scale=k_scale, v_scale=v_scale, keep=keep,
    )


def dequantize_kv(codes: jax.Array, scale: jax.Array) -> jax.Array:
    """THE int8-KV dequant arithmetic: bf16 cast of BOTH operands, then
    multiply. Every reader of int8 pages — the XLA gather here, the
    seq-sharded shard_map locals (``parallel/ring_attention.py``), and the
    dense prefill roundtrip (``models/llama._layer_step``) — must produce
    bit-identical reals from the same (codes, scale), so the arithmetic
    lives in exactly one place. ``scale`` must already broadcast against
    ``codes``."""
    return codes.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)


def _gather_ctx(
    pool: jax.Array, block_tables: jax.Array, block_size: int,
    scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Materialize a batch's paged context: head-major pool [N, Hkv, Bk, D]
    gathered by [B, M] tables → [B, J, Hkv, D] token-major context.

    ``scale`` ([N, Bk, D] bf16, int8 pools): the per-(page, token) scales
    gather alongside and dequantize the CONTEXT-sized result — never the
    whole pool (a full-pool dequant copy would be GBs at serving sizes)."""
    b, m = block_tables.shape
    _, hkv, _, d = pool.shape
    ctx = jnp.take(pool, block_tables, axis=0).transpose(
        0, 1, 3, 2, 4
    ).reshape(b, m * block_size, hkv, d)
    if scale is None:
        return ctx
    s_ctx = jnp.take(scale, block_tables, axis=0).reshape(
        b, m * block_size, d
    )
    return dequantize_kv(ctx, s_ctx[:, :, None, :])


def paged_attention_xla(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    kv_lens: jax.Array,
    block_size: int = 16,
    window: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    keep: Optional[jax.Array] = None,
) -> jax.Array:
    b, s, nh, d = q.shape
    hkv = k_pool.shape[1]
    qpk = nh // hkv
    m = block_tables.shape[1]
    j = m * block_size

    k_ctx = _gather_ctx(k_pool, block_tables, block_size, k_scale)
    v_ctx = _gather_ctx(v_pool, block_tables, block_size, v_scale)

    qg = q.reshape(b, s, hkv, qpk, d).astype(jnp.float32)
    scores = jnp.einsum(
        "bsgqd,bjgd->bgqsj", qg, k_ctx.astype(jnp.float32)
    ) * (d**-0.5)

    key_pos = jnp.arange(j, dtype=jnp.int32)[None, :]           # [1, J]
    causal = positions[:, :, None] >= key_pos[:, None, :]       # [B, S, J]
    in_len = key_pos[:, None, :] < kv_lens[:, None, None]       # [B, 1→S, J]
    visible = causal & in_len
    if window is not None:  # Mistral SWA: key must be within (p-window, p]
        visible &= key_pos[:, None, :] > positions[:, :, None] - window
    if keep is not None:    # a learned selection of what the query sees
        visible &= keep > 0
    mask = visible[:, None, None, :, :]                         # [B,1,1,S,J]
    scores = jnp.where(mask, scores, _NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (padded queries) → softmax of -inf row ≈ uniform junk;
    # zero them so padded outputs are exactly 0.
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    probs = jnp.where(any_valid, probs, 0.0)

    out = jnp.einsum("bgqsj,bjgd->bsgqd", probs, v_ctx.astype(jnp.float32))
    return out.reshape(b, s, nh, d).astype(q.dtype)


def dense_causal_attention(
    q: jax.Array,   # [B, S, Nh, D]
    k: jax.Array,   # [B, S, Hkv, D]
    v: jax.Array,   # [B, S, Hkv, D]
    lengths: Optional[jax.Array] = None,  # [B] valid lengths
    window: Optional[int] = None,
) -> jax.Array:
    """Plain causal GQA attention over contiguous KV — the test oracle."""
    b, s, nh, d = q.shape
    hkv = k.shape[2]
    qpk = nh // hkv
    qg = q.reshape(b, s, hkv, qpk, d).astype(jnp.float32)
    scores = jnp.einsum("bsgqd,bjgd->bgqsj", qg, k.astype(jnp.float32)) * (
        d**-0.5
    )
    idx = jnp.arange(s, dtype=jnp.int32)
    mask = idx[None, :, None] >= idx[None, None, :]             # [1, S, J]
    if window is not None:
        mask = mask & (idx[None, None, :] > idx[None, :, None] - window)
    if lengths is not None:
        mask = mask & (idx[None, None, :] < lengths[:, None, None])
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgqsj,bjgd->bsgqd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, nh, d).astype(q.dtype)
