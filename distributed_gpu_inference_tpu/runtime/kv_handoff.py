"""Real prefill→decode KV handoff: export a sequence's device KV pages,
move the bytes, and adopt them into another engine mid-generation.

The reference *simulates* this step — its KV migration body is a 50 ms sleep
(``server/app/services/pd_scheduler.py:462-472``) and its per-layer transfer
contract exists only as an unwired proto (``proto/inference.proto:110-135``).
Here the handoff is real:

- **Export**: gather the sequence's block chain out of the donor engine's HBM
  pools in ONE device gather (``kv["k"][:, block_ids]``), pull to host, and
  capture the exact generation state (committed kv_len, the pending sampled
  token whose KV is not yet written, generated tokens, sampling params).
- **Wire**: :func:`serialize_handoff` frames the pages with the same
  length-prefixed header + optional zstd used for all DCN/WAN tensor traffic
  (``utils/serialization.py``). Intra-slice PD pools skip this path entirely —
  prefill/decode partitions of one mesh exchange KV via device-to-device
  copies (`jax.device_put`) with no host serialization.
- **Adopt**: allocate a block chain in the recipient (prefix-cache aware — a
  shared system prompt already resident costs zero upload), stage page
  uploads through the manager's :class:`PendingDeviceOps`, and bind a slot
  with the exact pending-token state so the next ``decode_step`` continues
  the generation bit-for-bit.

Correctness invariant (tested): greedy decode continued on the recipient
produces the same tokens the donor would have produced.
"""

from __future__ import annotations

import functools
import io
import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from distributed_gpu_inference_tpu.testing import faults as _faults
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)
from distributed_gpu_inference_tpu.utils.serialization import (
    TensorSerializer,
    _pack_header,
    _unpack_header,
)

if TYPE_CHECKING:  # pragma: no cover
    from distributed_gpu_inference_tpu.runtime.engine import TPUEngine


@dataclass
class KVHandoff:
    """Everything needed to continue a generation on another engine."""

    request: InferenceRequest
    model_name: str
    block_size: int
    # token state
    token_ids: List[int]            # prompt + generated incl. pending token
    kv_len: int                     # committed positions (KV valid for [0, kv_len))
    pending_token: int              # sampled, KV not yet written
    prompt_len: int
    generated: List[int]
    # timing carried across so TTFT/E2E stay end-to-end truthful
    start_time: float
    first_token_time: Optional[float]
    # per-slot PRNG key: an UNSEEDED sampled generation keeps its exact
    # random stream across migration (seeded ones re-derive from the seed)
    slot_key: Optional[List[int]] = None
    # sliding-window models: leading logical blocks the donor already
    # released (their exported pages are pad-block garbage — the recipient
    # must skip uploading them and replicate the release state, or a
    # no-decode adopt could cache a garbage-prefixed chain; ADVICE r1 #1)
    window_front: int = 0
    # donor finish state: a sequence whose FIRST sampled token hit a stop id
    # finishes with generated=[] and a stale last_token — the recipient must
    # not decode it (it would feed garbage for max_new_tokens)
    finish_reason: Optional[str] = None
    # int8-KV donors: per-(page, token) scale pages [n, L, 2, Bk, D] bf16
    # (k and v scales stacked on axis 2) — pages are raw int8 then, and the
    # recipient must be an int8 engine (real = int * scale end to end, so
    # continuation stays bit-exact with zero requantization)
    scale_pages: Optional[np.ndarray] = field(repr=False, default=None)
    # pages: [n_blocks, L, 2, n_kv_heads, block_size, head_dim] (head-major)
    pages: np.ndarray = field(repr=False, default=None)  # type: ignore[assignment]

    @property
    def num_blocks(self) -> int:
        return 0 if self.pages is None else int(self.pages.shape[0])

    @property
    def nbytes(self) -> int:
        return 0 if self.pages is None else int(self.pages.nbytes)


def require_kv_pages(engine: "TPUEngine") -> None:
    """The wire formats here carry K and V pages. An engine whose cache is
    latent pages (``ModelConfig.latent_kv``; ``engine.stats["kv_layout"]``
    says ``latent``) is refused by every entry point — and, before any
    request, where a worker is configured for the handoff
    (``worker/main.py load_engines``)."""
    if getattr(getattr(engine, "model_cfg", None), "latent_kv", False):
        raise ValueError(
            f"{engine.model_cfg.name}: the KV handoff and migration wire "
            "carries K/V pages; this engine caches latent pages")
    if getattr(getattr(engine, "model_cfg", None), "index_topk", 0):
        raise ValueError(
            f"{engine.model_cfg.name}: the KV handoff and migration wire "
            "carries K/V pages, not the index keys this engine caches "
            "beside them")
    if getattr(getattr(engine, "model_cfg", None), "mixed_attention", False):
        raise ValueError(
            f"{engine.model_cfg.name}: the KV handoff and migration wire "
            "carries one kind's K/V pages under one block table; this "
            "engine keeps pages per layer kind")
    if getattr(getattr(engine, "model_cfg", None), "ssm_num_heads", 0):
        raise ValueError(
            f"{engine.model_cfg.name}: the KV handoff and migration wire "
            "carries K/V pages, not the state row this engine keeps beside "
            "them")


def export_slot_kv(engine: "TPUEngine", slot: int) -> KVHandoff:
    """Snapshot ``slot``'s sequence out of ``engine`` (slot stays live; callers
    that migrate should ``finish_slot(slot, cache=...)`` afterwards)."""
    require_kv_pages(engine)
    import jax.numpy as jnp

    s = engine.slots[slot]
    if s is None:
        raise ValueError(f"slot {slot} empty")
    blocks = engine.manager.seq_blocks[s.seq_id]
    ids = jnp.asarray(np.asarray(blocks, np.int32))
    # one gather per pool, host pull in native dtype (the wire codec frames
    # bfloat16 directly — no f32 inflation, no f16 precision loss)
    k = np.asarray(engine.kv["k"][:, ids])
    v = np.asarray(engine.kv["v"][:, ids])
    # → [n, L, 2, Hkv, Bk, D] so adoption can upload per block
    pages = np.stack([k, v], axis=0).transpose(2, 1, 0, 3, 4, 5)
    scale_pages = None
    if "k_scale" in engine.kv:
        ks = np.asarray(engine.kv["k_scale"][:, ids])   # [L, n, Bk, D]
        vs = np.asarray(engine.kv["v_scale"][:, ids])
        scale_pages = np.stack([ks, vs], axis=0).transpose(2, 1, 0, 3, 4)
    tokens = list(engine.manager.seq_tokens[s.seq_id])
    return KVHandoff(
        request=s.request,
        model_name=engine.model_cfg.name,
        block_size=engine.cfg.block_size,
        token_ids=tokens,
        kv_len=int(engine._kv_lens[slot]),
        pending_token=int(engine._last_tokens[slot]),
        prompt_len=s.prompt_len,
        generated=list(s.generated),
        start_time=s.start_time,
        first_token_time=s.first_token_time,
        slot_key=[int(x) for x in engine._slot_keys[slot]],
        window_front=engine.manager.seq_window_front.get(s.seq_id, 0),
        finish_reason=s.finish_reason,
        pages=pages,
        scale_pages=scale_pages,
    )


def _validate_capacity(engine: "TPUEngine", n_tokens: int,
                       kv_len: int, remaining: int) -> None:
    """Reject a migration the recipient cannot hold or finish — BEFORE any
    allocator/device/wire work, so a rejected handoff can't leak state.
    Shared by all three migration paths (one-shot, streamed, device)."""
    n_blocks = max(1, -(-n_tokens // engine.cfg.block_size))
    if n_blocks > engine.cfg.max_blocks_per_seq:
        raise ValueError(
            f"handoff needs {n_blocks} blocks > engine max_blocks_per_seq "
            f"{engine.cfg.max_blocks_per_seq}"
        )
    if n_tokens > engine.cfg.max_seq_len:
        raise ValueError("handoff sequence exceeds engine max_seq_len")
    if kv_len + 1 + remaining > engine.cfg.max_seq_len:
        raise ValueError(
            f"handoff needs headroom for {remaining} more tokens at kv_len "
            f"{kv_len}, exceeding engine max_seq_len {engine.cfg.max_seq_len}"
        )


def _bind_migrated(engine: "TPUEngine", slot: int, *, request, seq_id: str,
                   prompt_len: int, generated, cached_tokens: int,
                   start_time: float, first_token_time, kv_len: int,
                   pending_token: int, slot_key, finish_reason) -> None:
    """Install a migrated sequence into ``slot``: the one bind sequence all
    three migration paths share (so pending-token, PRNG-stream, and
    finish-state semantics cannot drift between them). Caller owns
    allocator/session cleanup on failure."""
    from distributed_gpu_inference_tpu.runtime.engine import _Slot

    s = _Slot(
        request=request,
        seq_id=seq_id,
        prompt_len=prompt_len,
        generated=list(generated),
        cached_tokens=cached_tokens,
        start_time=start_time,
        first_token_time=first_token_time,
        # a donor that already finished (e.g. first token hit a stop id)
        # must stay finished: the recipient's decode loop skips the slot
        # and finish_slot reports the donor's reason
        finish_reason=finish_reason,
    )
    engine._bind_slot(slot, s, kv_len=kv_len)
    engine._last_tokens[slot] = int(pending_token)
    if slot_key is not None:
        engine._slot_keys[slot] = np.asarray(slot_key, np.uint32)
    engine._apply_pending()


def adopt_kv(engine: "TPUEngine", handoff: KVHandoff,
             slot: Optional[int] = None) -> int:
    """Materialize ``handoff`` into ``engine``: allocate blocks, stage page
    uploads, bind a slot. Returns the slot index; the next ``decode_step``
    resumes the generation."""
    require_kv_pages(engine)
    if engine.model_cfg.name != handoff.model_name:
        raise ValueError(
            f"model mismatch: engine={engine.model_cfg.name} "
            f"handoff={handoff.model_name}"
        )
    if engine.cfg.block_size != handoff.block_size:
        raise ValueError("block_size mismatch between engines")
    if (handoff.scale_pages is not None) != ("k_scale" in engine.kv):
        raise ValueError(
            "kv_cache_dtype mismatch: an int8-KV handoff (raw int8 pages + "
            "scales) can only adopt into an int8-KV engine, and vice versa "
            "— re-serving through a different KV dtype would need a "
            "requantization pass this path does not do"
        )
    if slot is None:
        free = engine.free_slots()
        if not free:
            raise RuntimeError("no free slots")
        slot = free[0]
    if engine.slots[slot] is not None:
        raise RuntimeError(f"slot {slot} busy")

    req = handoff.request
    # validate capacity BEFORE touching allocator or pending-op state so a
    # rejected handoff can't leak blocks or leave stale uploads queued;
    # headroom mirrors submit(): the recipient must be able to FINISH the
    # generation, or the handoff would silently truncate with "length"
    _validate_capacity(
        engine, len(handoff.token_ids), handoff.kv_len,
        0 if handoff.finish_reason is not None else
        req.sampling.max_new_tokens - len(handoff.generated),
    )
    seq_id = f"{req.request_id}-pd"
    blocks, cached_tokens = engine.manager.allocate_sequence(
        seq_id, handoff.token_ids
    )
    staged: List[int] = []
    try:
        cached_blocks = cached_tokens // engine.cfg.block_size
        for i in range(cached_blocks, len(blocks)):
            if i < handoff.window_front:
                # donor released this block (sliding window): its exported
                # page is pad garbage — never upload it
                continue
            # pages[i] is [L, 2, Hkv, Bk, D] — the engine upload layout
            engine.manager.pending.uploads.append((blocks[i], handoff.pages[i]))
            if handoff.scale_pages is not None:
                engine.manager.pending.scale_uploads.append(
                    (blocks[i], handoff.scale_pages[i])
                )
            staged.append(blocks[i])
        # replicate the donor's release state BEFORE binding so the slot's
        # block table starts with the released entries pinned to pad block 0
        # and free_sequence keeps the truncated chain out of the radix
        if handoff.window_front > 0:
            engine.manager.seed_window_front(seq_id, handoff.window_front)

        _bind_migrated(
            engine, slot, request=req, seq_id=seq_id,
            prompt_len=handoff.prompt_len, generated=handoff.generated,
            cached_tokens=cached_tokens, start_time=handoff.start_time,
            first_token_time=handoff.first_token_time,
            kv_len=handoff.kv_len, pending_token=handoff.pending_token,
            slot_key=handoff.slot_key, finish_reason=handoff.finish_reason,
        )
    except Exception:
        engine.slots[slot] = None
        engine._kv_lens[slot] = 0
        # drop OUR staged uploads: after free_sequence those block ids return
        # to the free list and a later _apply_pending would write donor pages
        # over blocks that may belong to another live sequence
        if staged:
            drop = set(staged)
            engine.manager.pending.uploads = [
                (bid, page) for bid, page in engine.manager.pending.uploads
                if bid not in drop
            ]
            engine.manager.pending.scale_uploads = [
                (bid, page)
                for bid, page in engine.manager.pending.scale_uploads
                if bid not in drop
            ]
        engine.manager.free_sequence(seq_id, cache=False)
        raise
    return slot


# ---------------------------------------------------------------------------
# Wire format (DCN / cross-host handoff)
# ---------------------------------------------------------------------------


def _frame_blobs(*blobs: bytes) -> bytes:
    """THE 8-byte-little-endian length-prefixed multi-blob framing, shared
    by every handoff encoder (one-shot + streamed piece) so encoders and
    decoders cannot drift on offset arithmetic."""
    out = io.BytesIO()
    for b in blobs:
        out.write(len(b).to_bytes(8, "little"))
        out.write(b)
    return out.getvalue()


def _read_blobs(data: bytes, count: int) -> List[bytes]:
    view = memoryview(data)
    off, out = 0, []
    for _ in range(count):
        if off + 8 > len(view):
            raise ValueError(
                f"malformed handoff frame: truncated length prefix at "
                f"offset {off} (frame is {len(view)} bytes)"
            )
        n = int.from_bytes(view[off : off + 8], "little")
        if off + 8 + n > len(view):
            raise ValueError(
                f"malformed handoff frame: blob of {n} bytes at offset "
                f"{off} overruns the {len(view)}-byte frame"
            )
        out.append(bytes(view[off + 8 : off + 8 + n]))
        off += 8 + n
    return out


def serialize_handoff(h: KVHandoff, compress: bool = True) -> bytes:
    """Frame a handoff for a DCN hop: pickled metadata + framed pages.

    Pages use the shared tensor wire format (header + optional zstd), and the
    metadata rides the same msgpack header codec — the wire stays
    pickle-free so a peer can never smuggle executable payloads
    (reference keeps lz4/zstd for WAN only — SURVEY §2.3; same stance here).
    """
    meta = {
        "request": {
            "request_id": h.request.request_id,
            "model": h.request.model,
            "prompt_token_ids": h.request.prompt_token_ids,
            "sampling": h.request.sampling.to_dict(),
            "priority": h.request.priority,
            "session_id": h.request.session_id,
            # deadline crosses the PD boundary as an ABSOLUTE time (the
            # checkpoint-wire convention, runtime/engine.py): relative
            # deadline_s would silently re-anchor to the receiver's
            # arrival_time and hand a migrated job fresh slack. Omitted
            # (not null) when unset, so deadline-less wires are
            # byte-identical to the pre-deadline format.
            **({"deadline_at": h.request.deadline_at}
               if h.request.deadline_s is not None else {}),
        },
        "model_name": h.model_name,
        "block_size": h.block_size,
        "token_ids": h.token_ids,
        "kv_len": h.kv_len,
        "pending_token": h.pending_token,
        "prompt_len": h.prompt_len,
        "generated": h.generated,
        "start_time": h.start_time,
        "first_token_time": h.first_token_time,
        "slot_key": h.slot_key,
        "window_front": h.window_front,
        "finish_reason": h.finish_reason,
        "has_scales": h.scale_pages is not None,
    }
    ser = TensorSerializer(compress=compress)
    blobs = [_pack_header(meta), ser.serialize(h.pages)]
    if h.scale_pages is not None:
        blobs.append(ser.serialize(h.scale_pages))
    return _frame_blobs(*blobs)


# ---------------------------------------------------------------------------
# Device-path handoff: same-chip / same-slice engine pairs never touch host
# ---------------------------------------------------------------------------


def migrate_kv_device(src: "TPUEngine", dst: "TPUEngine", slot: int,
                      dst_slot: Optional[int] = None) -> int:
    """Move a live sequence between two engines whose KV pools share devices
    — pages move pool→pool in ONE jitted gather-scatter on the accelerator;
    only slot metadata (a few hundred bytes) rides the host.

    This is the intra-slice PD migration path: a DistServe-style deployment
    on one TPU slice runs prefill and decode pools in ONE process (BASELINE
    config 5 — prefill on 16 chips, decode on 48 of a v5e-64), so the
    handoff is an HBM/ICI copy, not a serialize→DCN→deserialize hop. The
    host path pays a device→host copy of every page (rate not measured on
    the current chip); this path is one device dispatch. The reference has no equivalent — its migration body is a
    50 ms sleep (``/root/reference/server/app/services/pd_scheduler.py:462``).

    The donor slot stays live (caller decides ``finish_slot`` semantics,
    matching :func:`export_slot_kv`).
    """
    require_kv_pages(src)
    import jax.numpy as jnp

    s = src.slots[slot]
    if s is None:
        raise ValueError(f"slot {slot} empty")
    if src.model_cfg.name != dst.model_cfg.name:
        raise ValueError("model mismatch between engines")
    if src.cfg.block_size != dst.cfg.block_size:
        raise ValueError("block_size mismatch between engines")
    if src.kv_dtype != dst.kv_dtype:
        raise ValueError("kv_cache_dtype mismatch between engines")
    # int8-KV pools migrate on every path: the jitted copy here moves scale
    # pools by key; the wire paths (one-shot + streamed) frame scale pages
    # alongside data pages. kv_dtype equality above guarantees both sides
    # agree on whether scales exist.
    src_devs = {d for leaf in (src.kv["k"],) for d in leaf.devices()}
    dst_devs = {d for leaf in (dst.kv["k"],) for d in leaf.devices()}
    if src_devs != dst_devs:
        raise ValueError(
            "migrate_kv_device needs engines sharing devices; use the "
            "host/wire path (export_slot_kv / StreamedExport) across hosts"
        )
    window_front = src.manager.seq_window_front.get(s.seq_id, 0)
    token_ids = list(src.manager.seq_tokens[s.seq_id])
    src_blocks = list(src.manager.seq_blocks[s.seq_id])

    if dst_slot is None:
        free = dst.free_slots()
        if not free:
            raise RuntimeError("no free slots")
        dst_slot = free[0]
    if dst.slots[dst_slot] is not None:
        raise RuntimeError(f"slot {dst_slot} busy")
    req = s.request
    kv_len = int(src._kv_lens[slot])
    # validate BEFORE the allocator and the device copy run; a finished
    # donor (first-token stop) needs no decode headroom
    _validate_capacity(
        dst, len(token_ids), kv_len,
        0 if s.finish_reason is not None else
        req.sampling.max_new_tokens - len(s.generated),
    )
    seq_id = f"{req.request_id}-pd"
    dst_blocks, cached_tokens = dst.manager.allocate_sequence(seq_id, token_ids)
    try:
        cached_blocks = cached_tokens // dst.cfg.block_size
        src_ids, dst_ids = [], []
        for i in range(len(dst_blocks)):
            if i < cached_blocks or i < window_front:
                continue    # resident via prefix cache / window-released
            if i < len(src_blocks):
                src_ids.append(src_blocks[i])
                dst_ids.append(dst_blocks[i])
        if src_ids:
            # recipient's own pending ops (CoW from allocate) must land
            # before we overwrite pages
            dst._apply_pending()
            dst.kv = _device_copy_pages(
                src.kv, dst.kv,
                jnp.asarray(np.asarray(src_ids, np.int32)),
                jnp.asarray(np.asarray(dst_ids, np.int32)),
            )
        if window_front > 0:
            dst.manager.seed_window_front(seq_id, window_front)
        _bind_migrated(
            dst, dst_slot, request=req, seq_id=seq_id,
            prompt_len=s.prompt_len, generated=s.generated,
            cached_tokens=cached_tokens, start_time=s.start_time,
            first_token_time=s.first_token_time, kv_len=kv_len,
            pending_token=int(src._last_tokens[slot]),
            slot_key=src._slot_keys[slot],
            finish_reason=s.finish_reason,
        )
    except Exception:
        dst.slots[dst_slot] = None
        dst._kv_lens[dst_slot] = 0
        dst.manager.free_sequence(seq_id, cache=False)
        raise
    return dst_slot


@functools.lru_cache(maxsize=8)
def _device_copy_fn(keys: Tuple[str, ...]):
    import jax

    def copy(src_kv, dst_kv, src_ids, dst_ids):
        return {
            k: dst_kv[k].at[:, dst_ids].set(src_kv[k][:, src_ids])
            for k in keys
        }

    # donate the destination pools: the copy mutates them in place
    return jax.jit(copy, donate_argnums=(1,))


def _device_copy_pages(src_kv, dst_kv, src_ids, dst_ids):
    # every pool entry with a block axis migrates — incl. int8 scale pools
    keys = tuple(sorted(src_kv.keys()))
    return _device_copy_fn(keys)(
        {k: src_kv[k] for k in keys}, {k: dst_kv[k] for k in keys},
        src_ids, dst_ids,
    )


# ---------------------------------------------------------------------------
# Streamed handoff (VERDICT r3 #3): chunk the export per page range and
# overlap the push with remaining prefill compute
# ---------------------------------------------------------------------------
#
# The round-3 handoff was whole-sequence, post-prefill, blocking: the donor
# finished the prompt, gathered EVERY page, pulled ~67 MB (512-token 8B bf16)
# to the host, and POSTed one blob — migration_ms landed entirely on the
# decode stage's start. The streamed protocol splits the handoff into three
# message kinds on the same ``/kv/transfer`` socket (magic-discriminated, so
# legacy one-shot blobs keep working):
#
# - ``begin``  — sent at prefill START: prompt tokens + sampling + framing.
#   The receiver allocates the block chain (prefix-cache aware) while the
#   donor is still computing.
# - ``piece``  — a block range's pages, sent as soon as those positions'
#   KV is final. During CHUNKED prefill, chunk i's pages cross the wire
#   while chunk i+1 computes: the page gather is dispatched right after
#   chunk i+1's prefill dispatch, so in-order device execution completes it
#   at ~chunk i's end while the host is free to pull/serialize/POST
#   (the same async-dispatch pattern as sub-wave admission staggering).
# - ``commit`` — after the first token samples: kv_len, pending token,
#   PRNG key, timing. The receiver binds the slot; the next decode_step
#   continues the generation bit-for-bit (same invariant + test as the
#   one-shot path).
#
# Sliding-window models fall back to the one-shot path (window release
# during admission would stream pages the commit then discards).
#
# Ref parity anchor: the per-layer KV messages the reference defines but
# never wires (/root/reference/proto/inference.proto:121-127) — here the
# streamed contract is page-range-framed and actually drives serving.

_STREAM_MAGIC = b"TPUS"
_KIND_BEGIN, _KIND_PIECE, _KIND_COMMIT, _KIND_ABORT = 0, 1, 2, 3


def is_stream_message(data: bytes) -> bool:
    return data[:4] == _STREAM_MAGIC


def message_kind(data: bytes) -> str:
    """Human name of a handoff wire message's kind — fault-rule ``match``
    context for the sender's push seam (rules can target, say, only
    ``commit`` frames) and log labelling. One-shot blobs are ``blob``."""
    if len(data) < 6 or not is_stream_message(data):
        return "blob"
    return {_KIND_BEGIN: "begin", _KIND_PIECE: "piece",
            _KIND_COMMIT: "commit", _KIND_ABORT: "abort"}.get(
                data[5], "unknown")


def _pack_stream(kind: int, meta: Dict[str, Any],
                 payload: bytes = b"") -> bytes:
    mb = _pack_header(meta)
    return b"".join([
        _STREAM_MAGIC, bytes([1, kind]), len(mb).to_bytes(4, "little"), mb,
        payload,
    ])


def _unpack_stream(data: bytes) -> Tuple[int, Dict[str, Any], bytes]:
    if data[:4] != _STREAM_MAGIC:
        raise ValueError("not a streamed handoff message")
    if len(data) < 10:
        raise ValueError(
            f"malformed handoff frame: {len(data)}-byte message is shorter "
            "than the 10-byte stream header"
        )
    if data[4] != 1:
        raise ValueError(f"unsupported stream version {data[4]}")
    kind = data[5]
    n = int.from_bytes(data[6:10], "little")
    if n == 0 or 10 + n > len(data):
        raise ValueError(
            f"malformed handoff frame: {n}-byte stream header overruns "
            f"the {len(data)}-byte message"
        )
    meta = _unpack_header(bytes(data[10:10 + n]))
    return kind, meta, bytes(data[10 + n:])


class StreamedExport:
    """Donor-side driver: runs a request's (chunked) prefill on ``engine``
    and generates the streamed handoff messages.

    Usage::

        exp = StreamedExport(engine, request, key)
        for msg in exp.messages():
            send(msg)                  # POST to the receiver, in order
        exp.first_token, exp.ttft_ms   # set once messages() is exhausted

    ``messages()`` interleaves page export with prefill compute: each loop
    iteration dispatches the next prefill chunk, dispatches the page gather
    for the blocks the PREVIOUS chunk completed, and only then yields the
    previous piece (whose device work already finished) — the host
    serialize/POST happens while the device runs the current chunk. The
    donor slot is freed when the generator completes (or aborts).
    """

    def __init__(self, engine: "TPUEngine", request: InferenceRequest,
                 key: str, piece_blocks: int = 4,
                 compress: bool = False) -> None:
        require_kv_pages(engine)
        if engine.model_cfg.sliding_window is not None:
            raise ValueError(
                "streamed handoff does not support sliding-window models "
                "(use the one-shot path)"
            )
        # kv_seq_sharded donors stream fine since round 4: chunked prefill
        # composes with sharded pools, and the page gather collects shards
        # through GSPMD before the host pull. int8-KV donors stream their
        # scale pages inside each piece (receiver must be int8 too).
        self._quant = "k_scale" in engine.kv
        self.engine = engine
        self.request = request
        self.key = key
        self.piece_blocks = max(1, piece_blocks)
        self.compress = compress
        # results (set when messages() completes)
        self.first_token: Optional[int] = None
        self.ttft_ms: Optional[float] = None
        self.prompt_tokens: int = 0
        self.bytes_sent: int = 0
        self.pieces_sent: int = 0
        # bytes that crossed the wire BEFORE prefill finished (overlap proof)
        self.bytes_before_first_token: int = 0

    # -- message builders ----------------------------------------------------

    def _begin_msg(self) -> bytes:
        req = self.request
        return _pack_stream(_KIND_BEGIN, {
            "key": self.key,
            "model_name": self.engine.model_cfg.name,
            "block_size": self.engine.cfg.block_size,
            "int8_kv": self._quant,
            "request": {
                "request_id": req.request_id,
                "model": req.model,
                "prompt_token_ids": req.prompt_token_ids,
                "sampling": req.sampling.to_dict(),
                "priority": req.priority,
                "session_id": req.session_id,
                # same absolute-deadline convention as serialize_handoff
                **({"deadline_at": req.deadline_at}
                   if req.deadline_s is not None else {}),
            },
        })

    def _piece_msg(self, block_lo: int, k, v, ks=None, vs=None) -> bytes:
        # k/v: device gathers [L, n, Hkv, Bk, D]; pull + relayout host-side
        # to the adopt upload layout [n, L, 2, Hkv, Bk, D]
        pages = np.stack([np.asarray(k), np.asarray(v)], axis=0)
        pages = pages.transpose(2, 1, 0, 3, 4, 5)
        ser = TensorSerializer(compress=self.compress)
        pb = ser.serialize(pages)
        if ks is None:
            return _pack_stream(
                _KIND_PIECE, {"key": self.key, "block_lo": block_lo}, pb
            )
        scales = np.stack([np.asarray(ks), np.asarray(vs)], axis=0)
        scales = scales.transpose(2, 1, 0, 3, 4)     # [n, L, 2, Bk, D]
        payload = _frame_blobs(pb, ser.serialize(scales))
        return _pack_stream(
            _KIND_PIECE,
            {"key": self.key, "block_lo": block_lo, "has_scales": True},
            payload,
        )

    def _gather(self, blocks: List[int]):
        import jax.numpy as jnp

        ids = jnp.asarray(np.asarray(blocks, np.int32))
        out = (self.engine.kv["k"][:, ids], self.engine.kv["v"][:, ids])
        if self._quant:
            out += (self.engine.kv["k_scale"][:, ids],
                    self.engine.kv["v_scale"][:, ids])
        return out

    # -- the driver ----------------------------------------------------------

    def messages(self):
        eng = self.engine
        bs = eng.cfg.block_size
        adm = eng.submit_chunked_start(self.request)
        slot = adm.slot
        try:
            yield self._begin_msg()
            chain = eng.manager.seq_blocks[adm.seq_id]
            sent = 0                    # blocks exported so far
            pending: Optional[Tuple] = None  # (block_lo, *gathers)
            # donor-side prefix-cache hits are final before any chunk runs
            while not adm.done:
                eng.submit_chunked_step(adm)    # dispatch chunk (async
                # unless last — the final chunk samples + syncs in-graph)
                full = adm.off // bs
                if pending is not None:
                    msg = self._piece_msg(pending[0], *pending[1:])
                    if self.first_token is None:
                        self.bytes_before_first_token += len(msg)
                    self.bytes_sent += len(msg)
                    self.pieces_sent += 1
                    yield msg
                    pending = None
                if full > sent:
                    hi = min(full, sent + self.piece_blocks)
                    pending = (sent, *self._gather(chain[sent:hi]))
                    sent = hi
            # prefill finished: record results, then flush the tail —
            # everything left is pure export latency (the part streaming
            # exists to shrink)
            s = eng.slots[slot]
            self.first_token = int(eng._last_tokens[slot])
            self.prompt_tokens = s.prompt_len
            self.ttft_ms = (
                (s.first_token_time - s.start_time) * 1000.0
                if s.first_token_time else None
            )
            if pending is not None:
                msg = self._piece_msg(pending[0], *pending[1:])
                self.bytes_sent += len(msg)
                self.pieces_sent += 1
                yield msg
                pending = None
            # the pending token's append may have grown the chain by one
            # block (its page is uncommitted garbage the receiver never
            # reads: kv_len marks validity — same as the one-shot path)
            chain = eng.manager.seq_blocks[adm.seq_id]
            while sent < len(chain):
                hi = min(len(chain), sent + self.piece_blocks)
                msg = self._piece_msg(sent, *self._gather(chain[sent:hi]))
                self.bytes_sent += len(msg)
                self.pieces_sent += 1
                yield msg
                sent = hi
            commit = _pack_stream(_KIND_COMMIT, {
                "key": self.key,
                "token_ids": list(eng.manager.seq_tokens[adm.seq_id]),
                "kv_len": int(eng._kv_lens[slot]),
                "pending_token": int(eng._last_tokens[slot]),
                "prompt_len": s.prompt_len,
                "generated": list(s.generated),
                "start_time": s.start_time,
                "first_token_time": s.first_token_time,
                "slot_key": [int(x) for x in eng._slot_keys[slot]],
                "finish_reason": s.finish_reason,
            })
            self.bytes_sent += len(commit)
            yield commit
        except BaseException:
            # free the donor slot on ANY exit — including the consumer
            # closing the generator early (failed POST). The transport layer
            # owns telling the receiver (abort_message(key)); a generator
            # cannot yield during GeneratorExit.
            if not adm.done:
                eng.abort_chunked(adm)
            elif eng.slots[slot] is not None:
                eng.finish_slot(slot, cache=False)
            raise
        else:
            eng.finish_slot(slot, cache=False)


def abort_message(key: str) -> bytes:
    """Tell a receiver to drop a streamed-handoff session (donor failed)."""
    return _pack_stream(_KIND_ABORT, {"key": key})


# ---------------------------------------------------------------------------
# Cluster-wide KV migration (round 13): prefix-only transfers over the SAME
# begin/piece/commit protocol.
#
# A cold worker that was routed a request whose prefix is hot on a peer can
# PULL the peer's cached KV instead of re-prefilling: it POSTs an export
# request to the peer's ``/kv/export`` data-plane endpoint, and the peer
# answers with a framed sequence of the chaos-hardened streamed-handoff
# messages — one ``begin`` (``prefix_only`` marked, carrying the exact
# prefix token ids), the ``piece`` frames, and one ``commit``. The puller
# feeds each frame through its own :class:`HandoffReceiver`, so duplicate
# tolerance, corrupt-piece session aborts, staged-coverage commit checks,
# and the TTL/progress purge machinery all apply unchanged. A prefix-only
# commit binds NO slot: it releases the staged sequence with
# ``free_sequence(cache=True)``, landing the pulled blocks in the radix
# prefix index — the very next admission of the real request hits L1 and
# skips the re-prefill.
#
# The export side sources blocks from EVERY tier: device-resident radix
# blocks come out in one pool gather (the ``export_slot_kv`` pattern), and
# blocks past the L1 run are probed out of the spill tiers
# (``_probe_spill`` — host RAM, then the remote store), which is what
# promotes the per-worker spill tiers into a cluster-servable cache.
# ---------------------------------------------------------------------------

EXPORT_REQUEST_VERSION = 1


def pack_export_request(*, key: str, token_ids: Sequence[int], model_name: str,
                        block_size: int, int8_kv: bool,
                        max_blocks: int = 64,
                        start_block: int = 0,
                        fp: Optional[str] = None) -> bytes:
    """Wire form of a ``/kv/export`` pull request (msgpack header codec —
    the same pickle-free framing as every other handoff message).
    ``start_block``: leading full blocks the puller ALREADY holds — the
    exporter ships pieces from there, so a partially-warm puller never
    re-transfers (and the peer never re-gathers) the overlap.
    ``fp`` (round 20, proactive replication): a text-space prefix
    fingerprint in place of token ids — a plane-hinted puller has never
    seen the prompt, so the WARM exporter resolves the fingerprint back
    to the token ids its radix is keyed by (miss → empty response, an
    honest "nothing cached"). ``token_ids`` may be empty when ``fp`` is
    given; the version stays 1 because old exporters simply see an
    empty-token request and answer with an empty body."""
    return _pack_header({
        "v": EXPORT_REQUEST_VERSION,
        "key": key,
        "token_ids": [int(t) for t in token_ids],
        "model_name": model_name,
        "block_size": int(block_size),
        "int8_kv": bool(int8_kv),
        "max_blocks": int(max_blocks),
        "start_block": max(0, int(start_block)),
        **({"fp": str(fp)} if fp else {}),
    })


def unpack_export_request(raw: bytes) -> Dict[str, Any]:
    req = _unpack_header(raw)
    if int(req.get("v") or 0) != EXPORT_REQUEST_VERSION:
        raise ValueError(
            f"unsupported kv export request version {req.get('v')!r}"
        )
    return req


def split_frames(data: bytes) -> List[bytes]:
    """Split a ``/kv/export`` response body back into its stream messages
    (the body is ``_frame_blobs(*frames)``; an empty body = no match).
    Raises on truncation — a peer dying mid-response must surface as a
    failed pull, never as a silently shorter prefix."""
    view = memoryview(data)
    off, out = 0, []
    while off < len(view):
        if off + 8 > len(view):
            raise ValueError(
                f"truncated kv export response: length prefix cut at "
                f"offset {off} of {len(view)} bytes"
            )
        n = int.from_bytes(view[off:off + 8], "little")
        if off + 8 + n > len(view):
            raise ValueError(
                f"truncated kv export response: {n}-byte frame at offset "
                f"{off} overruns the {len(view)}-byte body"
            )
        out.append(bytes(view[off + 8:off + 8 + n]))
        off += 8 + n
    return out


def export_prefix_frames(engine: "TPUEngine", token_ids: Sequence[int],
                         key: str, *, piece_blocks: int = 4,
                         max_blocks: int = 64, start_block: int = 0,
                         compress: bool = False) -> Tuple[List[bytes], Dict[str, int]]:
    """Build the prefix-only begin/piece/commit frames for the longest
    locally-cached full-block prefix of ``token_ids``.

    ``start_block``: leading full blocks the PULLER already holds — only
    blocks ``[start_block, n)`` are gathered and shipped (the receiver's
    own cached blocks satisfy the commit coverage check for the rest), so
    a partially-warm puller costs transfer proportional to what it is
    actually missing.

    Returns ``(frames, info)`` where ``info`` counts the shipped blocks by
    tier (``dev_blocks`` from the device radix, ``spill_blocks`` restored
    from the host/remote spill tiers). ``frames`` is empty when the peer
    has nothing beyond ``start_block`` — the caller answers "no match" and
    the puller recomputes.

    Must run serialized with the engine (the caller holds the engine lock /
    executor): the gather reads live pool pages and the spill probe mutates
    LRU state.
    """
    require_kv_pages(engine)
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.utils.data_structures import (
        compute_prefix_hash,
    )

    mgr = engine.manager
    bs = engine.cfg.block_size
    empty = {"dev_blocks": 0, "spill_blocks": 0}
    token_ids = [int(t) for t in token_ids]
    start = max(0, int(start_block))
    n_full = min(len(token_ids) // bs, max(0, int(max_blocks)))
    if n_full <= start or not mgr.enable_prefix_cache:
        return [], empty
    prefix = token_ids[: n_full * bs]
    cached = mgr.radix.match_prefix(prefix)[:n_full]
    quant = "k_scale" in engine.kv

    ship_dev = cached[start:]       # device blocks actually shipped
    dev_pages = dev_scales = None
    if ship_dev:
        # pad the gather to a bucketed width (block 0 is the reserved pad
        # block) so XLA compiles O(max_blocks / bucket) gather shapes, not
        # one per distinct prefix depth — export latency must not eat a
        # fresh compile on every new depth
        bucket = 4
        padded = list(ship_dev) + [0] * (-len(ship_dev) % bucket)
        ids = jnp.asarray(np.asarray(padded, np.int32))
        k = np.asarray(engine.kv["k"][:, ids])[:, : len(ship_dev)]
        v = np.asarray(engine.kv["v"][:, ids])[:, : len(ship_dev)]
        # → [n, L, 2, Hkv, Bk, D]: the adopt/spill upload layout
        dev_pages = np.stack([k, v], axis=0).transpose(2, 1, 0, 3, 4, 5)
        if quant:
            ks = np.asarray(
                engine.kv["k_scale"][:, ids]
            )[:, : len(ship_dev)]
            vs = np.asarray(
                engine.kv["v_scale"][:, ids]
            )[:, : len(ship_dev)]
            dev_scales = np.stack([ks, vs], axis=0).transpose(2, 1, 0, 3, 4)

    # past the device-resident run: the spill tiers are part of the
    # cluster cache — a block evicted to host RAM or the remote store is
    # still servable to a peer (validated for dtype/scale by the probe).
    # Probe hits are NOT the exporter's own serving traffic: restore the
    # l2/l3 hit counters so peer demand never skews this worker's cache
    # panels (promote-on-hit is kept — repeated pulls of the same remote-
    # tier prefix should get cheaper, and the L2 is a bounded LRU).
    spill: List[Tuple[np.ndarray, Optional[np.ndarray]]] = []
    spill_lo = max(len(cached), start)
    idx = spill_lo
    st = mgr.stats
    l2_before, l3_before = st.l2_hits, st.l3_hits
    try:
        while idx < n_full:
            hit = mgr._probe_spill(
                compute_prefix_hash(prefix, (idx + 1) * bs)
            )
            if hit is None:
                break
            spill.append(hit)
            idx += 1
    finally:
        st.l2_hits, st.l3_hits = l2_before, l3_before
    n = idx
    if n <= start:
        return [], empty

    def _block(i: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        if i < len(cached):
            j = i - start
            return dev_pages[j], (dev_scales[j] if quant else None)
        page, scale = spill[i - spill_lo]
        return page, scale

    frames = [_pack_stream(_KIND_BEGIN, {
        "key": key,
        "model_name": engine.model_cfg.name,
        "block_size": bs,
        "int8_kv": quant,
        "prefix_only": True,
        "token_ids": prefix[: n * bs],
    })]
    ser = TensorSerializer(compress=compress)
    pb_step = max(1, int(piece_blocks))
    for lo in range(start, n, pb_step):
        hi = min(n, lo + pb_step)
        pages = np.stack([_block(i)[0] for i in range(lo, hi)], axis=0)
        pb = ser.serialize(pages)
        if quant:
            scales = np.stack(
                [_block(i)[1] for i in range(lo, hi)], axis=0
            )
            frames.append(_pack_stream(
                _KIND_PIECE,
                {"key": key, "block_lo": lo, "has_scales": True},
                _frame_blobs(pb, ser.serialize(scales)),
            ))
        else:
            frames.append(_pack_stream(
                _KIND_PIECE, {"key": key, "block_lo": lo}, pb
            ))
    frames.append(_pack_stream(_KIND_COMMIT, {
        "key": key, "prefix_only": True, "kv_len": n * bs,
    }))
    return frames, {"dev_blocks": len(ship_dev),
                    "spill_blocks": len(spill)}


@dataclass
class _AdoptSession:
    seq_id: str
    request: InferenceRequest
    block_size: int
    blocks: List[int]
    cached_tokens: int
    prompt_len: int
    # cluster-KV migration: a prefix-only session transfers CACHED prefix
    # blocks with no live generation attached — its commit releases the
    # chain into the radix prefix index instead of binding a slot
    prefix_only: bool = False
    staged: List[int] = field(default_factory=list)
    # last-activity time, refreshed on every piece: a long streamed
    # migration (multi-GB KV over a slow link) must not be purged mid-stream by its own later messages — only
    # sessions with no traffic for SESSION_TTL_S are stale.
    last_activity: float = field(default_factory=time.monotonic)
    # refreshed only when a piece stages a NOT-previously-staged block:
    # legitimate migrations of any size keep making block progress (total
    # refreshes bounded by the block count), while a trickler re-sending
    # the same block forever stalls this clock and hits the backstop
    last_progress: float = field(default_factory=time.monotonic)


class HandoffReceiver:
    """Recipient-side session machine for streamed handoffs.

    One instance per engine; ``handle(raw)`` dispatches begin/piece/commit/
    abort messages AND legacy one-shot blobs (``adopt_kv`` path), so a data
    plane needs exactly one receiver callable. The caller provides the
    engine lock (the worker's job path and the data-plane thread share it).
    """

    SESSION_TTL_S = 180.0
    # no-progress backstop: a donor that keeps the session warm (pieces
    # every <TTL) without ever staging a new block must not pin its
    # allocated KV blocks forever. Progress-based, not a hard lifetime cap:
    # a legitimate migration of ANY size stages new blocks as it goes
    # (the window is 30 min: a 2 MB block lands inside it on any link
    # above ~1 kB/s), so only stalled/adversarial streams hit it.
    SESSION_MAX_NO_PROGRESS_S = 10 * 180.0
    # adopt-session count cap, enforced at ``_begin``: a flood of begins
    # (crashed donors that never send their abort, or a buggy peer
    # re-opening sessions) must not pin unbounded KV blocks while each
    # waits out its TTL — past the cap the stalest session is evicted to
    # make room. Sized well above any sane concurrent-migration fan-in.
    MAX_SESSIONS = 32

    # commit-replay memo size: a retried commit whose first delivery's ACK
    # was lost must answer idempotently (the slot is already bound — a
    # "no session" error would fail a handoff that actually LANDED), so
    # recent commits are remembered by key
    MAX_COMMIT_MEMO = 32

    def __init__(self, engine: "TPUEngine") -> None:
        require_kv_pages(engine)
        self.engine = engine
        self._sessions: Dict[str, _AdoptSession] = {}
        # recently committed keys → the result dict their commit returned
        # (insertion-ordered; oldest evicted past MAX_COMMIT_MEMO)
        self._recent_commits: Dict[str, Dict[str, Any]] = {}
        # sessions_purged: abandoned migrations reclaimed (TTL, no-progress
        # backstop, or count-cap eviction) — exported via worker heartbeats
        # as kv_handoff_sessions_purged_total so they are VISIBLE, not just
        # silently garbage-collected. The per-reason counters break the
        # total down (chaos suites assert each recovery path is COUNTED,
        # not silently absorbed); "rx_aborts" counts sender-requested
        # aborts, "commits" successful bindings.
        self.stats: Dict[str, int] = {
            "sessions_purged": 0,
            "purged_ttl": 0,
            "purged_no_progress": 0,
            "purged_cap": 0,
            "rx_aborts": 0,
            "commits": 0,
            "prefix_commits": 0,
            "begin_duplicates": 0,
            "commit_replays": 0,
        }
        # flight recorder: receiver-side begin/commit/abort instants keyed
        # by session key. The receiver knows only the kv_cache_key — the
        # decode stage that later claims the adoption pops these into the
        # request's Timeline (``pop_flight``). Bounded: oldest keys evict
        # past the cap, duplicate begins/commit replays don't double-note.
        self._flight: Dict[str, List[Tuple[str, float]]] = {}
        self.FLIGHT_KEY_CAP = 64

    def _flight_note(self, key: str, name: str) -> None:
        if not key:
            return
        evs = self._flight.get(key)
        if evs is None:
            while len(self._flight) >= self.FLIGHT_KEY_CAP:
                self._flight.pop(next(iter(self._flight)))
            evs = self._flight[key] = []
        if len(evs) < 8:
            evs.append((name, time.time()))

    def pop_flight(self, key: str) -> List[Tuple[str, float]]:
        """Drain the receiver-side flight events for one session key —
        ``[(event, wall_ts), ...]`` — for adoption into the claiming
        request's Timeline. Empty when nothing was recorded."""
        return self._flight.pop(key, [])

    def handle(self, raw: bytes) -> Dict[str, Any]:
        # chaos seam: an installed FaultPlan can truncate or lose this
        # message in transit (no-op passthrough otherwise)
        raw = _faults.mutate_bytes("kv.receiver.message", raw)
        self._purge_stale()
        if not is_stream_message(raw):
            handoff = deserialize_handoff(raw)
            key = handoff.request.session_id or handoff.request.request_id
            slot = adopt_kv(self.engine, handoff)
            return {"slot": slot, "bytes_received": len(raw),
                    "kv_cache_key": key, "streamed": False}
        kind, meta, payload = _unpack_stream(raw)
        if kind == _KIND_BEGIN:
            return self._begin(meta)
        if kind == _KIND_PIECE:
            try:
                return self._piece(meta, payload, len(raw))
            except Exception:
                # a malformed/truncated piece poisons the whole stream (its
                # block range can never be staged, so the commit could only
                # bind garbage): abort the session NOW so its blocks free
                # immediately instead of pinning KV until the TTL purge
                self._drop(str(meta.get("key", "")))
                raise
        if kind == _KIND_COMMIT:
            return self._commit(meta)
        if kind == _KIND_ABORT:
            return self._abort(meta)
        raise ValueError(f"unknown stream message kind {kind}")

    # -- session steps -------------------------------------------------------

    def _begin(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        eng = self.engine
        if eng.model_cfg.name != meta["model_name"]:
            raise ValueError(
                f"model mismatch: engine={eng.model_cfg.name} "
                f"handoff={meta['model_name']}"
            )
        if eng.cfg.block_size != meta["block_size"]:
            raise ValueError("block_size mismatch between engines")
        if bool(meta.get("int8_kv")) != ("k_scale" in eng.kv):
            raise ValueError(
                "kv_cache_dtype mismatch: int8-KV donors stream raw int8 "
                "pages + scales and can only land in int8-KV engines "
                "(and vice versa)"
            )
        key = meta["key"]
        prefix_only = bool(meta.get("prefix_only"))
        existing = self._sessions.get(key)
        if existing is not None:
            rid = (meta.get("request") or {}).get("request_id")
            if prefix_only:
                # prefix-only sessions carry no request; the key itself is
                # the idempotency token (pullers mint a fresh key per pull)
                rid = f"kvmig-{key}"
            if existing.request.request_id == rid:
                # duplicate delivery (sender retried a begin whose ACK was
                # lost): the session is already open for the SAME request —
                # answer idempotently so the retry ladder composes with the
                # streamed protocol instead of poisoning it
                self.stats["begin_duplicates"] = (
                    self.stats.get("begin_duplicates", 0) + 1
                )
                return {"kv_cache_key": key, "state": "begun",
                        "cached_tokens": existing.cached_tokens,
                        "duplicate": True}
            raise ValueError(
                f"streamed handoff {key!r} already begun by another request"
            )
        # purge on ADOPT-SESSION pressure too, not only on message arrival:
        # age out stale sessions first, then — if a begin flood still has
        # the table at the cap — evict the stalest session so abandoned
        # migrations can never pin the pool against live ones
        self._purge_stale()
        while len(self._sessions) >= self.MAX_SESSIONS:
            stalest = min(self._sessions,
                          key=lambda k: self._sessions[k].last_activity)
            self._drop(stalest)
            self.stats["sessions_purged"] = (
                self.stats.get("sessions_purged", 0) + 1
            )
            self.stats["purged_cap"] = self.stats.get("purged_cap", 0) + 1
        if prefix_only:
            toks = [int(t) for t in (meta.get("token_ids") or [])]
            bs = int(meta["block_size"])
            if not toks or len(toks) % bs != 0:
                raise ValueError(
                    "prefix-only handoff needs a whole-block token_ids "
                    f"prefix (got {len(toks)} tokens, block size {bs})"
                )
            if len(toks) // bs > eng.cfg.max_blocks_per_seq or \
                    len(toks) > eng.cfg.max_seq_len:
                raise ValueError(
                    "prefix-only handoff exceeds engine sequence bounds"
                )
            request = InferenceRequest(
                request_id=f"kvmig-{key}",
                prompt_token_ids=toks,
                sampling=SamplingParams(max_new_tokens=1),
            )
            seq_id = f"{key}-kvmig"
            # the transfer is NOT a serving request: allocate_sequence
            # would book the pulled prefix as one giant cache miss and
            # skew every hit-rate panel/bench — restore the query stats
            # (block/eviction accounting stays; kv_migrate counters own
            # the transfer's own observability)
            st = eng.manager.stats
            before = (st.prefix_queries, st.prefix_hit_tokens,
                      st.prefix_total_tokens, st.misses, st.l1_hits)
            try:
                blocks, cached_tokens = eng.manager.allocate_sequence(
                    seq_id, toks
                )
            finally:
                # restore on the failure path too (pool pressure raises
                # AFTER the query stats were bumped)
                (st.prefix_queries, st.prefix_hit_tokens,
                 st.prefix_total_tokens, st.misses, st.l1_hits) = before
            self._sessions[key] = _AdoptSession(
                seq_id=seq_id, request=request, block_size=bs,
                blocks=list(blocks), cached_tokens=cached_tokens,
                prompt_len=len(toks), prefix_only=True,
            )
            return {"kv_cache_key": key, "state": "begun",
                    "cached_tokens": cached_tokens, "prefix_only": True}
        r = meta["request"]
        request = InferenceRequest(
            request_id=r["request_id"],
            model=r.get("model"),
            prompt_token_ids=r.get("prompt_token_ids"),
            sampling=SamplingParams.from_dict(r["sampling"]),
            priority=r.get("priority", 0),
            session_id=r.get("session_id"),
        )
        if r.get("deadline_at") is not None:
            # re-derive the RELATIVE deadline against this engine's fresh
            # arrival_time so deadline_at lands on the original absolute
            # instant — elapsed handoff time stays spent, EDF order
            # survives the migration (clamped: already-missed deadlines
            # must not go negative)
            request.deadline_s = max(
                0.0, float(r["deadline_at"]) - request.arrival_time
            )
        prompt = list(request.prompt_token_ids or [])
        if not prompt:
            raise ValueError("streamed handoff with empty prompt")
        # full capacity check at BEGIN time — before any piece crosses the
        # wire. The commit-time state is prompt + 1 pending (first) token,
        # so remaining = max_new - 1: identical bound to the commit check.
        _validate_capacity(
            eng, len(prompt) + 1, len(prompt),
            max(request.sampling.max_new_tokens - 1, 0),
        )
        seq_id = f"{request.request_id}-pd"
        blocks, cached_tokens = eng.manager.allocate_sequence(seq_id, prompt)
        self._sessions[key] = _AdoptSession(
            seq_id=seq_id, request=request,
            block_size=meta["block_size"], blocks=list(blocks),
            cached_tokens=cached_tokens, prompt_len=len(prompt),
        )
        self._flight_note(key, "handoff.rx_begin")
        return {"kv_cache_key": key, "state": "begun",
                "cached_tokens": cached_tokens}

    def _piece(self, meta: Dict[str, Any], payload: bytes,
               raw_len: int) -> Dict[str, Any]:
        # io chaos seam (round 19): receiver-side STAGING faults — a torn
        # or corrupted staging buffer (io_bytes mutates payload, error
        # kinds raise) rides the existing corrupt-piece contract above:
        # handle() aborts the session and the sender's retry ladder runs
        payload = _faults.io_bytes(
            "io.handoff.stage", payload, key=str(meta.get("key", ""))
        )
        sess = self._require(meta["key"])
        sess.last_activity = time.monotonic()
        if meta.get("has_scales"):
            pb, sb = _read_blobs(payload, 2)
            pages = TensorSerializer().deserialize(pb)
            scales = TensorSerializer().deserialize(sb)
        else:
            pages = TensorSerializer().deserialize(payload)
            scales = None
        lo = int(meta["block_lo"])
        eng = self.engine
        cached_blocks = sess.cached_tokens // sess.block_size
        uploaded = 0
        already = set(sess.staged)
        for j in range(pages.shape[0]):
            i = lo + j
            if i >= len(sess.blocks):
                # the donor's chain can grow one block past the prompt
                # allocation (pending-token block) — extend lazily at
                # commit; an uncommitted page here is never read, skip it
                continue
            if i < cached_blocks:
                continue    # receiver-side prefix hit: page already resident
            eng.manager.pending.uploads.append((sess.blocks[i], pages[j]))
            if scales is not None:
                eng.manager.pending.scale_uploads.append(
                    (sess.blocks[i], scales[j])
                )
            if sess.blocks[i] not in already:
                sess.last_progress = time.monotonic()
            sess.staged.append(sess.blocks[i])
            uploaded += 1
        eng._apply_pending()
        return {"kv_cache_key": meta["key"], "state": "staged",
                "blocks": uploaded, "bytes_received": raw_len}

    def _commit(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        key = meta["key"]
        if key not in self._sessions and key in self._recent_commits:
            # retried commit after a lost ACK: the slot is already bound —
            # answer the original result instead of failing a handoff that
            # landed (the sender's retry ladder depends on this)
            self.stats["commit_replays"] = (
                self.stats.get("commit_replays", 0) + 1
            )
            return {**self._recent_commits[key], "replay": True}
        sess = self._require(key)
        eng = self.engine
        if sess.prefix_only:
            # prefix-only commit: no slot to bind — verify coverage, then
            # release the chain into the radix prefix index so the next
            # admission of the real request hits L1 instead of re-prefilling
            cached_blocks = sess.cached_tokens // sess.block_size
            kv_len = int(meta.get("kv_len") or sess.prompt_len)
            needed = -(-kv_len // sess.block_size)
            staged = set(sess.staged)
            missing = [
                i for i in range(cached_blocks,
                                 min(needed, len(sess.blocks)))
                if sess.blocks[i] not in staged
            ]
            if missing:
                self._drop(key)
                raise ValueError(
                    f"prefix handoff {key!r}: commit with unstaged blocks "
                    f"{missing[:8]}{'...' if len(missing) > 8 else ''} "
                    f"(piece lost in transit?) — session aborted"
                )
            eng.manager.free_sequence(sess.seq_id, cache=True)
            del self._sessions[key]
            self.stats["prefix_commits"] = (
                self.stats.get("prefix_commits", 0) + 1
            )
            result = {"kv_cache_key": key, "state": "committed",
                      "prefix_only": True, "blocks": len(sess.blocks),
                      "cached_tokens": sess.cached_tokens, "streamed": True}
            self._recent_commits[key] = result
            while len(self._recent_commits) > self.MAX_COMMIT_MEMO:
                self._recent_commits.pop(next(iter(self._recent_commits)))
            return result
        req = sess.request
        token_ids = list(meta["token_ids"])
        # every block covering the committed KV range must have been staged
        # (or be resident via the receiver's prefix cache): committing over
        # a lost piece would bind a slot to unwritten pages and the resumed
        # decode would silently diverge — abort instead, so the control
        # plane retries the stage cleanly
        cached_blocks = sess.cached_tokens // sess.block_size
        needed = -(-int(meta["kv_len"]) // sess.block_size)
        staged = set(sess.staged)
        missing = [
            i for i in range(cached_blocks, min(needed, len(sess.blocks)))
            if sess.blocks[i] not in staged
        ]
        if missing:
            self._drop(key)
            raise ValueError(
                f"streamed handoff {key!r}: commit with unstaged blocks "
                f"{missing[:8]}{'...' if len(missing) > 8 else ''} "
                f"(piece lost in transit?) — session aborted"
            )
        try:
            _validate_capacity(
                eng, len(token_ids), int(meta["kv_len"]),
                0 if meta.get("finish_reason") is not None else
                req.sampling.max_new_tokens - len(meta["generated"]),
            )
        except ValueError:
            self._drop(key)
            raise
        free = eng.free_slots()
        if not free:
            self._drop(key)
            raise RuntimeError("no free slots")
        slot = free[0]
        try:
            # mirror the donor's pending-token append (may grow the chain)
            for tok in token_ids[len(eng.manager.seq_tokens[sess.seq_id]):]:
                eng.manager.append_token(sess.seq_id, tok)
            _bind_migrated(
                eng, slot, request=req, seq_id=sess.seq_id,
                prompt_len=sess.prompt_len, generated=meta["generated"],
                cached_tokens=sess.cached_tokens,
                start_time=meta["start_time"],
                first_token_time=meta["first_token_time"],
                kv_len=int(meta["kv_len"]),
                pending_token=int(meta["pending_token"]),
                slot_key=meta.get("slot_key"),
                finish_reason=meta.get("finish_reason"),
            )
        except Exception:
            eng.slots[slot] = None
            eng._kv_lens[slot] = 0
            self._drop(key)
            raise
        del self._sessions[key]
        self.stats["commits"] = self.stats.get("commits", 0) + 1
        self._flight_note(key, "handoff.rx_commit")
        result = {"slot": slot, "kv_cache_key": key, "state": "committed",
                  "streamed": True}
        self._recent_commits[key] = result
        while len(self._recent_commits) > self.MAX_COMMIT_MEMO:
            self._recent_commits.pop(next(iter(self._recent_commits)))
        return result

    def _abort(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        if str(meta.get("key", "")) in self._sessions:
            self.stats["rx_aborts"] = self.stats.get("rx_aborts", 0) + 1
            self._flight_note(str(meta.get("key", "")), "handoff.rx_abort")
        self._drop(meta.get("key", ""))
        return {"kv_cache_key": meta.get("key"), "state": "aborted"}

    # -- bookkeeping ---------------------------------------------------------

    def _require(self, key: str) -> _AdoptSession:
        sess = self._sessions.get(key)
        if sess is None:
            raise ValueError(f"no streamed handoff session {key!r}")
        return sess

    def _drop(self, key: str) -> None:
        sess = self._sessions.pop(key, None)
        if sess is None:
            return
        eng = self.engine
        if sess.staged:
            staged = set(sess.staged)
            eng.manager.pending.uploads = [
                (bid, page) for bid, page in eng.manager.pending.uploads
                if bid not in staged
            ]
            eng.manager.pending.scale_uploads = [
                (bid, page)
                for bid, page in eng.manager.pending.scale_uploads
                if bid not in staged
            ]
        if sess.seq_id in eng.manager.seq_blocks:
            eng.manager.free_sequence(sess.seq_id, cache=False)

    def _purge_stale(self) -> None:
        now = time.monotonic()
        for key, sess in list(self._sessions.items()):
            if now - sess.last_activity > self.SESSION_TTL_S:
                reason = "purged_ttl"
            elif now - sess.last_progress > self.SESSION_MAX_NO_PROGRESS_S:
                reason = "purged_no_progress"
            else:
                continue
            self._drop(key)
            self.stats["sessions_purged"] = (
                self.stats.get("sessions_purged", 0) + 1
            )
            self.stats[reason] = self.stats.get(reason, 0) + 1


def deserialize_handoff(data: bytes) -> KVHandoff:
    mb = _read_blobs(data, 1)[0]
    meta: Dict[str, Any] = _unpack_header(mb)
    count = 3 if meta.get("has_scales") else 2
    blobs = _read_blobs(data, count)
    pages = TensorSerializer().deserialize(blobs[1])
    scale_pages = (
        TensorSerializer().deserialize(blobs[2])
        if meta.get("has_scales") else None
    )
    r = meta["request"]
    request = InferenceRequest(
        request_id=r["request_id"],
        model=r.get("model"),
        prompt_token_ids=r.get("prompt_token_ids"),
        sampling=SamplingParams.from_dict(r["sampling"]),
        priority=r.get("priority", 0),
        session_id=r.get("session_id"),
    )
    if r.get("deadline_at") is not None:
        # absolute → relative against the fresh arrival_time (same
        # re-derivation as the streamed _begin path): EDF ordering
        # survives the handoff, elapsed transfer time stays spent
        request.deadline_s = max(
            0.0, float(r["deadline_at"]) - request.arrival_time
        )
    return KVHandoff(
        request=request,
        model_name=meta["model_name"],
        block_size=meta["block_size"],
        token_ids=meta["token_ids"],
        kv_len=meta["kv_len"],
        pending_token=meta["pending_token"],
        prompt_len=meta["prompt_len"],
        generated=meta["generated"],
        start_time=meta["start_time"],
        first_token_time=meta["first_token_time"],
        slot_key=meta.get("slot_key"),
        window_front=meta.get("window_front", 0),
        finish_reason=meta.get("finish_reason"),
        pages=pages,
        scale_pages=scale_pages,
    )
