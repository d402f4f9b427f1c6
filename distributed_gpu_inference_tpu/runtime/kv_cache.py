"""Host-side paged KV-cache management: block allocator, radix prefix index,
copy-on-write, LRU eviction, and multi-tier spill (HBM → host RAM → KV store).

Capability parity with the reference's ``worker/distributed/kv_cache.py``
(CacheBlock:34, PagedKVCache:79, KVCachePool:250, DistributedKVCacheManager:326
with L1 GPU / L2 CPU / L3 Redis tiers and get_or_compute:389-445) plus the
RadixAttention-style prefix sharing the reference rents from SGLang
(SURVEY §2.3) — re-designed for TPU:

- The *device* side is a pair of pool arrays ``[L, N, Hkv, block, D]`` owned by
  the engine and mutated **inside jitted graphs** (scatter writes, block
  copies). This module never holds device tensors for blocks; it owns the
  *metadata*: free lists, refcounts, the radix tree, LRU order, and tier maps.
- Device-side effects the metadata layer decides on (CoW copies, spill-in
  uploads) are returned to the engine as explicit op lists
  (:class:`PendingDeviceOps`) so the engine can apply them as one fused jitted
  update — the TPU analogue of the reference's eager ``torch.Tensor`` block
  copies.
- Block 0 is reserved as the pad/garbage block (padded-token writes land
  there) and is never allocated.
"""

from __future__ import annotations

import logging
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from distributed_gpu_inference_tpu.testing import faults as _faults
from distributed_gpu_inference_tpu.utils.data_structures import (
    KV_BLOCK_TOKENS,
    KVBlockMeta,
    block_prefix_hashes,
    compute_prefix_hash,
)


class OutOfBlocksError(RuntimeError):
    pass


class SpillIntegrityError(ValueError):
    """A spilled entry failed its CRC — bit rot or a torn write. The probe
    path QUARANTINES the entry (best-effort delete + counter) and degrades
    to the next tier or recompute; this error never crosses a request."""


#: checksummed spill-entry framing (round 19): magic + CRC32 of the body.
#: Entries without the magic are the pre-round-19 legacy form and are
#: accepted unchecked — a mixed-version fleet sharing one remote tier must
#: keep hitting, and legacy entries age out under TTL anyway.
_SPILL_MAGIC = b"SPL2"


def _pack_spill(page: np.ndarray,
                scale_page: Optional[np.ndarray]) -> bytes:
    """L3 wire form of a spilled block: magic + CRC32, then the
    length-prefixed page blob and the optional scale blob. One entry per
    block — (page, scale) are atomic by construction, so there is no
    orphaned-scale state to defend against. The CRC covers the whole body,
    so both bit rot (corrupt read) and torn writes surface as
    :class:`SpillIntegrityError` at unpack time."""
    import zlib

    from distributed_gpu_inference_tpu.utils.serialization import (
        TensorSerializer,
    )

    ser = TensorSerializer()
    pb = ser.serialize(page)
    body = len(pb).to_bytes(8, "little") + pb
    if scale_page is not None:
        body += ser.serialize(scale_page)
    return _SPILL_MAGIC + zlib.crc32(body).to_bytes(4, "little") + body


def _unpack_spill(raw: bytes) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    import zlib

    from distributed_gpu_inference_tpu.utils.serialization import (
        TensorSerializer,
    )

    if raw[:4] == _SPILL_MAGIC:
        if len(raw) < 8:
            raise SpillIntegrityError(
                f"torn spill entry: {len(raw)} bytes is shorter than the "
                "checksummed header"
            )
        want = int.from_bytes(raw[4:8], "little")
        raw = raw[8:]
        got = zlib.crc32(raw)
        if got != want:
            raise SpillIntegrityError(
                f"spill entry checksum mismatch: stored {want:#010x}, "
                f"computed {got:#010x} over {len(raw)} bytes"
            )
    n = int.from_bytes(raw[:8], "little")
    if 8 + n > len(raw):
        raise ValueError(
            f"malformed spill entry: {n}-byte page blob overruns the "
            f"{len(raw)}-byte entry"
        )
    ser = TensorSerializer()
    page = ser.deserialize(raw[8:8 + n])
    scale = ser.deserialize(raw[8 + n:]) if len(raw) > 8 + n else None
    return page, scale


@dataclass
class PendingDeviceOps:
    """Device-side effects for the engine to apply in its next jitted update.

    downloads: (src_block, spill_key) pages to pull to host BEFORE any write
               (spill-on-evict: the block id is about to be reused)
    copies:    (src_block, dst_block) page copies (CoW / defrag)
    uploads:   (dst_block, host_kv) spill-tier promotions; host_kv is
               ``np.ndarray [L, 2, Hkv, block, D]`` (k and v stacked on axis 1)
    scale_uploads: (dst_block, host_scales) int8-KV scale pages riding with
               an adopted handoff; host_scales is ``np.ndarray
               [L, 2, block, D]`` (k and v scales stacked on axis 1). A
               separate channel (not a wider uploads tuple) so the many
               (bid, page) destructure sites stay valid.
    """

    downloads: List[Tuple[int, str]] = field(default_factory=list)
    copies: List[Tuple[int, int]] = field(default_factory=list)
    uploads: List[Tuple[int, np.ndarray]] = field(default_factory=list)
    scale_uploads: List[Tuple[int, np.ndarray]] = field(default_factory=list)

    def merge(self, other: "PendingDeviceOps") -> None:
        self.downloads.extend(other.downloads)
        self.copies.extend(other.copies)
        self.uploads.extend(other.uploads)
        self.scale_uploads.extend(other.scale_uploads)

    @property
    def empty(self) -> bool:
        return not (
            self.downloads or self.copies or self.uploads
            or self.scale_uploads
        )


class _RadixNode:
    __slots__ = ("children", "block_id", "parent", "edge", "last_access")

    def __init__(self, parent: Optional["_RadixNode"], edge: Optional[Tuple[int, ...]],
                 block_id: Optional[int]) -> None:
        self.children: Dict[Tuple[int, ...], _RadixNode] = {}
        self.block_id = block_id
        self.parent = parent
        self.edge = edge
        self.last_access = time.monotonic()


class RadixPrefixIndex:
    """Radix tree over full token blocks for prefix-cache lookup.

    Each edge is one *full* block of tokens (KV_BLOCK_TOKENS); a node holds the
    physical block id caching that prefix block. Partial blocks are never
    shared (matches vLLM semantics; the reference's SGLang engine exposes the
    same behavior through RadixAttention).
    """

    def __init__(self, block_size: int = KV_BLOCK_TOKENS) -> None:
        self.block_size = block_size
        self.root = _RadixNode(None, None, None)
        self._nodes_by_block: Dict[int, _RadixNode] = {}

    def _chunks(self, token_ids: Sequence[int]) -> List[Tuple[int, ...]]:
        bs = self.block_size
        n_full = len(token_ids) // bs
        return [tuple(token_ids[i * bs : (i + 1) * bs]) for i in range(n_full)]

    def match_prefix(self, token_ids: Sequence[int]) -> List[int]:
        """Longest cached full-block prefix → list of physical block ids."""
        node = self.root
        out: List[int] = []
        now = time.monotonic()
        for chunk in self._chunks(token_ids):
            child = node.children.get(chunk)
            if child is None:
                break
            child.last_access = now
            out.append(child.block_id)  # type: ignore[arg-type]
            node = child
        return out

    def insert(self, token_ids: Sequence[int], block_ids: Sequence[int]) -> int:
        """Index ``block_ids`` as the cache of the full blocks of ``token_ids``.

        Returns the number of *newly indexed* blocks (already-present prefix
        nodes are left untouched — caller dedups against match_prefix).
        """
        node = self.root
        added = 0
        for chunk, bid in zip(self._chunks(token_ids), block_ids):
            child = node.children.get(chunk)
            if child is None:
                child = _RadixNode(node, chunk, bid)
                node.children[chunk] = child
                self._nodes_by_block[bid] = child
                added += 1
            node = child
        return added

    def contains_block(self, block_id: int) -> bool:
        return block_id in self._nodes_by_block

    def is_leaf(self, block_id: int) -> bool:
        node = self._nodes_by_block.get(block_id)
        return node is not None and not node.children

    def remove_block(self, block_id: int) -> None:
        """Remove a (leaf) node from the tree; interior nodes must not be
        removed or descendant chains would dangle."""
        node = self._nodes_by_block.get(block_id)
        if node is None:
            return
        if node.children:
            raise ValueError(f"cannot evict interior radix block {block_id}")
        del self._nodes_by_block[block_id]
        assert node.parent is not None
        del node.parent.children[node.edge]  # type: ignore[index]

    def __len__(self) -> int:
        return len(self._nodes_by_block)


def make_radix_index(block_size: int = KV_BLOCK_TOKENS,
                     prefer_native: bool = True):
    """Prefix index factory: C++ implementation when the native library is
    buildable/loadable (``native/src/radix_index.cpp``), exact-semantics
    Python fallback otherwise. ``TPU_NATIVE=0`` forces the fallback."""
    if prefer_native:
        try:
            from distributed_gpu_inference_tpu.native import native_available

            if native_available():
                from distributed_gpu_inference_tpu.native.radix import (
                    NativeRadixPrefixIndex,
                )

                return NativeRadixPrefixIndex(block_size)
        except Exception as exc:  # any native issue → fallback, but say so
            logging.getLogger("tpu_native").warning(
                "native radix index unavailable, using Python fallback: %s",
                exc,
            )
    return RadixPrefixIndex(block_size)


@dataclass
class KVCacheStats:
    """Hit-rate statistics (reference kv_cache.py:544 get_stats)."""

    prefix_queries: int = 0
    prefix_hit_tokens: int = 0
    prefix_total_tokens: int = 0
    l1_hits: int = 0
    l2_hits: int = 0
    l3_hits: int = 0
    misses: int = 0
    evictions: int = 0
    spills: int = 0
    cow_copies: int = 0
    allocated_blocks: int = 0
    cached_blocks: int = 0
    free_blocks: int = 0
    window_released_blocks: int = 0
    # a manager with a pool a layer kind (``window_blocks``): blocks the
    # live rows hold, a kind; window-kind blocks a live row released that
    # stayed findable by their prefix, those the window pool took back from
    # that cache, and the lookups the full kind matched of which the window
    # kind cut some back (or to nothing) for want of the last window's pages
    blocks_in_use: int = 0
    window_blocks_in_use: int = 0
    window_blocks_retained: int = 0
    window_blocks_evicted: int = 0
    prefix_lookups_matched: int = 0
    prefix_hits_cut_by_window: int = 0
    prefix_hit_tokens_cut_by_window: int = 0
    # a manager with a state pool (``state_rows``): rows bound to a new
    # sequence, and lookups cut to zero because pages alone back them
    state_binds: int = 0
    prefix_hits_without_state: int = 0

    def as_dict(self) -> Dict[str, Any]:
        d = dict(self.__dict__)
        d["prefix_hit_rate"] = (
            self.prefix_hit_tokens / self.prefix_total_tokens
            if self.prefix_total_tokens
            else 0.0
        )
        return d


class HostKVStore:
    """L2 host-RAM spill tier: block-content-keyed entries with LRU cap.

    An entry is one spilled BLOCK: a bare page array, or a
    ``(page, scale_page | None)`` tuple for int8 pools — one LRU slot per
    block either way, so ``max_blocks`` means what it says.

    Reference analogue: DistributedKVCacheManager's CPU OrderedDict tier
    (kv_cache.py:326, promote-on-hit :447-462).
    """

    def __init__(self, max_blocks: int = 1024) -> None:
        self.max_blocks = max_blocks
        self._store: "OrderedDict[str, Any]" = OrderedDict()

    def get(self, key: str) -> Optional[Any]:
        # chaos seam: host-RAM tier IO (an injected error models the NUMA
        # pool / pinned-buffer allocation failing, not bit rot — RAM
        # entries are objects, so corrupt/torn kinds live on the remote
        # tier's byte seams instead)
        _faults.io_fault("io.spill.host.get", key=key)
        arr = self._store.get(key)
        if arr is not None:
            self._store.move_to_end(key)
        return arr

    def put(self, key: str, value: Any) -> None:
        if self.max_blocks <= 0:
            return
        _faults.io_fault("io.spill.host.put", key=key)
        self._store[key] = value
        self._store.move_to_end(key)
        while len(self._store) > self.max_blocks:
            self._store.popitem(last=False)

    def delete(self, key: str) -> None:
        """Quarantine hook: drop one entry (no seam — eviction of a bad
        entry must never be blockable by the chaos that exposed it)."""
        self._store.pop(key, None)

    def __len__(self) -> int:
        return len(self._store)


class RemoteKVStore:
    """L3 tier interface (reference: Redis with TTL, kv_cache.py:477-520).

    The in-process default is a TTL dict; a Redis/remote-store client can be
    dropped in by implementing get/put. Values are serialized frames so this
    tier can sit behind a network boundary.
    """

    def __init__(self, ttl_s: float = 3600.0) -> None:
        self.ttl_s = ttl_s
        self._store: Dict[str, Tuple[float, bytes]] = {}

    def get(self, key: str) -> Optional[bytes]:
        item = self._store.get(key)
        if item is None:
            # the seam still fires on a miss (an io_error is a failed READ,
            # hit or not) — mutating kinds pass None through untouched
            return _faults.io_bytes("io.spill.remote.get", None, key=key)
        expires, data = item
        if time.monotonic() > expires:
            del self._store[key]
            return None
        # chaos seam: corrupt reads flip a byte, short reads truncate,
        # errors raise OSError — what the entry CRC + quarantine defend
        return _faults.io_bytes("io.spill.remote.get", data, key=key)

    def put(self, key: str, data: bytes) -> None:
        # chaos seam: a torn write persists only a prefix — detected at
        # read time by the CRC, exactly like real partial-flush loss
        data = _faults.io_bytes("io.spill.remote.put", data, key=key)
        self._store[key] = (time.monotonic() + self.ttl_s, data)

    def delete(self, key: str) -> None:
        """Quarantine hook: evict one (corrupt) entry."""
        self._store.pop(key, None)

    def purge_expired(self) -> int:
        now = time.monotonic()
        dead = [k for k, (exp, _) in self._store.items() if now > exp]
        for k in dead:
            del self._store[k]
        return len(dead)


class _WindowPages:
    """The sliding kind's pages of a model of mixed attention kinds: a pool
    and a chain a sequence of their own, inside the one manager.

    A chain is indexed by logical block like the full kind's, 0 where the
    row holds nothing: before its window (released, or never held after a
    prefix hit) and past what it has written. A block is tied to the
    full-kind block that caches the same tokens (``partner`` /
    ``by_full``): that is how a prefix finds it, since the radix index
    holds the full kind's chain. A block no row holds stays **parked**
    while its partner lives, under the sequence that let go of it, and is
    taken back when the pool runs dry. A block of generated tokens that a
    running sequence lets go of is not parked at all: a later prompt ends
    inside this one's prompt or at its end (the next turn), and that end's
    window is still held when the sequence finishes. Of the parked blocks
    the asking sequence's own oldest goes first while it parks more than
    any other: a long cold prompt releases a pool's worth of blocks in a
    row, eats its own trail and leaves alone the few blocks the other rows
    parked, which lie where a next turn's prefix ends. Otherwise the
    sequence that finished first gives its oldest: what an idle sequence
    left ages out before what a running one parked, whose next request has
    not come yet. (Taken from whichever sequence parks the most, every
    sequence that ever parked kept an equal share for good: the remains of
    warm-up requests held theirs, and a running request's document end was
    gone before its second request came: PERF.md section 6, PR 59.) With
    no finished sequence parking, the running one that parks the most
    gives its oldest. A block a prefix hit has used once is
    **proven**: it is parked apart, oldest first, and taken back only when
    no other parked block is left or the proven ones pass half the pool (a
    session's shared prefix ends where it ended before). That never touches
    the full kind."""

    def __init__(self, num_blocks: int, block_size: int, window: int) -> None:
        if num_blocks < 2:
            raise ValueError("need at least 2 window blocks (0 is reserved)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.window = int(window)
        self.free_list: List[int] = list(range(num_blocks - 1, 0, -1))
        self.ref: Dict[int, int] = {}
        self.partner: Dict[int, int] = {}
        self.by_full: Dict[int, int] = {}
        # sequence -> the blocks it parked, oldest first; running sequences
        # in the order of their first parked block, finished ones behind
        # them in the order they finished
        self.parked: "OrderedDict[str, OrderedDict[int, None]]" = \
            OrderedDict()
        self.parked_by: Dict[int, str] = {}
        # blocks a hit has used, and those of them that are parked
        self.proven: set = set()
        self.parked_proven: "OrderedDict[int, None]" = OrderedDict()
        self.seq_blocks: Dict[str, List[int]] = {}
        # sequence -> the blocks that hold its prompt
        self.prompt_blocks: Dict[str, int] = {}

    def first_needed(self, tokens: int) -> int:
        """The first logical block a query at position ``tokens`` (the next
        one after ``tokens`` cached) still sees: keys past ``tokens -
        window``."""
        return max(tokens - self.window + 1, 0) // self.block_size

    def deepest_hit(self, cached: Sequence[int]) -> int:
        """The most leading blocks of a full-kind match that the window
        kind can back: the deepest ``d`` whose last window's blocks are all
        findable."""
        have = [0]
        for bid in cached:
            have.append(have[-1] + (bid in self.by_full))
        for d in range(len(cached), 0, -1):
            first = self.first_needed(d * self.block_size)
            if have[d] - have[first] == d - first:
                return d
        return 0

    def _unpark(self, wid: int) -> bool:
        if self.parked_proven.pop(wid, False) is None:
            return True
        owner = self.parked_by.pop(wid, None)
        if owner is None:
            return False
        mine = self.parked[owner]
        del mine[wid]
        if not mine:
            del self.parked[owner]
        return True

    def adopt(self, seq_id: str, cached: Sequence[int],
              prompt_blocks: int) -> None:
        """A new sequence's chain: the last window of its hit, shared."""
        self.prompt_blocks[seq_id] = prompt_blocks
        first = self.first_needed(len(cached) * self.block_size)
        chain = [0] * min(first, len(cached))
        for bid in cached[first:]:
            wid = self.by_full[bid]
            if self._unpark(wid):
                self.ref[wid] = 1
            else:
                self.ref[wid] += 1
            self.proven.add(wid)
            chain.append(wid)
        self.seq_blocks[seq_id] = chain

    def finish(self, seq_id: str) -> None:
        """The sequence lets go of all it holds, and is idle from here."""
        for wid in self.seq_blocks.pop(seq_id):
            if wid:
                self.drop(wid, seq_id)
        del self.prompt_blocks[seq_id]
        if seq_id in self.parked:
            self.parked.move_to_end(seq_id)

    def evict(self, wid: int, stats: "KVCacheStats") -> int:
        """Take a parked block back. Its full-kind partner stays as it is:
        the next lookup that needs this block's window finds it gone and is
        cut back."""
        self._unpark(wid)
        self.proven.discard(wid)
        self.by_full.pop(self.partner.pop(wid), None)
        stats.window_blocks_evicted += 1
        return wid

    def evict_one(self, stats: "KVCacheStats",
                  seq_id: Optional[str] = None) -> int:
        """Take back one parked block (for ``seq_id``, where one asks)."""
        if self.parked_proven and (
                not self.parked
                or len(self.parked_proven) > self.num_blocks // 2):
            return self.evict(next(iter(self.parked_proven)), stats)
        if not self.parked:
            raise OutOfBlocksError(
                "window-kind KV pool exhausted: 0 free, 0 parked, all "
                "others held by active sequences")
        # (``max`` keeps the first of equals: the sequence that parked first)
        victim = max(self.parked.values(), key=len)
        if self.parked.get(seq_id) is not victim:
            victim = next((blocks for sid, blocks in self.parked.items()
                           if sid not in self.seq_blocks), victim)
        return self.evict(next(iter(victim)), stats)

    def pop_block(self, stats: "KVCacheStats", seq_id: str) -> int:
        return self.free_list.pop() if self.free_list \
            else self.evict_one(stats, seq_id)

    def extend(self, seq_id: str, full_chain: Sequence[int], upto: int,
               stats: "KVCacheStats") -> List[int]:
        """Blocks for every position below ``upto`` -> the new ones. All or
        nothing: exhaustion gives back what this call took."""
        chain = self.seq_blocks[seq_id]
        need = -(-upto // self.block_size)
        added: List[int] = []
        try:
            while len(chain) + len(added) < need:
                added.append(self.pop_block(stats, seq_id))
        except OutOfBlocksError:
            self.free_list.extend(added)
            raise
        for wid in added:
            full = full_chain[len(chain)]
            self.ref[wid] = 1
            if full and full not in self.by_full:
                self.by_full[full], self.partner[wid] = wid, full
            chain.append(wid)
        return added

    def drop(self, wid: int, seq_id: str, retained: bool = True) -> bool:
        """``seq_id`` lets go of a block -> whether it stays findable."""
        self.ref[wid] -= 1
        if self.ref[wid]:
            return True
        del self.ref[wid]
        if retained and wid in self.partner:
            if wid in self.proven:
                self.parked_proven[wid] = None
            else:
                self.parked.setdefault(seq_id, OrderedDict())[wid] = None
                self.parked_by[wid] = seq_id
            return True
        self.proven.discard(wid)
        self.by_full.pop(self.partner.pop(wid, None), None)
        self.free_list.append(wid)
        return False

    def forget_partner(self, full: int) -> None:
        """The full-kind block is gone: what was findable under it is not."""
        wid = self.by_full.pop(full, None)
        if wid is None:
            return
        del self.partner[wid]
        self.proven.discard(wid)
        if self._unpark(wid):
            self.free_list.append(wid)

    def repartner(self, old_full: int, new_full: int) -> None:
        """The chain was indexed under another block of the same tokens:
        what is findable under ``old_full`` moves there, if there is room."""
        wid = self.by_full.get(old_full)
        if wid is None or new_full in self.by_full:
            return
        del self.by_full[old_full]
        self.by_full[new_full], self.partner[wid] = wid, new_full

    @property
    def in_use(self) -> int:
        return len(self.ref)

    @property
    def num_parked(self) -> int:
        return len(self.parked_by) + len(self.parked_proven)


class PagedKVCacheManager:
    """Metadata brain for the device KV pools.

    Responsibilities (reference PagedKVCache:79 + KVCachePool:250 +
    DistributedKVCacheManager:326, unified):

    - allocate/free per-sequence block chains with rollback on exhaustion
    - radix prefix reuse with refcounted sharing + copy-on-write
    - LRU eviction of cached (ref==0) leaf blocks, optional spill to L2/L3
    - emits :class:`PendingDeviceOps` for the engine's jitted pool updates
    """

    def __init__(
        self,
        num_blocks: int,
        block_size: int = KV_BLOCK_TOKENS,
        enable_prefix_cache: bool = True,
        host_store: Optional[HostKVStore] = None,
        remote_store: Optional[RemoteKVStore] = None,
        spill_on_evict: bool = False,
        kv_dtype: Optional[Any] = None,
        state_rows: int = 0,
        window_blocks: int = 0,
        window: Optional[int] = None,
    ) -> None:
        """``window_blocks`` / ``window``: a model of mixed attention kinds
        keeps its sliding layers' pages in a pool of ``window_blocks`` of
        their own (``_WindowPages``); ``num_blocks`` is then the full
        kind's. A live row holds every block of its context in the full
        kind and, in the window kind, the blocks its next queries can still
        see plus what is being written (``extend_window`` before a write,
        ``release_out_of_window`` after); a prefix hit needs both kinds'
        pages (``allocate_sequence``).

        ``state_rows``: rows of the state pool that lies beside the paged
        one (a hybrid model's linear-attention layers: one row a sequence,
        fixed in size, ``bind_state`` / ``free_state``). A sequence's pages
        then hold only part of its past, so a prefix found in the radix
        index is no hit: nothing snapshots the state at a block boundary.

        ``kv_dtype``: the engine's pool dtype — a probe hit must match
        it exactly (a token-keyed store shared across engines must never
        hand a bf16 engine int8 codes, f32 pages to a bf16 engine, etc.),
        and int8 hits must carry their scale page (spilled as one atomic
        (page, scale) entry). None disables the screen (manager used
        standalone in tests)."""
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_cache = enable_prefix_cache
        self.host_store = host_store
        self.remote_store = remote_store
        self.spill_on_evict = spill_on_evict
        self.kv_dtype = np.dtype(kv_dtype) if kv_dtype is not None else None
        self.quantized_kv = self.kv_dtype == np.int8
        self.state_rows = int(state_rows)
        self.win: Optional[_WindowPages] = None
        if window_blocks:
            if not window or host_store is not None \
                    or remote_store is not None or state_rows:
                raise ValueError(
                    "pages per layer kind need the window and have no "
                    "spill tiers or state rows")
            self.win = _WindowPages(window_blocks, block_size, window)

        # durable-tier immunity (round 19): per-tier circuit breakers +
        # cumulative error/quarantine counters. A tier put/get that raises
        # is counted and SKIPPED — an optional cache tier can never fail
        # eviction or a request (the PR 13 contract) — and a tier failing
        # repeatedly trips open so serving stops paying its latency tax.
        # Counters ride heartbeats (spill_wire_stats → engine_stats
        # ["kv_spill"]) into kv_spill_errors_total / spill_quarantined_
        # total / io_breaker_state on the plane.
        from distributed_gpu_inference_tpu.runtime.io_guard import (
            IOBreaker,
            breaker_env_config,
        )

        bcfg = breaker_env_config()
        self.breakers: Dict[str, Any] = {}
        if not bcfg["disabled"]:
            for tier in ("host", "remote"):
                self.breakers[tier] = IOBreaker(
                    tier, threshold=bcfg["threshold"],
                    open_s=bcfg["open_s"], jitter=bcfg["jitter"],
                )
        self.spill_io: Dict[str, int] = {
            "host_put_errors": 0, "host_get_errors": 0,
            "remote_put_errors": 0, "remote_get_errors": 0,
            "host_quarantined_corrupt": 0, "remote_quarantined_corrupt": 0,
            "breaker_skips": 0,
        }

        self.metas: Dict[int, KVBlockMeta] = {}
        self.free_list: List[int] = list(range(num_blocks - 1, 0, -1))  # pop() → 1..
        self.cached_lru: "OrderedDict[int, None]" = OrderedDict()  # ref==0, indexed
        self.radix = make_radix_index(block_size)
        self.seq_blocks: Dict[str, List[int]] = {}
        self.seq_tokens: Dict[str, List[int]] = {}
        self.seq_shared_count: Dict[str, int] = {}
        # first logical block not yet window-released, per sequence — keeps
        # release_out_of_window O(1) amortized instead of rescanning the
        # released prefix every decoded token
        self.seq_window_front: Dict[str, int] = {}
        self.stats = KVCacheStats()
        self.pending = PendingDeviceOps()

    # -- core alloc ---------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self.free_list)

    @property
    def num_reclaimable(self) -> int:
        return len(self.free_list) + len(self.cached_lru)

    def _pop_free_block(self) -> int:
        # chaos seam: a fired ``pressure`` rule makes this allocation see a
        # pool with zero free (and zero evictable) blocks — the same
        # OutOfBlocksError a saturated pool raises, so seeded storms drive
        # the engine/batcher preempt → spill → resume path end to end
        if _faults.kv_pressure("kv.block.alloc", num_free=len(self.free_list)):
            raise OutOfBlocksError(
                f"KV pool exhausted (kv_pressure fault injected with "
                f"{len(self.free_list)} actually free)"
            )
        if self.free_list:
            bid = self.free_list.pop()
        else:
            bid = self._evict_one()
        self.metas[bid] = KVBlockMeta(block_id=bid, capacity=self.block_size)
        self.stats.allocated_blocks += 1
        return bid

    def _evict_one(self) -> int:
        """Evict the LRU cached *leaf* block (reference LRU evict :229-238)."""
        is_leaf = self.radix.is_leaf
        bid = next((b for b in self.cached_lru if is_leaf(b)), None)
        if bid is not None:
            self._evict_block(bid)
            return bid
        raise OutOfBlocksError(
            f"KV pool exhausted: 0 free, {len(self.cached_lru)} cached "
            "(all interior), all others pinned by active sequences"
        )

    def clear_cached(self, spill: bool = False) -> int:
        """Drop EVERY reclaimable cached block back to the free list →
        count dropped. For bench sweeps (each measured configuration must
        start cold) and admin cache flushes. ``spill`` False suppresses
        spill-on-evict so a flush doesn't flood the spill tiers with
        pages nobody asked to keep."""
        n = 0
        saved = self.spill_on_evict
        self.spill_on_evict = spill and saved
        try:
            # leaf-at-a-time: parents become leaves as children go
            while self.cached_lru:
                self.free_list.append(self._evict_one())
                n += 1
        finally:
            self.spill_on_evict = saved
        return n

    def _evict_block(self, bid: int) -> None:
        meta = self.metas.pop(bid, None)
        if self.cached_lru.pop(bid, False) is None:
            # was present (values are literal None): keep the gauge honest
            self.stats.cached_blocks -= 1
        if self.spill_on_evict and meta is not None and meta.prefix_hash \
                and (self.host_store is not None
                     or self.remote_store is not None):
            # the block id is about to be reused: the engine pulls the page
            # to host FIRST (downloads run before any write in
            # _apply_pending) and hands it to store_spilled()
            self.pending.downloads.append((bid, meta.prefix_hash))
            self.stats.spills += 1
        self.radix.remove_block(bid)
        if self.win is not None:
            self.win.forget_partner(bid)
        self.stats.evictions += 1

    # -- spill tiers (reference get_or_compute chain, kv_cache.py:389-462) ---

    # -- tier guards (round 19): breaker gating + error isolation ------------

    def _tier_allow(self, tier: str) -> bool:
        """Breaker gate for one tier; an open breaker skips the tier
        entirely (and counts the skip) — no per-op latency tax from a
        browned-out device."""
        br = self.breakers.get(tier)
        if br is None or br.allow():
            return True
        self.spill_io["breaker_skips"] += 1
        return False

    def _tier_result(self, tier: str, ok: bool, op: str) -> None:
        br = self.breakers.get(tier)
        if ok:
            if br is not None:
                br.record_success()
            return
        self.spill_io[f"{tier}_{op}_errors"] += 1
        if br is not None:
            br.record_failure()
            if not br.closed:
                logging.getLogger("dgi_kv_spill").warning(
                    "spill tier %r breaker %s after %s failure",
                    tier, br.state, op,
                )

    def _quarantine(self, tier: str, key: str, reason: str) -> None:
        """A provably bad entry (CRC mismatch, torn frame) is deleted from
        its tier — best-effort: the delete itself failing must not block
        the degraded read path — and counted. Mirrors the handoff
        corrupt-piece contract: poison stays local, requests recompute."""
        store = self.host_store if tier == "host" else self.remote_store
        try:
            delete = getattr(store, "delete", None)
            if delete is not None:
                delete(key)
        except Exception:  # noqa: BLE001 — quarantine is best-effort
            pass
        self.spill_io[f"{tier}_quarantined_{reason}"] += 1

    def spill_wire_stats(self) -> Dict[str, int]:
        """Cumulative spill-IO counters + breaker states for the heartbeat
        ``engine_stats["kv_spill"]`` channel (plane delta-anchors the
        counters; breaker states are gauges)."""
        out = dict(self.spill_io)
        for tier, br in self.breakers.items():
            out[f"breaker_{tier}_state"] = br.state_code
            out[f"breaker_{tier}_trips"] = br.trips
        return out

    def store_spilled(self, key: str, page: np.ndarray,
                      scale_page: Optional[np.ndarray] = None) -> None:
        """Engine callback with the evicted page bytes: L2 host store plus
        write-through to L3 (reference async Redis writeback :506-520).

        ``scale_page`` (int8 pools, [L, 2, Bk, D] bf16): packed WITH the
        page as one atomic entry per block in both tiers — a page without
        its scale is garbage, the pair costs one LRU slot, and there is no
        orphaned-scale state.

        Tier writes are ISOLATED: a raising put is counted and skipped —
        losing a spill is a future miss, never a failed eviction (and
        never a failed request). A tier failing repeatedly trips its
        breaker and is skipped wholesale until a half-open probe heals."""
        if self.host_store is not None and self._tier_allow("host"):
            try:
                self.host_store.put(key, (page, scale_page))
            except Exception:  # noqa: BLE001 — optional tier, never fatal
                self._tier_result("host", False, "put")
            else:
                self._tier_result("host", True, "put")
        if self.remote_store is not None and self._tier_allow("remote"):
            try:
                self.remote_store.put(key, _pack_spill(page, scale_page))
            except Exception:  # noqa: BLE001 — optional tier, never fatal
                self._tier_result("remote", False, "put")
            else:
                self._tier_result("remote", True, "put")

    def _spill_entry_valid(self, page: np.ndarray,
                           scale: Optional[np.ndarray]) -> bool:
        """Screen a probed entry BEFORE adopting (or promoting) it: the
        page dtype must match this engine's pools exactly — a token-keyed
        store shared across engines of different dtypes must degrade to a
        miss, never a silent cast — and int8 entries must carry scales."""
        if self.kv_dtype is not None and page.dtype != self.kv_dtype:
            return False
        if self.quantized_kv and scale is None:
            return False
        return True

    def _probe_spill(
        self, key: str
    ) -> Optional[Tuple[np.ndarray, Optional[np.ndarray]]]:
        """Probe the tiers for a spilled block → (page, scale_page | None),
        or None on miss. An L3 hit is promoted to L2 (reference
        promote-on-hit :447-462) — but only AFTER validation, so a
        known-rejected entry never pollutes the bounded L2.

        Failure semantics (round 19): a RAISING tier get is counted,
        charged to the tier's breaker, and falls through to the next tier;
        a corrupt L3 entry (CRC mismatch / torn frame) is QUARANTINED
        (deleted + counted) and degrades to a miss; a failing promote put
        never discards the successfully fetched page. Nothing in here can
        fail the request that probed."""
        if self.host_store is not None and self._tier_allow("host"):
            entry: Any = None
            try:
                entry = self.host_store.get(key)
            except Exception:  # noqa: BLE001 — fall through to L3
                self._tier_result("host", False, "get")
            else:
                self._tier_result("host", True, "get")
            if entry is not None:
                page, scale = (
                    entry if isinstance(entry, tuple) else (entry, None)
                )
                if self._spill_entry_valid(page, scale):
                    self.stats.l2_hits += 1
                    return page, scale
                return None
        if self.remote_store is not None and self._tier_allow("remote"):
            raw = None
            try:
                raw = self.remote_store.get(key)
            except Exception:  # noqa: BLE001 — degraded tier = miss
                self._tier_result("remote", False, "get")
            else:
                self._tier_result("remote", True, "get")
            if raw is not None:
                try:
                    page, scale = _unpack_spill(raw)
                except Exception:
                    # corrupt entry: quarantine so the NEXT probe doesn't
                    # pay the deserialize-and-fail tax again, then miss
                    self._quarantine("remote", key, "corrupt")
                    return None
                if self._spill_entry_valid(page, scale):
                    self.stats.l3_hits += 1
                    if self.host_store is not None:
                        # promote-on-hit is advisory: a failing host put
                        # must NOT discard the page we already fetched
                        try:
                            self.host_store.put(key, (page, scale))
                        except Exception:  # noqa: BLE001
                            self._tier_result("host", False, "put")
                    return page, scale
        return None

    # -- sequence lifecycle -------------------------------------------------

    def bind_state(self, row: int) -> None:
        """A state row goes to a new sequence. Its first piece starts at
        position 0 (no lookup gives this model cached tokens), which is what
        zeroes the row (``models/kda.py``): counted here, no dispatch."""
        if not 0 <= row < self.state_rows:
            raise ValueError(f"state row {row} outside {self.state_rows}")
        self.stats.state_binds += 1

    def allocate_sequence(self, seq_id: str, token_ids: Sequence[int]) -> Tuple[List[int], int]:
        """Allocate the block chain for a prompt. Returns (block_ids,
        num_cached_tokens) — the first ``num_cached_tokens`` positions already
        hold valid KV from the prefix cache (engine skips recomputing them).

        Rollback on exhaustion (reference KVCachePool:283-313).
        """
        if seq_id in self.seq_blocks:
            raise ValueError(f"sequence {seq_id} already allocated")
        # probe the radix index with the CALLER's representation: a numpy
        # array crosses the native ABI zero-copy (the fast path — engines and
        # tokenizers should pass arrays); only the stored copy is a list
        probe = token_ids
        if isinstance(token_ids, np.ndarray):
            token_ids = token_ids.tolist()  # one C pass, python ints out
        else:
            token_ids = [int(t) for t in token_ids]
        n_tokens = len(token_ids)
        needed_blocks = max(1, -(-n_tokens // self.block_size))

        cached: List[int] = []
        spill_pages: List[Tuple[np.ndarray, Optional[np.ndarray]]] = []
        if self.enable_prefix_cache:
            self.stats.prefix_queries += 1
            self.stats.prefix_total_tokens += n_tokens
            cached = self.radix.match_prefix(probe)
            if self.state_rows and cached:
                # latent pages without the state that belongs to them
                self.stats.prefix_hits_without_state += 1
                cached = []
            # never reuse the *entire* prompt from cache: the last token's
            # logits must be recomputed, so keep at least one token fresh
            while cached and len(cached) * self.block_size >= n_tokens:
                cached.pop()
            if self.win is not None and cached:
                # the hit the window kind can back: its last window's pages
                # must still be findable, or the hit is cut back to the
                # deepest prefix whose are (or to nothing)
                self.stats.prefix_lookups_matched += 1
                keep = self.win.deepest_hit(cached)
                if keep < len(cached):
                    self.stats.prefix_hits_cut_by_window += 1
                    self.stats.prefix_hit_tokens_cut_by_window += (
                        len(cached) - keep) * self.block_size
                    del cached[keep:]
            # L1 miss past this point: probe the spill tiers block-by-block
            # (reference get_or_compute chain) — restored pages re-upload
            # into freshly allocated blocks, same fresh-token rule applies
            if self.host_store is not None or self.remote_store is not None:
                idx = len(cached)
                while (idx + 1) * self.block_size < n_tokens:
                    key = compute_prefix_hash(
                        token_ids, (idx + 1) * self.block_size
                    )
                    hit = self._probe_spill(key)
                    if hit is None:
                        break
                    spill_pages.append(hit)
                    idx += 1
        num_cached_tokens = (len(cached) + len(spill_pages)) * self.block_size
        self.stats.prefix_hit_tokens += num_cached_tokens
        if cached or spill_pages:
            self.stats.l1_hits += len(cached)
        else:
            self.stats.misses += 1

        blocks: List[int] = []
        try:
            for bid in cached:
                meta = self.metas[bid]
                if bid in self.cached_lru:  # revive from cached → active
                    del self.cached_lru[bid]
                    self.stats.cached_blocks -= 1
                    meta.ref_count = 1
                else:
                    meta.incref()
                meta.touch()
                blocks.append(bid)
            for page, scale_page in spill_pages:
                bid = self._pop_free_block()
                self.pending.uploads.append((bid, page))
                if scale_page is not None:
                    self.pending.scale_uploads.append((bid, scale_page))
                blocks.append(bid)
            for _ in range(needed_blocks - len(blocks)):
                blocks.append(self._pop_free_block())
        except OutOfBlocksError:
            # undo exactly what was done: drop OUR reference only; a block
            # another sequence still holds must never reach the free list.
            # Staged uploads for OUR fresh blocks must not fire either.
            ours = set(blocks) - set(cached)
            if ours:
                self.pending.uploads = [
                    (b, p) for b, p in self.pending.uploads if b not in ours
                ]
                self.pending.scale_uploads = [
                    (b, p) for b, p in self.pending.scale_uploads
                    if b not in ours
                ]
            for bid in blocks:
                if self.metas[bid].decref() == 0:
                    self._deactivate_block(bid)
            raise
        if spill_pages:
            # index the restored chain so concurrent/future requests hit L1
            n_idx = len(cached) + len(spill_pages)
            self.radix.insert(
                token_ids[: n_idx * self.block_size], blocks[:n_idx]
            )
        self.seq_blocks[seq_id] = blocks
        self.seq_tokens[seq_id] = token_ids
        self.seq_shared_count[seq_id] = len(cached) + len(spill_pages)
        if self.win is not None:
            self.win.adopt(seq_id, cached, needed_blocks)
            # the hit's chain starts at its last window: nothing before it
            # is held, as if released
            self.seq_window_front[seq_id] = min(
                self.win.first_needed(num_cached_tokens), len(cached))
        return blocks, num_cached_tokens

    def window_resident_blocks(self, seq_id: str) -> int:
        """Window-kind blocks the sequence holds (pages per layer kind)."""
        if self.win is None:
            return 0
        return len(self.win.seq_blocks[seq_id]) \
            - self.seq_window_front.get(seq_id, 0)

    def extend_window(self, seq_id: str, upto: int) -> List[int]:
        """Pages per layer kind: window-kind blocks for every position below
        ``upto``, before a forward pass writes them (the full kind's were
        allocated with the prompt) -> the new ones. ``OutOfBlocksError``
        leaves the chain as it was. One kind of pages: nothing to do."""
        if self.win is None:
            return []
        return self.win.extend(seq_id, self.seq_blocks[seq_id], upto,
                               self.stats)

    def append_token(self, seq_id: str, token_id: int) -> Optional[int]:
        """Account one generated token; returns a newly allocated block id if
        the sequence crossed a block boundary, else None. Applies CoW if the
        tail block is shared."""
        blocks = self.seq_blocks[seq_id]
        tokens = self.seq_tokens[seq_id]
        pos = len(tokens)
        tokens.append(token_id)
        logical = pos // self.block_size
        if logical >= len(blocks):
            bid = self._pop_free_block()
            blocks.append(bid)
            self.extend_window(seq_id, pos + 1)
            return bid
        if self.win is not None and self.extend_window(seq_id, pos + 1):
            return blocks[logical]
        tail = blocks[logical]
        meta = self.metas[tail]
        if meta.is_shared:
            new_bid = self._pop_free_block()
            meta.decref()
            blocks[logical] = new_bid
            self.pending.copies.append((tail, new_bid))
            self.stats.cow_copies += 1
            return new_bid
        return None

    def reserve_tokens(self, seq_id: str, n: int) -> List[int]:
        """Pre-allocate blocks so the sequence can grow by ``n`` tokens without
        further allocation (required before a multi-step on-device decode scan,
        where the host cannot allocate mid-scan). Also copy-on-writes a shared
        tail block. Returns newly allocated block ids."""
        blocks = self.seq_blocks[seq_id]
        cur = len(self.seq_tokens[seq_id])
        needed = max(1, -(-(cur + n) // self.block_size))
        added: List[int] = []
        try:
            # CoW the block the next token lands in, if shared
            logical = cur // self.block_size
            if logical < len(blocks):
                tail = blocks[logical]
                meta = self.metas[tail]
                if meta.is_shared:
                    new_bid = self._pop_free_block()
                    meta.decref()
                    blocks[logical] = new_bid
                    self.pending.copies.append((tail, new_bid))
                    self.stats.cow_copies += 1
                    added.append(new_bid)
            while len(blocks) < needed:
                bid = self._pop_free_block()
                blocks.append(bid)
                added.append(bid)
            # both kinds together: the caller's ``trim_reserved`` gives back
            # what either took
            added.extend(self.extend_window(seq_id, cur + n))
        except OutOfBlocksError:
            raise
        return added

    def trim_reserved(self, seq_id: str) -> List[int]:
        """Release trailing reserved blocks beyond what the sequence's
        tokens (committed + pending) occupy — the precise rollback after a
        partially rejected speculative verify window. Leaves the sequence
        holding exactly ``ceil(len(seq_tokens)/block_size)`` blocks, i.e.
        the same footprint a never-speculated per-step engine keeps.
        Returns the freed block ids (the engine refreshes its block-table
        mirror; device state never reads the trimmed tail — its positions
        are beyond the committed length)."""
        blocks = self.seq_blocks[seq_id]
        needed = max(1, -(-len(self.seq_tokens[seq_id]) // self.block_size))
        freed: List[int] = []
        while len(blocks) > needed:
            bid = blocks.pop()
            meta = self.metas.get(bid)
            # reserved tail blocks are exclusively owned and unindexed, but
            # go through decref/_deactivate_block so an unexpected share
            # can never be force-freed
            if meta is not None and meta.decref() == 0:
                self._deactivate_block(bid)
            freed.append(bid)
        if self.win is not None:
            chain = self.win.seq_blocks[seq_id]
            while len(chain) > needed:
                freed.append(chain.pop())
                self.win.drop(freed[-1], seq_id, retained=False)
        return freed

    def commit_tokens(self, seq_id: str, token_ids: Sequence[int]) -> None:
        """Record tokens whose KV was written on-device into already-reserved
        blocks (the multi-step decode path's post-scan bookkeeping)."""
        self.seq_tokens[seq_id].extend(int(t) for t in token_ids)
        if (len(self.seq_tokens[seq_id]) + self.block_size - 1) // self.block_size \
                > len(self.seq_blocks[seq_id]):
            raise RuntimeError(
                f"sequence {seq_id} outgrew its reserved blocks — reserve_tokens "
                "must cover the scan horizon"
            )

    def release_out_of_window(self, seq_id: str, window: int) -> List[int]:
        """Sliding-window models (Mistral): free leading blocks every future
        query is past. A query at position p sees keys in (p - window, p];
        the earliest future query is the pending token at position cur - 1
        (``seq_tokens`` counts committed + pending), which still sees key
        cur - window — so only keys ≤ cur - 1 - window are dead. Freed
        logical slots are pinned to the reserved pad block 0 — the attention
        window mask already drops those logical positions, so a pad-block
        read is never visible. Returns the released logical indices (the
        engine zeroes its block-table rows to match).

        This converts mask-only SWA into window-bounded KV memory — the
        rolling-buffer benefit vLLM gets for Mistral, without re-indexing."""
        blocks = self.seq_blocks[seq_id] if self.win is None \
            else self.win.seq_blocks[seq_id]
        cur = len(self.seq_tokens[seq_id])
        released: List[int] = []
        lb = self.seq_window_front.get(seq_id, 0)
        while lb < len(blocks):
            # block lb covers positions [lb*Bk, (lb+1)*Bk); dead iff its last
            # position (lb+1)*Bk - 1 ≤ cur - 1 - window
            if (lb + 1) * self.block_size > cur - window:
                break
            bid = blocks[lb]
            if self.win is not None:
                # pages per layer kind: the block stays findable by its
                # prefix (parked as evictable cache) while its full-kind
                # partner lives and the window pool does not need it
                if not bid:     # before the hit's last window: never held
                    lb += 1
                    continue
                # (what the sequence generated itself: let go for good)
                self.stats.window_blocks_retained += self.win.drop(
                    bid, seq_id, lb < self.win.prompt_blocks[seq_id])
            else:
                meta = self.metas.get(bid)
                if meta is not None and meta.decref() == 0:
                    self._deactivate_block(bid)
            blocks[lb] = 0
            released.append(lb)
            lb += 1
        if released:
            self.seq_window_front[seq_id] = lb
            self.stats.window_released_blocks += len(released)
        return released

    def seed_window_front(self, seq_id: str, front_blocks: int) -> List[int]:
        """Replicate a donor's sliding-window release state on an adopted
        sequence (PD handoff): force-release the leading ``front_blocks``
        logical blocks — decref/free the physical blocks, pin the chain
        entries to pad block 0, and record ``seq_window_front`` so
        ``free_sequence`` keeps the truncated chain out of the radix index
        (ADVICE r1 #1). Returns the released logical indices."""
        blocks = self.seq_blocks[seq_id]
        released: List[int] = []
        lb = self.seq_window_front.get(seq_id, 0)
        while lb < min(front_blocks, len(blocks)):
            bid = blocks[lb]
            if bid != 0:
                meta = self.metas.get(bid)
                if meta is not None and meta.decref() == 0:
                    self._deactivate_block(bid)
            blocks[lb] = 0
            released.append(lb)
            lb += 1
        if lb > self.seq_window_front.get(seq_id, 0):
            self.seq_window_front[seq_id] = lb
        return released

    def free_sequence(self, seq_id: str, cache: bool = True) -> None:
        """Release a sequence's blocks; full blocks are kept as prefix cache
        (ref 0, LRU-ordered) when ``cache=True``."""
        blocks = self.seq_blocks.pop(seq_id)
        tokens = self.seq_tokens.pop(seq_id, [])
        self.seq_shared_count.pop(seq_id, None)
        n_full = len(tokens) // self.block_size
        front = self.seq_window_front.pop(seq_id, 0)
        if self.win is None and (front > 0 or 0 in blocks[:n_full]):
            # window-released leading blocks: the chain is no longer a valid
            # prefix, so it cannot enter the radix index. (Pages per layer
            # kind: the full kind's chain is whole, it is the window kind's
            # that has holes, and a hit asks for its last window alone.)
            cache = False
        if cache and self.enable_prefix_cache and n_full > 0:
            idx_tokens: Sequence[int] = tokens
            if getattr(self.radix, "wants_arrays", False):
                # one bulk conversion → zero-copy across the native ABI
                idx_tokens = np.asarray(tokens, np.int32)
            self.radix.insert(idx_tokens, blocks[:n_full])
            if self.win is not None:
                # where an equal chain was indexed before this one, what is
                # findable under this row's blocks moves to the indexed ones
                indexed = self.radix.match_prefix(idx_tokens)
                for mine, theirs in zip(blocks, indexed):
                    if mine != theirs:
                        self.win.repartner(mine, theirs)
        hashes: Optional[List[str]] = None
        # leaf first: of one chain only its deepest cached block can be
        # evicted, so with the leaf ahead of its ancestors in the LRU
        # ``_evict_one`` meets it at once and not after the whole chain
        # (which blocks go, and in which order, is the same either way)
        for i in range(len(blocks) - 1, -1, -1):
            bid = blocks[i]
            meta = self.metas.get(bid)
            if meta is None:
                continue
            remaining = meta.decref()
            if remaining == 0:
                if cache and self.enable_prefix_cache and i < n_full and \
                        self.radix.contains_block(bid):
                    if hashes is None:
                        hashes = block_prefix_hashes(
                            tokens, self.block_size, n_full)
                    meta.prefix_hash = hashes[i]
                self._deactivate_block(bid)
        if self.win is not None:
            self.win.finish(seq_id)

    def _scrub_pending_for(self, bid: int) -> None:
        """Withdraw staged device ops that reference a block returning to
        the free list: the id can be reallocated before the ops apply, and
        a stale upload/copy would clobber the new owner's pages. Downloads
        are never scrubbed — a spill-on-evict download is the evicted
        page's only copy."""
        p = self.pending
        if p.copies:
            # filter by DESTINATION only: a freed source's page bytes are
            # still intact until the id is reallocated AND rewritten, and
            # the CoW owner needs them — the dst, though, must never be
            # written once it can belong to someone else
            p.copies = [c for c in p.copies if c[1] != bid]
        if p.uploads:
            p.uploads = [u for u in p.uploads if u[0] != bid]
        if p.scale_uploads:
            p.scale_uploads = [u for u in p.scale_uploads if u[0] != bid]

    def _deactivate_block(self, bid: int) -> None:
        """A block whose refcount just hit 0: park it as reusable cache if the
        radix still indexes it (interior nodes CANNOT be freed — descendant
        chains would dangle and match_prefix would hand out a freed id);
        otherwise return it to the free list."""
        if self.radix.contains_block(bid):
            self.cached_lru[bid] = None
            self.cached_lru.move_to_end(bid)
            self.stats.cached_blocks += 1
        else:
            self.metas.pop(bid, None)
            self._scrub_pending_for(bid)
            self.free_list.append(bid)
            if self.win is not None:
                self.win.forget_partner(bid)

    def _release_block(self, bid: int) -> None:
        """Force-free a block KNOWN to be unreferenced and unindexed."""
        self.metas.pop(bid, None)
        self.cached_lru.pop(bid, None)
        if self.radix.contains_block(bid):
            if not self.radix.is_leaf(bid):
                raise ValueError(
                    f"refusing to force-free interior radix block {bid}"
                )
            self.radix.remove_block(bid)
        self._scrub_pending_for(bid)
        self.free_list.append(bid)
        if self.win is not None:
            self.win.forget_partner(bid)

    # -- engine handshake ---------------------------------------------------

    def take_pending_ops(self) -> PendingDeviceOps:
        ops, self.pending = self.pending, PendingDeviceOps()
        return ops

    def block_table_for(self, seq_id: str, max_blocks: int, pad: int = 0) -> np.ndarray:
        blocks = self.seq_blocks[seq_id]
        if len(blocks) > max_blocks:
            raise ValueError(
                f"sequence {seq_id} uses {len(blocks)} blocks > table width {max_blocks}"
            )
        if self.win is not None:
            # a block table a layer kind, side by side in one row
            table = np.full((2 * max_blocks,), pad, dtype=np.int32)
            table[: len(blocks)] = blocks
            chain = self.win.seq_blocks[seq_id]
            table[max_blocks: max_blocks + len(chain)] = chain
            return table
        table = np.full((max_blocks,), pad, dtype=np.int32)
        table[: len(blocks)] = blocks
        return table

    def get_stats(self) -> Dict[str, Any]:
        self.stats.free_blocks = len(self.free_list)
        self.stats.blocks_in_use = \
            self.num_blocks - 1 - len(self.free_list) - len(self.cached_lru)
        if self.win is not None:
            self.stats.window_blocks_in_use = self.win.in_use
        return self.stats.as_dict()
