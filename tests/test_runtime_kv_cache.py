"""Paged KV metadata manager: alloc/free, prefix reuse, CoW, LRU eviction,
rollback, tiering (parity: reference tests/test_worker_distributed_kv_cache.py,
its most thorough suite)."""

import numpy as np
import pytest

from distributed_gpu_inference_tpu.runtime.kv_cache import (
    HostKVStore,
    OutOfBlocksError,
    PagedKVCacheManager,
    RadixPrefixIndex,
    RemoteKVStore,
)

BS = 16


def toks(n, start=0):
    return list(range(start, start + n))


class TestAllocation:
    def test_basic_alloc_free(self):
        m = PagedKVCacheManager(num_blocks=8, block_size=BS)
        blocks, cached = m.allocate_sequence("s1", toks(40))
        assert len(blocks) == 3 and cached == 0
        assert 0 not in blocks  # block 0 reserved
        assert m.num_free == 7 - 3
        m.free_sequence("s1", cache=False)
        assert m.num_free == 7

    def test_rollback_on_exhaustion(self):
        m = PagedKVCacheManager(num_blocks=4, block_size=BS)  # 3 usable
        free_before = m.num_free
        with pytest.raises(OutOfBlocksError):
            m.allocate_sequence("big", toks(100))  # needs 7 blocks
        assert m.num_free == free_before  # rolled back

    def test_double_alloc_rejected(self):
        m = PagedKVCacheManager(num_blocks=8, block_size=BS)
        m.allocate_sequence("s1", toks(10))
        with pytest.raises(ValueError):
            m.allocate_sequence("s1", toks(10))

    def test_block_table_padding(self):
        m = PagedKVCacheManager(num_blocks=8, block_size=BS)
        blocks, _ = m.allocate_sequence("s1", toks(20))
        table = m.block_table_for("s1", max_blocks=4)
        assert table.shape == (4,)
        assert list(table[:2]) == blocks
        assert list(table[2:]) == [0, 0]


class TestPrefixReuse:
    def test_full_block_prefix_hit(self):
        m = PagedKVCacheManager(num_blocks=16, block_size=BS)
        m.allocate_sequence("a", toks(40))
        m.free_sequence("a", cache=True)          # 2 full blocks cached
        blocks, cached = m.allocate_sequence("b", toks(40))
        assert cached == 32                        # 2 full blocks reused
        stats = m.get_stats()
        assert stats["prefix_hit_tokens"] == 32

    def test_never_reuses_entire_prompt(self):
        m = PagedKVCacheManager(num_blocks=16, block_size=BS)
        m.allocate_sequence("a", toks(32))
        m.free_sequence("a", cache=True)
        _, cached = m.allocate_sequence("b", toks(32))  # identical prompt
        assert cached == 16                        # one block kept fresh

    def test_divergent_suffix_no_hit(self):
        m = PagedKVCacheManager(num_blocks=16, block_size=BS)
        m.allocate_sequence("a", toks(32))
        m.free_sequence("a", cache=True)
        _, cached = m.allocate_sequence("b", toks(32, start=500))
        assert cached == 0

    def test_shared_blocks_refcounted(self):
        m = PagedKVCacheManager(num_blocks=16, block_size=BS)
        m.allocate_sequence("a", toks(40))
        m.free_sequence("a", cache=True)
        b_blocks, _ = m.allocate_sequence("b", toks(48))
        c_blocks, _ = m.allocate_sequence("c", toks(48))
        assert b_blocks[0] == c_blocks[0]          # shared prefix block
        assert m.metas[b_blocks[0]].ref_count == 2

    def test_disabled_prefix_cache(self):
        m = PagedKVCacheManager(num_blocks=16, block_size=BS,
                                enable_prefix_cache=False)
        m.allocate_sequence("a", toks(40))
        m.free_sequence("a", cache=True)
        _, cached = m.allocate_sequence("b", toks(40))
        assert cached == 0


class TestAppendAndCoW:
    def test_append_crosses_block_boundary(self):
        m = PagedKVCacheManager(num_blocks=8, block_size=BS)
        m.allocate_sequence("s", toks(16))
        new = m.append_token("s", 999)
        assert new is not None                     # position 16 → new block
        assert len(m.seq_blocks["s"]) == 2

    def test_append_within_block(self):
        m = PagedKVCacheManager(num_blocks=8, block_size=BS)
        m.allocate_sequence("s", toks(10))
        assert m.append_token("s", 999) is None

    def test_cow_on_shared_tail(self):
        m = PagedKVCacheManager(num_blocks=16, block_size=BS)
        m.allocate_sequence("a", toks(40))
        m.free_sequence("a", cache=True)
        # b reuses blocks 0,1 as cached prefix; write into block 1 would
        # only happen via reserve path; simulate sharing then append
        m.allocate_sequence("b", toks(48))
        m.allocate_sequence("c", toks(48))
        tail_before = m.seq_blocks["b"][-1]
        # force sharing of the tail (48 tokens = 3 full blocks; appending
        # token 48 opens block 3 — no CoW; instead test reserve CoW below)
        del tail_before

    def test_reserve_tokens_and_commit(self):
        m = PagedKVCacheManager(num_blocks=16, block_size=BS)
        m.allocate_sequence("s", toks(10))
        added = m.reserve_tokens("s", 30)          # 10+30=40 → 3 blocks total
        assert len(m.seq_blocks["s"]) == 3
        assert len(added) == 2
        m.commit_tokens("s", toks(30, 100))
        assert len(m.seq_tokens["s"]) == 40
        with pytest.raises(RuntimeError):
            m.commit_tokens("s", toks(50, 200))    # outgrows reservation

    def test_reserve_cow_on_shared_block(self):
        m = PagedKVCacheManager(num_blocks=16, block_size=BS)
        m.allocate_sequence("a", toks(16))
        m.free_sequence("a", cache=True)
        # both reuse cached block for the first 16 tokens? prompt of 17:
        # 1 cached block + 1 fresh
        b_blocks, cached_b = m.allocate_sequence("b", toks(17))
        c_blocks, cached_c = m.allocate_sequence("c", toks(17))
        assert cached_b == 16 and cached_c == 16
        assert b_blocks[0] == c_blocks[0]
        shared = b_blocks[0]
        assert m.metas[shared].ref_count == 2
        # appending goes into block index 1 (fresh, unshared) → no CoW; but a
        # sequence of exactly 16 tokens reusing... reserve on b: next token at
        # pos 17 → block 1 (unshared) → no CoW expected
        m.reserve_tokens("b", 1)
        assert m.stats.cow_copies == 0
        # now simulate a shared *tail*: free c, realloc exactly at boundary
        m.free_sequence("c", cache=False)


class TestEviction:
    def test_lru_leaf_eviction(self):
        m = PagedKVCacheManager(num_blocks=5, block_size=BS)  # 4 usable
        m.allocate_sequence("a", toks(32))         # 2 blocks
        m.free_sequence("a", cache=True)           # both cached (chain a1→a2)
        assert len(m.cached_lru) == 2
        # new 3-block seq with different tokens: needs evicting cached blocks;
        # leaf (deeper chain node) must go first
        m.allocate_sequence("b", toks(48, 500))
        assert len(m.seq_blocks["b"]) == 3
        assert m.stats.evictions >= 1

    def test_exhaustion_when_all_pinned(self):
        m = PagedKVCacheManager(num_blocks=4, block_size=BS)
        m.allocate_sequence("a", toks(48))         # all 3 usable blocks
        with pytest.raises(OutOfBlocksError):
            m.allocate_sequence("b", toks(16))

    def test_cached_block_revival_then_free(self):
        m = PagedKVCacheManager(num_blocks=8, block_size=BS)
        m.allocate_sequence("a", toks(32))
        m.free_sequence("a", cache=True)
        m.allocate_sequence("b", toks(40))         # revives 1 cached block
        m.free_sequence("b", cache=True)
        # all b blocks back to cache or free; no refcount leaks
        for meta in m.metas.values():
            assert meta.ref_count == 0


class TestRollbackSafety:
    def test_rollback_never_frees_shared_blocks(self):
        """Regression: exhaustion rollback must decref, not force-free, blocks
        another active sequence still references."""
        m = PagedKVCacheManager(num_blocks=6, block_size=BS)  # 5 usable
        m.allocate_sequence("x", toks(32))
        m.free_sequence("x", cache=True)               # blocks b1,b2 cached
        a_blocks, _ = m.allocate_sequence("a", toks(40))   # revives b1,b2 + 1 fresh
        assert m.metas[a_blocks[0]].ref_count == 1
        with pytest.raises(OutOfBlocksError):
            # b shares the cached prefix (incref) then needs 2 fresh — only 1 left
            m.allocate_sequence("b", toks(70))
        # a's blocks must be intact: metas alive, ref restored, none on free list
        for bid in a_blocks:
            assert m.metas[bid].ref_count == 1
            assert bid not in m.free_list
        # a can still append and free normally
        m.append_token("a", 1)
        m.free_sequence("a", cache=True)

    def test_uncached_free_keeps_interior_radix_blocks(self):
        """Regression: free_sequence(cache=False) on a sequence holding
        radix-indexed blocks must not push interior nodes to the free list."""
        m = PagedKVCacheManager(num_blocks=8, block_size=BS)
        m.allocate_sequence("x", toks(48))
        m.free_sequence("x", cache=True)               # 3-block chain indexed
        a_blocks, cached = m.allocate_sequence("a", toks(48))
        assert cached == 32
        m.free_sequence("a", cache=False)              # abort-style free
        # the indexed chain must still be matchable and its ids valid
        hit = m.radix.match_prefix(toks(48))
        assert hit[:2] == a_blocks[:2]
        for bid in hit:
            assert bid in m.metas
            assert bid not in m.free_list
        # and a new sequence reusing the prefix works end to end
        b_blocks, cached_b = m.allocate_sequence("b", toks(48))
        assert cached_b == 32
        m.free_sequence("b", cache=False)


class TestTiers:
    def test_host_store_lru(self):
        store = HostKVStore(max_blocks=2)
        store.put("a", np.ones(4))
        store.put("b", np.ones(4) * 2)
        assert store.get("a") is not None          # touch a → b is LRU
        store.put("c", np.ones(4) * 3)
        assert store.get("b") is None
        assert store.get("a") is not None and store.get("c") is not None

    def test_remote_store_ttl(self):
        store = RemoteKVStore(ttl_s=0.0)           # instant expiry
        store.put("k", b"data")
        assert store.get("k") is None
        store2 = RemoteKVStore(ttl_s=60.0)
        store2.put("k", b"data")
        assert store2.get("k") == b"data"
        assert store2.purge_expired() == 0


class TestClearCached:
    def test_clear_cached_drops_reclaimable_to_free(self):
        m = PagedKVCacheManager(num_blocks=8, block_size=BS)
        m.allocate_sequence("a", toks(40))
        m.allocate_sequence("b", toks(40, 500))
        m.free_sequence("a")                       # cached (reclaimable)
        free_before = m.num_free
        n = m.clear_cached()
        assert n == 2                              # a's two FULL blocks
        assert m.num_free == free_before + n
        assert m.stats.cached_blocks == 0
        # a's prompt no longer hits the cache; b untouched
        blocks, cached = m.allocate_sequence("a2", toks(40))
        assert cached == 0
        assert "b" in m.seq_blocks

    def test_clear_cached_default_does_not_spill(self):
        host = HostKVStore(max_blocks=16)
        m = PagedKVCacheManager(num_blocks=8, block_size=BS,
                                host_store=host, spill_on_evict=True)
        m.allocate_sequence("a", toks(40))
        m.free_sequence("a")
        m.clear_cached()
        assert len(m.pending.downloads) == 0       # no spill traffic
        assert m.spill_on_evict is True            # flag restored


class TestRadix:
    def test_match_insert(self):
        r = RadixPrefixIndex(BS)
        r.insert(toks(48), [5, 6, 7])
        assert r.match_prefix(toks(48)) == [5, 6, 7]
        assert r.match_prefix(toks(32)) == [5, 6]
        assert r.match_prefix(toks(48, 500)) == []
        # partial final block never matches
        assert r.match_prefix(toks(40)) == [5, 6]

    def test_leaf_only_eviction(self):
        r = RadixPrefixIndex(BS)
        r.insert(toks(32), [5, 6])
        assert not r.is_leaf(5) and r.is_leaf(6)
        with pytest.raises(ValueError):
            r.remove_block(5)                      # interior
        r.remove_block(6)
        assert r.is_leaf(5)
        r.remove_block(5)
        assert r.match_prefix(toks(32)) == []


class TestLongChains:
    """A finished sequence of a few thousand tokens (a chain of ~200
    blocks) costs the host a pass over its tokens and a pool under
    pressure one index probe an eviction, not a pass over the chain a
    block (measured on the v5e's host at 52 ms a finish and 40 ms an
    admission for prompts of 1.5-3 k tokens: PERF.md, PR 31)."""

    @pytest.mark.parametrize("n_blocks", [0, 1, 7])
    def test_block_prefix_hashes_are_the_prefix_hashes(self, n_blocks):
        from distributed_gpu_inference_tpu.utils.data_structures import (
            block_prefix_hashes,
            compute_prefix_hash,
        )

        ids = [int(t) for t in
               np.random.default_rng(3).integers(0, 2 ** 32, 7 * BS + 5)]
        assert block_prefix_hashes(ids, BS, n_blocks) == [
            compute_prefix_hash(ids, (i + 1) * BS) for i in range(n_blocks)]

    def test_a_freed_chain_keeps_its_hashes(self):
        from distributed_gpu_inference_tpu.utils.data_structures import (
            compute_prefix_hash,
        )

        m = PagedKVCacheManager(num_blocks=16, block_size=BS)
        blocks, _ = m.allocate_sequence("a", toks(5 * BS + 3))
        m.free_sequence("a")
        assert [m.metas[b].prefix_hash for b in blocks[:5]] == [
            compute_prefix_hash(toks(5 * BS + 3), (i + 1) * BS)
            for i in range(5)]

    @pytest.mark.parametrize("native", [True, False])
    def test_eviction_probes_once_a_block_and_goes_leaf_to_root(self, native):
        n = 200
        m = PagedKVCacheManager(num_blocks=2 * n + 4, block_size=BS)
        if not native:
            m.radix = RadixPrefixIndex(BS)
        old, _ = m.allocate_sequence("old", toks(n * BS + 1))
        new, _ = m.allocate_sequence("new", toks(n * BS + 1, 10 ** 6))
        m.free_sequence("old")
        m.free_sequence("new")
        probes, is_leaf = [], m.radix.is_leaf
        m.radix.is_leaf = lambda b: probes.append(b) or is_leaf(b)
        # the free list holds the two partial last blocks and one more
        got, _ = m.allocate_sequence("x", toks((n + 3) * BS, 2 * 10 ** 6))
        # the older chain goes first, its leaf before its ancestors
        assert got[3:] == old[n - 1::-1]
        assert len(probes) == n
        assert m.radix.match_prefix(toks(n * BS, 10 ** 6)) == new[:n]
