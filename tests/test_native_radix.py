"""Native C++ radix index: build, exact parity with the Python index.

The native component must be a DROP-IN for ``RadixPrefixIndex`` — same
results on identical operation sequences, including interior-eviction
refusal. Fuzzed against the Python implementation.
"""

import random

import pytest

from distributed_gpu_inference_tpu.native import native_available
from distributed_gpu_inference_tpu.runtime.kv_cache import (
    RadixPrefixIndex,
    make_radix_index,
)

needs_native = pytest.mark.skipif(
    not native_available(), reason="native toolchain unavailable"
)


def test_factory_returns_some_index():
    idx = make_radix_index(16)
    assert idx.block_size == 16
    assert idx.match_prefix([1] * 16) == []


def test_factory_fallback_forced(monkeypatch):
    idx = make_radix_index(16, prefer_native=False)
    assert isinstance(idx, RadixPrefixIndex)


@needs_native
def test_library_is_built_from_this_source(tmp_path, monkeypatch):
    """The library is named by the hash of the source it was built from, so
    a build left in the (git-ignored) directory by another checkout is
    never loaded in its place."""
    import hashlib

    from distributed_gpu_inference_tpu import native

    monkeypatch.setattr(native, "_BUILD_DIR", tmp_path)
    (tmp_path / "libtpu_native.so").write_bytes(b"not this source")
    lib = native._load_locked()
    assert lib is not None and lib.radix_new(4)
    src = native._SRC_DIR / "radix_index.cpp"
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert (tmp_path / f"libtpu_native-{digest}.so").exists()


@needs_native
def test_native_builds_and_loads():
    from distributed_gpu_inference_tpu.native.radix import (
        NativeRadixPrefixIndex,
    )

    idx = NativeRadixPrefixIndex(4)
    assert len(idx) == 0
    assert idx.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11]) == 2
    assert len(idx) == 2
    assert idx.match_prefix([1, 2, 3, 4, 5, 6, 7, 8, 9]) == [10, 11]
    assert idx.match_prefix([1, 2, 3, 4, 9, 9, 9, 9]) == [10]
    assert idx.match_prefix([9, 9, 9, 9]) == []
    assert idx.contains_block(10) and idx.contains_block(11)
    assert idx.is_leaf(11) and not idx.is_leaf(10)
    with pytest.raises(ValueError, match="interior"):
        idx.remove_block(10)
    idx.remove_block(11)
    assert not idx.contains_block(11)
    assert idx.is_leaf(10)
    idx.remove_block(99)  # absent: no-op


@needs_native
def test_native_partial_blocks_never_shared():
    from distributed_gpu_inference_tpu.native.radix import (
        NativeRadixPrefixIndex,
    )

    idx = NativeRadixPrefixIndex(4)
    # 6 tokens = 1 full block; the partial tail is not indexed
    assert idx.insert([1, 2, 3, 4, 5, 6], [20, 21]) == 1
    assert idx.match_prefix([1, 2, 3, 4, 5, 6]) == [20]


@needs_native
def test_native_matches_python_fuzz():
    """Identical op sequences must produce identical results."""
    from distributed_gpu_inference_tpu.native.radix import (
        NativeRadixPrefixIndex,
    )

    rng = random.Random(7)
    bs = 4
    py = RadixPrefixIndex(bs)
    cc = NativeRadixPrefixIndex(bs)
    next_block = [1]
    inserted = []

    for step in range(400):
        op = rng.random()
        n_tok = rng.randrange(0, 8 * bs)
        toks = [rng.randrange(0, 9) for _ in range(n_tok)]
        if op < 0.45:
            m_py = py.match_prefix(toks)
            m_cc = cc.match_prefix(toks)
            assert m_py == m_cc, f"step {step}: match diverged"
        elif op < 0.8:
            n_full = n_tok // bs
            blocks = [next_block[0] + i for i in range(n_full)]
            next_block[0] += n_full
            a_py = py.insert(toks, blocks)
            a_cc = cc.insert(toks, blocks)
            assert a_py == a_cc, f"step {step}: insert count diverged"
            inserted.extend(blocks)
        elif inserted:
            bid = rng.choice(inserted)
            assert py.contains_block(bid) == cc.contains_block(bid)
            assert py.is_leaf(bid) == cc.is_leaf(bid)
            err_py = err_cc = False
            try:
                py.remove_block(bid)
            except ValueError:
                err_py = True
            try:
                cc.remove_block(bid)
            except ValueError:
                err_cc = True
            assert err_py == err_cc, f"step {step}: remove behavior diverged"
            assert py.contains_block(bid) == cc.contains_block(bid)
    assert len(py) == len(cc)


@needs_native
def test_manager_works_with_native_index():
    """PagedKVCacheManager's full sequence lifecycle over the C++ index."""
    from distributed_gpu_inference_tpu.runtime.kv_cache import (
        PagedKVCacheManager,
    )
    from distributed_gpu_inference_tpu.native.radix import (
        NativeRadixPrefixIndex,
    )

    mgr = PagedKVCacheManager(32, block_size=4)
    assert isinstance(mgr.radix, NativeRadixPrefixIndex)
    blocks, cached = mgr.allocate_sequence("a", list(range(10)))
    assert cached == 0 and len(blocks) == 3
    mgr.free_sequence("a", cache=True)
    # same prefix → cache hit on the full blocks
    blocks2, cached2 = mgr.allocate_sequence("b", list(range(10)))
    assert cached2 == 8
    assert blocks2[:2] == blocks[:2]
    mgr.free_sequence("b", cache=False)
