"""Parity tests for the Pallas VMEM-dequant matmul (ops/qmm_pallas.py).

CPU runs the kernel in interpret mode against the XLA convert-on-read
reference (ops/quantization.matmul) — same contract the paged-attention
kernel's parity tests use.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_gpu_inference_tpu.ops import qmm_pallas
from distributed_gpu_inference_tpu.ops.qmm_pallas import (
    pick_tiles,
    qmm_stacked_pallas,
)
from distributed_gpu_inference_tpu.ops.quantization import (
    matmul,
    matmul_stacked,
    quantize_weight,
    split_stacked_quant,
)


def _stacked_quant(key, l, k, n, mode="int8"):
    w = jax.random.normal(key, (l, k, n), jnp.float32) * 0.05
    return quantize_weight(w, mode), w


@pytest.mark.parametrize("m", [1, 16, 32, 100])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_qmm_parity_rows(m, dtype):
    key = jax.random.PRNGKey(0)
    qw, _ = _stacked_quant(key, 1, 256, 256)
    x = (jax.random.normal(jax.random.PRNGKey(1), (m, 256)) * 0.1).astype(dtype)
    got = qmm_stacked_pallas(
        x, qw["qw"], qw["scale"], jnp.int32(0), interpret=True
    )
    want = matmul(x, {"qw": qw["qw"][0], "scale": qw["scale"][0]})
    assert got.shape == (m, 256)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_qmm_layer_index_selects_layer():
    key = jax.random.PRNGKey(2)
    qw, _ = _stacked_quant(key, 3, 128, 128)
    x = jnp.asarray(
        jax.random.normal(jax.random.PRNGKey(3), (16, 128)) * 0.1, jnp.bfloat16
    )
    for idx in range(3):
        got = qmm_stacked_pallas(
            x, qw["qw"], qw["scale"], jnp.int32(idx), interpret=True
        )
        want = matmul(
            x, {"qw": qw["qw"][idx], "scale": qw["scale"][idx]}
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2,
        )


# every (K, N) the one-chip cells send through the kernel (ISSUE 38)
CELL_SHAPES = {
    "qwen2.5-7b": [(3584, 3584), (3584, 512), (3584, 18944), (18944, 3584)],
    "mistral-7b": [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)],
    "olmoe-1b-7b": [(2048, 2048)],
    "openpangu-ultra-moe-718b-ep16": [
        (7680, 1536), (1536, 24576), (16384, 7680), (7680, 2048),
        (2048, 7680), (7680, 18432), (18432, 7680),
    ],
}
_MB = 1024 * 1024


def _tiles_128(dim):
    return [d for d in range(128, dim + 1, 128) if dim % d == 0]


@pytest.mark.parametrize(
    "k,n", [kn for shapes in CELL_SHAPES.values() for kn in shapes]
)
def test_block_rule_on_cell_shapes(k, n):
    """The block divides K and N, both of its dimensions are multiples of
    128 (K is the activation block's lane dimension, N the weight's), it is
    inside the byte budget, its column tile is the widest the shape has up
    to the cap, and it is under 1 MB only where no longer block of that
    column tile fits the budget (18944 x 3584: 512 x 1792, that shape's
    best reading on the chip, where the next contraction tile is 4736)."""
    bk, bn = pick_tiles(k, n)
    assert k % bk == 0 and n % bn == 0
    assert bk % 128 == 0 and bn % 128 == 0
    assert bk * bn <= qmm_pallas._BLOCK_BYTES
    assert bn == max(b for b in _tiles_128(n) if b <= qmm_pallas._BN_MAX)
    if bk * bn < _MB:
        longer = [a for a in _tiles_128(k)
                  if bk < a and a * bn <= qmm_pallas._BLOCK_BYTES]
        assert not longer, (bk, bn, longer[-1])
    # a two-byte weight takes half the rows of the same budget
    bk2, bn2 = pick_tiles(k, n, 2)
    assert k % bk2 == 0 and bk2 * bn2 * 2 <= qmm_pallas._BLOCK_BYTES


# K that is not a power of two: 7 x 128 and 37 x 128 stand in for Qwen's
# 3584 and 18944, as one block (num_k 1) and, long enough to pass the
# budget at the narrowest column tile, as several
@pytest.mark.parametrize("k,n,one_block", [
    (7 * 128, 512, True), (37 * 128, 256, True),
    (7 * 128 * 32, 128, False), (37 * 128 * 8, 128, False),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_qmm_parity_odd_k(k, n, one_block, dtype):
    assert (k == pick_tiles(k, n)[0]) == one_block
    qw, _ = _stacked_quant(jax.random.PRNGKey(4), 1, k, n)
    x = (jax.random.normal(jax.random.PRNGKey(5), (8, k)) * 0.05).astype(dtype)
    got = qmm_stacked_pallas(
        x, qw["qw"], qw["scale"], jnp.int32(0), interpret=True
    )
    want = matmul(
        x, {"qw": qw["qw"][0], "scale": qw["scale"][0]}, pallas=False
    )
    assert got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_qmm_multi_k_tiles_accumulate():
    # the accumulator across K tiles: 40960 x 128 is past the block budget
    # at the narrowest column tile
    key = jax.random.PRNGKey(4)
    k = 40960
    qw, _ = _stacked_quant(key, 1, k, 128)
    assert k // pick_tiles(k, 128)[0] > 1
    x = jnp.asarray(
        jax.random.normal(jax.random.PRNGKey(5), (8, k)) * 0.05,
        jnp.bfloat16,
    )
    got = qmm_stacked_pallas(
        x, qw["qw"], qw["scale"], jnp.int32(0), interpret=True
    )
    want = matmul(x, {"qw": qw["qw"][0], "scale": qw["scale"][0]})
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_qmm_fp8_storage():
    key = jax.random.PRNGKey(6)
    qw, _ = _stacked_quant(key, 1, 128, 128, mode="fp8")
    x = jnp.asarray(
        jax.random.normal(jax.random.PRNGKey(7), (16, 128)) * 0.1, jnp.bfloat16
    )
    got = qmm_stacked_pallas(
        x, qw["qw"], qw["scale"], jnp.int32(0), interpret=True
    )
    want = matmul(x, {"qw": qw["qw"][0], "scale": qw["scale"][0]})
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=4e-2, atol=4e-2,
    )


def test_pick_tiles_untileable():
    assert pick_tiles(100, 256) is None
    assert pick_tiles(256, 100) is None
    assert pick_tiles(7680, 576) is None     # openPangu's wkv_a: XLA's path
    assert pick_tiles(14336, 4096) == (1024, 2048)


def test_split_stacked_quant_partition():
    key = jax.random.PRNGKey(8)
    layers = {
        "attn_norm": jnp.ones((2, 8)),
        "wq": quantize_weight(
            jax.random.normal(key, (2, 8, 8)), "int8"
        ),
        "wo": jax.random.normal(key, (2, 8, 8)),  # NOT quantized → scanned
    }
    scanned, stacked = split_stacked_quant(layers)
    assert set(stacked) == {"wq"}
    assert set(scanned) == {"attn_norm", "wo"}
    # nothing quantized → identity, no split
    s2, st2 = split_stacked_quant({"attn_norm": layers["attn_norm"]})
    assert st2 is None and set(s2) == {"attn_norm"}


def test_matmul_stacked_xla_fallback_matches():
    # on CPU the pallas gate is off: matmul_stacked must slice + match the
    # plain path bit-for-bit
    key = jax.random.PRNGKey(9)
    qw, _ = _stacked_quant(key, 4, 64, 48)  # untileable on purpose
    x = jax.random.normal(jax.random.PRNGKey(10), (3, 5, 64))
    got = matmul_stacked(x, qw, jnp.int32(2))
    want = matmul(x, {"qw": qw["qw"][2], "scale": qw["scale"][2]})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
