"""Pallas paged-attention decode kernel vs the XLA oracle (interpret mode).

The kernel (ops/paged_attention_pallas.py) must match paged_attention_xla
bit-close on every masking case: GQA, partial pages, multi-group contexts,
sliding windows, inactive slots."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# compile-heavy (jit/scan graphs): excluded from the fast CI gate
pytestmark = pytest.mark.slow

from distributed_gpu_inference_tpu.ops.attention import paged_attention_xla
from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
    paged_attention_pallas,
)


def _setup(b, kv_lens, nh, hkv, d, block, m, seed=0):
    """Random pools with each sequence's pages filled up to its kv_len."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    num_blocks = 1 + b * m
    k_pool = jax.random.normal(ks[0], (num_blocks, hkv, block, d), jnp.float32)
    v_pool = jax.random.normal(ks[1], (num_blocks, hkv, block, d), jnp.float32)
    q = jax.random.normal(ks[2], (b, 1, nh, d), jnp.float32)
    tables = np.zeros((b, m), np.int32)
    nxt = 1
    for i in range(b):
        tables[i] = np.arange(nxt, nxt + m)
        nxt += m
    lens = np.asarray(kv_lens, np.int32)
    positions = (lens - 1)[:, None].astype(np.int32)
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(positions), jnp.asarray(lens))


def _compare(args, block, window=None, atol=2e-5):
    q, k_pool, v_pool, tables, positions, lens = args
    want = paged_attention_xla(
        q, k_pool, v_pool, tables, positions, lens, block, window=window
    )
    got = paged_attention_pallas(
        q, k_pool, v_pool, tables, positions, lens, block, window=window,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=atol)


def test_basic_decode_partial_page():
    _compare(_setup(2, [9, 23], nh=4, hkv=2, d=64, block=16, m=4), 16)


def test_multi_group_long_context():
    # 300 tokens → 19 pages → 3 page groups (8 pages each)
    _compare(_setup(2, [300, 17], nh=8, hkv=4, d=64, block=16, m=20), 16)


def test_group_boundary_exact():
    # kv_len exactly a group multiple (8 pages * 16 = 128)
    _compare(_setup(1, [128], nh=4, hkv=2, d=64, block=16, m=8), 16)


def test_single_token_context():
    _compare(_setup(1, [1], nh=4, hkv=4, d=64, block=16, m=2), 16)


def test_mqa_single_kv_head():
    _compare(_setup(2, [40, 7], nh=8, hkv=1, d=64, block=16, m=4), 16)


def test_inactive_slot_zero_output():
    args = _setup(3, [12, 0, 5], nh=4, hkv=2, d=64, block=16, m=2)
    q, k_pool, v_pool, tables, positions, lens = args
    got = paged_attention_pallas(
        q, k_pool, v_pool, tables, positions, lens, 16, interpret=True
    )
    assert np.all(np.asarray(got)[1] == 0.0)
    _compare(args, 16)


@pytest.mark.parametrize("window", [4, 16, 100])
def test_sliding_window(window):
    _compare(_setup(2, [150, 30], nh=4, hkv=2, d=64, block=16, m=10), 16,
             window=window)


def test_window_skips_leading_groups():
    """Window smaller than one group: dead leading groups are skipped but
    output still matches the oracle."""
    _compare(_setup(1, [290], nh=4, hkv=2, d=64, block=16, m=20), 16,
             window=32)


def test_head_dim_128():
    _compare(_setup(1, [21], nh=4, hkv=2, d=128, block=16, m=2), 16)


def test_bfloat16_pools():
    q, k_pool, v_pool, tables, positions, lens = _setup(
        2, [33, 60], nh=4, hkv=2, d=64, block=16, m=4
    )
    q = q.astype(jnp.bfloat16)
    k_pool = k_pool.astype(jnp.bfloat16)
    v_pool = v_pool.astype(jnp.bfloat16)
    want = paged_attention_xla(q, k_pool, v_pool, tables, positions, lens, 16)
    got = paged_attention_pallas(q, k_pool, v_pool, tables, positions, lens,
                                 16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_rejects_prefill_shapes():
    q = jnp.zeros((1, 4, 4, 64), jnp.float32)
    k = jnp.zeros((4, 2, 16, 64), jnp.float32)
    with pytest.raises(ValueError, match="decode"):
        paged_attention_pallas(
            q, k, k, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32),
            16, interpret=True,
        )


# ---------------------------------------------------------------------------
# Fused write+attention kernel (round 2): the decode step writes this step's
# K/V rows into their page slots inside the kernel (pools aliased in place).
# Reference = XLA scatter (models.llama._write_kv_pages) + paged_attention_xla
# over the same inputs.
# ---------------------------------------------------------------------------


def _mk_fused_case(seed, b, hkv, qpk, d, bs, m, n_layers, nblocks, lens):
    import numpy as np

    rng = np.random.default_rng(seed)
    nh = hkv * qpk
    q = rng.standard_normal((b, 1, nh, d), dtype=np.float32)
    new_k = rng.standard_normal((b, 1, hkv, d), dtype=np.float32)
    new_v = rng.standard_normal((b, 1, hkv, d), dtype=np.float32)
    k_pool = rng.standard_normal(
        (n_layers, nblocks, hkv, bs, d), dtype=np.float32
    )
    v_pool = rng.standard_normal(
        (n_layers, nblocks, hkv, bs, d), dtype=np.float32
    )
    tables = np.zeros((b, m), np.int32)
    for i in range(b):
        tables[i] = 1 + (np.arange(m) * b + i) % (nblocks - 1)
    lens = np.asarray(lens, np.int32)
    positions = (lens - 1)[:, None].astype(np.int32)  # write pos = len - 1
    return q, new_k, new_v, k_pool, v_pool, tables, positions, lens


@pytest.mark.parametrize("lens", [
    [33, 5, 64, 1],          # mixed short
    [0, 40, 0, 17],          # inactive rows (no write, zero out)
    [64, 64, 64, 64],        # full tables
])
def test_fused_write_attention_parity(lens):
    import numpy as np

    from distributed_gpu_inference_tpu.models.llama import _write_kv_pages
    from distributed_gpu_inference_tpu.ops.attention import paged_attention_xla
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_fused,
    )

    b, hkv, qpk, d, bs, m, L, nblocks = 4, 2, 3, 128, 16, 4, 3, 40
    layer = 1
    q, new_k, new_v, k_pool, v_pool, tables, positions, lens_a = \
        _mk_fused_case(0, b, hkv, qpk, d, bs, m, L, nblocks, lens)

    out, k2, v2 = paged_decode_attention_fused(
        jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v),
        jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.int32(layer),
        jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(lens_a),
        block_size=bs, interpret=True,
    )

    # reference: scatter the rows into the layer slice, then XLA attention
    ref_k = _write_kv_pages(
        jnp.asarray(k_pool[layer]), jnp.asarray(new_k),
        jnp.asarray(tables), jnp.asarray(positions), bs,
    )
    ref_v = _write_kv_pages(
        jnp.asarray(v_pool[layer]), jnp.asarray(new_v),
        jnp.asarray(tables), jnp.asarray(positions), bs,
    )
    ref_out = paged_attention_xla(
        jnp.asarray(q), ref_k, ref_v, jnp.asarray(tables),
        jnp.asarray(positions), jnp.asarray(lens_a), block_size=bs,
    )

    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), rtol=2e-2, atol=2e-2
    )
    # pool side effects: written layer matches the scatter reference bit-for
    # bit on touched pages; other layers untouched
    np.testing.assert_allclose(np.asarray(k2[layer]), np.asarray(ref_k),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2[layer]), np.asarray(ref_v),
                               rtol=1e-6, atol=1e-6)
    for other in (0, 2):
        np.testing.assert_array_equal(
            np.asarray(k2[other]), k_pool[other]
        )


def test_fused_write_respects_window():
    import numpy as np

    from distributed_gpu_inference_tpu.models.llama import _write_kv_pages
    from distributed_gpu_inference_tpu.ops.attention import paged_attention_xla
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_fused,
    )

    b, hkv, qpk, d, bs, m, L, nblocks = 2, 2, 2, 128, 16, 6, 1, 30
    q, new_k, new_v, k_pool, v_pool, tables, positions, lens_a = \
        _mk_fused_case(3, b, hkv, qpk, d, bs, m, L, nblocks, [80, 41])

    out, k2, v2 = paged_decode_attention_fused(
        jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v),
        jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.int32(0),
        jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(lens_a),
        block_size=bs, window=32, interpret=True,
    )
    ref_k = _write_kv_pages(
        jnp.asarray(k_pool[0]), jnp.asarray(new_k),
        jnp.asarray(tables), jnp.asarray(positions), bs,
    )
    ref_v = _write_kv_pages(
        jnp.asarray(v_pool[0]), jnp.asarray(new_v),
        jnp.asarray(tables), jnp.asarray(positions), bs,
    )
    ref_out = paged_attention_xla(
        jnp.asarray(q), ref_k, ref_v, jnp.asarray(tables),
        jnp.asarray(positions), jnp.asarray(lens_a), block_size=bs,
        window=32,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), rtol=2e-2, atol=2e-2
    )


def test_fp8_pools_parity():
    """fp8 KV pools (kv_cache_dtype="fp8"): kernel upcasts pages to bf16 in
    VMEM; parity vs the XLA path over the SAME fp8-rounded values."""
    q, k_pool, v_pool, tables, positions, lens = _setup(
        2, [33, 60], nh=4, hkv=2, d=64, block=16, m=4
    )
    q = q.astype(jnp.bfloat16)
    k_pool = k_pool.astype(jnp.float8_e4m3fn)
    v_pool = v_pool.astype(jnp.float8_e4m3fn)
    want = paged_attention_xla(q, k_pool, v_pool, tables, positions, lens, 16)
    got = paged_attention_pallas(q, k_pool, v_pool, tables, positions, lens,
                                 16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_fused_write_fp8_pools():
    """Fused write+attention with fp8 pools: new rows are cast to fp8 before
    the kernel (models/llama._layer_step does this); the written layer must
    match the XLA scatter of the same fp8 rows and attention must agree."""
    import numpy as np

    from distributed_gpu_inference_tpu.models.llama import _write_kv_pages
    from distributed_gpu_inference_tpu.ops.attention import paged_attention_xla
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_fused,
    )

    b, hkv, qpk, d, bs, m, L, nblocks = 2, 2, 2, 128, 16, 4, 2, 20
    q, new_k, new_v, k_pool, v_pool, tables, positions, lens_a = \
        _mk_fused_case(7, b, hkv, qpk, d, bs, m, L, nblocks, [33, 5])
    fp8 = jnp.float8_e4m3fn
    k_pool8 = jnp.asarray(k_pool).astype(fp8)
    v_pool8 = jnp.asarray(v_pool).astype(fp8)
    nk8 = jnp.asarray(new_k).astype(fp8)
    nv8 = jnp.asarray(new_v).astype(fp8)

    out, k2, v2 = paged_decode_attention_fused(
        jnp.asarray(q, jnp.bfloat16), nk8, nv8,
        k_pool8, v_pool8, jnp.int32(1),
        jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(lens_a),
        block_size=bs, interpret=True,
    )
    ref_k = _write_kv_pages(
        k_pool8[1], nk8, jnp.asarray(tables), jnp.asarray(positions), bs
    )
    ref_v = _write_kv_pages(
        v_pool8[1], nv8, jnp.asarray(tables), jnp.asarray(positions), bs
    )
    ref_out = paged_attention_xla(
        jnp.asarray(q, jnp.bfloat16), ref_k, ref_v, jnp.asarray(tables),
        jnp.asarray(positions), jnp.asarray(lens_a), block_size=bs,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        rtol=3e-2, atol=3e-2,
    )
    np.testing.assert_array_equal(
        np.asarray(k2[1], np.float32), np.asarray(ref_k, np.float32)
    )
    np.testing.assert_array_equal(
        np.asarray(v2[1], np.float32), np.asarray(ref_v, np.float32)
    )


# ---------------------------------------------------------------------------
# int8 KV pool: per-token scales, scores/PV rescale in-kernel (VERDICT r3 #4)
# ---------------------------------------------------------------------------


from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (  # noqa: E402
    quantize_kv_pool as _quantize_pool,
)


def _compare_int8(args, block, window=None):
    """Oracle = XLA attention over the DEQUANTIZED pool: the kernel must
    reproduce the quantized-pool math, not hide extra error beyond it."""
    q, k_pool, v_pool, tables, positions, lens = args
    k_i8, ks = _quantize_pool(k_pool)
    v_i8, vs = _quantize_pool(v_pool)
    k_deq = k_i8.astype(jnp.float32) * ks.astype(jnp.float32)[:, None, :, :]
    v_deq = v_i8.astype(jnp.float32) * vs.astype(jnp.float32)[:, None, :, :]
    want = paged_attention_xla(
        q, k_deq, v_deq, tables, positions, lens, block, window=window
    )
    got = paged_attention_pallas(
        q, k_i8, v_i8, tables, positions, lens, block, window=window,
        interpret=True, k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_int8_pool_basic():
    _compare_int8(_setup(2, [9, 23], nh=4, hkv=2, d=64, block=32, m=4), 32)


def test_int8_pool_multi_group():
    _compare_int8(_setup(2, [300, 17], nh=8, hkv=4, d=64, block=32, m=12), 32)


def test_int8_pool_window():
    _compare_int8(_setup(2, [200, 64], nh=4, hkv=2, d=64, block=32, m=8), 32,
                  window=48)


def test_int8_pool_inactive_rows():
    args = _setup(3, [40, 1, 16], nh=4, hkv=2, d=64, block=32, m=4)
    q, k_pool, v_pool, tables, positions, lens = args
    positions = positions.at[1, 0].set(-1)   # row 1 inactive
    _compare_int8((q, k_pool, v_pool, tables, positions, lens), 32)


def test_int8_quantization_error_vs_full_precision_bounded():
    """Sanity: int8-KV output stays within ~1% of the FULL-precision
    attention (per-token amax scaling) — the capacity knob must not wreck
    quality."""
    args = _setup(2, [100, 50], nh=4, hkv=2, d=64, block=32, m=4)
    q, k_pool, v_pool, tables, positions, lens = args
    full = paged_attention_xla(
        q, k_pool, v_pool, tables, positions, lens, 32
    )
    k_i8, ks = _quantize_pool(k_pool)
    v_i8, vs = _quantize_pool(v_pool)
    got = paged_attention_pallas(
        q, k_i8, v_i8, tables, positions, lens, 32,
        interpret=True, k_scale=ks, v_scale=vs,
    )
    err = float(jnp.max(jnp.abs(got - full)))
    ref = float(jnp.max(jnp.abs(full)))
    assert err < 0.02 * max(ref, 1.0), f"int8 KV error too large: {err}"


def test_int8_fused_write_quantizes_in_kernel():
    """Fused decode on int8 pools: the kernel quantizes this step's K/V
    rows in place (per-token scale, same contract as quantize_kv_pool) and
    its own attention sees them. Oracle: quantize the row on the host with
    the same contract, place it in the pool, run the read-only path."""
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_fused,
    )

    b, nh, hkv, d, block, m = 2, 4, 2, 64, 32, 3
    args = _setup(b, [33, 9], nh, hkv, d, block, m)
    q, k_pool, v_pool, tables, positions, lens = args
    # context BEFORE this step's token
    prev_lens = jnp.asarray([32, 8], jnp.int32)
    new_lens = prev_lens + 1
    wpos = prev_lens[:, None]        # write at the next slot

    key = jax.random.PRNGKey(9)
    new_k = jax.random.normal(key, (b, 1, hkv, d), jnp.float32)
    new_v = jax.random.normal(jax.random.fold_in(key, 1), (b, 1, hkv, d),
                              jnp.float32)

    k_i8, ks = _quantize_pool(k_pool)
    v_i8, vs = _quantize_pool(v_pool)

    out, k2, v2, ks2, vs2 = paged_decode_attention_fused(
        q, new_k, new_v, k_i8[None], v_i8[None], jnp.int32(0),
        tables, wpos, new_lens, block, interpret=True,
        k_scale=ks[None], v_scale=vs[None],
    )

    # oracle: quantize the new rows host-side with the same contract and
    # rebuild the dequantized pool the kernel should have attended over
    def host_write(pool_i8, scales, new_rows):
        pool_i8, scales = np.asarray(pool_i8).copy(), \
            np.asarray(scales, np.float32).copy()
        for r in range(b):
            p = int(np.asarray(tables)[r, int(prev_lens[r]) // block])
            slot = int(prev_lens[r]) % block
            row = np.asarray(new_rows[r, 0], np.float32)      # [Hkv, D]
            s = np.float32(max(np.abs(row).max(), 1e-6) / 127.0)
            s = np.float32(jnp.bfloat16(s))                   # stored bf16
            pool_i8[p, :, slot, :] = np.clip(
                np.round(row / s), -127, 127
            ).astype(np.int8)
            scales[p, slot, :] = s
        return pool_i8, scales

    k_ref, ks_ref = host_write(k_i8, ks, new_k)
    v_ref, vs_ref = host_write(v_i8, vs, new_v)
    np.testing.assert_array_equal(np.asarray(k2[0]), k_ref)
    np.testing.assert_array_equal(np.asarray(v2[0]), v_ref)
    np.testing.assert_allclose(np.asarray(ks2[0], np.float32), ks_ref,
                               rtol=1e-2, atol=1e-4)

    k_deq = k_ref.astype(np.float32) * np.asarray(ks_ref)[:, None, :, :]
    v_deq = v_ref.astype(np.float32) * np.asarray(vs_ref)[:, None, :, :]
    want = paged_attention_xla(
        q, jnp.asarray(k_deq), jnp.asarray(v_deq), tables,
        prev_lens[:, None], new_lens, block
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


# --------------------------------------------------------------------- #
# the in-place page write (PR 28) against the scatter it replaces: what
# the round-level cases of tests/test_ragged_attention.py do not reach
# --------------------------------------------------------------------- #

def _write_both(positions, *, hkv=2, d=128, block=16, m=4, layers=2,
                layer=1, dtype=jnp.bfloat16, token_index=None, seed=0):
    """→ ((k, v) through ``write_kv_pages_in_place``, (k, v) through
    ``_write_kv_pages`` into the sliced layer), float32 numpy."""
    from distributed_gpu_inference_tpu.models.llama import _write_kv_pages
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        page_write_plan,
        write_kv_pages_in_place,
    )

    rng = np.random.default_rng(seed)
    positions = jnp.asarray(positions, jnp.int32)
    b, s = positions.shape
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32).astype(dtype)
    pools = [normal(layers, 1 + b * m, hkv, block, d) for _ in "kv"]
    new = [normal(b, s, hkv, d) for _ in "kv"]
    tables = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    want = [p.at[layer].set(_write_kv_pages(p[layer], n, tables, positions,
                                            block))
            for p, n in zip(pools, new)]
    flat = [n.reshape(b * s, hkv, d) for n in new]
    tokens = b * s
    if token_index is not None:
        # the packed round's form: the tokens in another order on a flat
        # axis, and where each position of the rectangle lies on it
        order = rng.permutation(b * s)
        flat = [f[order] for f in flat]
        token_index = jnp.asarray(np.argsort(order).reshape(b, s), jnp.int32)
    plan = page_write_plan(
        tables, positions, block, hkv * block * d * pools[0].dtype.itemsize,
        token_index=token_index, num_tokens=tokens)
    got = write_kv_pages_in_place(*flat, *pools, jnp.int32(layer), plan,
                                  interpret=True)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    return plan, [f32(g) for g in got], [f32(w) for w in want]


def _span(start, n, s):
    return [start + i if i < n else -1 for i in range(s)]


def test_page_write_splits_a_row_into_tiles(monkeypatch):
    """A span whose pages outgrow the VMEM budget: several grid steps a
    row, the padding cells of the last one written nowhere."""
    from distributed_gpu_inference_tpu.ops import paged_attention_pallas as pap

    page_bytes = 2 * 16 * 128 * 2
    monkeypatch.setattr(pap, "_WRITE_VMEM_BUDGET_BYTES", 6 * 2 * page_bytes)
    plan, got, want = _write_both(
        [_span(9, 60, 60), _span(0, 1, 60), _span(-1, 0, 60)], m=5)
    # a 60-token span touches up to five pages: three tiles of two cells
    assert plan.tile == 2 and plan.page.shape[0] == 3 * 6
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("block,dtype", [(32, jnp.bfloat16),
                                         (32, jnp.float8_e4m3fn),
                                         (64, jnp.float32)],
                         ids=["block32_bf16", "block32_fp8", "block64_f32"])
def test_page_write_block_sizes_and_pool_dtypes(block, dtype):
    """Blocks of 32 (one whole mask word) and 64 (two), and a pool that
    stores a narrower dtype than the rows it is given."""
    _, got, want = _write_both(
        [_span(31, 70, 70), _span(64, 1, 70), _span(5, 3, 70)],
        block=block, m=4, dtype=dtype)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_page_write_keeps_the_scatters_semantics_inside_the_window():
    """Positions a row's scatter would take in any order, with pads
    between them, and one past the end of the row's table (dropped by
    both)."""
    _, got, want = _write_both(
        [[19, -1, 3, 4, -1, 17, 30, -1],
         [-1, -1, 63, 62, 64, -1, -1, 66],      # table holds 0..63
         [-1] * 8],
        m=4)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_page_write_reads_tokens_from_a_packed_axis():
    _, got, want = _write_both(
        [_span(7, 20, 24), _span(40, 1, 24), _span(16, 16, 24)],
        token_index=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# one wait a pool and slot for a group's pages: every form that shares it
# ---------------------------------------------------------------------------

from distributed_gpu_inference_tpu.ops import (  # noqa: E402
    paged_attention_pallas as pp,
)

# three rows over a table of 32 pages walked in groups of 8 (128 tokens): no
# context is a multiple of the group, so every row's last group is partly
# past its end
_F_ROWS, _F_PAGES, _F_BK, _F_HKV, _F_NH, _F_D, _F_GROUP = 3, 32, 16, 2, 4, 128, 128
_F_LENS = (300, 77, 500)
DECODE_FORMS = {
    # name: (pool dtype, window, selection, lens)
    "dense": ("bf16", None, None, _F_LENS),
    # rows 0 and 2 start their walk at groups 1 and 3
    "window_starts_past_group_0": ("bf16", 60, None, _F_LENS),
    # rows 0 and 2 fetch 11 and 17 pages: both walks end inside a group
    "selection_ends_mid_group": ("bf16", None, (11, 3, 17), _F_LENS),
    # an inactive row, and a row whose selection holds nothing
    "a_row_with_no_page": ("bf16", None, (5, 0, 0), (300, 0, 77)),
    "int8_pool": ("int8", None, None, _F_LENS),
    "fp8_pool": ("fp8", None, None, _F_LENS),
}


def _form_keep(pages_kept, lens, rng):
    """``keep [B, 1, J]``: a token or two in ``pages_kept[r]`` of row r's
    pages, its last page (the query's own) first among them."""
    keep = np.zeros((_F_ROWS, 1, _F_PAGES * _F_BK), np.float32)
    for r, n in enumerate(pages_kept):
        if not n:
            continue
        last = (lens[r] - 1) // _F_BK
        chosen = np.append(rng.choice(last, n - 1, replace=False), last)
        for page in chosen:
            top = min(_F_BK, lens[r] - page * _F_BK)
            keep[r, 0, page * _F_BK + rng.choice(top, min(2, top),
                                                 replace=False)] = 1
    return keep


def _form_walked(tables, lens, window, keep):
    """The pool pages a walk may read: under a selection those that hold a
    kept token, else every column of the row's live groups (the columns past
    its end within the last group are read and masked)."""
    gp = _F_GROUP // _F_BK
    walked = set()
    for r, n in enumerate(lens):
        if keep is not None:
            hit = keep[r, 0].reshape(_F_PAGES, _F_BK).any(-1)
            walked.update(tables[r, hit].tolist())
        elif n:
            first = 0 if window is None else max(n - window, 0) // _F_GROUP
            cols = range(first * gp, min(-(-n // _F_GROUP) * gp, _F_PAGES))
            walked.update(tables[r, list(cols)].tolist())
    return sorted(walked)


def decode_form(name, interpret=True):
    """→ (the kernel's output over pools whose every page outside the walk
    is poisoned, the XLA reference's over the clean pools)."""
    dtype, window, kept, lens = DECODE_FORMS[name]
    rng = np.random.default_rng(50)
    lens = np.asarray(lens, np.int32)
    n = 1 + _F_ROWS * _F_PAGES
    tables = (1 + rng.permutation(_F_ROWS * _F_PAGES)).reshape(
        _F_ROWS, _F_PAGES).astype(np.int32)
    shape = (n, _F_HKV, _F_BK, _F_D)
    kf = rng.standard_normal(shape, np.float32)
    vf = rng.standard_normal(shape, np.float32)
    q = jnp.asarray(rng.standard_normal((_F_ROWS, 1, _F_NH, _F_D), np.float32),
                    jnp.bfloat16)
    keep = None if kept is None else _form_keep(kept, lens, rng)
    walked = _form_walked(tables, lens, window, keep)

    def poisoned(pool, fill):
        out = np.full(pool.shape, fill, np.asarray(pool).dtype)
        out[walked] = np.asarray(pool)[walked]
        return jnp.asarray(out)

    scales = bad_scales = {}
    if dtype == "int8":
        (k, ks), (v, vs) = pp.quantize_kv_pool(kf), pp.quantize_kv_pool(vf)
        scales = {"k_scale": ks, "v_scale": vs}
        bad_scales = {"k_scale": poisoned(ks, np.nan),
                      "v_scale": poisoned(vs, np.nan)}
        k_bad, v_bad = poisoned(k, 127), poisoned(v, 127)
    else:
        to = jnp.float8_e4m3fn if dtype == "fp8" else jnp.bfloat16
        k, v = jnp.asarray(kf, to), jnp.asarray(vf, to)
        k_bad, v_bad = poisoned(k, np.nan), poisoned(v, np.nan)
    args = (jnp.asarray(tables), jnp.asarray(lens - 1)[:, None],
            jnp.asarray(lens), _F_BK)
    sel = {} if keep is None else {"keep": jnp.asarray(keep)}
    want = paged_attention_xla(q, k, v, *args, window=window, **scales, **sel)
    got = pp.paged_attention_pallas(
        q, k_bad, v_bad, *args, window=window, interpret=interpret,
        **bad_scales, **sel)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("name", DECODE_FORMS)
def test_one_wait_a_group_in_every_form_of_the_walk(name, monkeypatch):
    """Dense, behind a window, under a selection, with an empty row, over
    int8 and fp8 pools: a group's pages are started a page at a time and
    waited for once a pool, the walk reads no page outside it (NaN there
    would reach the output), and the result is the reference's."""
    monkeypatch.setattr(pp, "_GROUP_TOKENS", _F_GROUP)
    monkeypatch.setattr(pp, "_SELECTED_GROUP_TOKENS", _F_GROUP)
    got, want = decode_form(name)
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_the_selected_table_ends_in_the_rows_last_fetched_page():
    """What the kernel's one wait a group rests on: past a row's fetched
    pages its table holds the last of them again, up to the walk's whole
    groups, so a group always starts its full count of copies and none is
    of a page the selection dropped."""
    rng = np.random.default_rng(3)
    lens = np.asarray([300, 0, 77], np.int32)
    kept = (11, 0, 3)
    keep = _form_keep(kept, lens, rng)
    tables = (1 + rng.permutation(_F_ROWS * _F_PAGES)).reshape(
        _F_ROWS, _F_PAGES).astype(np.int32)
    columns = 40                      # five groups of 8 over 32 pages
    pages, laid, count = pp._selected_pages(
        jnp.asarray(keep), jnp.asarray(tables), jnp.asarray(lens - 1),
        jnp.asarray(lens), _F_BK, None, columns)
    pages, laid, count = map(np.asarray, (pages, laid, count))
    assert pages.shape == (_F_ROWS, columns)
    assert laid.shape == (_F_ROWS, 1, columns * _F_BK)
    assert count.tolist() == list(kept)
    for r, n in enumerate(kept):
        hit = keep[r, 0].reshape(_F_PAGES, _F_BK).any(-1)
        assert pages[r, :n].tolist() == tables[r, hit].tolist()
        assert laid[r, 0, :n * _F_BK].sum() == keep[r].sum()
        assert not laid[r, 0, n * _F_BK:].any()
        if n:
            assert (pages[r, n:] == pages[r, n - 1]).all()
