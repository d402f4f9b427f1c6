"""Small-q multi-query paged attention (the speculative verify path,
q_len = K+1 per slot) vs the XLA oracle: causal masking within the chunk,
padded queries, sliding windows, int8 pools, and dispatch facts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_gpu_inference_tpu.ops.attention import (
    paged_attention_xla,
    resolve_impl,
)


def _setup(b, s, kv_lens, nh, hkv, d, block, m, seed=0, pad_tail=0):
    """Random pools + a chain-shaped query chunk: row i's queries sit at
    positions kv_len - s .. kv_len - 1 (the verify window), with the
    chunk's KV already present in the pool — exactly the state the verify
    pass reads. ``pad_tail`` marks that many trailing queries per row as
    padding (position -1)."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    num_blocks = 1 + b * m
    k_pool = jax.random.normal(ks[0], (num_blocks, hkv, block, d), jnp.float32)
    v_pool = jax.random.normal(ks[1], (num_blocks, hkv, block, d), jnp.float32)
    q = jax.random.normal(ks[2], (b, s, nh, d), jnp.float32)
    tables = np.zeros((b, m), np.int32)
    nxt = 1
    for i in range(b):
        tables[i] = np.arange(nxt, nxt + m)
        nxt += m
    lens = np.asarray(kv_lens, np.int32)
    positions = np.zeros((b, s), np.int32)
    for i in range(b):
        positions[i] = np.arange(lens[i] - s, lens[i])
    if pad_tail:
        positions[:, s - pad_tail:] = -1
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(positions), jnp.asarray(lens))


def _compare(args, block, window=None, atol=2e-5):
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_attention_pallas_multiquery,
    )

    q, k_pool, v_pool, tables, positions, lens = args
    want = paged_attention_xla(
        q, k_pool, v_pool, tables, positions, lens, block, window=window
    )
    got = paged_attention_pallas_multiquery(
        q, k_pool, v_pool, tables, positions, lens, block, window=window,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=atol)


def test_verify_window_basic():
    _compare(_setup(2, 4, [9, 23], nh=4, hkv=2, d=64, block=16, m=4), 16)


def test_multi_group_context():
    # 300 tokens -> multiple page groups per query row
    _compare(_setup(2, 5, [300, 37], nh=8, hkv=4, d=64, block=16, m=20), 16)


def test_padded_tail_queries_are_zero():
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_attention_pallas_multiquery,
    )

    args = _setup(2, 5, [17, 40], nh=4, hkv=2, d=64, block=16, m=4,
                  pad_tail=2)
    _compare(args, 16)
    q, k_pool, v_pool, tables, positions, lens = args
    got = paged_attention_pallas_multiquery(
        q, k_pool, v_pool, tables, positions, lens, 16, interpret=True
    )
    assert np.all(np.asarray(got)[:, -2:] == 0.0)


@pytest.mark.parametrize("window", [4, 16])
def test_sliding_window(window):
    _compare(_setup(2, 3, [33, 50], nh=4, hkv=2, d=64, block=16, m=4), 16,
             window=window)


def test_int8_pool_parity():
    from distributed_gpu_inference_tpu.ops.attention import dequantize_kv
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_attention_pallas_multiquery,
        quantize_kv_pool,
    )

    q, k_pool, v_pool, tables, positions, lens = _setup(
        2, 4, [9, 40], nh=4, hkv=2, d=64, block=32, m=4
    )
    k_i8, k_s = quantize_kv_pool(k_pool)
    v_i8, v_s = quantize_kv_pool(v_pool)
    k_deq = dequantize_kv(k_i8, k_s[:, None, :, :])
    v_deq = dequantize_kv(v_i8, v_s[:, None, :, :])
    want = paged_attention_xla(
        q, k_deq, v_deq, tables, positions, lens, 32
    )
    got = paged_attention_pallas_multiquery(
        q, k_i8, v_i8, tables, positions, lens, 32, interpret=True,
        k_scale=k_s, v_scale=v_s,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_resolve_impl_small_q_dispatch():
    # q=1 decode stays on the fused kernel; EVERY multi-token span takes
    # the ragged kernel since round 6 — the old q_len <= 8 multi-query cap
    # (pages re-staged per query) is gone
    assert resolve_impl(1, 128, 1024, backend_is_tpu=True) == "pallas"
    for s in (2, 5, 8, 9, 16, 512):
        assert resolve_impl(s, 128, 1024, backend_is_tpu=True) == "ragged"
    # the existing guards still apply to multi-token spans
    assert resolve_impl(4, 64, 1024, backend_is_tpu=True) == "xla"
    assert resolve_impl(4, 128, 128, backend_is_tpu=True) == "xla"
    assert resolve_impl(4, 128, 1024, backend_is_tpu=False) == "xla"
