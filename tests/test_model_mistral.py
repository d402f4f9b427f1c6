"""Mistral family: sliding-window attention through the paged serving stack.

The reference serves Mistral via vLLM/SGLang HF-config auto-detection
(``worker/engines/llm_vllm.py:42``); here the window is first-class in the
paged attention mask (``ops/attention.py``) and is validated against a dense
windowed oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# compile-heavy (jit/scan graphs): excluded from the fast CI gate
pytestmark = pytest.mark.slow

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.ops.attention import (
    dense_causal_attention,
    paged_attention_xla,
)
from distributed_gpu_inference_tpu.runtime.engine import EngineConfig, TPUEngine
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)

MODEL = "mistral-tiny"     # sliding_window=8
PROMPT = [5, 17, 3, 99, 42, 7, 256, 31, 12, 88, 45, 2]


def test_mistral_config_registered():
    cfg = get_model_config("mistral-7b")
    assert cfg.sliding_window == 4096
    assert cfg.vocab_size == 32000 and cfg.num_kv_heads == 8
    tiny = get_model_config(MODEL)
    assert tiny.sliding_window == 8


# ------------------------------------------------------------ op-level oracle


def _paged_setup(b, s, hkv, d, block):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    k = jax.random.normal(ks[0], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    m = -(-s // block)
    num_blocks = 1 + b * m
    k_pool = jnp.zeros((num_blocks, hkv, block, d), jnp.float32)
    v_pool = jnp.zeros((num_blocks, hkv, block, d), jnp.float32)
    tables = np.zeros((b, m), np.int32)
    nxt = 1
    for i in range(b):
        tables[i] = np.arange(nxt, nxt + m)
        nxt += m
    for i in range(b):
        for t in range(s):
            blk, slot = tables[i][t // block], t % block
            k_pool = k_pool.at[blk, :, slot].set(k[i, t])
            v_pool = v_pool.at[blk, :, slot].set(v[i, t])
    return k, v, k_pool, v_pool, jnp.asarray(tables)


@pytest.mark.parametrize("window", [4, 8])
def test_windowed_paged_matches_dense_oracle(window):
    b, s, nh, hkv, d, block = 2, 24, 4, 2, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(7), (b, s, nh, d), jnp.float32)
    k, v, k_pool, v_pool, tables = _paged_setup(b, s, hkv, d, block)
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
    lens = jnp.full((b,), s, jnp.int32)
    got = paged_attention_xla(
        q, k_pool, v_pool, tables, positions, lens, block, window=window
    )
    want = dense_causal_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_window_none_is_full_causal():
    b, s, nh, hkv, d, block = 1, 16, 4, 2, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(3), (b, s, nh, d), jnp.float32)
    k, v, k_pool, v_pool, tables = _paged_setup(b, s, hkv, d, block)
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
    lens = jnp.full((b,), s, jnp.int32)
    full = paged_attention_xla(q, k_pool, v_pool, tables, positions, lens, block)
    wide = paged_attention_xla(q, k_pool, v_pool, tables, positions, lens,
                               block, window=10_000)
    np.testing.assert_allclose(np.asarray(full), np.asarray(wide),
                               rtol=1e-6, atol=1e-6)


def test_window_actually_restricts():
    """A distant key must not influence a windowed query."""
    b, s, nh, hkv, d, block = 1, 20, 2, 2, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(5), (b, s, nh, d), jnp.float32)
    k, v, k_pool, v_pool, tables = _paged_setup(b, s, hkv, d, block)
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
    lens = jnp.full((b,), s, jnp.int32)
    base = paged_attention_xla(q, k_pool, v_pool, tables, positions, lens,
                               block, window=4)
    # perturb key/value at position 0 (block 1, slot 0 across heads) —
    # outside every window-4 query ≥ 4
    k_pool2 = k_pool.at[1, :, 0].add(100.0)
    v_pool2 = v_pool.at[1, :, 0].add(100.0)
    pert = paged_attention_xla(q, k_pool2, v_pool2, tables, positions, lens,
                               block, window=4)
    np.testing.assert_allclose(np.asarray(base[:, 4:]), np.asarray(pert[:, 4:]),
                               rtol=1e-6, atol=1e-6)
    # sanity: early queries DO see it
    assert not np.allclose(np.asarray(base[:, :4]), np.asarray(pert[:, :4]))


# -------------------------------------------------------------- model/engine


def test_mistral_forward_differs_from_unwindowed():
    """The window must change logits once the context exceeds it."""
    cfg = get_model_config(MODEL, dtype="float32")
    cfg_nw = get_model_config(MODEL, dtype="float32", sliding_window=None)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    s = 16  # > window (8)
    tokens = jnp.asarray(np.array([PROMPT + [9, 14, 60, 71]], np.int32))
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), (1, 1))
    tables = jnp.asarray(np.arange(1, 3, dtype=np.int32)[None, :])
    lens = jnp.full((1,), s, jnp.int32)

    def run(c):
        kv = llama.init_kv_pools(c, 4, 16, jnp.float32)
        return np.asarray(
            llama.forward_chunk(c, params, tokens, positions, kv, tables,
                                lens, block_size=16, last_only=True).logits
        )

    assert not np.allclose(run(cfg), run(cfg_nw))


def test_mistral_engine_generates_past_window():
    """Decode well past the window: greedy, deterministic, valid ids."""
    eng = TPUEngine(
        MODEL,
        EngineConfig(max_batch_size=2, max_seq_len=64, block_size=16,
                     prefill_buckets=(16,), dtype="float32"),
        seed=0,
    )
    req = InferenceRequest(
        prompt_token_ids=list(PROMPT),
        sampling=SamplingParams(max_new_tokens=20, temperature=0.0),
    )
    out = eng.generate([req])[0]
    assert len(out.token_ids) == 20
    assert all(0 <= t < 512 for t in out.token_ids)
    again = eng.generate([InferenceRequest(
        prompt_token_ids=list(PROMPT),
        sampling=SamplingParams(max_new_tokens=20, temperature=0.0),
    )])[0]
    assert again.token_ids == out.token_ids


def test_window_release_frees_dead_blocks():
    """Decode far past the window: leading blocks return to the pool and the
    block table points them at pad block 0 — window-bounded KV memory."""
    eng = TPUEngine(
        MODEL,  # sliding_window=8, block_size 16 > window → ~2 live blocks
        EngineConfig(max_batch_size=1, max_seq_len=128, block_size=8,
                     prefill_buckets=(16,), dtype="float32",
                     enable_prefix_cache=False),
        seed=0,
    )
    req = InferenceRequest(
        prompt_token_ids=list(PROMPT),  # 12 tokens
        sampling=SamplingParams(max_new_tokens=60, temperature=0.0),
    )
    slot = eng.submit(req)
    while eng.slots[slot] is not None and eng.slots[slot].finish_reason is None:
        eng.decode_step()
    stats = eng.manager.get_stats()
    assert stats["window_released_blocks"] > 0
    # released leading logical slots are pinned to pad block 0
    table = eng._block_tables[slot]
    assert table[0] == 0
    # live blocks ≈ ceil(window/bs) + current tail, not the whole context
    live = [b for b in eng.manager.seq_blocks[eng.slots[slot].seq_id] if b != 0]
    assert len(live) <= (8 // 8) + 2
    eng.finish_slot(slot)


def test_window_release_off_by_one_boundary():
    """The pending query at cur-1 still sees key cur-window: that key's block
    must NOT be released."""
    from distributed_gpu_inference_tpu.runtime.kv_cache import (
        PagedKVCacheManager,
    )

    m = PagedKVCacheManager(num_blocks=32, block_size=4,
                            enable_prefix_cache=False)
    m.allocate_sequence("s", list(range(16)))  # 16 tokens → blocks 0..3 full
    # pending token position = 15; window 8 → visible keys ≥ 16-8 = 8
    released = m.release_out_of_window("s", window=8)
    # blocks covering positions 0-3 and 4-7 are dead; 8-11 must survive
    assert released == [0, 1]
    blocks = m.seq_blocks["s"]
    assert blocks[0] == 0 and blocks[1] == 0 and blocks[2] != 0


def test_window_released_chain_not_prefix_cached():
    from distributed_gpu_inference_tpu.runtime.kv_cache import (
        PagedKVCacheManager,
    )

    m = PagedKVCacheManager(num_blocks=32, block_size=4,
                            enable_prefix_cache=True)
    m.allocate_sequence("s", list(range(16)))
    m.release_out_of_window("s", window=8)
    m.free_sequence("s", cache=True)
    assert len(m.radix) == 0  # broken chain must not enter the radix


def test_mistral_tp_matches_single(cpu_devices):
    from distributed_gpu_inference_tpu.parallel.mesh import MeshPlan, make_mesh

    cfgE = EngineConfig(max_batch_size=1, max_seq_len=64, block_size=16,
                        prefill_buckets=(16,), dtype="float32")
    req = lambda: InferenceRequest(
        prompt_token_ids=list(PROMPT),
        sampling=SamplingParams(max_new_tokens=12, temperature=0.0),
    )
    single = TPUEngine(MODEL, cfgE, seed=0).generate([req()])[0].token_ids
    mesh = make_mesh(MeshPlan(model=2), cpu_devices[:2],
                     keep_trivial_axes=False)
    tp = TPUEngine(MODEL, cfgE, seed=0, mesh=mesh).generate([req()])[0].token_ids
    assert single == tp
