"""Engine end-to-end on the tiny model: determinism, prefix cache, stop
tokens, multi-step scan equivalence, slot recycling."""

import numpy as np
import pytest

# compile-heavy (jit/scan graphs): excluded from the fast CI gate
pytestmark = pytest.mark.slow

from distributed_gpu_inference_tpu.runtime.engine import EngineConfig, TPUEngine
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)

ECFG = EngineConfig(
    max_batch_size=4, max_seq_len=128, prefill_buckets=(16, 32, 64), multi_step=8
)


@pytest.fixture(scope="module")
def engine():
    return TPUEngine("llama3-tiny", ECFG)


def _req(prompt, max_new=8, **kw):
    return InferenceRequest(
        prompt_token_ids=prompt, sampling=SamplingParams(max_new_tokens=max_new, **kw)
    )


def test_greedy_deterministic(engine):
    p = list(range(10, 30))
    r1 = engine.generate([_req(p)])[0]
    r2 = engine.generate([_req(p)])[0]
    assert r1.token_ids == r2.token_ids
    assert r1.completion_tokens == 8
    assert r1.finish_reason == "length"
    assert r1.ttft_ms is not None and r1.e2e_ms is not None


def test_prefix_cache_hit_on_repeat(engine):
    p = list(range(40, 80))  # 40 tokens → 2 full blocks cacheable
    r1 = engine.generate([_req(p)])[0]
    r2 = engine.generate([_req(p)])[0]
    assert r2.cached_tokens >= 32
    assert r1.token_ids == r2.token_ids  # cache must not change results


def test_batch_matches_solo(engine):
    pa, pb = list(range(5, 25)), list(range(100, 130))
    solo_a = engine.generate([_req(pa)])[0]
    solo_b = engine.generate([_req(pb)])[0]
    both = engine.generate([_req(pa), _req(pb)])
    assert both[0].token_ids == solo_a.token_ids
    assert both[1].token_ids == solo_b.token_ids


def test_multi_step_equivalence():
    e1 = TPUEngine("llama3-tiny", ECFG)
    e2 = TPUEngine("llama3-tiny", ECFG)
    p = list(range(10, 30))
    r1 = e1.generate([_req(p, max_new=20)])[0]
    r2 = e2.generate([_req(p, max_new=20)], use_multi_step=True)[0]
    assert r1.token_ids == r2.token_ids


def test_stop_token(engine):
    p = list(range(10, 30))
    free_run = engine.generate([_req(p, max_new=12)])[0]
    assert len(free_run.token_ids) == 12
    stop_at = free_run.token_ids[3]  # stop when the 4th token appears
    stopped = engine.generate(
        [_req(p, max_new=12, stop_token_ids=(stop_at,))]
    )[0]
    assert stopped.finish_reason == "stop"
    assert stopped.token_ids == free_run.token_ids[:3]


def test_stop_token_multi_step():
    e1 = TPUEngine("llama3-tiny", ECFG)
    p = list(range(10, 30))
    free_run = e1.generate([_req(p, max_new=12)])[0]
    stop_at = free_run.token_ids[3]
    e2 = TPUEngine("llama3-tiny", ECFG)
    stopped = e2.generate([_req(p, max_new=12, stop_token_ids=(stop_at,))],
                          use_multi_step=True)[0]
    assert stopped.finish_reason == "stop"
    assert stopped.token_ids == free_run.token_ids[:3]


def test_sampled_generation_runs(engine):
    p = list(range(10, 30))
    r = engine.generate([_req(p, max_new=6, temperature=0.8, top_k=40,
                              top_p=0.9)])[0]
    assert len(r.token_ids) == 6
    assert all(0 <= t < 512 for t in r.token_ids)


def test_slot_exhaustion_and_recycling(engine):
    # more requests than slots: generate() runs in waves
    reqs = [_req(list(range(i, i + 12)), max_new=4) for i in range(10, 20)]
    resps = engine.generate(reqs)
    assert len(resps) == 10
    assert all(r.completion_tokens == 4 for r in resps)
    assert engine.num_active == 0


def test_prompt_too_long_rejected(engine):
    with pytest.raises(ValueError):
        engine.submit(_req(list(range(200)), max_new=8))


def test_engine_stats(engine):
    s = engine.get_stats()
    assert s["requests"] > 0
    assert s["kv_cache"]["prefix_queries"] > 0


def test_submit_batch_rollback_on_invalid_request():
    """A failed wave must not leak sequences or half-bound slots."""
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )
    from distributed_gpu_inference_tpu.utils.data_structures import (
        InferenceRequest,
        SamplingParams,
    )
    import pytest as _pytest

    eng = TPUEngine(
        "llama3-tiny",
        EngineConfig(max_batch_size=2, max_seq_len=64, block_size=16,
                     prefill_buckets=(16,), dtype="float32"),
    )
    good = InferenceRequest(
        prompt_token_ids=[5, 17, 3],
        sampling=SamplingParams(max_new_tokens=4, temperature=0.0),
    )
    bad = InferenceRequest(
        prompt_token_ids=[], sampling=SamplingParams(max_new_tokens=4),
    )
    free_before = eng.manager.num_free
    with _pytest.raises(ValueError):
        eng.submit_batch([good, bad])
    assert eng.num_active == 0
    assert eng.manager.num_free == free_before
    assert not eng.manager.seq_blocks
    # engine still serviceable after the failed wave
    out = eng.generate([good])
    assert len(out[0].token_ids) == 4


def test_submit_batch_rollback_scrubs_pending_and_stats():
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )
    from distributed_gpu_inference_tpu.utils.data_structures import (
        InferenceRequest,
        SamplingParams,
    )
    import pytest as _pytest

    eng = TPUEngine(
        "llama3-tiny",
        EngineConfig(max_batch_size=2, max_seq_len=64, block_size=16,
                     prefill_buckets=(16,), dtype="float32"),
    )
    before = dict(eng.stats)
    good = InferenceRequest(
        prompt_token_ids=[5, 17, 3],
        sampling=SamplingParams(max_new_tokens=4, temperature=0.0),
    )
    bad = InferenceRequest(
        prompt_token_ids=[], sampling=SamplingParams(max_new_tokens=4),
    )
    with _pytest.raises(ValueError):
        eng.submit_batch([good, bad])
    for k in ("requests", "prefill_tokens", "prefill_calls",
              "generated_tokens"):
        assert eng.stats[k] == before[k], k
    # no pending device ops may reference freed blocks
    alive = eng.manager.metas
    assert all(u[0] in alive for u in eng.manager.pending.uploads)
    assert all(c[0] in alive and c[1] in alive
               for c in eng.manager.pending.copies)


def test_fp8_kv_cache_serves():
    """kv_cache_dtype="fp8": pools store float8_e4m3, generation still works
    and is deterministic; spill round-trips keep the fp8 dtype."""
    import jax.numpy as jnp

    cfg = EngineConfig(
        max_batch_size=2, max_seq_len=64, block_size=16,
        prefill_buckets=(16, 32), multi_step=4, kv_cache_dtype="fp8",
    )
    e = TPUEngine("llama3-tiny", cfg)
    assert e.kv["k"].dtype == jnp.float8_e4m3fn
    assert e.kv["v"].dtype == jnp.float8_e4m3fn
    p = list(range(10, 26))
    r1 = e.generate([_req(p)])[0]
    r2 = e.generate([_req(p)])[0]
    assert r1.token_ids == r2.token_ids
    assert r1.completion_tokens == 8
    assert all(0 <= t < e.model_cfg.vocab_size for t in r1.token_ids)


def test_fp8_kv_outputs_close_to_bf16_kv():
    """fp8 KV is a rounding of the same cache values: greedy outputs on a
    short prompt should agree with the bf16-KV engine (tiny model, short
    horizon — divergence would mean a plumbing bug, not rounding)."""
    base = EngineConfig(
        max_batch_size=2, max_seq_len=64, block_size=16,
        prefill_buckets=(16,), multi_step=4,
    )
    fp8 = EngineConfig(
        max_batch_size=2, max_seq_len=64, block_size=16,
        prefill_buckets=(16,), multi_step=4, kv_cache_dtype="fp8",
    )
    e_bf16 = TPUEngine("llama3-tiny", base, seed=3)
    e_fp8 = TPUEngine("llama3-tiny", fp8, seed=3)
    p = list(range(30, 44))
    t_bf16 = e_bf16.generate([_req(p, max_new=4)])[0].token_ids
    t_fp8 = e_fp8.generate([_req(p, max_new=4)])[0].token_ids
    assert t_fp8[0] == t_bf16[0]  # first token: same prefill numerics


def test_bad_kv_dtype_rejected():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        TPUEngine(
            "llama3-tiny",
            EngineConfig(max_batch_size=1, max_seq_len=32,
                         kv_cache_dtype="int4"),
        )
