"""Benchmark harnesses stay runnable (tiny shapes, in-process).

The reference's distributed/PD/speculative benchmarks are analytic
simulators; ours drive real compute, so these smoke tests double as
end-to-end exercises of batcher/pipeline/PD/speculative serving paths.
"""

import json
import sys

import pytest

# compile-heavy (jit/scan graphs): excluded from the fast CI gate
pytestmark = pytest.mark.slow


def _run(module_main, argv, capsys):
    old = sys.argv
    sys.argv = argv
    try:
        module_main()
    finally:
        sys.argv = old
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_single_worker_bench(capsys):
    from benchmarks.single_worker import main

    res = _run(main, [
        "single_worker", "--model", "llama3-tiny", "--requests", "4",
        "--concurrency", "2", "--prompt-len", "16", "--max-tokens", "8",
        "--shared-prefix", "8",
    ], capsys)
    assert res["benchmark"] == "single_worker"
    assert res["ok"] == 4
    assert res["value"] > 0
    assert res["ttft_ms"]["p50"] is not None


def test_worker_serving_bench(capsys):
    """The deployed-path harness: open-loop arrivals over HTTP against a
    real DirectServer + batcher-backed TPULLMEngine, with the bench-only
    comparison leg."""
    from benchmarks.worker_serving import main

    res = _run(main, [
        "worker_serving", "--model", "llama3-tiny", "--requests", "4",
        "--concurrency", "2", "--prompt-len", "16", "--max-tokens", "8",
        "--shared-prefix", "8", "--arrival-rate", "20", "--compare",
    ], capsys)
    assert res["benchmark"] == "worker_serving"
    assert res["mode"] == "open_loop"
    assert res["deployed"]["ok"] == 4
    assert res["deployed"]["ttft_ms"]["p50"] is not None
    assert res["bench_only"]["ok"] == 4
    assert res["tokens_per_s_ratio"] > 0
    assert res["batcher"]["decode_rounds"] > 0


def test_worker_serving_timeline_smoke(capsys):
    """--timeline: the flight-recorder attribution leg — per-phase
    p50/p95 instead of one opaque TTFT number, plus the recorder-on-vs-off
    byte-identity assertion."""
    from benchmarks.worker_serving import main

    res = _run(main, [
        "worker_serving", "--model", "llama3-tiny", "--requests", "4",
        "--concurrency", "2", "--prompt-len", "16", "--max-tokens", "8",
        "--shared-prefix", "8", "--arrival-rate", "20", "--timeline",
    ], capsys)
    assert res["benchmark"] == "worker_serving"
    tl = res["timeline"]
    assert tl["samples"] == 4
    assert tl["outputs_identical_recorder_on_vs_off"] is True
    for phase in ("queue_wait", "ttft", "decode", "e2e"):
        assert tl["phase_ms"][phase]["p50"] is not None
        assert tl["phase_ms"][phase]["p95"] is not None


def test_speculative_bench(capsys):
    from benchmarks.speculative import main

    res = _run(main, [
        "speculative", "--model", "llama3-tiny", "--requests", "2",
        "--prompt-len", "16", "--max-tokens", "12", "--widths", "2,2",
    ], capsys)
    assert res["benchmark"] == "speculative"
    assert res["spec_tokens_per_s"] > 0
    assert res["vanilla_tokens_per_s"] > 0
    assert 0.0 <= res["accept_rate"] <= 1.0


def test_distributed_http_bench(capsys):
    from benchmarks.distributed import main

    res = _run(main, [
        "distributed", "--mode", "http", "--model", "llama3-tiny",
        "--stages", "2", "--prompt-len", "16", "--max-tokens", "6",
    ], capsys)
    assert res["mode"] == "http"
    assert res["value"] > 0
    assert res["ttft_ms"] > 0


def test_distributed_spmd_bench(capsys):
    from benchmarks.distributed import main

    res = _run(main, [
        "distributed", "--mode", "spmd", "--model", "llama3-mini",
        "--stages", "4", "--microbatches", "2", "--microbatch-size", "1",
        "--prompt-len", "16", "--iters", "1",
    ], capsys)
    assert res["mode"] == "spmd"
    assert res["value"] > 0


def test_pd_separation_bench(capsys):
    from benchmarks.pd_separation import main

    res = _run(main, [
        "pd_separation", "--model", "llama3-tiny", "--requests", "3",
        "--prompt-len", "16", "--max-tokens", "6", "--migration", "both",
    ], capsys)
    assert res["benchmark"] == "pd_separation"
    assert res["hybrid"]["tpot_ms"]["p50"] is not None
    for mode in ("host", "device"):
        assert res[f"separated_{mode}"]["tpot_ms"]["p50"] is not None
        assert res[f"separated_{mode}"]["migration_ms"]["p50"] is not None


def test_paged_attention_micro_no_baked_pool_literals(capsys):
    """Regression for the round-4 batch-32 x ctx-4096 'wedge': the micro
    bench's jitted loops take pools/scales as ARGUMENTS, so no pool-sized
    literal is baked into the computation (~540 MB of constants at batch
    32 x ctx 4096). CPU smoke runs the XLA variant (the Pallas variants
    need the chip — interpret-mode pallas inside the timing fori_loop
    trips a JAX lowering-cache limitation)."""
    from benchmarks.paged_attention_micro import main

    res = _run(main, [
        "paged_attention_micro", "--batch", "2", "--kv-heads", "2",
        "--q-heads", "4", "--head-dim", "128", "--ctx", "64",
        "--iters", "3", "--mixed", "--skip-pallas",
    ], capsys)
    assert res["metric"] == "paged_attention_decode_us"
    assert res["xla_us"] > 0 and res["live_kv_gb_s"] > 0

    # the no-pool-literals property, checked structurally: a pool passed
    # as an argument appears as a parameter in the lowered HLO; a captured
    # pool appears as a multi-MB constant. Bench-style loop at a shape big
    # enough that a baked literal would dominate the HLO text.
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.ops.attention import (
        paged_attention_xla,
    )

    kp = jnp.ones((129, 2, 16, 32), jnp.bfloat16)     # ~0.5 MB pool
    tables = jnp.zeros((2, 4), jnp.int32)
    pos = jnp.zeros((2, 1), jnp.int32)
    lens = jnp.full((2,), 64, jnp.int32)
    q = jnp.ones((2, 1, 4, 32), jnp.bfloat16)

    def loop_args(q, kp, vp):
        def body(i, o):
            return paged_attention_xla(
                q + (o * 1e-9).astype(q.dtype), kp, vp, tables, pos, lens
            )
        return jax.lax.fori_loop(0, 3, body, q)

    text = jax.jit(loop_args).lower(q, kp, kp).as_text()
    # a baked [129,2,16,32] bf16 literal would serialize to >100 kB of HLO
    assert len(text) < 100_000, (
        f"HLO unexpectedly large ({len(text)} B): pool-sized literal "
        "baked into the computation?"
    )


def test_spec_params_npz_roundtrip_preserves_bfloat16(tmp_path=None):
    """bfloat16 does not survive a plain np.savez round-trip (loads back as
    void |V2); the spec benchmark's subprocess handoff must restore it."""
    import json

    import ml_dtypes
    import numpy as np

    from benchmarks.speculative import _flatten_params, _unflatten_params

    params = {
        "embedding": np.arange(6, dtype=np.float32).reshape(2, 3)
        .astype(ml_dtypes.bfloat16),
        "layers": {"wq": np.ones((2, 2), np.float32)},
    }
    flat, dtypes = _flatten_params(params)
    import io

    buf = io.BytesIO()
    np.savez(buf, dtypes=json.dumps(dtypes),
             **{f"p.{k}": v for k, v in flat.items()})
    buf.seek(0)
    data = np.load(buf, allow_pickle=False)
    out = _unflatten_params(data)
    assert out["embedding"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(
        out["embedding"].astype(np.float32),
        params["embedding"].astype(np.float32),
    )
    assert out["layers"]["wq"].dtype == np.float32
