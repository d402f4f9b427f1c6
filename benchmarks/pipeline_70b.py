#!/usr/bin/env python
"""70B pipeline artifact: measured per-layer cost → 8-chip projection.

BASELINE config 4 (Llama-3-70B layer-sharded across 8 chips) cannot be
MEASURED end-to-end on one chip, but it can be measured-grounded: every
input to the projection is a real measurement.

1. **Per-layer cost, real chip**: build TWO int8 engines at true 70B layer
   width (hidden 8192, GQA 64/8, intermediate 28672) with different layer
   counts; the timing DIFFERENCE isolates pure per-layer decode/prefill
   cost from the embed/head ends — the same subtraction a pipeline's
   middle stages experience.
2. **HBM fit, arithmetic from the same config**: per-stage bytes at 80/8 =
   10 layers/stage int8 + bf16 embed (stage 0) / LM head (stage 7) + the
   KV pool a serving batch needs.
3. **Projection**: steady-state pipeline decode tokens/s = microbatch
   size / bottleneck-stage step time, with the ICI hop cost bounded from
   the activation bytes ([B, 8192] bf16 per hop). The ppermute schedule
   itself is validated for real on an 8-device virtual mesh at the same
   layer geometry (``benchmarks/distributed.py --mode spmd --model
   llama3-70b-micro``).

The reference's version of this benchmark simulates 10 ms/layer and a
synthetic 10 Gbps link (``/root/reference/benchmarks/distributed.py:
128-171``); here nothing is simulated — per-layer times are measured on
the target silicon at the target width.

Usage:
    python -m benchmarks.pipeline_70b --layers 4,8 --batch 8
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import (
    ICI_GBPS,
    V5E_HBM_GB,
    add_platform_arg,
    emit,
    init_backend,
    measure_slice,
)

def _mk_slice_engine(cfg70, n_layers, args, quant):
    from distributed_gpu_inference_tpu.models.loader import (
        init_quantized_streamed,
    )
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )

    cfg = dataclasses.replace(cfg70, name=f"llama3-70b-slice{n_layers}",
                              num_layers=n_layers)
    max_seq = args.prompt_len + args.decode_tokens + 32
    # ALWAYS stream-init quantized: a 4-layer 70B-width slice is ~11 GB
    # bf16 — past the engine's on-device full-precision build limit
    # (runtime/engine.py _QUANT_DEVICE_BUILD_LIMIT). Streamed init peaks
    # at the int8 tree + one f32 layer slice.
    params = (
        init_quantized_streamed(cfg, quant, dtype="bfloat16", seed=0)
        if quant else None
    )
    # no quant_cache_dir: explicit params bypass the engine's orbax cache
    # entirely (it only applies to engine-built trees), and the streamed
    # init IS the fast path for random-init weights
    return TPUEngine(
        cfg,
        EngineConfig(
            max_batch_size=args.batch, max_seq_len=max_seq, block_size=32,
            prefill_buckets=(args.prompt_len,), enable_prefix_cache=False,
            quantization=quant,
        ),
        params=params,
    ), cfg

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", default="4,8",
                    help="two slice depths; the difference isolates "
                         "per-layer cost")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--decode-tokens", type=int, default=64)
    ap.add_argument("--stages", type=int, default=8)
    ap.add_argument("--quantization", default="int8")
    add_platform_arg(ap)
    args = ap.parse_args()

    backend = init_backend(args)

    from distributed_gpu_inference_tpu.models.configs import get_model_config

    cfg70 = get_model_config("llama3-70b")
    l_lo, l_hi = (int(x) for x in args.layers.split(","))

    measured = {}
    for n in (l_lo, l_hi):
        eng, cfg = _mk_slice_engine(cfg70, n, args, args.quantization)
        t_prefill, t_step = measure_slice(
            eng, cfg, args.batch, args.prompt_len, args.decode_tokens
        )
        measured[n] = {"prefill_s": round(t_prefill, 3),
                       "decode_step_ms": round(t_step * 1e3, 2)}
        del eng
        import gc

        gc.collect()

    # per-layer cost from the slice DIFFERENCE (embed/head cancel)
    d_layers = l_hi - l_lo
    per_layer_decode_ms = (
        measured[l_hi]["decode_step_ms"] - measured[l_lo]["decode_step_ms"]
    ) / d_layers
    per_layer_prefill_s = (
        measured[l_hi]["prefill_s"] - measured[l_lo]["prefill_s"]
    ) / d_layers
    # what's left of the lo-slice after removing its layers ≈ embed+head+
    # dispatch overhead (the ends of the pipeline + per-call cost)
    ends_decode_ms = (
        measured[l_lo]["decode_step_ms"] - l_lo * per_layer_decode_ms
    )

    # ---- per-stage HBM fit (80 layers / stages), int8 weights ----
    layers_per_stage = cfg70.num_layers // args.stages
    layer_bytes_int8 = cfg70.layer_param_bytes(1)
    embed_bytes = cfg70.vocab_size * cfg70.hidden_size * 2      # bf16
    head_bytes = embed_bytes                                     # untied
    # serving KV pool per stage: batch x ctx 8k, GQA 8 heads x 128, bf16,
    # only this stage's layers
    ctx = 8192
    kv_stage_bytes = (
        args.batch * ctx * cfg70.num_kv_heads * cfg70.head_dim * 2 * 2
        * layers_per_stage
    )
    stage_mid_gb = (layers_per_stage * layer_bytes_int8 + kv_stage_bytes) / 1e9
    stage_end_gb = stage_mid_gb + max(embed_bytes, head_bytes) / 1e9

    # ---- projection: steady-state pipeline decode ----
    # bottleneck stage = 10 layers + the head end (stage 7); hop = [B, 8192]
    # bf16 per microbatch over ICI
    hop_ms = (args.batch * cfg70.hidden_size * 2) / (ICI_GBPS * 1e9) * 1e3
    stage_ms = layers_per_stage * per_layer_decode_ms + hop_ms
    stage_end_ms = stage_ms + ends_decode_ms        # head-bearing stage
    bottleneck_ms = max(stage_ms, stage_end_ms)
    # pipeline full (microbatches >= stages): one microbatch of B tokens
    # emerges per bottleneck step
    proj_decode_tps = args.batch / (bottleneck_ms / 1e3)
    # per-token latency = sum of stage times
    token_latency_ms = args.stages * stage_ms + ends_decode_ms

    emit({
        "benchmark": "pipeline_70b",
        "metric": "projected_70b_8chip_decode_tokens_per_s",
        "value": round(proj_decode_tps, 1),
        "unit": "tokens/s (measured-grounded projection)",
        "backend": backend,
        "quantization": args.quantization,
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "measured_slices": measured,
        "per_layer_decode_ms": round(per_layer_decode_ms, 3),
        "per_layer_prefill_s": round(per_layer_prefill_s, 4),
        "ends_decode_ms": round(ends_decode_ms, 2),
        "projection": {
            "stages": args.stages,
            "layers_per_stage": layers_per_stage,
            "hop_ms_per_microbatch": round(hop_ms, 4),
            "stage_ms_mid": round(stage_ms, 2),
            "stage_ms_head_end": round(stage_end_ms, 2),
            "decode_tokens_per_s": round(proj_decode_tps, 1),
            "token_latency_ms": round(token_latency_ms, 1),
            "prefill_s_512_batch": round(
                args.stages * layers_per_stage * per_layer_prefill_s, 2
            ),
        },
        "hbm_fit": {
            "layer_bytes_int8_gb": round(layer_bytes_int8 / 1e9, 3),
            "stage_mid_gb": round(stage_mid_gb, 2),
            "stage_end_gb": round(stage_end_gb, 2),
            "v5e_hbm_gb": V5E_HBM_GB,
            "fits": stage_end_gb < V5E_HBM_GB,
            "kv_note": f"KV pool: batch {args.batch} x {ctx} ctx bf16, "
                       f"per-stage layers only",
        },
        "schedule_validation": "benchmarks/distributed.py --mode spmd "
                               "--model llama3-70b-micro (8-dev virtual "
                               "mesh, real ppermute microbatch schedule at "
                               "70B layer width)",
    })

if __name__ == "__main__":
    main()
