#!/usr/bin/env python
"""Mixtral-8x7B expert-parallel artifact: measured per-device cost → 8-chip
projection (VERDICT r4 #5).

Beyond-reference scope — SURVEY §2.2 lists MoE/EP as ABSENT even upstream —
so the bar is the same measured-grounded method as ``pipeline_70b.py``
(BASELINE config 4's artifact): every input to the projection is a real
measurement on the target silicon, nothing simulated.

The EP design (``parallel/sharding.py``, ``models/llama.py _moe_mlp``):
expert weights shard their E axis over ``model`` alongside the attention
heads; each device computes its LOCAL expert(s) for ALL tokens and XLA
all-reduces the top-k combine. On an 8-device mesh each chip therefore
holds exactly the "per-device width" of Mixtral-8x7B:

- 1 of 8 experts per layer (the dominant bytes: ~176 MB int8 each),
- 4 of 32 query heads and 1 of 8 KV heads (head_dim 128),
- the replicated router / norms / embeddings.

1. **Per-device per-layer cost, real chip**: build TWO engines at exactly
   that width (num_experts=1, top-1, heads 4/1, head_dim 128 — wq/wk/wo
   and the expert mats are byte-identical to one chip's shard) with
   different layer counts; the timing difference isolates per-layer cost
   from embed/head ends, as in pipeline_70b.
2. **HBM fit, arithmetic from the same config**: 32 layers x (expert +
   attention shard) int8 + replicated bf16 embeddings + KV pool shard.
3. **Projection**: decode step = 32 x per-device layer cost + the
   per-layer combine all-reduces bounded from activation bytes over ICI.
   The EP schedule itself executes for real on the 8-device virtual mesh
   (``__graft_entry__._dryrun_moe_expert_parallel``: mixtral-tiny
   expert-sharded serve step, bit-exact vs single-device) and at engine
   level in ``tests/test_model_moe.py``.

Usage:
    python -m benchmarks.mixtral_ep --layers 2,6 --batch 16
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

N_DEVICES = 8


def _per_device_cfg(base, n_layers: int):
    """Mixtral-8x7B's exact per-device shard width as a standalone config:
    the E/heads slices one chip of an 8-way ``model`` mesh owns."""
    return dataclasses.replace(
        base,
        name=f"mixtral-ep-slice{n_layers}",
        num_layers=n_layers,
        num_experts=1,
        num_experts_per_tok=1,
        num_heads=base.num_heads // N_DEVICES,        # 4
        num_kv_heads=base.num_kv_heads // N_DEVICES,  # 1
        head_dim=base.head_dim,                       # keep 128
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", default="2,6",
                    help="two slice depths; the difference isolates "
                         "per-layer cost")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--decode-tokens", type=int, default=64)
    ap.add_argument("--quantization", default="int8")
    add_platform_arg(ap)
    args = ap.parse_args()

    backend = init_backend(args)

    from distributed_gpu_inference_tpu.models.configs import get_model_config
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )

    base = get_model_config("mixtral-8x7b")
    l_lo, l_hi = (int(x) for x in args.layers.split(","))
    max_seq = args.prompt_len + args.decode_tokens + 32

    measured = {}
    for n in (l_lo, l_hi):
        cfg = _per_device_cfg(base, n)
        eng = TPUEngine(
            cfg,
            EngineConfig(
                max_batch_size=args.batch, max_seq_len=max_seq,
                block_size=32, prefill_buckets=(args.prompt_len,),
                enable_prefix_cache=False,
                quantization=args.quantization,
            ),
        )
        t_prefill, t_step = measure_slice(
            eng, cfg, args.batch, args.prompt_len, args.decode_tokens
        )
        measured[n] = {"prefill_s": round(t_prefill, 3),
                       "decode_step_ms": round(t_step * 1e3, 2)}
        del eng
        import gc

        gc.collect()

    d_layers = l_hi - l_lo
    per_layer_decode_ms = (
        measured[l_hi]["decode_step_ms"] - measured[l_lo]["decode_step_ms"]
    ) / d_layers
    per_layer_prefill_s = (
        measured[l_hi]["prefill_s"] - measured[l_lo]["prefill_s"]
    ) / d_layers
    ends_decode_ms = (
        measured[l_lo]["decode_step_ms"] - l_lo * per_layer_decode_ms
    )

    # ---- per-device HBM fit (int8 weights) ----
    # expert mats: 3 x hidden x intermediate per expert, 1 expert/device
    expert_bytes = 3 * base.hidden_size * base.intermediate_size
    # attention shard: wq 4 heads + wk/wv 1 kv head + wo, all x128
    attn_bytes = base.hidden_size * base.head_dim * (
        base.num_heads // N_DEVICES * 2          # wq + wo
        + base.num_kv_heads // N_DEVICES * 2     # wk + wv
    )
    router_bytes = base.hidden_size * base.num_experts   # replicated, f32/4
    layer_dev_bytes = expert_bytes + attn_bytes + router_bytes
    embed_bytes = base.vocab_size * base.hidden_size * 2   # bf16, replicated
    head_bytes = embed_bytes                               # untied
    ctx = 4096
    kv_dev_bytes = (
        args.batch * ctx * (base.num_kv_heads // N_DEVICES) * base.head_dim
        * 2 * 2 * base.num_layers
    )
    dev_gb = (
        base.num_layers * layer_dev_bytes + embed_bytes + head_bytes
        + kv_dev_bytes
    ) / 1e9

    # ---- projection: 8-way EP decode ----
    # two all-reduces per layer ([T, H] combine + attention wo), bf16
    ar_bytes = 2 * args.batch * base.hidden_size * 2
    # ring all-reduce moves ~2x the payload over the slowest link
    ar_ms = (2 * ar_bytes) / (ICI_GBPS * 1e9) * 1e3
    step_ms = base.num_layers * (per_layer_decode_ms + ar_ms) \
        + ends_decode_ms
    proj_decode_tps = args.batch / (step_ms / 1e3)
    prefill_s = base.num_layers * per_layer_prefill_s

    emit({
        "benchmark": "mixtral_ep",
        "metric": "projected_mixtral8x7b_8chip_decode_tokens_per_s",
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
            "devices": N_DEVICES,
            "experts_per_device": 1,
            "allreduce_ms_per_layer": round(ar_ms, 4),
            "decode_step_ms": round(step_ms, 2),
            "decode_tokens_per_s": round(proj_decode_tps, 1),
            "prefill_s_512_batch": round(prefill_s, 2),
        },
        "hbm_fit": {
            "expert_bytes_int8_mb": round(expert_bytes / 1e6, 1),
            "layer_dev_bytes_int8_mb": round(layer_dev_bytes / 1e6, 1),
            "per_device_gb": round(dev_gb, 2),
            "v5e_hbm_gb": V5E_HBM_GB,
            "fits": dev_gb < V5E_HBM_GB,
            "kv_note": f"KV pool shard: batch {args.batch} x {ctx} ctx "
                       "bf16, 1/8 of the KV heads",
        },
        "schedule_validation": "__graft_entry__ dryrun regime 7 "
                               "(mixtral-tiny EP serve step, bit-exact vs "
                               "single-device) + tests/test_model_moe.py",
    })


if __name__ == "__main__":
    main()
