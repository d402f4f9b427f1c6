#!/usr/bin/env python
"""Prefill/decode disaggregation benchmark: hybrid vs separated, for real.

Parity with the reference's ``benchmarks/pd_separation.py`` metrics (TTFT and
TPOT, hybrid vs separated) — but the reference computes both from an analytic
roofline model (:182-225); here both configurations RUN:

- **hybrid**: one engine interleaves new prefills with ongoing decodes (the
  classic interference regime — a long prefill stalls every decode step).
- **separated**: a prefill engine and a decode engine; each finished prefill
  migrates its KV to the decode engine over the real export→wire→adopt path
  (``runtime/kv_handoff.py``), decodes run without prefill interference.

Usage:
    python -m benchmarks.pd_separation --requests 8 --prompt-len 128 \
        --max-tokens 32
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


from benchmarks.common import (
    Timer,
    add_platform_arg,
    emit,
    make_request,
    percentiles,
    resolve_backend_model,
    synth_prompts,
)


def _mk_engine(model, batch, max_seq, params=None, prefill_buckets=(128,)):
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )

    return TPUEngine(
        model,
        EngineConfig(
            max_batch_size=batch, max_seq_len=max_seq,
            prefill_buckets=prefill_buckets, enable_prefix_cache=False,
        ),
        params=params,
    )


def _req(p, max_tokens):
    return make_request(p, max_tokens)


_DECODE_T = 4  # decode scan length per round (amortizes host round-trips)


def _warm(eng, prompt):
    """Compile every graph the measured loops touch: the batched-wave
    prefill (generate), the single-request [1, bucket] prefill (submit),
    and the T-step decode scan — mid-measurement XLA compiles would
    otherwise dominate the percentiles."""
    eng.generate([_req(prompt, 2)])
    slot = eng.submit(_req(prompt, 3))
    while eng.slots[slot] is not None and \
            eng.slots[slot].finish_reason is None:
        eng.decode_multi(_DECODE_T)
    eng.finish_slot(slot, cache=False)


def _decode_round(eng, tpots):
    d0 = time.perf_counter()
    out = eng.decode_multi(_DECODE_T)
    if out:
        # normalize by the steps the round actually advanced (a slot can
        # finish mid-scan) — dividing by the fixed T would understate
        # per-token latency in tail rounds
        steps_run = max(len(v) for v in out.values())
        if steps_run:
            per_tok = (time.perf_counter() - d0) * 1000.0 / steps_run
            tpots.extend([per_tok] * steps_run)
    return out


def run_hybrid(model, prompts, args, params):
    """One engine, staggered arrivals: prefills interleave with decodes."""
    eng = _mk_engine(model, args.requests, args.max_seq, params,
                     (args.prompt_len,))
    _warm(eng, prompts[0])

    ttfts, tpots = [], []
    with Timer() as t:
        for p in prompts:
            # a new request arrives: prefill NOW (stalls ongoing decodes)
            t0 = time.perf_counter()
            eng.submit(_req(p, args.max_tokens))
            ttfts.append((time.perf_counter() - t0) * 1000.0)
            # run a few decode rounds for everyone between arrivals
            for _ in range(args.decode_per_arrival):
                _decode_round(eng, tpots)
        # drain
        while eng.num_active:
            _decode_round(eng, tpots)
            for i, s in enumerate(list(eng.slots)):
                if s is not None and s.finish_reason is not None:
                    eng.finish_slot(i)
    return ttfts, tpots, t.elapsed


def run_separated(model, prompts, args, params, migration="host"):
    """Prefill engine + decode engine + real KV migration between them.

    ``migration="host"``: export → serialize → deserialize → adopt (the
    DCN/cross-host wire path; it pays a device→host copy of every page,
    whose rate is not measured on the current chip).
    ``migration="device"``: ``migrate_kv_device`` — pages move pool→pool in
    one jitted gather-scatter, zero host bytes (the intra-slice PD path:
    prefill and decode pools of one process/slice, BASELINE config 5).
    """
    import jax

    from distributed_gpu_inference_tpu.runtime.kv_handoff import (
        adopt_kv,
        deserialize_handoff,
        export_slot_kv,
        migrate_kv_device,
        serialize_handoff,
    )

    pre = _mk_engine(model, 2, args.max_seq, params, (args.prompt_len,))
    dec = _mk_engine(model, args.requests, args.max_seq, pre.params,
                     (args.prompt_len,))
    _warm(pre, prompts[0])
    _warm(dec, prompts[0])
    # warm the migration path (export/copy + adopt graphs)
    wslot = pre.submit(_req(prompts[0], 3))
    if migration == "device":
        aslot = migrate_kv_device(pre, dec, wslot)
        pre.finish_slot(wslot, cache=False)
    else:
        wire = serialize_handoff(export_slot_kv(pre, wslot))
        pre.finish_slot(wslot, cache=False)
        aslot = adopt_kv(dec, deserialize_handoff(wire))
    dec.finish_slot(aslot, cache=False)

    ttfts, tpots, migrate_ms = [], [], []
    migrate_bytes = 0
    with Timer() as t:
        pending = list(prompts)
        active = 0
        while pending or active:
            if pending:
                p = pending.pop(0)
                t0 = time.perf_counter()
                slot = pre.submit(_req(p, args.max_tokens))
                ttfts.append((time.perf_counter() - t0) * 1000.0)
                m0 = time.perf_counter()
                if migration == "device":
                    dslot = migrate_kv_device(pre, dec, slot)
                    # wait for the device copy so migrate_ms covers it,
                    # not just its dispatch — same basis as host mode
                    jax.block_until_ready(dec.kv["k"])
                    migrate_bytes += 0
                    pre.finish_slot(slot, cache=False)
                else:
                    wire = serialize_handoff(export_slot_kv(pre, slot))
                    migrate_bytes += len(wire)
                    pre.finish_slot(slot, cache=False)
                    adopt_kv(dec, deserialize_handoff(wire))
                migrate_ms.append((time.perf_counter() - m0) * 1000.0)
                active += 1
            # decode pool advances independently of prefill arrivals
            for _ in range(args.decode_per_arrival):
                _decode_round(dec, tpots)
            for i, s in enumerate(list(dec.slots)):
                if s is not None and s.finish_reason is not None:
                    dec.finish_slot(i)
                    active -= 1
            if not pending and not dec.num_active:
                break
    return ttfts, tpots, migrate_ms, migrate_bytes, t.elapsed


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-tokens", type=int, default=32)
    ap.add_argument("--decode-per-arrival", type=int, default=4)
    ap.add_argument("--migration", default="device",
                    choices=("host", "device", "both"),
                    help="separated-pool KV migration path: host = "
                         "serialize/wire (DCN shape), device = pool→pool "
                         "jitted copy (intra-slice shape)")
    add_platform_arg(ap)
    args = ap.parse_args()

    import jax

    backend, model = resolve_backend_model(args)
    args.max_seq = args.prompt_len + args.max_tokens + 16

    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.models.configs import get_model_config

    cfg = get_model_config(model)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompts = synth_prompts(args.requests, args.prompt_len, cfg.vocab_size)

    hy_ttft, hy_tpot, hy_s = run_hybrid(model, prompts, args, params)
    modes = ["host", "device"] if args.migration == "both" \
        else [args.migration]
    sep_out = {}
    for mode in modes:
        sep_ttft, sep_tpot, mig_ms, mig_bytes, sep_s = run_separated(
            model, prompts, args, params, migration=mode
        )
        sep_out[mode] = {
            "ttft_ms": percentiles(sep_ttft),
            "tpot_ms": percentiles(sep_tpot),
            "migration_ms": percentiles(mig_ms),
            "migration_mb": round(mig_bytes / 1e6, 2),
            "migration_mb_s": round(
                (mig_bytes / 1e6) / (sum(mig_ms) / 1e3), 2
            ) if mig_ms and sum(mig_ms) and mig_bytes else None,
            "elapsed_s": round(sep_s, 3),
        }

    hy = percentiles(hy_tpot)
    best = sep_out.get("device") or sep_out[modes[0]]
    sep = best["tpot_ms"]
    emit({
        "benchmark": "pd_separation",
        "metric": "decode_tpot_p95_improvement",
        "value": round(hy["p95"] / sep["p95"], 3)
        if hy["p95"] and sep["p95"] else None,
        "unit": "x (hybrid p95 TPOT / separated p95 TPOT)",
        "model": model,
        "backend": backend,
        "requests": args.requests,
        "prompt_len": args.prompt_len,
        "max_tokens": args.max_tokens,
        "hybrid": {
            "ttft_ms": percentiles(hy_ttft),
            "tpot_ms": hy,
            "elapsed_s": round(hy_s, 3),
        },
        **{f"separated_{m}": v for m, v in sep_out.items()},
        # both pools share ONE chip here, so device work serializes and the
        # TPOT comparison cannot show disaggregation's full benefit — on a
        # real deployment the pools run on disjoint slice partitions
        # (BASELINE.json config 5: v5e-64); what this measures for real is
        # the migration path cost (device copy vs export → wire → adopt)
        "single_chip_note": "pools share one device; see migration_*",
    })


if __name__ == "__main__":
    main()
