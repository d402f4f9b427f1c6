#!/usr/bin/env python
"""Single-worker serving benchmark: real engine, real tokens.

Parity with ``benchmarks/single_worker.py`` in the reference (the only
reference harness that drives real engines): decode tokens/s, TTFT and E2E
p50/p95/p99, prefix-cache hit rate — measured over the continuous batcher
at a given concurrency (reference defaults: 100 requests, 8 concurrent,
256 max_tokens, :76-97).

Usage:
    python -m benchmarks.single_worker --model llama3-mini --requests 32 \
        --concurrency 8 --prompt-len 128 --max-tokens 64
"""

from __future__ import annotations

import argparse
import asyncio
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-tokens", type=int, default=64)
    ap.add_argument("--shared-prefix", type=int, default=64,
                    help="tokens of shared system prefix (prefix-cache hits)")
    ap.add_argument("--no-prefix-cache", action="store_true")
    # -- open-loop SLO mode (VERDICT r4 #3: publish a TTFT-SLO frontier) --
    ap.add_argument("--arrival-rate", default=None,
                    help="OPEN-loop mode: Poisson arrivals at this req/s "
                    "(seeded), no concurrency gate — TTFT then includes "
                    "queue wait, which is what an SLO means. "
                    "--concurrency still sizes the engine's slot count. "
                    "Comma-separated rates sweep a frontier on ONE "
                    "engine (one line per rate; the sweep pays the 8B "
                    "engine init once)")
    ap.add_argument("--seed", type=int, default=7, help="arrival-process seed")
    ap.add_argument("--quantization", default=None,
                    help="weight quantization (e.g. int8 — the 8B flagship "
                    "needs it to fit a 16 GB chip)")
    ap.add_argument("--kv-dtype", default=None, help="kv_cache_dtype")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--subwave", type=int, default=0,
                    help="admission sub-wave width (engine admission_subwave)")
    ap.add_argument("--interleave", type=int, default=0,
                    help="decode steps interleaved between admission "
                    "sub-waves/chunks (engine admission_interleave_steps)")
    ap.add_argument("--max-horizon", type=int, default=64,
                    help="cap the adaptive decode horizon (batcher "
                    "max_multi_step): an SLO config bounds the longest "
                    "admission stall to max_horizon x step, trading "
                    "peak decode throughput for TTFT")
    add_platform_arg(ap)
    args = ap.parse_args()

    import jax

    backend, model = resolve_backend_model(args)

    from distributed_gpu_inference_tpu.runtime.batcher import (
        BatcherConfig,
        ContinuousBatcher,
    )
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )
    max_seq = args.prompt_len + args.max_tokens + 16
    eng = TPUEngine(
        model,
        EngineConfig(
            max_batch_size=args.concurrency,
            max_seq_len=max_seq,
            block_size=args.block_size,
            prefill_buckets=(args.prompt_len,),
            enable_prefix_cache=not args.no_prefix_cache,
            quantization=args.quantization,
            kv_cache_dtype=args.kv_dtype,
            admission_subwave=args.subwave,
            admission_interleave_steps=args.interleave,
        ),
    )
    prompts = synth_prompts(
        args.requests, args.prompt_len, eng.model_cfg.vocab_size,
        shared_prefix_len=args.shared_prefix,
    )

    def req(p):
        return make_request(p, args.max_tokens)

    # warmup compile: prefill bucket + EVERY decode-horizon graph the
    # batcher may request (each distinct scan length T is its own XLA
    # compile — they must not land mid-measurement). Warm with a prompt
    # OUTSIDE the measured set (and cache=False) so the warmup neither
    # pre-warms the prefix cache for a measured prompt nor skews the
    # reported hit rate.
    bcfg = BatcherConfig(default_timeout_s=600.0,
                         max_multi_step=args.max_horizon)
    warm_prompt = synth_prompts(
        1, args.prompt_len, eng.model_cfg.vocab_size, seed=987,
        shared_prefix_len=0,
    )[0]
    eng.generate([make_request(warm_prompt, 2)])
    if args.subwave > 0:
        # each power-of-2 sub-wave width is its own narrow prefill graph:
        # _prefill_subwave buckets a chunk of k<=subwave requests to the
        # next power of 2 CLAMPED to the slot count — warm exactly that
        # set (e.g. subwave 6 can produce a width-8 graph; concurrency 6
        # clamps it to width 6)
        w = 1
        while True:
            width = min(w, args.concurrency)
            eng.generate(
                [make_request(warm_prompt, 2) for _ in range(width)]
            )
            if w >= args.subwave or width == args.concurrency:
                break
            w *= 2
    for T in bcfg.horizon_levels:
        # 2 tokens suffice: on-device budgets finish the slot inside the
        # T-step scan, and the T graph still compiles
        slot = eng.submit(make_request(warm_prompt, 2))
        while eng.slots[slot] is not None and \
                eng.slots[slot].finish_reason is None:
            eng.decode_multi(T)
        eng.finish_slot(slot, cache=False)
    # counters accumulated by warmup must not enter the report
    eng.manager.stats.prefix_queries = 0
    eng.manager.stats.prefix_hit_tokens = 0
    eng.manager.stats.prefix_total_tokens = 0

    async def run(rate):
        batcher = ContinuousBatcher(eng, bcfg)
        batcher.start()
        results = []

        if rate:
            # open loop via the shared driver (benchmarks/common.py
            # open_loop_drive — the one arrival-process implementation)
            from benchmarks.common import open_loop_drive

            results, elapsed, span = await open_loop_drive(
                batcher, prompts, args.max_tokens, rate, seed=args.seed
            )
            stats_snap = batcher.get_stats()
            await batcher.stop()
            return results, elapsed, span, stats_snap
        else:
            sem = asyncio.Semaphore(args.concurrency)

            async def one(p):
                async with sem:
                    t0 = time.perf_counter()
                    resp = await batcher.submit(req(p))
                    return resp, (time.perf_counter() - t0) * 1000.0

            with Timer() as t:
                results = await asyncio.gather(*(one(p) for p in prompts))
        await batcher.stop()
        return results, t.elapsed, 0.0, batcher.get_stats()

    rates = (
        [float(r) for r in str(args.arrival_rate).split(",")]
        if args.arrival_rate else [None]
    )
    for i, rate in enumerate(rates):
        if i > 0:
            # each rate must measure the same COLD state the first did:
            # drop blocks the previous rate's requests left in the prefix
            # cache (identical prompts would otherwise prefill as cache
            # hits from rate 2 on) and re-zero the per-rate counters
            eng.manager.clear_cached()
            eng.manager.stats.prefix_queries = 0
            eng.manager.stats.prefix_hit_tokens = 0
            eng.manager.stats.prefix_total_tokens = 0
        results, elapsed, arrival_span, last_batcher_stats = \
            asyncio.run(run(rate))
        resps = [r for r, _ in results]
        e2es = [ms for _, ms in results]
        ok = [r for r in resps if r.error is None]
        decoded = sum(r.completion_tokens for r in ok)
        ttfts = [r.ttft_ms for r in ok if r.ttft_ms is not None]
        stats = eng.get_stats()

        out = {
            "benchmark": "single_worker",
            "metric": "decode_tokens_per_s",
            "value": round(decoded / elapsed, 2),
            "unit": "tokens/s",
            "model": model,
            "backend": backend,
            "requests": args.requests,
            "ok": len(ok),
            "concurrency": args.concurrency,
            "prompt_len": args.prompt_len,
            "max_tokens": args.max_tokens,
            "elapsed_s": round(elapsed, 3),
            "requests_per_s": round(len(ok) / elapsed, 3),
            "ttft_ms": percentiles(ttfts),
            "e2e_ms": percentiles(e2es),
            "prefix_hit_rate": round(
                stats["kv_cache"].get("prefix_hit_rate", 0.0), 4
            ),
        }
        if rate:
            tpots = [
                (ms - r.ttft_ms) / (r.completion_tokens - 1)
                for r, ms in results
                if r.error is None and r.ttft_ms is not None
                and r.completion_tokens > 1
            ]
            b = last_batcher_stats
            out.update({
                "mode": "open_loop",
                "arrival_rate_rps": rate,
                "batcher": {
                    "decode_rounds": b.get("decode_rounds"),
                    "avg_occupancy": round(b.get("avg_occupancy", 0.0), 2),
                    "horizon": b.get("horizon"),
                    "step_latency_ema_ms": round(
                        b.get("step_latency_ema_ms", 0.0), 1
                    ),
                    "chunked_admissions": b.get("chunked_admissions"),
                    "batched_waves": b.get("batched_waves"),
                },
                # sustained = the server kept up with the offered load:
                # the run finishes within ~one service time of the last
                # arrival, i.e. the queue was not growing without bound
                "offered_span_s": round(float(arrival_span), 3),
                "drain_s": round(elapsed - float(arrival_span), 3),
                "tpot_ms": percentiles(tpots),
                "quantization": args.quantization,
                "kv_cache_dtype": args.kv_dtype,
                "interleave": args.interleave,
                "subwave": args.subwave,
            })
        emit(out)


if __name__ == "__main__":
    main()
