#!/usr/bin/env python
"""Speculative decoding benchmark: real draft/verify loop, measured speedup.

Parity with the reference's ``benchmarks/speculative.py`` metrics (accept
rate, tokens/step, speedup, draft overhead) — but the reference's harness is
an analytic accept-rate SIMULATOR (:123-272); this one runs the actual
on-device tree draft→verify→accept loop and an identical vanilla decode for
the speedup denominator.

Methodology: random-init weights have near-uniform logits no draft can
match, so the harness first TRAINS the target on a learnable synthetic task
(noisy Markov chain, ``benchmarks/common.train_toy_lm``) and then distills
the EAGLE draft head against it on-device
(``runtime.speculative.distill_draft_params``) — every number is real
compute on real (trained) weights, no simulated accept rates.

Usage:
    python -m benchmarks.speculative --model llama3-mini --requests 4 \
        --max-tokens 64
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import (
    Timer,
    add_platform_arg,
    emit,
    make_request,
    percentiles,
    resolve_backend_model,
    train_toy_lm,
)


def _flatten_params(params, prefix=""):
    """Nested dict-of-arrays → ({'a.b.c': array}, {'a.b.c': dtype_name}).

    bfloat16 does not survive np.savez/np.load (comes back as raw void
    ``|V2``), so extended dtypes ride as uint16 bit patterns with their
    dtype name in a sidecar map."""
    import numpy as np

    out, dtypes = {}, {}
    for k, v in params.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            sub, subd = _flatten_params(v, key + ".")
            out.update(sub)
            dtypes.update(subd)
        else:
            arr = np.asarray(v)
            dtypes[key] = arr.dtype.name
            if arr.dtype.name == "bfloat16":
                arr = arr.view(np.uint16)
            out[key] = arr
    return out, dtypes


def _unflatten_params(data):
    """Inverse of _flatten_params over an npz (ignoring non 'p.' keys)."""
    import json as _json

    import ml_dtypes
    import numpy as np

    dtypes = _json.loads(str(data["dtypes"])) if "dtypes" in data.files else {}
    out = {}
    for key in data.files:
        if not key.startswith("p."):
            continue
        arr = data[key]
        name = dtypes.get(key[2:])
        if name == "bfloat16":
            arr = arr.view(ml_dtypes.bfloat16)
        parts = key[2:].split(".")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return out


def _run_micro(args, spec, vanilla, reqs, model, backend,
               t_train, t_distill, widths, fl) -> None:
    """The direct spec-vs-vanilla measurement (rounds 2-4 metric): both
    engines driven by their own generate() loops, no batcher. NOTE the
    vanilla side decodes per-token here (1 host round per token) — the
    serving comparison below is the one whose baseline amortizes the host
    round over a multi-step scan."""
    # warmup both paths (compile), then reset counters: warmup drafting
    # must not contaminate the reported accept rate / tokens-per-step
    spec.generate(reqs())
    vanilla.generate(reqs())
    for k in spec.stats:
        spec.stats[k] = 0

    with Timer() as t_spec:
        spec_resps = spec.generate(reqs())
    with Timer() as t_van:
        van_resps = vanilla.generate(reqs())

    spec_tokens = sum(r.completion_tokens for r in spec_resps)
    van_tokens = sum(r.completion_tokens for r in van_resps)
    st = spec.get_stats()
    spec_tps = spec_tokens / t_spec.elapsed
    van_tps = van_tokens / t_van.elapsed

    emit({
        "benchmark": "speculative",
        "metric": "speculative_speedup",
        "value": round(spec_tps / van_tps, 3) if van_tps else None,
        "unit": "x vs vanilla decode",
        "model": model,
        "backend": backend,
        "configured_widths": list(widths),
        "widths_at_measurement": st.get("current_widths"),
        "accept_rate": round(
            st["accepted"] / st["drafted"] if st.get("drafted") else 0.0, 4
        ),
        "tokens_per_step": round(st.get("tokens_per_step", 0.0), 3),
        "spec_tokens_per_s": round(spec_tps, 2),
        "vanilla_tokens_per_s": round(van_tps, 2),
        "spec_elapsed_s": round(t_spec.elapsed, 3),
        "vanilla_elapsed_s": round(t_van.elapsed, 3),
        "target_train_s": round(t_train.elapsed, 1),
        "draft_distill_s": round(t_distill.elapsed, 1),
        "target_trained": not (args.no_train or args.quantization),
        "quantization": args.quantization,
        "feature_layers": list(fl) if fl else None,
        "distill_data": args.distill_data,
    })


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-tokens", type=int, default=64)
    ap.add_argument("--widths", default="4,2,2",
                    help="tree widths per level, comma-separated")
    ap.add_argument("--train-steps", type=int, default=1500,
                    help="target-model training steps on the synthetic task")
    ap.add_argument("--distill-steps", type=int, default=800,
                    help="EAGLE draft-head distillation steps")
    ap.add_argument("--distill-seq-len", type=int, default=64,
                    help="distill stream length: must COVER the serving "
                         "positions (prompt + max-tokens) or acceptance "
                         "collapses out-of-distribution past it — the "
                         "round-5 finding that explained serving accept "
                         "at 256-token generations being ~0 while the "
                         "64-token micro measured 0.36")
    ap.add_argument("--task-vocab", type=int, default=4096,
                    help="Markov-chain state count for target training; "
                         "smaller = sharper target at a fixed step budget")
    ap.add_argument("--no-adaptive", action="store_true",
                    help="pin the tree widths (no adaptive depth changes): "
                         "mid-measurement depth changes compile fresh "
                         "step graphs (seconds of XLA time inside the timed "
                         "window) and make accept rates incomparable "
                         "across ablation cells")
    ap.add_argument("--feature-layers", default=None,
                    help="EAGLE-3 multi-layer draft features: comma layer "
                         "indices (e.g. 1,2,3) or 'auto' (low/mid/high). "
                         "Default: last layer only (EAGLE-1)")
    ap.add_argument("--distill-data", default="random",
                    choices=("random", "on-policy", "task"),
                    help="distill streams: uniform-random tokens (round-3 "
                         "behavior), the target's own sampled generations "
                         "(on-policy), or the trained task distribution")
    ap.add_argument("--quantization", default=None,
                    help="weight-only target quantization (int8 | fp8): the "
                         "flagship 8B target only fits the chip quantized; "
                         "implies --no-train (a quantized target cannot be "
                         "trained) — measures the real tree machinery cost "
                         "at flagship scale (VERDICT r3 #1a)")
    ap.add_argument("--no-train", action="store_true",
                    help="skip target training (random-init target): the "
                         "draft is still distilled against the real frozen "
                         "target, so accept rates are real but lower — for "
                         "targets whose f32 training does not fit the chip")
    ap.add_argument("--task-noise", type=float, default=0.05,
                    help="Markov-chain noise for target training: lower = "
                         "more deterministic continuations = the high-"
                         "acceptance regime real trained models live in "
                         "(reference claims 2-3x THERE, README.md:30)")
    ap.add_argument("--rounds-per-dispatch", type=int, default=8,
                    help="tree rounds fused per device dispatch "
                         "(SpeculativeConfig.rounds_per_dispatch): the "
                         "spec analogue of decode_multi's T — the serving "
                         "comparison is only fair when BOTH paths amortize "
                         "the host round")
    # -- serving mode (VERDICT r4 #4): spec THROUGH the batcher ----------
    ap.add_argument("--serving-rate", default=None,
                    help="after the micro measurement, drive an open-loop "
                         "Poisson workload at this req/s THROUGH the "
                         "ContinuousBatcher twice — spec-on vs spec-off — "
                         "and emit a speculative_serving line per rate "
                         "(comma-separated rates sweep)")
    ap.add_argument("--serving-requests", type=int, default=24)
    ap.add_argument("--skip-micro", action="store_true",
                    help="skip the micro spec-vs-vanilla measurement and "
                         "go straight to the serving comparison (the "
                         "micro vanilla baseline decodes per-token, which "
                         "dominates wall-clock at long max-tokens)")
    ap.add_argument("--spec-max-batch", type=int, default=2,
                    help="batcher routing knob: spec fires only when the "
                         "entire waiting load is <= this many greedy "
                         "requests")
    ap.add_argument("--spec-max-active", type=int, default=2,
                    help="batcher routing knob: a wave may start while up "
                         "to this many paged slots are active (0 = require "
                         "an idle engine — sticky-paged at steady rates)")
    ap.add_argument("--train-out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--measure-from", default=None, help=argparse.SUPPRESS)
    add_platform_arg(ap)
    args = ap.parse_args()

    from distributed_gpu_inference_tpu.models.configs import get_model_config

    widths = tuple(int(w) for w in args.widths.split(","))
    # big models train in a process of their own (the f32 training peak
    # goes back to the chip when it exits). A chip belongs to one process
    # at a time, so the parent that runs the two phases in turn must stay
    # off the backend: everything up to the return below is decided
    # jax-free (models.configs imports no jax).
    big = bool(args.model) and \
        get_model_config(args.model).num_params > 5e8
    if big and not args.no_train and not args.quantization \
            and not args.train_out \
            and not args.measure_from and args.platform != "cpu":
        # ORCHESTRATE ONLY: phase 1 (train) and phase 2 (distill +
        # measure) each run in their own fresh process, one after the
        # other; this one never touches jax and just shuttles the npz.
        import subprocess
        import sys as _sys
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            out = f"{td}/trained.npz"
            base = [_sys.executable, "-m", "benchmarks.speculative",
                    "--model", args.model,
                    "--train-steps", str(args.train_steps),
                    "--distill-steps", str(args.distill_steps),
                    "--requests", str(args.requests),
                    "--prompt-len", str(args.prompt_len),
                    "--max-tokens", str(args.max_tokens),
                    "--widths", args.widths,
                    "--task-vocab", str(args.task_vocab),
                    "--task-noise", str(args.task_noise),
                    "--distill-seq-len", str(args.distill_seq_len),
                    "--rounds-per-dispatch", str(args.rounds_per_dispatch),
                    "--spec-max-batch", str(args.spec_max_batch),
                    "--spec-max-active", str(args.spec_max_active),
                    "--serving-requests", str(args.serving_requests),
                    "--distill-data", args.distill_data]
            if args.serving_rate:
                base += ["--serving-rate", str(args.serving_rate)]
            if args.skip_micro:
                base += ["--skip-micro"]
            if args.feature_layers:
                base += ["--feature-layers", args.feature_layers]
            if args.no_adaptive:
                base += ["--no-adaptive"]
            import time as _time

            t0 = _time.perf_counter()
            subprocess.run(base + ["--train-out", out], check=True)
            t_train_s = _time.perf_counter() - t0
            import os as _os

            _os.environ["DGI_SPEC_TRAIN_S"] = f"{t_train_s:.1f}"
            subprocess.run(base + ["--measure-from", out], check=True)
        return

    trained_blob = None
    if args.measure_from:
        import numpy as _np

        trained_blob = _np.load(args.measure_from, allow_pickle=False)

    import jax

    backend, model = resolve_backend_model(args, tpu_default="llama3-tiny")

    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )
    from distributed_gpu_inference_tpu.runtime.speculative import (
        SpeculativeConfig,
        SpeculativeDecoder,
        distill_draft_params,
    )

    cfg = get_model_config(model)
    big = cfg.num_params > 5e8

    def run_training():
        return train_toy_lm(
            cfg, jax.random.PRNGKey(0), steps=args.train_steps,
            optimizer="adafactor" if big else "adam",
            task_vocab=args.task_vocab,
            noise=args.task_noise,
            batch=8 if big else 16,
        )

    if args.train_out:
        # subprocess mode: train, dump bf16 params + chain spec, exit —
        # the process boundary returns the whole f32 training peak
        import numpy as _np

        params, sample_stream = run_training()
        import json as _json

        flat, dtypes = _flatten_params(params)
        _np.savez(args.train_out, perm=_np.asarray(sample_stream.perm),
                  noise=sample_stream.noise, dtypes=_json.dumps(dtypes),
                  **{f"p.{k}": v for k, v in flat.items()})
        return

    if trained_blob is not None:
        import os as _os

        from benchmarks.common import make_chain_sampler

        class _T:  # orchestrator-measured training wall time
            elapsed = float(_os.environ.get("DGI_SPEC_TRAIN_S", "0"))

        t_train = _T()
        params = _unflatten_params(trained_blob)
        sample_stream = make_chain_sampler(
            trained_blob["perm"], float(trained_blob["noise"]))
    elif args.no_train or args.quantization:
        class _T0:
            elapsed = 0.0

        t_train = _T0()
        if args.quantization:
            # flagship-scale target (8B int8): build through the engine's
            # quantized loader so the content-keyed orbax cache applies —
            # a second run restores int8 from disk instead of re-initing
            from distributed_gpu_inference_tpu.runtime.engine import (
                EngineConfig as _EC,
                TPUEngine as _TE,
            )

            cache = str(Path(__file__).resolve().parent.parent / ".cache" /
                        "quant")
            loader = _TE(cfg, _EC(
                max_batch_size=1, max_seq_len=64, num_blocks=4,
                prefill_buckets=(32,), quantization=args.quantization,
                quant_cache_dir=cache,
            ))
            params = loader.params
            del loader
        else:
            from distributed_gpu_inference_tpu.models import llama

            params = llama.init_params(cfg, jax.random.PRNGKey(0))

        def sample_stream(key, n, length):
            return jax.random.randint(
                key, (n, length), 1, min(cfg.vocab_size, 4096), "int32"
            )
    else:
        with Timer() as t_train:
            params, sample_stream = run_training()
    # EAGLE-3 knobs: multi-layer features + distill-data distribution
    if args.feature_layers == "auto":
        L = cfg.num_layers
        fl = tuple(sorted({max(L // 4, 0), L // 2, L - 1}))
    elif args.feature_layers:
        fl = tuple(int(x) for x in args.feature_layers.split(","))
    else:
        fl = None
    distill_kw = dict(feature_layers=fl, seq_len=args.distill_seq_len)
    if args.distill_data == "on-policy":
        distill_kw["on_policy"] = True
    elif args.distill_data == "task":
        if args.no_train or args.quantization:
            raise SystemExit("--distill-data task needs a trained target")
        distill_kw["data_stream"] = sample_stream

    with Timer() as t_distill:
        draft_params = distill_draft_params(
            cfg, params, jax.random.PRNGKey(1),
            steps=args.distill_steps, **distill_kw,
        )

    max_seq = args.prompt_len + args.max_tokens + 64
    spec = SpeculativeDecoder(
        cfg,
        params=params,
        draft_params=draft_params,
        spec_cfg=SpeculativeConfig(widths=widths, feature_layers=fl,
                                   adaptive=not args.no_adaptive,
                                   rounds_per_dispatch=args.rounds_per_dispatch),
        max_batch_size=args.requests,
        max_seq_len=max_seq,
        prefill_buckets=(args.prompt_len,),
    )
    vanilla = TPUEngine(
        cfg,
        EngineConfig(
            max_batch_size=args.requests, max_seq_len=max_seq,
            prefill_buckets=(args.prompt_len,), enable_prefix_cache=False,
        ),
        params=spec.params,  # same weights: same tokens, fair timing
    )

    prompts = [
        [int(t) for t in row]
        for row in sample_stream(
            jax.random.PRNGKey(42), args.requests, args.prompt_len
        )
    ]

    def reqs():
        return [make_request(p, args.max_tokens) for p in prompts]

    if not args.skip_micro:
        _run_micro(args, spec, vanilla, reqs, model, backend,
                   t_train, t_distill, widths, fl)

    # ---- serving mode (VERDICT r4 #4): the SAME open-loop workload through
    # the ContinuousBatcher, spec-on vs spec-off. The spec decoder only ever
    # engages through its routing gate (all-greedy waiting load <=
    # spec_max_batch, paged engine idle), so this measures the spec
    # integration as DEPLOYED, not the micro harness.
    if args.serving_rate:
        import asyncio

        from distributed_gpu_inference_tpu.runtime.batcher import (
            BatcherConfig,
            ContinuousBatcher,
        )

        # pin tree adaptation for the measurement: the scan cache is keyed
        # by (widths, rounds), so a mid-serving depth change would
        # cold-compile an unwarmed scan graph inside someone's TTFT — the
        # warmup ladder below covers exactly the pinned widths
        spec.spec_cfg.adaptive = False
        n = args.serving_requests
        srv_prompts = [
            [int(t) for t in row]
            for row in sample_stream(jax.random.PRNGKey(77), n,
                                     args.prompt_len)
        ]
        # warmup prompts come from OUTSIDE the measured set (and the spec
        # pool's prefix cache is cleared below): warming with measured
        # prompts would hand the spec-on side cached prefills the paged
        # spec-off side (prefix cache disabled) never gets
        warm_prompts = [
            [int(t) for t in row]
            for row in sample_stream(jax.random.PRNGKey(555),
                                     max(args.spec_max_batch, 1),
                                     args.prompt_len)
        ]
        bcfg = BatcherConfig(
            default_timeout_s=600.0,
            spec_max_batch=args.spec_max_batch,
            spec_max_active=args.spec_max_active,
        )
        # warm every wave width the router can start (each is a distinct
        # scan-graph batch shape) — with the SERVING budget, so the same
        # power-of-two rounds bucket compiles now, not mid-wave.
        # ALSO walk the whole rounds ladder per width: block pressure can
        # shrink a dispatch to any lower power of two at runtime
        # (advance_wave blocks_needed), and a generation's tail uses the
        # small buckets — every (width, rounds) pair must pre-compile.
        ladder = [args.max_tokens]
        r = 1
        while r < args.rounds_per_dispatch:
            ladder.append(r + 1)    # max_remaining = r+1-1 = r → bucket r
            r *= 2
        for wb in range(1, min(args.spec_max_batch,
                               spec.max_batch_size) + 1):
            for mt in ladder:
                spec.generate(
                    [make_request(p, mt) for p in warm_prompts[:wb]]
                )
        spec.manager.clear_cached()     # no warm prefixes into the measure
        for T in bcfg.horizon_levels:
            slot = vanilla.submit(make_request(srv_prompts[0], 2))
            while vanilla.slots[slot] is not None and \
                    vanilla.slots[slot].finish_reason is None:
                vanilla.decode_multi(T)
            vanilla.finish_slot(slot, cache=False)
        for k in spec.stats:
            spec.stats[k] = 0

        async def drive(spec_obj, rate):
            from benchmarks.common import open_loop_drive

            batcher = ContinuousBatcher(vanilla, bcfg, spec=spec_obj)
            batcher.start()
            res, elapsed, _ = await open_loop_drive(
                batcher, srv_prompts, args.max_tokens, rate
            )
            stats = batcher.get_stats()
            await batcher.stop()
            return res, elapsed, stats

        def side(spec_obj, rate):
            # each side starts with a cold spec prefix cache
            spec.manager.clear_cached()
            res, elapsed, stats = asyncio.run(drive(spec_obj, rate))
            okr = [r for r, _ in res if r.error is None]
            toks = sum(r.completion_tokens for r in okr)
            return {
                "ok": len(okr),
                "tokens_per_s": round(toks / elapsed, 2),
                "e2e_ms": percentiles([ms for _, ms in res]),
                "ttft_ms": percentiles(
                    [r.ttft_ms for r in okr if r.ttft_ms is not None]
                ),
                "spec_waves": stats.get("spec_waves", 0),
                "spec_completed": stats.get("spec_completed", 0),
            }

        for rate in [float(r) for r in str(args.serving_rate).split(",")]:
            off = side(None, rate)
            st0 = {k: v for k, v in spec.get_stats().items()}
            on = side(spec, rate)
            st1 = spec.get_stats()
            drafted = st1.get("drafted", 0) - st0.get("drafted", 0)
            accepted = st1.get("accepted", 0) - st0.get("accepted", 0)
            emit({
                "benchmark": "speculative_serving",
                "metric": "spec_on_vs_off_tokens_per_s",
                "value": round(
                    on["tokens_per_s"] / off["tokens_per_s"], 3
                ) if off["tokens_per_s"] else None,
                "unit": "x (open-loop through the batcher)",
                "model": model,
                "arrival_rate_rps": rate,
                "requests": n,
                "spec_max_batch": args.spec_max_batch,
                "spec_max_active": args.spec_max_active,
                "rounds_per_dispatch": args.rounds_per_dispatch,
                "serving_accept_rate": round(
                    accepted / drafted, 4) if drafted else 0.0,
                "spec_on": on,
                "spec_off": off,
            })


if __name__ == "__main__":
    main()
