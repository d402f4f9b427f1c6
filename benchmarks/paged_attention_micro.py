#!/usr/bin/env python
"""Micro-benchmark: Pallas paged-attention kernels vs the XLA gather
fallback, on-device. Each variant runs ``--iters`` calls chained inside one
jitted ``fori_loop`` (one dispatch, a data dependence between calls), the
timer stops after ``block_until_ready``, and the time of a trivial jitted
op is subtracted as the dispatch overhead.

Not measured on the current chip: the crossover constants in
``ops/attention.py`` (padded-context floor, bare-read row count) date from
another chip set-up whose records are deleted.

The kernel's design advantage is walking only live pages: the XLA path
gathers the full padded block table for every sequence, the kernel's
fori_loop bound is the sequence's actual page count (and the
sliding-window start group). Mixed lengths are the continuous-batching
steady state, so the kernel is the default on TPU for decode
(ops/attention.py impl="auto").

Batch-size crossover: this micro-bench's NON-FUSED read kernel re-stages
pages per row, so its cost scales with rows where one gather amortizes.
SERVING never sees this: the model's decode path calls the fused kernel
through ``ops/attention.py resolve_impl`` (label emitted as
``serving_impl`` below). Since round 6 the crossover itself lives in
``resolve_impl`` (``fused=False`` + ``rows``; ``MICRO_READ_XLA_MIN_BATCH``
is an env OVERRIDE only) — this bench calls it instead of duplicating the
threshold, and the emitted ``micro_auto_impl`` labels the auto-selected
variant whose timing feeds the derived ``live_kv_gb_s``.

``--impl ragged`` measures the round-6 ragged kernel — one invocation over
a flattened row batch whose rows carry their own query spans. With
``--q-span 1`` it is an apples-to-apples decode read against the other two
variants; wider spans measure the mixed prefill+decode round shape serving
actually dispatches (``--mixed-spans`` builds the decode-heavy + one-chunk
row mix of a ragged admission round).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--q-heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--ctx", type=int, default=8000)
    ap.add_argument("--mixed", action="store_true",
                    help="heterogeneous lens 50..ctx (continuous batching)")
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--impl", choices=["all", "xla", "pallas", "ragged"],
                    default="all",
                    help="which read variant(s) to measure: the XLA "
                         "gather, the non-fused decode kernel, the ragged "
                         "prefill+decode kernel, or all of them")
    ap.add_argument("--q-span", type=int, default=1,
                    help="query span per row for the ragged variant "
                         "(1 = decode-shaped rows; >1 = uniform "
                         "verify/chunk rows)")
    ap.add_argument("--mixed-spans", action="store_true",
                    help="ragged variant only: decode rows (span 1) plus "
                         "ONE prefill chunk row of --q-span queries — the "
                         "row mix of a ragged admission round")
    ap.add_argument("--skip-xla", action="store_true",
                    help="skip the XLA-gather variant (its full-table "
                         "gather materializes [B, M*Bk, Hkv, D] context — "
                         "hundreds of MB at batch 32 x ctx 4k)")
    ap.add_argument("--skip-pallas", action="store_true",
                    help="skip the Pallas kernel variants (CPU smoke runs: "
                         "interpret-mode pallas inside the timing fori_loop "
                         "trips a JAX lowering-cache limitation)")
    ap.add_argument("--int8", action="store_true",
                    help="also measure the int8-KV (per-token scales) "
                         "kernel path")
    args = ap.parse_args()
    want = {
        "all": {"xla", "pallas", "ragged"},
        "xla": {"xla"}, "pallas": {"pallas"}, "ragged": {"ragged"},
    }[args.impl]
    if args.skip_xla:
        want.discard("xla")
    if args.skip_pallas:
        want -= {"pallas", "ragged"}
    if not want:
        ap.error("the --impl/--skip flags leave nothing to measure")
    if args.int8 and "pallas" not in want:
        ap.error("--int8 measures the Pallas int8 kernel; it needs the "
                 "pallas variant selected")
    if args.mixed_spans and "ragged" not in want:
        ap.error("--mixed-spans shapes the ragged variant's rows; it needs "
                 "the ragged variant selected")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_gpu_inference_tpu.ops.attention import (
        paged_attention_xla,
        resolve_impl,
    )
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_attention_pallas,
        ragged_paged_attention,
    )

    b, hkv, nh, d = args.batch, args.kv_heads, args.q_heads, args.head_dim
    block, ctx, iters = args.block_size, args.ctx, args.iters

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))  # compile + warm
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            best = min(best, time.perf_counter() - t0)
        return best

    tiny = jnp.ones((8, 128), jnp.float32)
    dispatch = min(timed(jax.jit(lambda x: x + 1), tiny) for _ in range(3))

    m = -(-ctx // block)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    kp = jax.random.normal(ks[0], (1 + b * m, hkv, block, d), jnp.bfloat16)
    vp = jax.random.normal(ks[1], (1 + b * m, hkv, block, d), jnp.bfloat16)
    tables = jnp.asarray(
        np.arange(1, 1 + b * m, dtype=np.int32).reshape(b, m)
    )
    if args.mixed:
        base = [ctx, 100, ctx // 2, 50, ctx // 4, ctx, 500, 1000]
        lens = jnp.asarray((base * (b // len(base) + 1))[:b], jnp.int32)
    else:
        lens = jnp.full((b,), ctx, jnp.int32)
    pos = (lens - 1)[:, None]
    q = jax.random.normal(ks[3], (b, 1, nh, d), jnp.bfloat16)

    # ragged-variant operands: [B, S] spans. Default S = --q-span for every
    # row; --mixed-spans keeps decode rows at span 1 and gives ONE row the
    # full chunk (the ragged admission round's shape).
    s_rag = max(1, args.q_span)
    pos_rag = np.full((b, s_rag), -1, np.int32)
    lens_np = np.asarray(lens)
    for i in range(b):
        span = 1 if (args.mixed_spans and i != 0) else s_rag
        span = min(span, int(lens_np[i]))
        pos_rag[i, :span] = np.arange(
            lens_np[i] - span, lens_np[i], dtype=np.int32
        )
    q_rag = jax.random.normal(ks[2], (b, s_rag, nh, d), jnp.bfloat16)
    pos_rag = jnp.asarray(pos_rag)

    # the crossover label comes from the ONE dispatch authority (bare read:
    # fused=False + row count), not a bench-local constant
    auto_impl = resolve_impl(
        q_seq=1, head_dim=d, padded_ctx=m * block, rows=b, fused=False,
    )
    variants = []
    if "xla" in want:
        variants.append(
            ("xla", partial(paged_attention_xla, block_size=block),
             (kp, vp), (), (q, pos)),
        )
    if "pallas" in want:
        variants.append(
            ("pallas", partial(paged_attention_pallas, block_size=block),
             (kp, vp), (), (q, pos))
        )
    if "ragged" in want:
        variants.append(
            ("ragged", partial(ragged_paged_attention, block_size=block),
             (kp, vp), (), (q_rag, pos_rag))
        )
    if args.int8:
        # int8 pools + per-(page, token) scales (VERDICT r3 #4): HBM sees
        # ~62% of the bf16 bytes per token; the kernel dequantizes in-page
        from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
            quantize_kv_pool,
        )

        kp8, kss = quantize_kv_pool(kp)
        vp8, vss = quantize_kv_pool(vp)
        variants.append((
            "pallas_int8",
            partial(paged_attention_pallas, block_size=block),
            (kp8, vp8), (kss, vss), (q, pos),
        ))

    results = {}
    for name, att, pools, scales, qp in variants:
        # pools/scales/tables/lens are jit ARGUMENTS, never closure
        # captures: a captured device array is baked into the computation
        # as a literal — at batch 32 x ctx 4096 the two pools are ~540 MB
        # of constants in the module the compiler is handed
        @jax.jit
        def many(q, kpool, vpool, tables, pos, lens, scales, _a=att):
            kw = (
                {"k_scale": scales[0], "v_scale": scales[1]}
                if scales else {}
            )

            def body(i, o):
                return _a(q + (o * 1e-9).astype(q.dtype),
                          kpool, vpool, tables, pos, lens, **kw)
            return jax.lax.fori_loop(0, iters, body, q)

        dt = (timed(many, qp[0], pools[0], pools[1], tables, qp[1], lens,
                    scales) - dispatch) / iters
        results[name] = dt * 1e6

    live = int(np.sum(np.asarray(lens)))
    out = {"metric": "paged_attention_decode_us"}
    for name in ("pallas", "xla", "ragged"):
        if name in results:
            out[f"{name}_us"] = round(results[name], 1)
    if "xla" in results and "pallas" in results:
        out["speedup"] = round(results["xla"] / results["pallas"], 2)
    if "xla" in results and "ragged" in results:
        out["ragged_speedup_vs_xla"] = round(
            results["xla"] / results["ragged"], 2
        )
    # crossover labelling: which variant the bare-read
    # dispatch selects for this row count, what it measured, and —
    # separately — the FUSED path serving actually reads through (the
    # model-level resolve_impl on the same static shape facts)
    out["micro_auto_impl"] = auto_impl
    if auto_impl in results:
        out["micro_auto_us"] = round(results[auto_impl], 1)
    out["serving_impl"] = resolve_impl(
        q_seq=1, head_dim=d, padded_ctx=m * block,
    )
    out["serving_uses_fused_kernel"] = out["serving_impl"] != "xla"
    best = results.get(auto_impl,
                       results.get("pallas",
                                   results.get("ragged",
                                               results.get("xla"))))
    out.update(**{
        "live_kv_gb_s": round(
            (live * hkv * d * 2 * 2) / (best / 1e6) / 1e9, 1
        ),
        "config": {"batch": b, "ctx": ctx, "mixed": args.mixed,
                   "impl": args.impl, "q_span": s_rag,
                   "mixed_spans": args.mixed_spans,
                   "block_size": block, "backend": jax.default_backend()},
    })
    if "pallas_int8" in results:
        out["pallas_int8_us"] = round(results["pallas_int8"], 1)
        out["int8_vs_bf16"] = round(
            results["pallas"] / results["pallas_int8"], 2
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
