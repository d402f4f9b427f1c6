#!/usr/bin/env python3
"""Logits of the served model against the configuration's plain reference,
at the published widths, on the chip, outside any timed window.

    python3 benchmark/compare_logits.py --config <name> [--out <file>]

For a seeded sample of prompts (12 to 500 tokens, at least six) the
reference (the module the configuration file names under ``reference``,
weights regenerated from ``weights_seed``) gives the logits at the last
prompt position and at each of ``--steps`` further positions, its own
argmax fed back, each from a full forward pass over the tokens so far.
Then the configuration's engine is loaded the way the worker loads it, and
``forward_chunk`` runs the same tokens on the engine's weights through
paged pools with the kernels on: the prompts in 256-token chunks as rows
of one ``[max_batch_size, ragged_chunk]`` rectangle, then one token a row a
step through the decode kernels. Reported: the root-mean-square logit
difference over every position and vocabulary row (what the configuration's
``logit_tolerance`` bounds: the largest single difference among 2.7 million
is a noisy reading, the root mean square is not), the largest absolute
difference, the share of (token, layer) pairs whose set of experts agrees
with the reference's, and all of it again for two runs that must FAIL the
tolerance: int8 KV pools (the nearest precision
below the served bf16), and the kept expert weights renormalised. The
round programs return tokens, not logits, which is why this is a script of
its own beside the golden maker.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import numpy as np  # noqa: E402

from harness import spec  # noqa: E402


def sample_prompts(n: int, seed: int, lo: int = 12, hi: int = 500):
    """``n`` prompts of byte tokens: the two ends of the range and seeded
    lengths between them."""
    rng = np.random.default_rng(seed)
    lengths = [lo, hi] + [int(x) for x in rng.integers(lo, hi + 1, n - 2)]
    return [[int(t) for t in rng.integers(4, 260, m)] for m in lengths]


def reference_chain(cfg, prompts, steps):
    """Per prompt: the reference's logits ``[steps + 1, V]`` along its own
    greedy chain, the tokens it fed back, and its routing ``[L, S, k]``."""
    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    seqs = [list(p) for p in prompts]
    width = max(map(len, seqs)) + steps
    logits = [[] for _ in seqs]
    for _ in range(steps + 1):
        got, routes = ref.forward(cfg, weights, seqs, width=width)
        for n, lg in enumerate(got):
            logits[n].append(lg[0])
            seqs[n].append(int(lg[0].argmax()))
    fed = [s[len(p):-1] for s, p in zip(seqs, prompts)]
    return [np.stack(x) for x in logits], fed, routes


def served_chain(eng, mc, prompts, fed, kv, geo):
    """The same tokens through ``forward_chunk`` on the engine's weights:
    logits ``[steps + 1, V]`` and routing ``[L, S, k]`` per prompt."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import llama

    rows, block = geo["max_batch_size"], geo["block_size"]
    chunk, pages = geo["ragged_chunk"], geo["max_seq_len"] // block
    assert len(prompts) <= rows
    tables = np.zeros((rows, pages), np.int32)
    for r in range(len(prompts)):       # row r owns pages 1 + r*pages ...
        tables[r] = 1 + r * pages + np.arange(pages)
    fwd = jax.jit(
        functools.partial(llama.forward_chunk, mc, block_size=block,
                          last_only=True, collect_routing=True),
        donate_argnums=(3,))

    def run(tokens, positions, kv):
        lens = (positions.max(axis=1) + 1).clip(min=0)
        out = fwd(eng.params, jnp.asarray(tokens), jnp.asarray(positions),
                  kv, jnp.asarray(tables), jnp.asarray(lens))
        route = np.asarray(out.routing).reshape(
            out.routing.shape[0], *tokens.shape, -1)
        return np.asarray(out.logits[:, 0], np.float32), route, out.kv

    logits = [[] for _ in prompts]
    routes = [[] for _ in prompts]
    for start in range(0, max(map(len, prompts)), chunk):
        tokens = np.zeros((rows, chunk), np.int32)
        positions = np.full((rows, chunk), -1, np.int32)
        for r, p in enumerate(prompts):
            piece = p[start:start + chunk]
            tokens[r, :len(piece)] = piece
            positions[r, :len(piece)] = start + np.arange(len(piece))
        lg, route, kv = run(tokens, positions, kv)
        for r, p in enumerate(prompts):
            n = len(p[start:start + chunk])
            routes[r].append(route[:, r, :n])
            if n and start + n == len(p):
                logits[r].append(lg[r])
    for step in range(len(fed[0])):
        tokens = np.zeros((rows, 1), np.int32)
        positions = np.full((rows, 1), -1, np.int32)
        for r, p in enumerate(prompts):
            tokens[r, 0] = fed[r][step]
            positions[r, 0] = len(p) + step
        lg, route, kv = run(tokens, positions, kv)
        for r in range(len(prompts)):
            logits[r].append(lg[r])
            routes[r].append(route[:, r])
    return ([np.stack(x) for x in logits],
            [np.concatenate(x, axis=1) for x in routes], kv)


def compare(want, got, want_routes, got_routes):
    diff = max(float(np.abs(a - b).max()) for a, b in zip(want, got))
    square = sum(float(np.square(a - b).sum()) for a, b in zip(want, got))
    count = sum(a.size for a in want)
    same = total = 0
    for a, b in zip(want_routes, got_routes):
        n = min(a.shape[1], b.shape[1])
        eq = np.all(np.sort(a[:, :n], -1) == np.sort(b[:, :n], -1), axis=-1)
        same, total = same + int(eq.sum()), total + eq.size
    argmax = sum(int((a.argmax(-1) == b.argmax(-1)).sum())
                 for a, b in zip(want, got))
    return {"rms_logit_diff": (square / count) ** 0.5,
            "max_abs_logit_diff": diff,
            "expert_set_agreement": same / total,
            "token_layer_pairs": total,
            "argmax_agreement": argmax / sum(len(a) for a in want)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompts", type=int, default=6)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"platform {dev.platform!r}: the comparison at the "
                         "published widths is made on the chip")
    cfg = spec.load_config(spec.BENCH / "configs" / f"{args.config}.json")
    tol = float(cfg["logit_tolerance"]["value"])
    geo = cfg["serving_geometry"]
    prompts = sample_prompts(max(args.prompts, 6), args.seed)
    t0 = time.monotonic()
    want, fed, want_routes = reference_chain(cfg, prompts, args.steps)
    print(f"reference: {len(prompts)} prompts of "
          f"{[len(p) for p in prompts]} tokens, {args.steps + 1} passes in "
          f"{time.monotonic() - t0:.1f}s", flush=True)

    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.worker.engines import create_engine

    llm = create_engine("llm", dict(cfg["worker_engine"]))
    llm.load_model()
    eng = llm.engine
    mc, blocks = eng.model_cfg, eng.kv["k"].shape[1]
    report = {"config": args.config, "device": dev.device_kind,
              "prompt_tokens": [len(p) for p in prompts],
              "positions_each": args.steps + 1, "tolerance": tol,
              "tolerance_reason": cfg["logit_tolerance"]["reason"]}
    kv, eng.kv = eng.kv, None           # the engine's own pools, donated
    runs = (
        ("served", mc, None),
        ("int8_kv", mc, jnp.int8),
        ("renormalised", dataclasses.replace(mc, norm_topk_prob=True), None),
    )
    for name, model, kv_dtype in runs:
        if kv is None:
            kv = llama.init_kv_pools(model, blocks, geo["block_size"],
                                     kv_dtype)
        t0 = time.monotonic()
        got, got_routes, kv = served_chain(eng, model, prompts, fed, kv, geo)
        kv = None
        report[name] = compare(want, got, want_routes, got_routes)
        report[name]["within_tolerance"] = \
            report[name]["rms_logit_diff"] <= tol
        print(f"{name}: {report[name]} in {time.monotonic() - t0:.1f}s",
              flush=True)
    report["ok"] = (report["served"]["within_tolerance"]
                    and not report["int8_kv"]["within_tolerance"]
                    and not report["renormalised"]["within_tolerance"])
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
