#!/usr/bin/env python3
"""Logits of the served latent-attention model against its plain reference,
at the published widths, on the chip, outside any timed window: what
``compare_logits.py`` does, for a configuration whose cache is latent pages
(its pool has no ``"k"``, and its controls are its own).

    python3 benchmark/compare_logits_mla.py --config <name> [--out <file>]

Two seeded samples of prompts: **short** (12 to 500 tokens, at least six:
they cross the 256-token chunk boundary) and **long** (``--long`` prompts
spread over 1536 to 3072 tokens, the lengths the cell serves: their caches
span three to seven of the latent kernel's 512-token page groups, so the
online softmax is rescaled across groups and the next group prefetched in
every chunk and step). For each the reference (the module the configuration
file names under ``reference``, weights regenerated from ``weights_seed``)
gives the logits at the last prompt position and at each of ``--steps``
further positions, its own argmax fed back, each from a full forward pass in
the expanded form. Then the configuration's engine is loaded the way the
worker loads it, and ``forward_chunk`` runs the same tokens on the engine's
weights through the latent paged pool: a sample's prompts in 256-token
chunks as the rows of one rectangle, then one token a row a step. Four
runs over both samples together, the output of ``compare_logits.py`` for
each and the same statistic over the long sample alone (``long``):

``served``        the kernels on, full block tables: the absorbed kernel
                  for chunks and steps, the in-place page write
``served_xla``    the latent kernels off (``models/mla.kernels_on`` held
                  false), every other kernel as served, over block tables
                  as long as a sample's prompts need: expanded chunks,
                  absorbed steps, the pool scattered into and gathered from
                  by XLA. Must pass: it shows that what fails below is the
                  precision, not the path
``fp8_latent``    the same path over a float8_e4m3 pool (a one-byte pool
                  takes that path by itself), the nearest precision below
                  the served bf16. Must FAIL the tolerance
``unnormalised``  the kernels on, the kept scores left unnormalised
                  (``norm_topk_prob`` false). Must FAIL

(``pallas=False`` would also send the experts through XLA's gather of a
whole expert a row tile, 4 GB of temporaries beside 9.5 GB of weights: that
path is the CPU's.)
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import numpy as np  # noqa: E402

from compare_logits import compare, reference_chain, sample_prompts  # noqa: E402
from harness import spec  # noqa: E402


def long_prompts(n: int, seed: int, lo: int = 1536, hi: int = 3072):
    """``n`` prompts of byte tokens inside the cell's range: the lengths
    split ``lo .. hi`` evenly, each moved by a seeded offset so that no
    sample ends on a page or group boundary by construction."""
    rng = np.random.default_rng(seed + 1)
    step = (hi - lo) // n
    lengths = [lo + i * step + int(rng.integers(0, step)) for i in range(n)]
    return [[int(t) for t in rng.integers(4, 260, m)] for m in lengths]


@contextlib.contextmanager
def latent_kernels(on: bool):
    """What is traced inside runs the latent kernels where the program
    would, or (``on`` false) its XLA forms whatever the pool."""
    from distributed_gpu_inference_tpu.models import mla

    real = mla.kernels_on
    if not on:
        mla.kernels_on = lambda *a, **kw: False
    try:
        yield
    finally:
        mla.kernels_on = real


def served_chain(eng, mc, prompts, fed, kv, geo, pages):
    """The same tokens through ``forward_chunk`` on the engine's weights:
    logits ``[steps + 1, V]`` and routing ``[L, S, k]`` per prompt."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import llama

    rows, block = len(prompts), geo["block_size"]
    chunk = geo["ragged_chunk"]
    assert rows <= geo["max_batch_size"]
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompts", type=int, default=6)
    ap.add_argument("--long", type=int, default=2)
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
    samples = [sample_prompts(max(args.prompts, 6), args.seed),
               long_prompts(max(args.long, 2), args.seed)]
    t0 = time.monotonic()
    # a sample at a time: the reference pads a call's prompts to one width
    chains = [reference_chain(cfg, prompts, args.steps)
              for prompts in samples]
    print(f"reference: prompts of "
          f"{[[len(p) for p in prompts] for prompts in samples]} tokens, "
          f"{args.steps + 1} passes each in {time.monotonic() - t0:.1f}s",
          flush=True)
    want = [x for chain in chains for x in chain[0]]
    want_routes = [x for chain in chains for x in chain[2]]
    n_short = len(samples[0])

    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.worker.engines import create_engine

    llm = create_engine("llm", dict(cfg["worker_engine"]))
    llm.load_model()
    eng = llm.engine
    mc = eng.model_cfg
    eng.kv = None                       # each run draws a pool of its own
    report = {"config": args.config, "device": dev.device_kind,
              "prompt_tokens": [len(p) for ps in samples for p in ps],
              "long_prompts": len(samples[1]),
              "positions_each": args.steps + 1, "tolerance": tol,
              "tolerance_reason": cfg["logit_tolerance"]["reason"]}
    full = geo["max_seq_len"] // geo["block_size"]
    runs = (
        ("served", mc, None, True),
        ("served_xla", mc, None, False),
        ("fp8_latent", mc, jnp.float8_e4m3fn, False),
        ("unnormalised", dataclasses.replace(mc, norm_topk_prob=False),
         None, True),
    )
    fails = ("fp8_latent", "unnormalised")
    for name, model, kv_dtype, kernels in runs:
        t0 = time.monotonic()
        got, got_routes = [], []
        for prompts, (_, fed, _) in zip(samples, chains):
            pages = full if kernels else -(
                -(max(map(len, prompts)) + args.steps + 1)
                // geo["block_size"])
            kv = llama.init_kv_pools(
                model, 1 + len(prompts) * pages, geo["block_size"], kv_dtype)
            with latent_kernels(kernels):
                logits, routes, kv = served_chain(
                    eng, model, prompts, fed, kv, geo, pages)
            del kv
            got += logits
            got_routes += routes
        report[name] = compare(want, got, want_routes, got_routes)
        report[name]["long"] = compare(
            want[n_short:], got[n_short:], want_routes[n_short:],
            got_routes[n_short:])
        report[name]["within_tolerance"] = \
            report[name]["rms_logit_diff"] <= tol
        print(f"{name}: {report[name]} in {time.monotonic() - t0:.1f}s",
              flush=True)
    report["ok"] = all(report[name]["within_tolerance"] != (name in fails)
                       for name, *_ in runs)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
