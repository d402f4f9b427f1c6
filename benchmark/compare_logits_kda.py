#!/usr/bin/env python3
"""The served hybrid model (gated delta-rule layers over a state pool beside
latent pages) against its plain reference, at the published widths, on the
chip, outside any timed window: what ``compare_logits_mla.py`` does, for a
configuration some of whose layers keep a state and no pages, along the
path the cell times. Two statistics, a limit each in the configuration file:

    python3 benchmark/compare_logits_kda.py --config <name> [--out <file>]

**The logits** (``logit_tolerance``). Eight seeded prompts inside the cell's
range, one a row of the engine's eight: **short** (12 to 500 tokens, at
least six) and **long** (``--long`` prompts spread over 512 to 2048
tokens). The reference (the module the configuration file names under
``reference``, weights regenerated from ``weights_seed``) gives the logits
at the last prompt position and along its own greedy chain for ``--early``
further positions, each from a full forward pass; the rows are then fed
seeded random tokens up to ``--steps`` positions, and one more pass gives
the logits at the last ``--late`` of them, by which the state has been
rewritten ``--steps`` times. Then the configuration's engine is loaded the
way the worker loads it, and ``forward_chunk`` runs the same tokens on the
engine's weights through both pools **as the engine's rounds do**: packed
rounds (``llama.Packing``, at the engine's own ladder of packed lengths) in
which every row still in its prompt sends its next 256-token piece and
every row past it a decode token beside them, then one token a row a step,
rows that have their ``--steps`` masked. Under bf16 activations and a
router whose near-ties flip, 27 layers deep, this statistic has a floor of
~0.2 that no precision of the state shows above (PERF.md section 6).

**The state** (``state_tolerance``). That last pass of the reference also
shows, at the first, the middle and the last linear-attention layer, the
normed input of the layer for every token of every row. Rounded to
bfloat16 (what the served layer is handed) it goes through the reference's
recurrence, token by token in float32, and through the served layer's
attention sub-block (``models/kda.attention``: the engine's weights of that
layer, its row of the engine-sized state pool, the same rounds and steps,
the same kernels). Compared: the state the pool holds for the layer's
eight rows after the last step with the recurrence's, as the norm of the
difference over the norm of the reference's. Both sides see the same
input, so neither the depth nor the router sets this floor: what is left
is the served layer's own rounding.

Five runs:

``served``            the kernels on: ``dgi_kda_chunk`` for the rounds,
                      ``dgi_kda_step`` for the steps, the latent kernels.
                      Must pass both limits
``served_xla``        the two KDA kernels off (``models/kda.kernels_on``
                      held false), all else as served. Must pass both
``state_bf16``        the state pool carried in bfloat16, the nearest
                      precision below the served float32. Must FAIL (the
                      state's limit: the logits cannot show it)
``no_selection_bias`` the router's bias dropped. Must FAIL (the logits')
``tail_dropped``      the convolution's tails read as zero at every round
                      (a piece boundary). Must FAIL (both)

Each run also reports, over the compared positions, the largest deficit of
a served first token (the reference's logit at its own argmax less its
logit at the served argmax): what the golden file's ``margin`` bounds in
``run.py``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import numpy as np  # noqa: E402

from compare_logits import compare, reference_chain, sample_prompts  # noqa: E402
from compare_logits_mla import long_prompts  # noqa: E402
from harness import spec  # noqa: E402


def probed_layers(cfg):
    """The first, the middle and the last linear-attention layer
    (0-based)."""
    full = set(cfg["linear_attn_config"]["full_attn_layers"])
    linear = [l for l in range(int(cfg["num_hidden_layers"]))
              if l + 1 not in full]
    return sorted({linear[0], linear[len(linear) // 2], linear[-1]})


def late_logits(cfg, prompts, fed, late, probed):
    """One full forward pass of the reference over ``prompt + fed``: its
    logits at the last ``late`` fed positions ``[late, V]`` per prompt,
    and for each layer of ``probed`` and each prompt the layer's normed
    input rounded to bfloat16 ``[S, h]`` with the state the reference's
    recurrence leaves after it ``[H, d, d]``."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    dims = ref.dims(cfg)
    seqs = [list(p) + list(f) for p, f in zip(prompts, fed)]
    at = [list(range(len(s) - late, len(s))) for s in seqs]
    probes = {l: [None] * len(seqs) for l in probed}
    recurrence = jax.jit(lambda w, a: ref.kda_recurrence(dims, w, a)[1])

    def tap(l, n, w, x):
        if l in probes:
            a = ref._rms_norm(x[:len(seqs[n])], w["attn_norm"], dims["eps"])
            a = a.astype(jnp.bfloat16)
            with jax.default_matmul_precision("highest"):
                state = recurrence(w, a.astype(jnp.float32))
            probes[l][n] = (np.asarray(a), np.asarray(state))

    return ref.forward(cfg, weights, seqs, at=at, tap=tap)[0], probes


@contextlib.contextmanager
def patched(module, name, value):
    real = getattr(module, name)
    if value is not None:
        setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, real)


def schedule(prompt_lens, total_lens, chunk):
    """The engine's order of work for rows that start together: rounds in
    which every row still in its prompt sends its next piece of at most
    ``chunk`` tokens and every row past it one token beside them, then one
    token a live row a step. Yields (a packed round?, [(row, start,
    count)])."""
    done = [0] * len(prompt_lens)
    while any(d < t for d, t in zip(done, total_lens)):
        packed = any(d < p for d, p in zip(done, prompt_lens))
        segs = [(r, d, min(chunk, prompt_lens[r] - d)
                 if d < prompt_lens[r] else 1)
                for r, d in enumerate(done) if d < total_lens[r]]
        yield packed, segs
        for r, _, m in segs:
            done[r] += m


def pack(segs, tp, rows):
    """A round's segments on one axis of ``tp`` entries: each entry's row,
    place in its segment and position (a pad: row ``rows``, position -1),
    and per row its last entry and its length after the round."""
    row = np.full(tp, rows, np.int32)
    col = np.zeros(tp, np.int32)
    pos = np.full(tp, -1, np.int32)
    last = np.zeros(rows, np.int32)
    lens = np.zeros(rows, np.int32)
    n = 0
    for r, start, m in segs:
        row[n:n + m], col[n:n + m] = r, np.arange(m)
        pos[n:n + m] = start + np.arange(m)
        n += m
        last[r], lens[r] = n - 1, start + m
    return row, col, pos, last, lens


def zero_tails(pool, layer):
    import jax.numpy as jnp

    return jnp.zeros(pool.shape[1:], pool.dtype)


def served_chain(eng, mc, prompts, fed, kv, geo, *, kda_kernels, drop_tails):
    """The same tokens through ``forward_chunk`` on the engine's weights,
    scheduled as the engine's rounds are. Returns per row the logits after
    its prompt and after each fed token ``[1 + len(fed), V]``, and the
    routing of its tokens ``[L, tokens, k]``."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import kda, llama

    rows, block, chunk = len(prompts), geo["block_size"], geo["ragged_chunk"]
    assert rows == geo["max_batch_size"] == kv[kda.STATE].shape[1]
    pages = geo["max_seq_len"] // block
    tables = jnp.asarray(
        1 + np.arange(rows * pages).reshape(rows, pages), jnp.int32)
    common = dict(block_size=block, last_only=True, collect_routing=True)

    def packed(params, tok, pos, kv, lens, row, col, last, width):
        return llama.forward_chunk(
            mc, params, tok, pos, kv, tables, lens,
            packing=llama.Packing(row, col, last, width), **common)

    def stepped(params, tok, pos, kv, lens):
        return llama.forward_chunk(mc, params, tok, pos, kv, tables, lens,
                                   **common)

    packed = jax.jit(packed, static_argnames=("width",), donate_argnums=(3,))
    stepped = jax.jit(stepped, donate_argnums=(3,))
    off = None if kda_kernels else (lambda *a, **kw: False)
    seqs = [list(p) + list(f) for p, f in zip(prompts, fed)]
    logits = [[] for _ in prompts]
    routes = [[] for _ in prompts]
    with patched(kda, "kernels_on", off):
        for is_round, segs in schedule(
                list(map(len, prompts)), list(map(len, seqs)), chunk):
            if is_round:
                tp, width = eng._ragged_shape(sum(m for *_, m in segs))
                row, col, pos, last, lens = pack(segs, tp, rows)
                tok = np.zeros(tp, np.int32)
                tok[:int((pos >= 0).sum())] = [
                    t for r, start, m in segs for t in seqs[r][start:start + m]]
                with patched(kda, "read_tails",
                             zero_tails if drop_tails else None):
                    out = packed(eng.params, tok, pos, kv, lens, row, col,
                                 last, width=width)
                at = np.cumsum([0] + [m for *_, m in segs])
            else:
                tok = np.zeros((rows, 1), np.int32)
                pos = np.full((rows, 1), -1, np.int32)
                for r, start, _ in segs:
                    tok[r, 0], pos[r, 0] = seqs[r][start], start
                out = stepped(eng.params, tok, pos, kv,
                              (pos[:, 0] + 1).clip(min=0))
                at = [r for r, *_ in segs] + [0]
            kv = out.kv
            lg = np.asarray(out.logits[:, 0], np.float32)
            route = np.asarray(out.routing)
            for n, (r, start, m) in enumerate(segs):
                routes[r].append(route[:, at[n]:at[n] + m])
                # the logits after the prompt's last token and after each
                # fed one
                if start + m >= len(prompts[r]):
                    logits[r].append(lg[r])
    return ([np.stack(x) for x in logits],
            [np.concatenate(x, axis=1) for x in routes], kv)


def state_error(eng, mc, cfg, probes, prompt_lens, geo, state_dtype, *,
                kda_kernels, drop_tails):
    """Each probed layer's attention sub-block alone, on the engine's
    weights of that layer and its row of a state pool of the engine's
    size, fed the inputs of ``probes`` in the order of :func:`schedule`:
    the norm of (the pool's state of the layer's rows after the last step
    - the reference's) over the norm of the reference's, over all probed
    layers, and per layer."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import kda
    from distributed_gpu_inference_tpu.ops.quantization import matmul

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    dims = ref.dims(cfg)
    rows, chunk = len(prompt_lens), geo["ragged_chunk"]
    linear = [l for l in range(dims["L"]) if l + 1 not in dims["full"]]
    kv = kda.init_state_pools(mc, rows)
    if state_dtype is not None:
        kv[kda.STATE] = kv[kda.STATE].astype(state_dtype)
    kernels = kda_kernels and kda.kernels_on(mc, kv[kda.STATE].dtype)

    def sub_block(lp, x, kv, layer, plan, positions):
        return kda.attention(
            mc, x, lp, lambda x_, name: matmul(x_, lp[name], True), kv,
            layer, plan=plan, positions=positions, kernels=kernels)[1]

    def packed(lp, x, row, col, pos, kv, layer):
        return sub_block(lp, x[None], kv, layer,
                         kda.make_plan(row, col, pos, rows), None)

    def stepped(lp, x, pos, kv, layer):
        return sub_block(lp, x[:, None], kv, layer, None, pos[:, None])

    packed = jax.jit(packed, donate_argnums=(5,))
    stepped = jax.jit(stepped, donate_argnums=(3,))
    num, den, per_layer = 0.0, 0.0, {}
    for l, seen in probes.items():
        group, at = ref.group_of(dims, l)
        lp = jax.tree.map(lambda a: a[at], eng.params[group])
        layer = jnp.int32(linear.index(l))
        inputs = [a for a, _ in seen]
        for is_round, segs in schedule(
                prompt_lens, [len(a) for a in inputs], chunk):
            x = np.concatenate([inputs[r][start:start + m]
                                for r, start, m in segs])
            if is_round:
                tp, _ = eng._ragged_shape(len(x))
                row, col, pos, *_ = pack(segs, tp, rows)
                x = np.concatenate(
                    [x, np.zeros((tp - len(x), x.shape[1]), x.dtype)])
                with patched(kda, "read_tails",
                             zero_tails if drop_tails else None):
                    kv = packed(lp, x, row, col, pos, kv, layer)
            else:
                full = np.zeros((rows, x.shape[1]), x.dtype)
                pos = np.full(rows, -1, np.int32)
                for n, (r, start, _) in enumerate(segs):
                    full[r], pos[r] = x[n], start
                kv = stepped(lp, full, pos, kv, layer)
        got = np.asarray(kv[kda.STATE][linear.index(l)], np.float32)
        want = np.stack([state for _, state in seen])
        a, b = float(np.sum((got - want) ** 2)), float(np.sum(want ** 2))
        per_layer[str(l)] = (a / b) ** 0.5
        num, den = num + a, den + b
    return (num / den) ** 0.5, per_layer


def first_token_deficits(want, got):
    """Per compared position the reference's logit at its own argmax less
    its logit at the served argmax (0 where they agree)."""
    return np.concatenate([
        w.max(axis=-1) - np.take_along_axis(
            w, g.argmax(axis=-1)[:, None], axis=-1)[:, 0]
        for w, g in zip(want, got)])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--long", type=int, default=2)
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--early", type=int, default=8)
    ap.add_argument("--late", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--runs", default=None,
                    help="comma-separated subset of the five runs")
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
    state_tol = float(cfg["state_tolerance"]["value"])
    geo = cfg["serving_geometry"]
    n_long = max(args.long, 2)
    samples = [sample_prompts(geo["max_batch_size"] - n_long, args.seed),
               long_prompts(n_long, args.seed, lo=512, hi=2048)]
    rng = np.random.default_rng(args.seed + 2)
    probed = probed_layers(cfg)
    t0 = time.monotonic()
    want, want_routes, fed = [], [], []
    probes = {l: [] for l in probed}
    for prompts in samples:     # the reference pads a call to one width
        early, chain, routes = reference_chain(cfg, prompts, args.early)
        rest = [chain_r + [int(t) for t in rng.integers(
            4, 260, args.steps - args.early)] for chain_r in chain]
        late, seen = late_logits(cfg, prompts, rest, args.late, probed)
        want += [np.concatenate([a, b]) for a, b in zip(early, late)]
        want_routes += routes
        fed += rest
        for l in probed:
            probes[l] += seen[l]
    prompts = [p for ps in samples for p in ps]
    print(f"reference: prompts of {[len(p) for p in prompts]} tokens, "
          f"{args.early + 2} passes a sample in "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    # positions compared: after the prompt, steps 1..early, the last `late`
    at = list(range(args.early + 1)) + list(
        range(args.steps - args.late + 1, args.steps + 1))

    from distributed_gpu_inference_tpu.models import kda, llama
    from distributed_gpu_inference_tpu.worker.engines import create_engine

    llm = create_engine("llm", dict(cfg["worker_engine"]))
    llm.load_model()
    eng = llm.engine
    mc = eng.model_cfg
    eng.kv = None                       # each run draws pools of its own
    report = {"config": args.config, "device": dev.device_kind,
              "prompt_tokens": [len(p) for p in prompts],
              "steps": args.steps, "positions_compared": at,
              "layers_probed": probed,
              "tolerance": tol, "state_tolerance": state_tol,
              "tolerance_reason": cfg["logit_tolerance"]["reason"],
              "state_tolerance_reason": cfg["state_tolerance"]["reason"]}
    runs = (
        ("served", mc, None, True, False),
        ("served_xla", mc, None, False, False),
        ("state_bf16", mc, jnp.bfloat16, True, False),
        ("no_selection_bias",
         dataclasses.replace(mc, router_selection_bias=False), None, True,
         False),
        ("tail_dropped", mc, None, True, True),
    )
    fails = ("state_bf16", "no_selection_bias", "tail_dropped")
    chosen = set(args.runs.split(",")) if args.runs else None
    runs = tuple(r for r in runs if chosen is None or r[0] in chosen)
    for name, model, state_dtype, kernels, drop in runs:
        t0 = time.monotonic()
        kv = llama.init_kv_pools(
            model, 1 + len(prompts) * (geo["max_seq_len"]
                                       // geo["block_size"]),
            geo["block_size"], state_rows=len(prompts))
        if state_dtype is not None:
            kv[kda.STATE] = kv[kda.STATE].astype(state_dtype)
        logits, routes, kv = served_chain(
            eng, model, prompts, fed, kv, geo, kda_kernels=kernels,
            drop_tails=drop)
        del kv
        got = [lg[at] for lg in logits]
        out = report[name] = compare(want, got, want_routes, routes)
        for label, where in (("early", slice(0, args.early + 1)),
                             ("late", slice(args.early + 1, None))):
            out[label] = float(np.sqrt(np.mean(np.concatenate(
                [(w[where] - g[where]).ravel()
                 for w, g in zip(want, got)]) ** 2)))
        deficits = first_token_deficits(want, got)
        out["first_token_deficit_max"] = float(deficits.max())
        out["first_token_deficit_p90"] = float(np.quantile(deficits, 0.9))
        out["state_rel_err"], out["state_rel_err_by_layer"] = state_error(
            eng, model, cfg, probes, [len(p) for p in prompts], geo,
            state_dtype, kda_kernels=kernels, drop_tails=drop)
        out["within_tolerance"] = out["rms_logit_diff"] <= tol
        out["within_state_tolerance"] = out["state_rel_err"] <= state_tol
        print(f"{name}: {out} in {time.monotonic() - t0:.1f}s", flush=True)
    report["ok"] = all(
        (report[name]["within_tolerance"]
         and report[name]["within_state_tolerance"]) != (name in fails)
        for name, *_ in runs)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
