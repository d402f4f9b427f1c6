#!/usr/bin/env python3
"""The served latent-attention model of two attention kinds (dots3-note-prev:
windowed latent layers with a pool, a width and a head count of their own
beside full latent layers under an indexer, a gate a head, a rescale on the
normed latents) against its plain reference, at the published widths and the
timed context lengths, on the chip, outside any timed window. Two
statistics, a limit each in the configuration file:

    python3 benchmark/compare_logits_mla_window.py --config <name> [--out <file>]

**The logits** (``logit_tolerance``). Eight seeded prompts, one a row of the
engine's eight: four **short** (under 513 tokens: no window slides, no
selection drops a token) and four **long** (4,096 to 20,000 tokens, one of
at least 16,384), each followed by ``--steps`` seeded random tokens. The
reference (the module the configuration file names under ``reference``,
weights regenerated from ``weights_seed``) gives, from ONE full forward pass
a row, the logits at the last prompt position, after each of the first
``--early`` fed tokens and after the last ``--late``. The configuration's
engine is loaded the way the worker loads it, and ``forward_chunk`` runs the
same tokens on the engine's weights through the three pools **as the
engine's rounds do**: packed rounds in which every row still in its prompt
sends its next 256-token piece and every row past it a decode token beside
them, then one token a row a step. The window kind's table maps a row's
logical blocks onto a ring of 64 physical pages (a window and a piece and
room to spare): what the engine's release of pages that left the window
leaves, the older positions masked by the window rule.

**The attention sub-blocks** (``attention_tolerance``). That pass of the
reference also shows the input ``X`` of the first full EXPERT layer for
every token of every row. Rounded to bfloat16 it goes through two sub-blocks
of the reference (float32) and of the served path (the engine's weights, its
own pools, the same packed rounds and kernels), a two-layer model (that full
layer, then the sliding layer behind it) whose experts give zero:

``full``     the full layer's attention alone (the sliding layer's ``W_o``
             zeroed): projections, rescale, the indexer, ``S_t``, attention
             over ``S_t``, the gate, ``W_o``;
``sliding``  the sliding layer's attention alone on the same ``X`` (the full
             layer's ``W_o`` zeroed): its own projections and rescale, the
             window, the gate, ``W_o``.

Compared: the norm of the difference over the norm of the reference's, a
block at a time against that block's own limit (``attention_tolerance``
``full`` / ``sliding``: one key short of a window moves the sliding block by
two thirds of its reading and the full block not at all); and
``selection_overlap``, the share of the
reference's ``S_t`` the served selection chose, over the queries past
``index_topk``. Both sides see the same input, so neither the depth nor the
router sets this floor.

The runs: ``served`` (the kernels on) must pass both limits; each planted
fault must FAIL one (``--logit-runs`` names the runs whose whole model is
served for the logits; the others are judged by the sub-blocks alone, which
every one of these faults moves): ``no_rescale``, ``no_gate``,
``window_512`` (one key short), ``window_everywhere`` (full layers attend
the window and no selection), ``dense_full_layers`` (no selection),
``sliding_rope_theta_as_full`` and ``fp8_latents`` (both pools' rows and the
index keys rounded to float8_e4m3: the nearest precision below the served
bfloat16, which the engine refuses for this model). ``--seed`` draws another
sample of prompts and fed tokens; ``--readings`` judges a report an earlier
call wrote against the limits the configuration holds now.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import numpy as np  # noqa: E402

from compare_logits import compare, sample_prompts  # noqa: E402
from compare_logits_kda import first_token_deficits  # noqa: E402
from compare_logits_mla_sparse import planted  # noqa: E402
from compare_logits_sparse import pack, schedule  # noqa: E402
from harness import spec  # noqa: E402

FAILS = ("no_rescale", "no_gate", "window_512", "window_everywhere",
         "dense_full_layers", "sliding_rope_theta_as_full", "fp8_latents")
# physical pages a row's window-kind table cycles through
RING = 64


def faults(mc):
    """name → (model configuration, module patches) of the planted runs."""
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.ops import index_select
    from distributed_gpu_inference_tpu.ops import mla_attention_pallas as mk

    real_select = index_select.select

    def windowed(qi, wts, pool, layer, tables, positions, kv_lens, topk,
                 **kw):
        """Full layers attend the window, as sliding layers do, and no
        selection."""
        keep = real_select(qi, wts, pool, layer, tables, positions, kv_lens,
                           topk, **kw)
        key = jnp.arange(keep.shape[-1], dtype=jnp.int32)[None, None, :]
        seen = (key <= positions[:, :, None]) \
            & (key > positions[:, :, None] - mc.sliding_window) \
            & (key < kv_lens[:, None, None])
        return jnp.where(seen, 1.0, 0.0).astype(keep.dtype)

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    real_write, real_keys = mk.write_latent_pages_in_place, \
        index_select.write_index_keys
    real_append = index_select.append_scan_keys
    fp8_patches = (
        (mk, "write_latent_pages_in_place",
         lambda rows, *a, **kw: real_write(fp8(rows), *a, **kw)),
        (index_select, "write_index_keys",
         lambda pool, new, *a, **kw: real_keys(pool, fp8(new), *a, **kw)),
        (index_select, "append_scan_keys",
         lambda keys, new, *a, **kw: real_append(keys, fp8(new), *a, **kw)),
    )
    rep = dataclasses.replace
    return {
        "no_rescale": (rep(mc, mla_lora_rescale=False), ()),
        "no_gate": (rep(mc, head_gate=False), ()),
        "window_512": (rep(mc, sliding_window=mc.sliding_window - 1), ()),
        "window_everywhere": (mc, ((index_select, "select", windowed),)),
        "dense_full_layers": (rep(mc, index_topk=2 ** 30), ()),
        "sliding_rope_theta_as_full": (
            rep(mc, sliding_rope_theta=mc.rope_theta), ()),
        "fp8_latents": (mc, fp8_patches),
    }


def two_tables(rows, pages):
    """A row's two block tables side by side: the full kind's pages one
    after another, the window kind's logical blocks on a ring of ``RING``
    physical pages a row."""
    full = 1 + np.arange(rows * pages).reshape(rows, pages)
    ring = 1 + np.arange(rows)[:, None] * RING + np.arange(pages)[None] % RING
    return np.concatenate([full, ring], axis=1).astype(np.int32)


def pools(model, rows, geo):
    from distributed_gpu_inference_tpu.models import llama

    pages = geo["max_seq_len"] // geo["block_size"]
    return llama.init_kv_pools(model, 1 + rows * pages, geo["block_size"],
                               window_blocks=1 + rows * RING)


def served_chain(eng, mc, params, prompts, fed, kv, geo, *, hidden=False):
    """``compare_logits_sparse.served_chain`` for a model that keeps a block
    table a layer kind: the same tokens through ``forward_chunk`` on
    ``params``, scheduled as the engine's rounds are. Returns per row the
    logits after its prompt and after each fed token ``[1 + len(fed), V]``
    and the routing of its tokens; with ``hidden``, per row the final hidden
    state of every token ``[tokens, h]`` instead of the logits."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import llama

    rows, block, chunk = len(prompts), geo["block_size"], geo["ragged_chunk"]
    tables = jnp.asarray(two_tables(rows, geo["max_seq_len"] // block))
    common = dict(block_size=block, last_only=True, collect_routing=True,
                  with_logits=not hidden, pallas=True)

    def packed(params, tok, pos, kv, lens, row, col, last, width):
        return llama.forward_chunk(
            mc, params, tok, pos, kv, tables, lens,
            packing=llama.Packing(row, col, last, width), **common)

    def stepped(params, tok, pos, kv, lens):
        return llama.forward_chunk(mc, params, tok, pos, kv, tables, lens,
                                   **common)

    packed = jax.jit(packed, static_argnames=("width",), donate_argnums=(3,))
    stepped = jax.jit(stepped, donate_argnums=(3,))
    seqs = [list(p) + list(f) for p, f in zip(prompts, fed)]
    out_rows = [[] for _ in prompts]
    routes = [[] for _ in prompts]
    for is_round, segs in schedule(
            list(map(len, prompts)), list(map(len, seqs)), chunk):
        if is_round:
            tp, width = eng._ragged_shape(sum(m for *_, m in segs))
            row, col, pos, last, lens = pack(segs, tp, rows)
            tok = np.zeros(tp, np.int32)
            tok[:int((pos >= 0).sum())] = [
                t for r, start, m in segs for t in seqs[r][start:start + m]]
            out = packed(params, tok, pos, kv, lens, row, col, last,
                         width=width)
            at = np.cumsum([0] + [m for *_, m in segs])
            states = None if not hidden else np.asarray(
                out.hidden[0], np.float32)
        else:
            tok = np.zeros((rows, 1), np.int32)
            pos = np.full((rows, 1), -1, np.int32)
            for r, start, _ in segs:
                tok[r, 0], pos[r, 0] = seqs[r][start], start
            out = stepped(params, tok, pos, kv, (pos[:, 0] + 1).clip(min=0))
            at = [r for r, *_ in segs] + [0]
            states = None if not hidden else np.asarray(
                out.hidden[:, 0], np.float32)
        kv = out.kv
        lg = None if hidden else np.asarray(out.logits[:, 0], np.float32)
        route = None if out.routing is None else np.asarray(out.routing)
        for n, (r, start, m) in enumerate(segs):
            if route is not None:
                routes[r].append(route[:, at[n]:at[n] + m])
            if hidden:
                out_rows[r].append(states[at[n]:at[n] + m])
            elif start + m >= len(prompts[r]):
                out_rows[r].append(lg[r])
    join = np.concatenate if hidden else np.stack
    return ([join(x) for x in out_rows],
            [np.concatenate(x, axis=1) if x else None for x in routes], kv)


def reference_pass(cfg, prompts, fed, at, full_layer):
    """The reference's side, one pass: per row its logits at the positions
    ``at`` counts from the last prompt position, its routing, and the
    input of ``full_layer`` rounded to bfloat16 ``[S, h]``."""
    import jax.numpy as jnp

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    seqs = [list(p) + list(f) for p, f in zip(prompts, fed)]
    inputs = [None] * len(seqs)

    def tap(l, n, w, x):
        if l == full_layer:
            inputs[n] = np.asarray(x.astype(jnp.bfloat16))

    want, routes = ref.forward(
        cfg, weights, seqs, tap=tap,
        at=[[len(p) - 1 + i for i in at] for p in prompts])
    return want, routes, inputs


def reference_blocks(cfg, inputs, geo, full_layer):
    """The reference's side of the sub-blocks, made before the engine is
    loaded: ``S_t`` of the full layer on ``X`` a block of queries (packed
    bits), and both sub-blocks' outputs after ``W_o``."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    s = ref.dims(cfg)
    chunk = geo["ragged_chunk"]
    assert ref.BLOCK == chunk
    layers = {"full": full_layer, "sliding": full_layer + 1}
    assert s["kinds"][full_layer + 1] == "sliding"
    fns = {}
    for name, layer in layers.items():
        k = ref.kind_of(s, layer)
        fns[name] = (
            jax.jit(lambda w, x, k=k: ref.project(s, k, w, x)),
            jax.jit(lambda p, lo, k=k: ref.select(s, k, p, lo, ref.BLOCK)),
            jax.jit(lambda w, p, keep, lo, k=k:
                    ref.attend(k, w, p, keep, lo, ref.BLOCK) @ w["wo"]))
    lens = [len(x) for x in inputs]
    width = -(-max(lens) // chunk) * chunk
    want = {name: [] for name in layers}
    keeps = []
    with jax.default_matmul_precision("highest"):
        ws = {name: weights.layer(layer, ref.ATTENTION)
              for name, layer in layers.items()}
        for x, n in zip(inputs, lens):
            x = jnp.asarray(np.concatenate(
                [x, np.zeros((width - n, x.shape[1]), x.dtype)]), jnp.float32)
            los = range(0, -(-n // chunk) * chunk, chunk)
            for name in layers:
                project, select, attend = fns[name]
                p = project(ws[name], x)
                masks = [select(p, jnp.int32(lo)) for lo in los]
                if name == "full":
                    keeps.append([np.packbits(np.asarray(m)[:, :n], axis=1)
                                  for m in masks])
                want[name].append(np.concatenate([
                    np.asarray(attend(ws[name], p, keep, jnp.int32(lo)))
                    for keep, lo in zip(masks, los)])[:n])
    return want, keeps


def sub_blocks(eng, cfg, runs, inputs, want_blocks, keeps, geo, full_layer):
    """The full layer's and the sliding layer's attention sub-blocks alone,
    for every run, against ``reference_blocks``' side: ``{run: {"rel_err",
    "selection_overlap", by block}}``."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import llama, mla
    from distributed_gpu_inference_tpu.ops import index_select
    from distributed_gpu_inference_tpu.ops.quantization import matmul

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    s = ref.dims(cfg)
    block, chunk = geo["block_size"], geo["ragged_chunk"]
    pages = geo["max_seq_len"] // block
    lens = [len(x) for x in inputs]
    width = -(-max(lens) // chunk) * chunk
    padded = [np.concatenate([x, np.zeros((width - len(x), x.shape[1]),
                                          x.dtype)]) for x in inputs]

    def stack_of(layer):
        group, at, _ = ref.group_of(s, layer)
        return jax.tree.map(lambda a: a[at:at + 1], eng.params[group])

    def zeroed(stack, names):
        return dict(stack, **{n: jax.tree.map(jnp.zeros_like, stack[n])
                              for n in names})

    experts = ("we_down", "ws_down")
    full = zeroed(stack_of(full_layer), experts)
    sliding = zeroed(stack_of(full_layer + 1), experts)
    lp_full = jax.tree.map(lambda a: a[0], full)
    base = np.cumsum([0] + lens)
    table = jnp.asarray(np.concatenate(inputs), jnp.bfloat16)
    ids = [list(range(base[r], base[r + 1])) for r in range(len(inputs))]

    def served_selection(model, x, n):
        """The served selection of one row's ``n`` tokens: index keys into
        a pool of the row's own, then ``select`` a piece at a time."""
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)[None]
        tables = jnp.asarray(1 + np.arange(pages)[None], jnp.int32)
        kernels = mla.kernels_on(model, pages * block, jnp.bfloat16)
        plan = llama._index_plan(model, 1 + pages, tables, pos, pos, None,
                                 block)
        kind = model.latent_kind("full")

        def proj(x_, name):
            return matmul(x_, lp_full[name], True)

        normed = llama.rms_norm(jnp.asarray(x)[None], lp_full["attn_norm"],
                                model.rms_norm_eps)
        c_q = llama.rms_norm(proj(normed, "wq_a"), lp_full["q_a_norm"],
                             model.rms_norm_eps)
        c_q = c_q * jnp.asarray(kind.q_scale, c_q.dtype)
        qi, kin, wts = mla.index_inputs(model, lp_full, normed, c_q, proj,
                                        plan)
        pool = jnp.zeros((1, 1 + pages, block,
                          index_select.pool_lanes(model.index_head_dim)),
                         kin.dtype)
        pool = index_select.write_index_keys(
            pool, kin.reshape(-1, model.index_head_dim), jnp.int32(0),
            *plan.scatter)

        def piece(lo):
            at = lo + jnp.arange(chunk)
            at = jnp.where(at < n, at, -1)[None]
            return index_select.select(
                jax.lax.dynamic_slice_in_dim(qi, lo, chunk, 1),
                jax.lax.dynamic_slice_in_dim(wts, lo, chunk, 1), pool,
                jnp.int32(0), tables, at, jnp.minimum(lo + chunk, n)[None],
                model.index_topk, kernels=kernels)[0]

        return jax.jit(piece)

    out = {}
    for name, model, patches in runs:
        with planted(patches):
            got = {}
            small = dataclasses.replace(
                model, num_layers=2, first_k_dense=0,
                layer_types=("full", "sliding"))
            for which, params in (
                ("full", {"ix_layers": full,
                          "sw_layers": zeroed(sliding, ("wo",))}),
                ("sliding", {"ix_layers": zeroed(full, ("wo",)),
                             "sw_layers": sliding}),
            ):
                params = dict(params, embedding=table,
                              final_norm=eng.params["final_norm"])
                hidden, _, kv = served_chain(
                    eng, small, params, ids, [[] for _ in inputs],
                    pools(small, len(inputs), geo), geo, hidden=True)
                del kv
                got[which] = [h - x.astype(np.float32)
                              for h, x in zip(hidden, inputs)]
            hit = of = 0
            topk = s["topk"]
            for x, n, ks in zip(padded, lens, keeps):
                if n <= topk:
                    continue
                piece = served_selection(model, x, n)
                for b_i, packed_keep in enumerate(ks):
                    lo = b_i * chunk
                    if lo + chunk <= topk:
                        continue
                    keep = np.unpackbits(packed_keep, axis=1,
                                         count=n).astype(bool)
                    mine = np.asarray(piece(jnp.int32(lo)))[:, :n] > 0
                    live = np.arange(lo, lo + chunk) >= topk
                    live &= np.arange(lo, lo + chunk) < n
                    hit += int((keep & mine)[live].sum())
                    of += int(keep[live].sum())
        errs = {}
        for which in ("full", "sliding"):
            n_ = sum(float(np.sum((g - a) ** 2))
                     for g, a in zip(got[which], want_blocks[which]))
            d_ = sum(float(np.sum(a ** 2)) for a in want_blocks[which])
            errs[which] = (n_ / d_) ** 0.5
        out[name] = {"rel_err": max(errs.values()),
                     "selection_overlap": hit / of if of else None,
                     "by_block": errs}
    return out


def judge(report, names, tol, sel):
    """Each run's readings against the limits the configuration holds, and
    the verdict ``report["ok"]``: the served run inside every limit, each
    run of ``FAILS`` outside at least one."""
    for name in names:
        out = report[name]
        out["within_tolerance"] = None \
            if out["rms_logit_diff"] is None or tol is None \
            else out["rms_logit_diff"] <= tol
        overlap = out["selection_overlap"]
        out["within_attention_tolerance"] = None \
            if sel.get("full") is None else (
                all(out["by_block"][k] <= sel[k]
                    for k in ("full", "sliding"))
                and (overlap is None or overlap >= sel["overlap_floor"]))
        print(f"{name}: rel_err {out['rel_err']:.5f} overlap {overlap} "
              f"by block {out['by_block']} logits "
              f"{out['within_tolerance']} sub-blocks "
              f"{out['within_attention_tolerance']}", flush=True)
    # a run passes when no limit it was read against fails; before the
    # limits are set (the first call), the readings alone are the result
    limits_set = tol is not None and sel.get("full") is not None
    report["ok"] = None if not limits_set else all(
        (report[name]["within_tolerance"] is not False
         and report[name]["within_attention_tolerance"])
        != (name in FAILS) for name in names)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--early", type=int, default=8)
    ap.add_argument("--late", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--long-lo", type=int, default=4096)
    ap.add_argument("--long-hi", type=int, default=20000)
    ap.add_argument("--timed-context", type=int, default=16384,
                    help="the longest prompt has at least this many tokens")
    ap.add_argument("--runs", default=None,
                    help="comma-separated subset of the runs (default: "
                         "served and the planted faults)")
    ap.add_argument("--logit-runs", default="served,fp8_latents",
                    help="comma-separated runs that serve the whole model "
                         "for the logits (the others: the sub-blocks alone)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--readings", default=None,
                    help="a report an earlier call wrote with --out: judge "
                         "its readings against the limits the configuration "
                         "holds now (any machine, nothing is run)")
    args = ap.parse_args()
    if args.readings:
        cfg = spec.load_config(spec.BENCH / "configs" / f"{args.config}.json")
        report = json.loads(Path(args.readings).read_text())
        judge(report, [n for n in ("served",) + FAILS if n in report],
              cfg["logit_tolerance"].get("value"), cfg["attention_tolerance"])
        print(json.dumps({"ok": report["ok"], "readings": args.readings}))
        return 0 if report["ok"] is not False else 1

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"platform {dev.platform!r}: the comparison at the "
                         "published widths is made on the chip")
    cfg = spec.load_config(spec.BENCH / "configs" / f"{args.config}.json")
    tol = cfg["logit_tolerance"].get("value")
    sel = cfg["attention_tolerance"]
    geo = cfg["serving_geometry"]
    window = int(cfg["sliding_window_size"])
    kinds = [t.split("_")[0] for t in cfg["layer_types"]]
    lead = int(cfg["first_k_dense_replace"])
    # the first full EXPERT layer, with a sliding layer behind it
    full_layer = next(l for l, k in enumerate(kinds)
                      if k == "full" and l >= lead)
    n_long = geo["max_batch_size"] // 2
    rng = np.random.default_rng(args.seed + 1)
    step = (args.long_hi - args.long_lo) // n_long
    lengths = [args.long_lo + i * step + int(rng.integers(0, step))
               for i in range(n_long)]
    lengths[-1] = max(lengths[-1], args.timed_context)
    prompts = sample_prompts(geo["max_batch_size"] - n_long, args.seed,
                             lo=12, hi=max(window - args.steps - 1, 13)) \
        + [[int(t) for t in rng.integers(4, 260, m)] for m in lengths]
    fed_rng = np.random.default_rng(args.seed + 2)
    fed = [[int(t) for t in fed_rng.integers(4, 260, args.steps)]
           for _ in prompts]
    at = list(range(args.early + 1)) + list(
        range(args.steps - args.late + 1, args.steps + 1))
    t0 = time.monotonic()
    want, want_routes, inputs = reference_pass(cfg, prompts, fed, at,
                                               full_layer)
    print(f"reference: prompts of {[len(p) for p in prompts]} tokens, one "
          f"pass in {time.monotonic() - t0:.1f}s", flush=True)
    t0 = time.monotonic()
    want_blocks, keeps = reference_blocks(cfg, inputs, geo, full_layer)
    print(f"reference sub-blocks of layers {full_layer} (full) and "
          f"{full_layer + 1} (sliding) in {time.monotonic() - t0:.1f}s",
          flush=True)

    from distributed_gpu_inference_tpu.worker.engines import create_engine

    llm = create_engine("llm", dict(cfg["worker_engine"]))
    llm.load_model()
    eng = llm.engine
    mc = eng.model_cfg
    eng.kv = None                       # each run draws pools of its own
    eng._scan_keys = None
    report = {"config": args.config, "device": dev.device_kind,
              "prompt_tokens": [len(p) for p in prompts],
              "steps": args.steps, "positions_compared": at,
              "full_layer": full_layer, "sliding_layer": full_layer + 1,
              "tolerance": tol,
              "attention_tolerance": {k: sel.get(k)
                                      for k in ("full", "sliding")},
              "overlap_floor": sel.get("overlap_floor")}
    runs = [("served", mc, ())] + [
        (name, model, patches)
        for name, (model, patches) in faults(mc).items()]
    chosen = set(args.runs.split(",")) if args.runs else {r[0] for r in runs}
    runs = [r for r in runs if r[0] in chosen]
    whole = set(args.logit_runs.split(","))
    for name, model, patches in runs:
        if name not in whole:
            report[name] = {"rms_logit_diff": None}
            continue
        t0 = time.monotonic()
        with planted(patches):
            logits, routes, kv = served_chain(
                eng, model, eng.params, prompts, fed,
                pools(model, len(prompts), geo), geo)
        del kv
        got = [lg[at] for lg in logits]
        out = report[name] = compare(want, got, want_routes, routes)
        for label, rows in (("short_rows", slice(0, len(prompts) - n_long)),
                            ("long_rows", slice(len(prompts) - n_long, None))):
            out[label] = float(np.sqrt(np.mean(np.concatenate(
                [(w - g).ravel()
                 for w, g in zip(want[rows], got[rows])]) ** 2)))
        deficits = first_token_deficits(want, got)
        out["first_token_deficit_max"] = float(deficits.max())
        out["first_token_deficit_p90"] = float(np.quantile(deficits, 0.9))
        print(f"{name}: {out} in {time.monotonic() - t0:.1f}s", flush=True)
    t0 = time.monotonic()
    blocks = sub_blocks(eng, cfg, runs, inputs, want_blocks, keeps, geo,
                        full_layer)
    print(f"served sub-blocks in {time.monotonic() - t0:.1f}s", flush=True)
    for name, *_ in runs:
        report[name].update(blocks[name])
    judge(report, [name for name, *_ in runs], tol, sel)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] is not False else 1


if __name__ == "__main__":
    sys.exit(main())
