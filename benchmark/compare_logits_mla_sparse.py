#!/usr/bin/env python3
"""The served latent-attention model with an indexer whose selection most
layers borrow (GLM-5.2: the indexer on latent pages, IndexShare) against
its plain reference, at the published widths and the timed context
lengths, on the chip, outside any timed window. Two statistics, a limit
each in the configuration file:

    python3 benchmark/compare_logits_mla_sparse.py --config <name> [--out <file>]

**The logits** (``logit_tolerance``). Eight seeded prompts, one a row of
the engine's eight: four **short** (under ``index_topk`` tokens: the
selection keeps everything) and four **long** (4,096 to 20,000 tokens, one
of at least 16,384), each followed by ``--steps`` seeded random tokens. The
reference (the module the configuration file names under ``reference``,
weights regenerated from ``weights_seed``) gives, from ONE full forward
pass a row, the logits at the last prompt position, after each of the first
``--early`` fed tokens and after the last ``--late``. The configuration's
engine is loaded the way the worker loads it, and ``forward_chunk`` runs
the same tokens on the engine's weights through the two pools **as the
engine's rounds do** (``compare_logits_sparse.served_chain``): packed
rounds in which every row still in its prompt sends its next 256-token
piece and every row past it a decode token beside them, then one token a
row a step.

**The attention sub-blocks** (``selection_tolerance``). That pass of the
reference also shows the input ``X`` of one FULL expert layer for every
token of every row. Rounded to bfloat16 it goes through two sub-blocks of
the reference (float32) and of the served path (the engine's weights, its
own pools, the same packed rounds and kernels):

``full``    the full layer's attention alone: projections, the indexer,
            ``S_t``, attention over ``S_t``, ``W_o`` (a one-layer model
            whose experts give zero: hidden out - hidden in);
``shared``  a SHARED layer's attention over the selection the full layer
            computes on the same ``X`` (a two-layer model, the full layer's
            ``W_o`` and both layers' experts zeroed, so that the shared
            layer's input is ``X`` too and hidden out - hidden in is its
            sub-block's output).

Compared: the norm of the difference over the norm of the reference's,
over every token of both; and ``selection_overlap``, the share of the
reference's ``S_t`` the served selection chose, over the queries past
``index_topk``. Both sides see the same input, so neither the depth nor
the router sets this floor.

The runs: ``served`` (the kernels on) must pass both limits
(``served_xla``, the XLA forms a row at a time, runs where ``--runs`` names
it: its expanded form holds a 24,576-position row's per-head keys, values
and scores in float32, several GB beside the engine's weights); each
planted fault must FAIL one (``--logit-runs`` names the runs whose whole
model is served for the logits; the others are judged by the sub-blocks
alone, which every fault but the router's moves):
``no_selection`` (dense attention everywhere), ``topk_1024``,
``shared_dense`` (shared layers attend everything), ``shared_stale``
(shared layers read the selection of the full layer one period earlier),
``index_not_rotated``, ``rope_halves`` (rope by halves, not pairs),
``no_renorm`` (router weights not renormalised) and ``fp8`` (the latent
rows, the index keys and the index queries rounded to float8_e4m3: the
nearest precision below the served bfloat16, which the engine refuses for
this model).
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

from compare_logits import compare, sample_prompts  # noqa: E402
from compare_logits_kda import first_token_deficits, patched  # noqa: E402
from compare_logits_sparse import served_chain  # noqa: E402
from harness import spec  # noqa: E402

FAILS = ("no_selection", "topk_1024", "shared_dense", "shared_stale",
         "index_not_rotated", "rope_halves", "no_renorm", "fp8")


@contextlib.contextmanager
def planted(patches):
    """Every ``(module, attribute, value)`` of a run's planted fault."""
    with contextlib.ExitStack() as stack:
        for module, name, value in patches:
            stack.enter_context(patched(module, name, value))
        yield


def faults(mc):
    """name → (model configuration, module patches) of the planted runs."""
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import llama, mla
    from distributed_gpu_inference_tpu.ops import index_select
    from distributed_gpu_inference_tpu.ops import mla_attention_pallas as mk

    real_attention = mla._latent_attention

    def shared_dense(cfg, bs, x, lp, proj, kv, layer, *, sel=None,
                     scored=False, **kw):
        attn, kv, own = real_attention(
            cfg, bs, x, lp, proj, kv, layer, sel=sel if scored else None,
            scored=scored, **kw)
        return attn, kv, own if scored else sel

    def shared_stale(cfg, bs, x, lp, proj, kv, layer, *, sel=None,
                     scored=False, **kw):
        attn, kv, own = real_attention(
            cfg, bs, x, lp, proj, kv, layer, sel=sel, scored=scored, **kw)
        # an expert layer that scores attends its own selection and hands
        # on the one it was given: the dense full layer's, a period old
        return attn, kv, sel if scored and "w_router" in lp else own

    real_plan = llama._index_plan

    def unrotated(*a, **kw):
        out = real_plan(*a, **kw)
        return out._replace(cos=jnp.ones_like(out.cos),
                            sin=jnp.zeros_like(out.sin))

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    real_write, real_keys = mk.write_latent_pages_in_place, \
        index_select.write_index_keys
    real_append, real_select = index_select.append_scan_keys, \
        index_select.select
    fp8_patches = (
        (mk, "write_latent_pages_in_place",
         lambda rows, *a, **kw: real_write(fp8(rows), *a, **kw)),
        (index_select, "write_index_keys",
         lambda pool, new, *a, **kw: real_keys(pool, fp8(new), *a, **kw)),
        (index_select, "append_scan_keys",
         lambda keys, new, *a, **kw: real_append(keys, fp8(new), *a, **kw)),
        (index_select, "select",
         lambda qi, *a, **kw: real_select(fp8(qi), *a, **kw)),
    )
    rep = dataclasses.replace
    return {
        "no_selection": (rep(mc, index_topk=2 ** 30), ()),
        "topk_1024": (rep(mc, index_topk=mc.index_topk // 2), ()),
        "shared_dense": (mc, ((mla, "_latent_attention", shared_dense),)),
        "shared_stale": (mc, ((mla, "_latent_attention", shared_stale),)),
        "index_not_rotated": (mc, ((llama, "_index_plan", unrotated),)),
        "rope_halves": (rep(mc, rope_interleave=False), ()),
        "no_renorm": (rep(mc, norm_topk_prob=False), ()),
        "fp8": (mc, fp8_patches),
    }


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
    loaded (a 20k-token row's float32 projections and the engine's weights
    do not fit the chip together): ``S_t`` of the full layer on ``X`` a
    block of queries (packed bits), and both sub-blocks' outputs."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    s = ref.dims(cfg)
    chunk = geo["ragged_chunk"]
    assert ref.BLOCK == chunk
    shared_layer = shared_layer_of(cfg, full_layer)
    project = jax.jit(lambda w, x: ref.project(s, w, x))
    select = jax.jit(lambda p, lo: ref.select(s, p, lo, ref.BLOCK))
    attend = jax.jit(
        lambda w, p, keep, lo: ref.attend(s, w, p, keep, lo, ref.BLOCK)
        @ w["wo"])
    lens = [len(x) for x in inputs]
    width = -(-max(lens) // chunk) * chunk
    want = {"full": [], "shared": []}
    keeps = []
    with jax.default_matmul_precision("highest"):
        w_full, w_shared = weights.layer(full_layer, ref.ATTENTION), \
            weights.layer(shared_layer, ref.ATTENTION)
        for x, n in zip(inputs, lens):
            x = jnp.asarray(np.concatenate(
                [x, np.zeros((width - n, x.shape[1]), x.dtype)]), jnp.float32)
            p_full = project(w_full, x)
            los = range(0, -(-n // chunk) * chunk, chunk)
            masks = [select(p_full, jnp.int32(lo)) for lo in los]
            keeps.append([np.packbits(np.asarray(k)[:, :n], axis=1)
                          for k in masks])
            for name, w in (("full", w_full), ("shared", w_shared)):
                p = p_full if name == "full" else project(w, x)
                want[name].append(np.concatenate([
                    np.asarray(attend(w, p, keep, jnp.int32(lo)))
                    for keep, lo in zip(masks, los)])[:n])
    return want, keeps


def shared_layer_of(cfg, full_layer):
    """The last shared layer behind ``full_layer`` (its period's end)."""
    kinds = list(cfg["indexer_types"])
    layer = full_layer + 1
    while layer + 1 < len(kinds) and kinds[layer + 1] == "shared":
        layer += 1
    if layer >= len(kinds) or kinds[layer] != "shared":
        layer = max(l for l in range(full_layer) if kinds[l] == "shared")
    return layer


def sub_blocks(eng, cfg, runs, inputs, want_blocks, keeps, geo, full_layer):
    """The full layer's and the shared layer's attention sub-blocks alone,
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
    shared_layer = shared_layer_of(cfg, full_layer)
    lens = [len(x) for x in inputs]
    width = -(-max(lens) // chunk) * chunk
    padded = [np.concatenate([x, np.zeros((width - len(x), x.shape[1]),
                                          x.dtype)]) for x in inputs]

    def stack_of(group, layer):
        at = ref.group_of(s, layer)[1]
        return jax.tree.map(lambda a: a[at:at + 1], eng.params[group])

    def zeroed(stack, names):
        return dict(stack, **{n: jax.tree.map(jnp.zeros_like, stack[n])
                              for n in names})

    full = zeroed(stack_of("ix_layers", full_layer), ("we_down", "ws_down"))
    shared = zeroed(stack_of("layers", shared_layer), ("we_down", "ws_down"))
    lp_full = jax.tree.map(lambda a: a[0], full)
    base = np.cumsum([0] + lens)
    table = jnp.asarray(np.concatenate(inputs), jnp.bfloat16)
    ids = [list(range(base[r], base[r + 1])) for r in range(len(inputs))]

    def served_selection(model, x, n, pallas):
        """The served selection of one row's ``n`` tokens: index keys into
        a pool of the row's own, then ``select`` a piece at a time."""
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)[None]
        tables = jnp.asarray(1 + np.arange(pages)[None], jnp.int32)
        kernels = pallas and mla.kernels_on(model, pages * block,
                                            jnp.bfloat16)
        plan = llama._index_plan(model, 1 + pages, tables, pos, pos, None,
                                 block)

        def proj(x_, name):
            return matmul(x_, lp_full[name], pallas)

        normed = llama.rms_norm(jnp.asarray(x)[None], lp_full["attn_norm"],
                                model.rms_norm_eps)
        c_q = llama.rms_norm(proj(normed, "wq_a"), lp_full["q_a_norm"],
                             model.rms_norm_eps)
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
    for name, model, pallas, patches in runs:
        with planted(patches):
            got = {}
            for which, layers, params in (
                ("full", ("full",), {"ix_layers": full}),
                ("shared", ("full", "shared"),
                 {"ix_layers": zeroed(full, ("wo",)), "layers": shared}),
            ):
                small = dataclasses.replace(
                    model, num_layers=len(layers), first_k_dense=0,
                    index_types=layers)
                params = dict(params, embedding=table,
                              final_norm=eng.params["final_norm"])
                kv = llama.init_kv_pools(
                    small, 1 + len(inputs) * pages, block)
                hidden, _, kv = served_chain(
                    eng, small, params, ids, [[] for _ in inputs], kv, geo,
                    pallas=pallas, hidden=True)
                del kv
                got[which] = [h - x.astype(np.float32)
                              for h, x in zip(hidden, inputs)]
            hit = of = 0
            topk = s["topk"]
            for x, n, ks in zip(padded, lens, keeps):
                if n <= topk:
                    continue
                piece = served_selection(model, x, n, pallas)
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
        num = den = 0.0
        want = want_blocks
        for which in ("full", "shared"):
            n_ = sum(float(np.sum((g - a) ** 2))
                     for g, a in zip(got[which], want[which]))
            d_ = sum(float(np.sum(a ** 2)) for a in want[which])
            errs[which] = (n_ / d_) ** 0.5
            num, den = num + n_, den + d_
        out[name] = {"rel_err": max(errs.values()),
                     "rel_err_pooled": (num / den) ** 0.5,
                     "selection_overlap": hit / of if of else None,
                     "by_block": errs}
    return out


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
    ap.add_argument("--logit-runs", default=None,
                    help="comma-separated runs that serve the whole model "
                         "for the logits (default: every run)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"platform {dev.platform!r}: the comparison at the "
                         "published widths is made on the chip")
    cfg = spec.load_config(spec.BENCH / "configs" / f"{args.config}.json")
    tol = cfg["logit_tolerance"]["value"]
    sel = cfg["selection_tolerance"]
    geo = cfg["serving_geometry"]
    topk = int(cfg["index_topk"])
    kinds = list(cfg["indexer_types"])
    # the first full EXPERT layer and the last shared layer behind it
    full_layer = next(l for l, k in enumerate(kinds)
                      if k == "full" and cfg["mlp_layer_types"][l] == "sparse")
    shared_layer = shared_layer_of(cfg, full_layer)
    n_long = geo["max_batch_size"] // 2
    rng = np.random.default_rng(args.seed + 1)
    step = (args.long_hi - args.long_lo) // n_long
    lengths = [args.long_lo + i * step + int(rng.integers(0, step))
               for i in range(n_long)]
    lengths[-1] = max(lengths[-1], args.timed_context)
    prompts = sample_prompts(geo["max_batch_size"] - n_long, args.seed,
                             lo=12, hi=max(topk - args.steps - 1, 13)) \
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
          f"{shared_layer} (shared) in {time.monotonic() - t0:.1f}s",
          flush=True)

    from distributed_gpu_inference_tpu.models import llama
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
              "full_layer": full_layer, "shared_layer": shared_layer,
              "tolerance": tol,
              "selection_tolerance": sel.get("value"),
              "overlap_floor": sel.get("overlap_floor")}
    runs = [("served", mc, True, ()), ("served_xla", mc, False, ())] + [
        (name, model, True, patches)
        for name, (model, patches) in faults(mc).items()]
    chosen = set(args.runs.split(",")) if args.runs \
        else {r[0] for r in runs} - {"served_xla"}
    runs = [r for r in runs if r[0] in chosen]
    whole = set(args.logit_runs.split(",")) if args.logit_runs else chosen
    for name, model, pallas, patches in runs:
        if name not in whole:
            report[name] = {"rms_logit_diff": None}
            continue
        t0 = time.monotonic()
        kv = llama.init_kv_pools(
            model, 1 + len(prompts) * (geo["max_seq_len"]
                                       // geo["block_size"]),
            geo["block_size"])
        with planted(patches):
            logits, routes, kv = served_chain(
                eng, model, eng.params, prompts, fed, kv, geo, pallas=pallas)
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
        out = report[name]
        out.update(blocks[name])
        out["within_tolerance"] = None if out["rms_logit_diff"] is None \
            else tol is not None and out["rms_logit_diff"] <= tol
        overlap = out["selection_overlap"]
        out["within_selection_tolerance"] = sel.get("value") is not None \
            and out["rel_err"] <= sel["value"] \
            and (overlap is None or overlap >= sel["overlap_floor"])
        print(f"{name}: rel_err {out['rel_err']:.5f} overlap {overlap} "
              f"by block {out['by_block']}", flush=True)
    # a run passes when no limit it was read against fails
    report["ok"] = all(
        (report[name]["within_tolerance"] is not False
         and report[name]["within_selection_tolerance"]) != (name in FAILS)
        for name, *_ in runs)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
