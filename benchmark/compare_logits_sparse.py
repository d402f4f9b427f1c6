#!/usr/bin/env python3
"""The served model with an indexer (learned sparse attention inside paged
GQA attention, an index-key pool beside the K/V pages) against its plain
reference, at the published widths and the timed context lengths, on the
chip, outside any timed window. Two statistics, a limit each in the
configuration file:

    python3 benchmark/compare_logits_sparse.py --config <name> [--out <file>]

**The logits** (``logit_tolerance``). Eight seeded prompts, one a row of
the engine's eight: four **short** (under ``topk`` tokens: the selection
keeps everything) and four **long** (4,096 to 20,000 tokens, one of at
least 16,384). The reference (the module the configuration file names
under ``reference``, weights regenerated from ``weights_seed``) gives the
logits at the last prompt position and along its own greedy chain for
``--early`` further positions, each from a full forward pass; the rows are
then fed seeded random tokens up to ``--steps`` positions, and one more
pass gives the logits at the last ``--late`` of them. The configuration's
engine is loaded the way the worker loads it, and ``forward_chunk`` runs
the same tokens on the engine's weights through the three pools **as the
engine's rounds do**: packed rounds (``llama.Packing``, at the engine's own
ladder of packed lengths) in which every row still in its prompt sends its
next 256-token piece and every row past it a decode token beside them, then
one token a row a step.

**The attention sub-block** (``selection_tolerance``). That last pass of
the reference also shows the input of the first, the middle and the last
layer for every token of every row. Rounded to bfloat16 (what the served
layer is handed) it goes through the reference's attention sub-block
(float32: projections, the per-head norms, the indexer, ``S_t``, attention
over ``S_t``, ``W_o``) and through the served layer's (the engine's weights
of that layer as a one-layer model whose experts are zeroed, its own
engine-sized pools, the same packed rounds, the same kernels). Compared:
the served sub-block's output with the reference's, as the norm of the
difference over the norm of the reference's, over every token; and
``selection_overlap``, the share of the reference's ``S_t`` the served
selection chose (``ops/index_select.select`` on the served indexer's
queries and its pool, piece by piece), over the queries past ``topk``. Both
sides see the same input, so neither the depth nor the router sets this
floor. With random weights attention is near uniform and the logits may
not tell a dense model from the sparse one; this limit must.

Seven runs:

``served``             the kernels on: ``dgi_index_*``, ``dgi_paged_write``,
                       ``dgi_ragged_attention``, ``dgi_paged_decode``. Must
                       pass both limits
``served_xla``         ``forward_chunk(pallas=False)``: the selection and
                       attention through their XLA forms, a row at a time
                       (a rectangle of eight rows of 24,576 positions is
                       6 GB of float32 scores there): pieces, then steps.
                       Must pass both
``no_selection``       dense attention (``index_topk`` past any context).
                       Must FAIL a limit
``topk_1024``          half the published ``topk``. Must FAIL a limit
``no_qk_norm``         the per-head QK-norm dropped. Must FAIL a limit
``index_not_rotated``  index queries and keys not rotated. Must FAIL a limit
``fp8_keys``           q, k and the indexer's queries and keys rounded to
                       float8_e4m3 after their rotation: what an fp8 key
                       cache (the nearest precision below the served
                       bfloat16; the engine refuses it for this model)
                       would hold. Must FAIL a limit
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
from compare_logits_kda import (  # noqa: E402
    first_token_deficits, pack, patched, schedule,
)
from harness import spec  # noqa: E402


@contextlib.contextmanager
def planted(patches):
    """Every ``(module attribute, value)`` of a run's planted fault."""
    from distributed_gpu_inference_tpu.models import llama

    with contextlib.ExitStack() as stack:
        for name, value in patches:
            stack.enter_context(patched(llama, name, value))
        yield


def reference_passes(cfg, prompts, early, steps, late, probed, seed):
    """The reference's side: per prompt its logits at the last prompt
    position, along its greedy chain for ``early`` steps and at the last
    ``late`` of ``steps`` fed positions; the tokens fed; its routing of the
    whole sequence; and for each probed layer and prompt the layer's input
    rounded to bfloat16 ``[S, h]``."""
    import jax.numpy as jnp

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    rng = np.random.default_rng(seed + 2)
    seqs = [list(p) for p in prompts]
    logits = [[] for _ in seqs]
    for _ in range(early + 1):
        got, _ = ref.forward(cfg, weights, seqs)
        for n, lg in enumerate(got):
            logits[n].append(lg[0])
            seqs[n].append(int(lg[0].argmax()))
    fed = [s[len(p):-1] + [int(t) for t in rng.integers(4, 260, steps - early)]
           for s, p in zip(seqs, prompts)]
    seqs = [list(p) + f for p, f in zip(prompts, fed)]
    inputs = {l: [None] * len(seqs) for l in probed}

    def tap(l, n, w, x):
        if l in inputs:
            inputs[l][n] = np.asarray(x.astype(jnp.bfloat16))

    got, routes = ref.forward(
        cfg, weights, seqs, tap=tap,
        at=[list(range(len(s) - late, len(s))) for s in seqs])
    want = [np.concatenate([np.stack(a), b]) for a, b in zip(logits, got)]
    return want, fed, routes, inputs


def served_chain(eng, mc, params, prompts, fed, kv, geo, *, pallas=True,
                 hidden=False):
    """The same tokens through ``forward_chunk`` on ``params``, scheduled
    as the engine's rounds are. Returns per row the logits after its prompt
    and after each fed token ``[1 + len(fed), V]`` and the routing of its
    tokens ``[L, tokens, k]``; with ``hidden``, per row the final hidden
    state of every token ``[tokens, h]`` instead of the logits."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import llama

    rows, block, chunk = len(prompts), geo["block_size"], geo["ragged_chunk"]
    pages = geo["max_seq_len"] // block
    if not pallas and rows > 1:
        # the XLA forms hold a row's scores whole: a row at a time, each
        # with pools of its own
        del kv
        outs = [served_chain(
            eng, mc, params, [p], [f],
            llama.init_kv_pools(mc, 1 + pages, block), geo, pallas=False,
            hidden=hidden) for p, f in zip(prompts, fed)]
        return [o[0][0] for o in outs], [o[1][0] for o in outs], None
    tables = jnp.asarray(
        1 + np.arange(rows * pages).reshape(rows, pages), jnp.int32)
    common = dict(block_size=block, last_only=True, collect_routing=True,
                  with_logits=not hidden, pallas=pallas)

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
        route = np.asarray(out.routing)
        for n, (r, start, m) in enumerate(segs):
            routes[r].append(route[:, at[n]:at[n] + m])
            if hidden:
                out_rows[r].append(states[at[n]:at[n] + m])
            elif start + m >= len(prompts[r]):
                out_rows[r].append(lg[r])
    join = np.concatenate if hidden else np.stack
    return ([join(x) for x in out_rows],
            [np.concatenate(x, axis=1) for x in routes], kv)


def sub_block(eng, cfg, variants, inputs, geo):
    """Each probed layer's attention sub-block alone, for every variant:
    ``{variant: {"rel_err", "selection_overlap", by layer}}``. The served
    side is the engine's weights of that layer as a one-layer model whose
    experts give zero, so that ``hidden out - hidden in`` is the
    sub-block's output; its selection is read by the calls the layer makes
    (``llama.index_inputs``, ``index_select.select``) on the same input."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.ops import index_select
    from distributed_gpu_inference_tpu.ops.quantization import matmul

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    s = ref.dims(cfg)
    block, chunk = geo["block_size"], geo["ragged_chunk"]
    pages = geo["max_seq_len"] // block
    project = jax.jit(lambda w, x: ref.project(s, w, x))
    attend = jax.jit(lambda p, lo: ref.attend(s, p, lo, ref.BLOCK))
    out_proj = jax.jit(lambda w, a: a @ w["wo"])
    sums = {v[0]: {"num": 0.0, "den": 0.0, "hit": 0, "of": 0, "layers": {}}
            for v in variants}

    def served_selection(model, lp, x, n, pallas):
        """The served selection of one row's ``n`` tokens, as a function of
        the piece: keys into a pool of the row's own, then ``select`` a
        piece of ``chunk`` queries at a time."""
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)[None]
        tables = jnp.asarray(1 + np.arange(pages)[None], jnp.int32)
        # the selection's kernels where the layer's graph takes them
        kernels = pallas and llama.ragged_kv_path(
            model, pages * block, False) == "in_place"
        plan = llama._index_plan(model, 1 + pages, tables, pos, pos, None,
                                 block)
        normed = llama.rms_norm(jnp.asarray(x)[None], lp["attn_norm"],
                                model.rms_norm_eps)
        qi, kin, wts = llama.index_inputs(
            model, lp, normed, lambda x_, name: matmul(x_, lp[name], pallas),
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

    assert ref.BLOCK == chunk
    for l, rows in inputs.items():
        w = weights.layer(l)
        lp = jax.tree.map(lambda a: a[l], eng.params["layers"])
        one = jax.tree.map(lambda a: a[l:l + 1], eng.params["layers"])
        one["we_down"] = jax.tree.map(jnp.zeros_like, one["we_down"])
        lens = [len(x) for x in rows]
        width = -(-max(lens) // chunk) * chunk
        padded = [np.concatenate([x, np.zeros((width - len(x), x.shape[1]),
                                              x.dtype)]) for x in rows]
        # the reference's output of the sub-block, and S_t block by block
        want, keeps = [], []
        with jax.default_matmul_precision("highest"):
            for x, n in zip(padded, lens):
                p = project(w, jnp.asarray(x, jnp.float32))
                outs, ks = [], []
                for lo in range(0, -(-n // chunk) * chunk, chunk):
                    a, keep = attend(p, jnp.int32(lo))
                    outs.append(np.asarray(out_proj(w, a)))
                    ks.append(np.packbits(np.asarray(keep)[:, :n], axis=1))
                want.append(np.concatenate(outs)[:n])
                keeps.append(ks)
        del w
        for name, model, pallas, patches in variants:
            tot = sums[name]
            with planted(patches):
                # the output: a one-layer model over an embedding table
                # that IS the input, row by row
                model1 = dataclasses.replace(model, num_layers=1)
                base = np.cumsum([0] + lens)
                table = jnp.asarray(np.concatenate(rows), jnp.bfloat16)
                params = {"embedding": table, "layers": one,
                          "final_norm": eng.params["final_norm"]}
                kv = llama.init_kv_pools(
                    model1, 1 + len(rows) * pages, block)
                ids = [list(range(base[r], base[r + 1]))
                       for r in range(len(rows))]
                hidden, _, kv = served_chain(
                    eng, model1, params, ids, [[] for _ in rows], kv, geo,
                    pallas=pallas, hidden=True)
                del kv
                got = [h - x.astype(np.float32)
                       for h, x in zip(hidden, rows)]
                num = sum(float(np.sum((g - a) ** 2))
                          for g, a in zip(got, want))
                den = sum(float(np.sum(a ** 2)) for a in want)
                hit = of = 0
                topk = s["topk"]
                for x, n, ks in zip(padded, lens, keeps):
                    if n <= topk:
                        continue
                    piece = served_selection(model, lp, x, n, pallas)
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
            tot["num"] += num
            tot["den"] += den
            tot["hit"] += hit
            tot["of"] += of
            tot["layers"][str(l)] = {
                "rel_err": (num / den) ** 0.5,
                "selection_overlap": hit / of if of else None}
    return {name: {"rel_err": (t["num"] / t["den"]) ** 0.5,
                   "selection_overlap": t["hit"] / t["of"] if t["of"]
                   else None, "by_layer": t["layers"]}
            for name, t in sums.items()}


def fp8_rope(real):
    """``llama.apply_rope`` whose result is rounded to float8_e4m3."""
    import jax.numpy as jnp

    def rope(x, cos, sin):
        out = real(x, cos, sin)
        return out.astype(jnp.float8_e4m3fn).astype(out.dtype)

    return rope


def unrotated_plan(real):
    """``llama._index_plan`` with the identity for a rotation."""
    import jax.numpy as jnp

    def plan(*a, **kw):
        out = real(*a, **kw)
        return out._replace(cos=jnp.ones_like(out.cos),
                            sin=jnp.zeros_like(out.sin))

    return plan


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--early", type=int, default=8)
    ap.add_argument("--late", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--long-lo", type=int, default=4096)
    ap.add_argument("--long-hi", type=int, default=20000)
    ap.add_argument("--runs", default=None,
                    help="comma-separated subset of the six runs")
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
    topk = int(cfg["sa_config"]["topk"])
    layers = int(cfg["num_hidden_layers"])
    probed = sorted({0, layers // 2, layers - 1})
    n_long = geo["max_batch_size"] // 2
    rng = np.random.default_rng(args.seed + 1)
    step = (args.long_hi - args.long_lo) // n_long
    lengths = [args.long_lo + i * step + int(rng.integers(0, step))
               for i in range(n_long)]
    lengths[-1] = max(lengths[-1], 16384)       # one at the timed context
    prompts = sample_prompts(geo["max_batch_size"] - n_long, args.seed,
                             lo=12, hi=topk - args.steps - 1) \
        + [[int(t) for t in rng.integers(4, 260, m)] for m in lengths]
    t0 = time.monotonic()
    want, fed, want_routes, inputs = reference_passes(
        cfg, prompts, args.early, args.steps, args.late, probed, args.seed)
    print(f"reference: prompts of {[len(p) for p in prompts]} tokens, "
          f"{args.early + 2} passes in {time.monotonic() - t0:.1f}s",
          flush=True)
    at = list(range(args.early + 1)) + list(
        range(args.steps - args.late + 1, args.steps + 1))

    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.worker.engines import create_engine

    llm = create_engine("llm", dict(cfg["worker_engine"]))
    llm.load_model()
    eng = llm.engine
    mc = eng.model_cfg
    eng.kv = None                       # each run draws pools of its own
    report = {"config": args.config, "device": dev.device_kind,
              "prompt_tokens": [len(p) for p in prompts],
              "steps": args.steps, "positions_compared": at,
              "layers_probed": probed, "tolerance": tol,
              "selection_tolerance": sel.get("value"),
              "overlap_floor": sel.get("overlap_floor")}
    runs = (
        ("served", mc, True, ()),
        ("served_xla", mc, False, ()),
        ("no_selection", dataclasses.replace(mc, index_topk=2 ** 30), True,
         ()),
        ("topk_1024", dataclasses.replace(mc, index_topk=topk // 2), True,
         ()),
        ("no_qk_norm", dataclasses.replace(mc, qk_norm_per_head=False), True,
         ()),
        ("index_not_rotated", mc, True,
         (("_index_plan", unrotated_plan(llama._index_plan)),)),
        ("fp8_keys", mc, True, (("apply_rope", fp8_rope(llama.apply_rope)),)),
    )
    fails = ("no_selection", "topk_1024", "no_qk_norm", "index_not_rotated",
             "fp8_keys")
    chosen = set(args.runs.split(",")) if args.runs else None
    runs = tuple(r for r in runs if chosen is None or r[0] in chosen)
    for name, model, pallas, patches in runs:
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
    blocks = sub_block(eng, cfg, runs, inputs, geo)
    print(f"sub-blocks of layers {probed} in {time.monotonic() - t0:.1f}s",
          flush=True)
    for name, *_ in runs:
        out = report[name]
        out.update(blocks[name])
        out["within_tolerance"] = tol is not None \
            and out["rms_logit_diff"] <= tol
        overlap = out["selection_overlap"]
        out["within_selection_tolerance"] = sel.get("value") is not None \
            and out["rel_err"] <= sel["value"] \
            and (overlap is None or overlap >= sel["overlap_floor"])
        print(f"{name}: rel_err {out['rel_err']:.5f} overlap {overlap} "
              f"by layer {out['by_layer']}", flush=True)
    report["ok"] = all(
        (report[name]["within_tolerance"]
         and report[name]["within_selection_tolerance"]) != (name in fails)
        for name, *_ in runs)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
