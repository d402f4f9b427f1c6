#!/usr/bin/env python3
"""The served model of mixed attention kinds (window and full layers with a
head count and a rotation per kind, a per-head output gate, K/V pages per
layer kind) against its plain reference, at the published widths and the
timed context lengths, on the chip, outside any timed window. Two
statistics, a limit each in the configuration file:

    python3 benchmark/compare_logits_window.py --config <name> [--out <file>]

**The logits** (``logit_tolerance``). Eight seeded prompts, one a row of
the engine's eight: four **short** (under 512 tokens: a sliding layer sees
the whole context) and four **long** (4,096 to 20,000 tokens, one of at
least 16,384), each followed by ``--steps`` seeded tokens. The reference
(the module the configuration file names under ``reference``, weights
regenerated from ``weights_seed``) gives, from ONE full forward pass a
sequence, the logits at the last prompt position, after each of the first
``--early`` fed tokens and after the last ``--late``. The configuration's
engine is loaded the way the worker loads it, and ``forward_chunk`` runs
the same tokens on the engine's weights through both kinds' pools **as the
engine's rounds do**: packed rounds (``llama.Packing``, at the engine's own
ladder of packed lengths) in which every row still in its prompt sends its
next 256-token piece and every row past it a decode token beside them, then
one token a row a step; a block table a kind, and before every call the
window kind's entries that every coming query is past set to the pad block,
as the engine sets them when the cache manager releases those blocks.

**A second request on a prefix hit** (same limit). Then each long row's
first 16,384 tokens (its whole prompt where that is shorter, cut to a whole
block) come again followed by a fresh question, admitted as the cache
manager admits a hit on a model of mixed kinds: the full kind's table points
at the first request's pages of the whole prefix, the window kind's at its
pages of the prefix's last window alone (pad block before them), and only
the question is prefilled. Its logits at the last prompt position and after
``--early`` fed tokens are held to the same limit against a full forward
pass of the reference over the whole sequence.

**The attention sub-block** (``attention_tolerance``). The reference's pass
also shows the input of one full layer and of the sliding layer after it
for every token of every row. Rounded to bfloat16 (what the served layer is
handed) it goes through the reference's attention sub-block of that layer
(float32: projections, the kind's rotation, the window, attention, the
gate, ``W_o``) and through the served one's (the engine's weights of the
two layers as a two-layer model in which everything but the probed
sub-block gives zero, its own pools, the same packed rounds, the same
kernels). Compared: the norm of the difference over the norm of the
reference's, over every token. Both sides see the same input, so neither
the depth nor the router sets this floor.

Eight runs:

``served``          the kernels on: ``dgi_paged_write``,
                    ``dgi_ragged_attention``, ``dgi_paged_decode``,
                    ``dgi_moe_gmm`` / ``_step``; cold and on the hit. Must
                    pass both limits
``fp8_qk``          q and k rounded to float8_e4m3 after their rotation
                    (the nearest precision below the served bfloat16).
                    Must FAIL a limit
``no_window``       the sliding layers attend their whole context
``window_256``      half the published window
``no_yarn``         the full layers rotate at the plain frequencies
``full_rotation``   a full layer rotates all 128 values of a head
``no_gate``         the per-head output gate dropped
``no_scale``        the routed weights not scaled by 2.5
                    -- each must FAIL a limit
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
from compare_logits_sparse import fp8_rope  # noqa: E402
from harness import spec  # noqa: E402

FAILS = ("fp8_qk", "no_window", "window_256", "no_yarn", "full_rotation",
         "no_gate", "no_scale")


def reference_pass(cfg, prompts, fed, at, probed=()):
    """One full forward pass a sequence ``prompt + fed``: per sequence the
    logits at the positions ``len(prompt) - 1 + at``, its routing, and for
    each probed layer the layer's input rounded to bfloat16 ``[S, h]``."""
    import jax.numpy as jnp

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    seqs = [list(p) + list(f) for p, f in zip(prompts, fed)]
    inputs = {l: [None] * len(seqs) for l in probed}

    def tap(l, n, w, x):
        if l in inputs:
            inputs[l][n] = np.asarray(x.astype(jnp.bfloat16))

    got, routes = ref.forward(
        cfg, weights, seqs, tap=tap,
        at=[[len(p) - 1 + j for j in at] for p in prompts])
    # the routed layers' rows alone, as the served pass emits them
    routed = [t == "sparse" for t in
              cfg["mlp_layer_types"][:int(cfg["num_hidden_layers"])]]
    return got, [r[routed] for r in routes], inputs


class Pages:
    """Both kinds' block tables of the comparison's rows, ``[rows, 2 x
    pages]`` side by side as the engine keeps them, over pools that hold
    every block of every row (the comparison keeps what the engine's cache
    would: a released window block stays where it was, findable)."""

    def __init__(self, rows: int, pages: int, block: int, window: int):
        self.rows, self.pages, self.block, self.window = \
            rows, pages, block, window
        self.full = np.zeros((rows, pages), np.int32)
        self.win = np.zeros((rows, pages), np.int32)    # as allocated
        self.live = np.zeros((rows, pages), np.int32)   # as a call sees it
        self.next_full = self.next_win = 1

    def allocate(self, row: int, lo_block: int, tokens: int) -> None:
        """Fresh blocks of both kinds for ``row`` from ``lo_block`` up to
        the block that holds token ``tokens - 1``."""
        hi = -(-tokens // self.block)
        n = hi - lo_block
        self.full[row, lo_block:hi] = self.next_full + np.arange(n)
        self.win[row, lo_block:hi] = self.next_win + np.arange(n)
        self.live[row, lo_block:hi] = self.win[row, lo_block:hi]
        self.next_full += n
        self.next_win += n

    def share(self, row: int, donor: int, prefix: int) -> None:
        """A prefix hit of ``prefix`` tokens on ``donor``'s pages: the
        whole prefix in the full kind, its last window's blocks in the
        window kind, the pad block before them."""
        d = prefix // self.block
        first = max(prefix - self.window + 1, 0) // self.block
        self.full[row], self.win[row], self.live[row] = 0, 0, 0
        self.full[row, :d] = self.full[donor, :d]
        self.win[row, first:d] = self.win[donor, first:d]
        self.live[row, first:d] = self.win[donor, first:d]

    def release(self, row: int, next_query: int) -> None:
        """What the engine does when the manager releases blocks: entries
        every query from ``next_query`` on is past point at the pad
        block."""
        dead = max(next_query - self.window + 1, 0) // self.block
        self.live[row, :dead] = 0

    def tables(self) -> np.ndarray:
        return np.concatenate([self.full, self.live], axis=1)


def served_chain(eng, mc, params, pg: Pages, rows_of, prompts, fed, kv, geo,
                 *, cached=None, release=True, hidden=False):
    """The sequences ``prompt + fed`` of the batch rows ``rows_of`` through
    ``forward_chunk`` on ``params``, scheduled as the engine's rounds are,
    from position ``cached[i]`` of each (a prefix hit; 0: cold). Returns
    per sequence the logits after its prompt and after each fed token
    ``[1 + len(fed), V]`` and the routing of its tokens ``[L, tokens, k]``;
    with ``hidden``, the final hidden state of every token ``[tokens, h]``
    instead of the logits; and the pools."""
    import jax

    from distributed_gpu_inference_tpu.models import llama

    rows, block, chunk = pg.rows, geo["block_size"], geo["ragged_chunk"]
    cached = list(cached or [0] * len(prompts))
    common = dict(block_size=block, last_only=True, collect_routing=True,
                  with_logits=not hidden, pallas=True)

    def packed(params, tok, pos, kv, tables, lens, row, col, last, width):
        return llama.forward_chunk(
            mc, params, tok, pos, kv, tables, lens,
            packing=llama.Packing(row, col, last, width), **common)

    def stepped(params, tok, pos, kv, tables, lens):
        return llama.forward_chunk(mc, params, tok, pos, kv, tables, lens,
                                   **common)

    packed = jax.jit(packed, static_argnames=("width",), donate_argnums=(3,))
    stepped = jax.jit(stepped, donate_argnums=(3,))
    seqs = [list(p) + list(f) for p, f in zip(prompts, fed)]
    out_rows = [[] for _ in prompts]
    routes = [[] for _ in prompts]
    for is_round, segs in schedule(
            [len(p) - c for p, c in zip(prompts, cached)],
            [len(s) - c for s, c in zip(seqs, cached)], chunk):
        # (sequence, start, count) with absolute starts, on its batch row
        segs = [(n, cached[n] + start, m) for n, start, m in segs]
        on_rows = [(rows_of[n], start, m) for n, start, m in segs]
        if release:
            for r, start, _ in on_rows:
                pg.release(r, start)
        tables = pg.tables()
        if is_round:
            tp, width = eng._ragged_shape(sum(m for *_, m in segs))
            row, col, pos, last, lens = pack(on_rows, tp, rows)
            tok = np.zeros(tp, np.int32)
            tok[:int((pos >= 0).sum())] = [
                t for n, start, m in segs for t in seqs[n][start:start + m]]
            out = packed(params, tok, pos, kv, tables, lens, row, col, last,
                         width=width)
            at = np.cumsum([0] + [m for *_, m in segs])
            states = None if not hidden else np.asarray(
                out.hidden[0], np.float32)
        else:
            tok = np.zeros((rows, 1), np.int32)
            pos = np.full((rows, 1), -1, np.int32)
            for (n, start, _), (r, *_) in zip(segs, on_rows):
                tok[r, 0], pos[r, 0] = seqs[n][start], start
            out = stepped(params, tok, pos, kv, tables,
                          (pos[:, 0] + 1).clip(min=0))
            at = [r for r, *_ in on_rows] + [0]
            states = None if not hidden else np.asarray(
                out.hidden[:, 0], np.float32)
        kv = out.kv
        lg = None if hidden else np.asarray(out.logits[:, 0], np.float32)
        route = np.asarray(out.routing)
        for i, ((n, start, m), (r, *_)) in enumerate(zip(segs, on_rows)):
            routes[n].append(route[:, at[i]:at[i] + m])
            if hidden:
                out_rows[n].append(states[at[i]:at[i] + m])
            elif start + m >= len(prompts[n]):
                out_rows[n].append(lg[r])
    join = np.concatenate if hidden else np.stack
    return ([join(x) for x in out_rows],
            [np.concatenate(x, axis=1) for x in routes], kv)


def pools_for(mc, pg: Pages, block: int):
    from distributed_gpu_inference_tpu.models import llama

    return llama.init_kv_pools(mc, pg.next_full, block,
                               window_blocks=pg.next_win)


def without(tree, name):
    """The parameter tree with leaf ``name`` taken out of every stack."""
    return {k: ({n: v for n, v in g.items() if n != name}
                if isinstance(g, dict) else g) for k, g in tree.items()}


def sub_block(eng, cfg, variants, inputs, geo):
    """Each probed layer's attention sub-block alone, for every variant:
    ``{variant: {"rel_err", by layer}}``. The served side is the engine's
    weights of a full layer and a sliding layer as a two-layer model in
    which all but the probed sub-block gives zero (the other layer's
    ``W_o``, both MLPs' down projections), so that ``hidden out - hidden
    in`` is the sub-block's output on an embedding table that IS the
    input."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    s = ref.dims(cfg)
    block = geo["block_size"]
    pages = geo["max_seq_len"] // block
    mc = eng.model_cfg
    sums = {v[0]: {"num": 0.0, "den": 0.0, "layers": {}} for v in variants}
    probed = sorted(inputs)
    full_l = next(l for l in probed if mc.attn_kinds[l] == "full")
    slid_l = next(l for l in probed if mc.attn_kinds[l] == "sliding")

    def silenced(stack, attention: bool):
        out = dict(stack)
        for name in ("we_down", "ws_down", "w_down") + (
                ("wo",) if attention else ()):
            if name in out:
                out[name] = jax.tree.map(jnp.zeros_like, out[name])
        return out

    for l in probed:
        w = weights.layer(l)
        rows = inputs[l]
        lens = [len(x) for x in rows]
        want = []
        for x in rows:
            padded = ref.pad_to_block(jnp.asarray(x, jnp.float32))
            want.append(np.asarray(
                ref.attention(s, l, w, padded))[:len(x)])
        del w
        base = np.cumsum([0] + lens)
        table = jnp.asarray(np.concatenate(rows), jnp.bfloat16)
        ids = [list(range(base[r], base[r + 1])) for r in range(len(rows))]
        for name, model, params_of, patches, _ in variants:
            pair = dataclasses.replace(
                model, num_layers=2, layer_types=("full", "sliding"),
                first_k_dense=0, vocab_size=int(table.shape[0]))
            tree = params_of(eng.params)
            probe_full = l == full_l
            params = {
                "embedding": table, "final_norm": eng.params["final_norm"],
                "full_layers": silenced(_stack(tree, mc, full_l),
                                        not probe_full),
                "layers": silenced(_stack(tree, mc, slid_l), probe_full),
            }
            pg = Pages(len(rows), pages, block, model.sliding_window)
            for r, n in enumerate(lens):
                pg.allocate(r, 0, n)
            with planted(patches):
                hidden, _, kv = served_chain(
                    eng, pair, params, pg, list(range(len(rows))), ids,
                    [[] for _ in rows], pools_for(pair, pg, block), geo,
                    release=name != "no_window", hidden=True)
            del kv
            got = [h - x.astype(np.float32) for h, x in zip(hidden, rows)]
            num = sum(float(np.sum((g - a) ** 2)) for g, a in zip(got, want))
            den = sum(float(np.sum(a ** 2)) for a in want)
            sums[name]["num"] += num
            sums[name]["den"] += den
            sums[name]["layers"][str(l)] = (num / den) ** 0.5
    return {name: {"rel_err": (t["num"] / t["den"]) ** 0.5,
                   "by_layer": t["layers"]} for name, t in sums.items()}


def _stack(tree, mc, l):
    """Layer ``l``'s slice of its parameter stack, as a stack of one."""
    import jax

    from distributed_gpu_inference_tpu.models import llama

    group = llama.group_of(mc, l)
    at = [j for j in range(mc.num_layers)
          if llama.group_of(mc, j) == group].index(l)
    return jax.tree.map(lambda a: a[at:at + 1], tree[group])


@contextlib.contextmanager
def planted(patches):
    """Every ``(module attribute, value)`` of a run's planted fault."""
    from distributed_gpu_inference_tpu.models import llama

    with contextlib.ExitStack() as stack:
        for name, value in patches:
            stack.enter_context(patched(llama, name, value))
        yield


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--early", type=int, default=8)
    ap.add_argument("--late", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--long-lo", type=int, default=4096)
    ap.add_argument("--long-hi", type=int, default=20000)
    ap.add_argument("--hit-prefix", type=int, default=16384)
    ap.add_argument("--runs", default=None,
                    help="comma-separated subset of the eight runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"platform {dev.platform!r}: the comparison at the "
                         "published widths is made on the chip")
    cfg = spec.load_config(spec.BENCH / "configs" / f"{args.config}.json")
    tol = cfg["logit_tolerance"]["value"]
    sub_tol = cfg["attention_tolerance"]["value"]
    geo = cfg["serving_geometry"]
    block, window = geo["block_size"], int(cfg["sliding_window"])
    pages = geo["max_seq_len"] // block
    kinds = cfg["layer_types"]
    # a full layer with experts and the sliding layer after it
    full_l = next(l for l, t in enumerate(kinds)
                  if t == "full_attention" and l > 0)
    probed = [full_l, full_l + 1]
    n_rows = geo["max_batch_size"]
    n_long = n_rows // 2
    rng = np.random.default_rng(args.seed + 1)
    step = (args.long_hi - args.long_lo) // n_long
    lengths = [args.long_lo + i * step + int(rng.integers(0, step))
               for i in range(n_long)]
    lengths[-1] = max(lengths[-1], args.hit_prefix + block)
    prompts = sample_prompts(n_rows - n_long, args.seed, lo=12,
                             hi=window - args.steps - 1) \
        + [[int(t) for t in rng.integers(4, 260, m)] for m in lengths]
    fed = [[int(t) for t in rng.integers(4, 260, args.steps)]
           for _ in prompts]
    at = list(range(args.early + 1)) + list(
        range(args.steps - args.late + 1, args.steps + 1))
    # the second requests: a long row's prefix again, then a question
    prefixes = [min(args.hit_prefix, len(p) // block * block)
                for p in prompts[n_rows - n_long:]]
    hit_prompts = [p[:c] + [int(t) for t in rng.integers(4, 260, 300)]
                   for p, c in zip(prompts[n_rows - n_long:], prefixes)]
    hit_fed = [[int(t) for t in rng.integers(4, 260, args.early)]
               for _ in hit_prompts]
    hit_at = list(range(args.early + 1))
    t0 = time.monotonic()
    want, want_routes, inputs = reference_pass(cfg, prompts, fed, at, probed)
    hit_want, hit_routes, _ = reference_pass(cfg, hit_prompts, hit_fed,
                                             hit_at)
    print(f"reference: prompts of {[len(p) for p in prompts]} tokens and "
          f"hits of {prefixes} of {[len(p) for p in hit_prompts]} in "
          f"{time.monotonic() - t0:.1f}s", flush=True)

    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.worker.engines import create_engine

    llm = create_engine("llm", dict(cfg["worker_engine"]))
    llm.load_model()
    eng = llm.engine
    mc = eng.model_cfg
    eng.kv = None                       # each run draws pools of its own
    report = {"config": args.config, "device": dev.device_kind,
              "prompt_tokens": [len(p) for p in prompts],
              "hit_prefix_tokens": prefixes,
              "hit_prompt_tokens": [len(p) for p in hit_prompts],
              "steps": args.steps, "positions_compared": at,
              "layers_probed": probed, "tolerance": tol,
              "attention_tolerance": sub_tol}
    same = lambda tree: tree            # noqa: E731
    replace = dataclasses.replace
    runs = (
        ("served", mc, same, (), True),
        ("fp8_qk", mc, same,
         (("apply_rope", fp8_rope(llama.apply_rope)),), False),
        ("no_window", replace(mc, sliding_window=2 ** 30), same, (), False),
        ("window_256", replace(mc, sliding_window=window // 2), same, (),
         False),
        ("no_yarn", replace(mc, rope_yarn=None), same, (), False),
        ("full_rotation", replace(mc, partial_rotary_factor=1.0), same, (),
         False),
        ("no_gate", mc, lambda tree: without(tree, "w_hgate"), (), False),
        ("no_scale", replace(mc, routed_scaling_factor=1.0), same, (),
         False),
    )
    chosen = set(args.runs.split(",")) if args.runs else None
    runs = tuple(r for r in runs if chosen is None or r[0] in chosen)
    long_rows = list(range(n_rows - n_long, n_rows))
    for name, model, params_of, patches, with_hit in runs:
        t0 = time.monotonic()
        pg = Pages(n_rows, pages, block, model.sliding_window)
        for r, (p, f) in enumerate(zip(prompts, fed)):
            pg.allocate(r, 0, len(p) + len(f))
        if with_hit:        # the hits' own blocks, past their prefixes
            spare = Pages(n_rows, pages, block, model.sliding_window)
            spare.next_full, spare.next_win = pg.next_full, pg.next_win
            for r, (p, f, c) in enumerate(zip(hit_prompts, hit_fed,
                                              prefixes)):
                spare.allocate(r, c // block, len(p) + len(f))
            pg.next_full, pg.next_win = spare.next_full, spare.next_win
        params = params_of(eng.params)
        kv = pools_for(model, pg, block)
        with planted(patches):
            logits, routes, kv = served_chain(
                eng, model, params, pg, list(range(n_rows)), prompts, fed,
                kv, geo, release=name != "no_window")
            got = [lg[at] for lg in logits]
            out = report[name] = compare(want, got, want_routes, routes)
            if with_hit:
                # rows 0 .. n_long - 1 (the short rows are done) take the
                # second requests, each a hit on a long row's pages
                for r, (donor, c) in enumerate(zip(long_rows, prefixes)):
                    pg.share(r, donor, c)
                    hi = -(-(len(hit_prompts[r]) + len(hit_fed[r])) // block)
                    lo = c // block
                    pg.full[r, lo:hi] = spare.full[r, lo:hi]
                    pg.win[r, lo:hi] = spare.win[r, lo:hi]
                    pg.live[r, lo:hi] = spare.win[r, lo:hi]
                hit_logits, hit_got_routes, kv = served_chain(
                    eng, model, params, pg, list(range(n_long)), hit_prompts,
                    hit_fed, kv, geo, cached=prefixes)
                hit_got = [lg[hit_at] for lg in hit_logits]
                out["on_a_hit"] = compare(
                    hit_want, hit_got,
                    [r_[:, c:] for r_, c in zip(hit_routes, prefixes)],
                    hit_got_routes)
        del kv
        for label, rows in (("short_rows", slice(0, n_rows - n_long)),
                            ("long_rows", slice(n_rows - n_long, None))):
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
        worst = max([out["rms_logit_diff"]] + (
            [out["on_a_hit"]["rms_logit_diff"]] if "on_a_hit" in out else []))
        out["within_tolerance"] = tol is not None and worst <= tol
        out["within_attention_tolerance"] = sub_tol is not None \
            and out["rel_err"] <= sub_tol
        print(f"{name}: rel_err {out['rel_err']:.5f} by layer "
              f"{out['by_layer']}", flush=True)
    report["ok"] = all(
        (report[name]["within_tolerance"]
         and report[name]["within_attention_tolerance"]) != (name in FAILS)
        for name, *_ in runs)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
