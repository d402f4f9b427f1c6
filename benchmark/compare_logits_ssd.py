#!/usr/bin/env python3
"""The served model with a state-space mixer beside attention in every layer
(a state pool beside K/V pages) against its plain reference, at the
published widths, on the chip, outside any timed window: what
``compare_logits_kda.py`` does, for the K/V recipe, along the path the cell
times. Two statistics, a limit each in the configuration file:

    python3 benchmark/compare_logits_ssd.py --config <name> [--out <file>]

**The logits** (``logit_tolerance``). Eight seeded prompts, one a row of the
engine's eight: six of 12 to 128 tokens and ``--long`` (two) of 300 to
1,000. The reference (the module the configuration file names under
``reference``, weights regenerated from ``weights_seed``) gives the logits
at the last prompt position and along its own greedy chain for ``--early``
further positions, each from a full forward pass; the rows are then fed
seeded random tokens up to ``--steps`` positions, and one more pass gives
the logits at the last ``--late`` of them, by which the state has been
rewritten ``--steps`` times. All of that is made BEFORE the engine loads
(the reference's float32 layers and the engine's weights do not fit the
chip together). Then the configuration's engine is loaded the way the
worker loads it, and ``forward_chunk`` runs the same tokens on the engine's
weights through pool and pages **as the engine's rounds do**: packed rounds
(``llama.Packing``, at the engine's own ladder of packed lengths) in which
every row still in its prompt sends its next 256-token piece and every row
past it a decode token beside them, then one token a row a step. The
statistic is relative, because ``lm_head_multiplier`` makes this model's
logits 128 times smaller than the other cells': the root mean square of the
difference over the root mean square of the reference's logits.

**The state** (``state_tolerance``). That last pass of the reference also
shows, at layers 0, 9 and 17, the normed input of the layer for every token
of every row. Rounded to bfloat16 (what the served layer is handed) it goes
through the reference's recurrence (``mixer_recurrence``), token by token in
float32, and through the served layer's mixer alone (``models/ssd.mixer``:
the engine's weights of that layer, its row of an engine-sized state pool,
the same rounds and steps, the same kernels). Compared: the state the pool
holds for the layer's eight rows after the last step with the recurrence's,
as the norm of the difference over the norm of the reference's.

Eight runs, the faults planted on the served side:

``served``             the kernels on: ``dgi_ssd_chunk`` for the rounds,
                       ``dgi_ssd_step`` for the steps, the K/V kernels.
                       Must pass both limits
``served_xla``         the two SSD kernels off (``models/ssd.kernels_on``
                       held false), all else as served. Must pass both
``state_bf16``         the state pool carried in bfloat16, the nearest
                       precision below the served float32. Must FAIL
``tail_dropped``       the convolution's tails read as zero at every round.
                       Must FAIL
``no_key_multiplier``  ``key_multiplier`` dropped. Must FAIL
``no_b_multiplier``    the ``B`` entry of ``ssm_multipliers`` dropped. Must
                       FAIL
``no_attention``       the attention branch left out of the sum. Must FAIL
``gate_after_norm``    the gate applied after the grouped norm. Must FAIL

Each run also reports, over the compared positions, the largest deficit of a
served first token (the reference's logit at its own argmax less its logit
at the served argmax): what the golden file's ``margin`` bounds in
``run.py``, on this model's logit scale.
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

from compare_logits import sample_prompts  # noqa: E402
from compare_logits_kda import (  # noqa: E402
    first_token_deficits, pack, patched, schedule, zero_tails,
)
from compare_logits_mla import long_prompts  # noqa: E402
from harness import spec  # noqa: E402

FAILS = ("state_bf16", "tail_dropped", "no_key_multiplier",
         "no_b_multiplier", "no_attention", "gate_after_norm")


def probed_layers(cfg):
    """The first, the middle and the last layer (0-based)."""
    n = int(cfg["num_hidden_layers"])
    return sorted({0, n // 2, n - 1})


def reference_side(cfg, prompts, early, steps, late, probed, rng):
    """Everything the reference gives, before the engine loads: per prompt
    its logits after the prompt and along its own greedy chain of ``early``
    tokens, then (fed seeded tokens up to ``steps``) at the last ``late``
    positions, ``[1 + early + late, V]``; the tokens fed; and for each
    probed layer and prompt the layer's normed input rounded to bfloat16
    with the state the recurrence leaves after it."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"harness.{cfg['reference']}")
    weights = ref.SeedStream(cfg, cfg["weights_seed"])
    dims = ref.dims(cfg)
    seqs = [list(p) for p in prompts]
    width = max(map(len, seqs)) + steps
    logits = [[] for _ in seqs]
    for _ in range(early + 1):
        for n, lg in enumerate(ref.forward(cfg, weights, seqs, width=width)):
            logits[n].append(lg[0])
            seqs[n].append(int(lg[0].argmax()))
    fed = [s[len(p):-1] + [int(t) for t in rng.integers(4, 260, steps - early)]
           for s, p in zip(seqs, prompts)]
    seqs = [list(p) + f for p, f in zip(prompts, fed)]
    probes = {l: [None] * len(seqs) for l in probed}
    recurrence = jax.jit(lambda w, a: ref.mixer_recurrence(dims, w, a)[2])

    def tap(l, n, w, x):
        if l in probes:
            a = ref._rms_norm(x[:len(seqs[n])], w["attn_norm"], dims["eps"])
            a = a.astype(jnp.bfloat16)
            with jax.default_matmul_precision("highest"):
                state = recurrence(w, a.astype(jnp.float32))
            probes[l][n] = (np.asarray(a), np.asarray(state))

    at = [list(range(len(s) - late, len(s))) for s in seqs]
    tail = ref.forward(cfg, weights, seqs, at=at, tap=tap)
    want = [np.concatenate([np.stack(a), b]) for a, b in zip(logits, tail)]
    return want, fed, probes


def gate_after_norm(cfg, y, z, w_norm):
    """The planted fault: the grouped norm first, the gate after it."""
    import jax
    import jax.numpy as jnp

    t, g = y.shape[0], cfg.ssm_num_groups
    y = y.reshape(t, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    return y.reshape(t, -1) * w_norm.astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))


def faults(ssd, kernels, drop_tails, gate):
    """The patches a run holds while it traces."""
    import contextlib

    stack = contextlib.ExitStack()
    stack.enter_context(patched(
        ssd, "kernels_on", None if kernels else (lambda *a, **kw: False)))
    stack.enter_context(patched(
        ssd, "read_tails", zero_tails if drop_tails else None))
    stack.enter_context(patched(
        ssd, "gated_norm", gate_after_norm if gate else None))
    return stack


def served_chain(eng, mc, prompts, fed, kv, geo, **fault):
    """The same tokens through ``forward_chunk`` on the engine's weights,
    scheduled as the engine's rounds are. Returns per row the logits after
    its prompt and after each fed token ``[1 + len(fed), V]``."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import llama, ssd

    rows, block, chunk = len(prompts), geo["block_size"], geo["ragged_chunk"]
    assert rows == geo["max_batch_size"] == kv[ssd.STATE].shape[1]
    pages = geo["max_seq_len"] // block
    tables = jnp.asarray(
        1 + np.arange(rows * pages).reshape(rows, pages), jnp.int32)

    def packed(params, tok, pos, kv, lens, row, col, last, width):
        return llama.forward_chunk(
            mc, params, tok, pos, kv, tables, lens, block_size=block,
            packing=llama.Packing(row, col, last, width))

    def stepped(params, tok, pos, kv, lens):
        return llama.forward_chunk(mc, params, tok, pos, kv, tables, lens,
                                   block_size=block)

    packed = jax.jit(packed, static_argnames=("width",), donate_argnums=(3,))
    stepped = jax.jit(stepped, donate_argnums=(3,))
    seqs = [list(p) + list(f) for p, f in zip(prompts, fed)]
    logits = [[] for _ in prompts]
    with faults(ssd, **fault):
        for is_round, segs in schedule(
                list(map(len, prompts)), list(map(len, seqs)), chunk):
            if is_round:
                tp, width = eng._ragged_shape(sum(m for *_, m in segs))
                row, col, pos, last, lens = pack(segs, tp, rows)
                tok = np.zeros(tp, np.int32)
                tok[:int((pos >= 0).sum())] = [
                    t for r, start, m in segs for t in seqs[r][start:start + m]]
                out = packed(eng.params, tok, pos, kv, lens, row, col, last,
                             width=width)
            else:
                tok = np.zeros((rows, 1), np.int32)
                pos = np.full((rows, 1), -1, np.int32)
                for r, start, _ in segs:
                    tok[r, 0], pos[r, 0] = seqs[r][start], start
                out = stepped(eng.params, tok, pos, kv,
                              (pos[:, 0] + 1).clip(min=0))
            kv = out.kv
            lg = np.asarray(out.logits[:, 0], np.float32)
            for r, start, m in segs:
                if start + m >= len(prompts[r]):
                    logits[r].append(lg[r])
    return [np.stack(x) for x in logits], kv


def state_error(eng, mc, probes, prompt_lens, geo, state_dtype, **fault):
    """Each probed layer's mixer alone, on the engine's weights of that
    layer and its row of a state pool of the engine's size, fed the inputs
    of ``probes`` in the order of :func:`schedule`: the norm of (the pool's
    state of the layer's rows after the last step - the reference's) over
    the norm of the reference's, over all probed layers, and per layer."""
    import jax
    import jax.numpy as jnp

    from distributed_gpu_inference_tpu.models import ssd
    from distributed_gpu_inference_tpu.ops.quantization import matmul

    rows, chunk = len(prompt_lens), geo["ragged_chunk"]
    one = dataclasses.replace(mc, num_layers=1)     # a pool of one layer

    def fresh():
        return ssd.init_state_pools(one, rows, state_dtype=(
            state_dtype if state_dtype is not None else jnp.float32))

    def mixer(lp, x, kv, plan, positions):
        kernels = ssd.kernels_on(mc, kv[ssd.STATE].dtype)
        return ssd.mixer(
            mc, x, lp, lambda x_, name: matmul(x_, lp[name], True), kv,
            jnp.int32(0), plan=plan, positions=positions, kernels=kernels)[1]

    def packed(lp, x, row, col, pos, kv):
        return mixer(lp, x[None], kv, ssd.make_plan(mc, row, col, pos, rows),
                     None)

    def stepped(lp, x, pos, kv):
        return mixer(lp, x[:, None], kv, None, pos[:, None])

    packed = jax.jit(packed, donate_argnums=(5,))
    stepped = jax.jit(stepped, donate_argnums=(3,))
    num, den, per_layer = 0.0, 0.0, {}
    with faults(ssd, **fault):
        for l, seen in probes.items():
            lp = jax.tree.map(lambda a: a[l], eng.params["layers"])
            kv = fresh()
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
                    kv = packed(lp, x, row, col, pos, kv)
                else:
                    full = np.zeros((rows, x.shape[1]), x.dtype)
                    pos = np.full(rows, -1, np.int32)
                    for n, (r, start, _) in enumerate(segs):
                        full[r], pos[r] = x[n], start
                    kv = stepped(lp, full, pos, kv)
            got = np.asarray(kv[ssd.STATE][0], np.float32)
            want = np.stack([state for _, state in seen])
            a, b = float(np.sum((got - want) ** 2)), float(np.sum(want ** 2))
            per_layer[str(l)] = (a / b) ** 0.5
            num, den = num + a, den + b
    return (num / den) ** 0.5, per_layer


def logit_stats(want, got):
    """The logits' statistic and what stands beside it."""
    diff = np.concatenate([(w - g).ravel() for w, g in zip(want, got)])
    ref = np.concatenate([w.ravel() for w in want])
    argmax = sum(int((w.argmax(-1) == g.argmax(-1)).sum())
                 for w, g in zip(want, got))
    rms_ref = float(np.sqrt(np.mean(ref ** 2)))
    return {"rel_rms_logit_diff": float(np.sqrt(np.mean(diff ** 2)))
            / rms_ref,
            "rms_logit_diff": float(np.sqrt(np.mean(diff ** 2))),
            "rms_reference_logit": rms_ref,
            "max_abs_logit_diff": float(np.abs(diff).max()),
            "argmax_agreement": argmax / sum(len(w) for w in want)}


def runs_of(mc):
    """(name, the model as the run configures it, the state pool's dtype or
    None for the served float32, the patches)."""
    import jax.numpy as jnp

    mz, mx, _, mc_, mdt = mc.ssm_multipliers
    clean = dict(kernels=True, drop_tails=False, gate=False)
    return (
        ("served", mc, None, clean),
        ("served_xla", mc, None, dict(clean, kernels=False)),
        ("state_bf16", mc, jnp.bfloat16, clean),
        ("tail_dropped", mc, None, dict(clean, drop_tails=True)),
        ("no_key_multiplier", dataclasses.replace(mc, key_multiplier=1.0),
         None, clean),
        ("no_b_multiplier", dataclasses.replace(
            mc, ssm_multipliers=(mz, mx, 1.0, mc_, mdt)), None, clean),
        ("no_attention", dataclasses.replace(
            mc, attention_out_multiplier=0.0), None, clean),
        ("gate_after_norm", mc, None, dict(clean, gate=True)),
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--long", type=int, default=2)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--early", type=int, default=8)
    ap.add_argument("--late", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
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
    tol = float(cfg["logit_tolerance"]["value"])
    state_tol = float(cfg["state_tolerance"]["value"])
    geo = cfg["serving_geometry"]
    n_long = max(args.long, 2)
    prompts = sample_prompts(geo["max_batch_size"] - n_long, args.seed,
                             lo=12, hi=128) \
        + long_prompts(n_long, args.seed, lo=300, hi=1000)
    rng = np.random.default_rng(args.seed + 2)
    probed = probed_layers(cfg)
    t0 = time.monotonic()
    want, fed, probes = reference_side(cfg, prompts, args.early, args.steps,
                                       args.late, probed, rng)
    print(f"reference: prompts of {[len(p) for p in prompts]} tokens, "
          f"{args.early + 2} passes in {time.monotonic() - t0:.1f}s",
          flush=True)
    # positions compared: after the prompt, steps 1..early, the last `late`
    at = list(range(args.early + 1)) + list(
        range(args.steps - args.late + 1, args.steps + 1))

    from distributed_gpu_inference_tpu.models import llama, ssd
    from distributed_gpu_inference_tpu.worker.engines import create_engine

    llm = create_engine("llm", dict(cfg["worker_engine"]))
    llm.load_model()
    eng = llm.engine
    eng.kv = None                       # each run draws pools of its own
    report = {"config": args.config, "device": dev.device_kind,
              "prompt_tokens": [len(p) for p in prompts],
              "steps": args.steps, "positions_compared": at,
              "layers_probed": probed,
              "tolerance": tol, "state_tolerance": state_tol}
    chosen = set(args.runs.split(",")) if args.runs else None
    runs = tuple(r for r in runs_of(eng.model_cfg)
                 if chosen is None or r[0] in chosen)
    for name, model, state_dtype, fault in runs:
        t0 = time.monotonic()
        kv = llama.init_kv_pools(
            model, 1 + len(prompts) * (geo["max_seq_len"]
                                       // geo["block_size"]),
            geo["block_size"], state_rows=len(prompts))
        if state_dtype is not None:
            kv[ssd.STATE] = kv[ssd.STATE].astype(state_dtype)
        logits, kv = served_chain(eng, model, prompts, fed, kv, geo, **fault)
        del kv
        got = [lg[at] for lg in logits]
        out = report[name] = logit_stats(want, got)
        deficits = first_token_deficits(want, got)
        out["first_token_deficit_max"] = float(deficits.max())
        out["first_token_deficit_p90"] = float(np.quantile(deficits, 0.9))
        out["top_two_gap_p10"] = float(np.quantile(np.concatenate([
            np.diff(np.sort(w, axis=-1)[:, -2:], axis=-1)[:, 0]
            for w in want]), 0.1))
        out["state_rel_err"], out["state_rel_err_by_layer"] = state_error(
            eng, model, probes, [len(p) for p in prompts], geo, state_dtype,
            **fault)
        out["within_tolerance"] = out["rel_rms_logit_diff"] <= tol
        out["within_state_tolerance"] = out["state_rel_err"] <= state_tol
        print(f"{name}: {out} in {time.monotonic() - t0:.1f}s", flush=True)
    report["ok"] = all(
        (report[name]["within_tolerance"]
         and report[name]["within_state_tolerance"]) != (name in FAILS)
        for name, *_ in runs)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
