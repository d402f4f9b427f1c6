#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the machine that holds the cell's chips.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``. Everything else worth keeping goes to earlier lines
and to ``<out>/<cell>.seed<n>.trace<t>.json``.

The run fails (exit code 1, no result line) when JAX finds no TPU or fewer
chips than the cell asks for, when the device is not in the peaks table,
when the program is not in the checkout, or when the engine does not load
as the configuration file says. ``JAX_PLATFORMS=cpu`` allows the dry run of
the stand-in cells under ``benchmark/testdata/`` and nothing else.
"""

from __future__ import annotations

import time

T0 = time.monotonic()       # process start, as near as Python can read it

import argparse             # noqa: E402
import faulthandler         # noqa: E402
import json                 # noqa: E402
import sys                  # noqa: E402
from pathlib import Path    # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

from harness import layers, metrics, reference, spec, trace_reduce  # noqa: E402
from harness.session import RunFailed, Session, check_spec, say  # noqa: E402
from harness.window import (  # noqa: E402
    detail_requests, run_window, window_compared, within,
)

ROUND_PROGRAMS = ("ragged_round", "decode_multi")


def probe_check(s: Session, cell: Dict[str, Any]) -> Dict[str, Any]:
    """Each probe prompt's first served token against the golden file's
    rule (``reference.first_token_verdict``)."""
    path = cell["_golden"]
    if not path.is_file():
        return {"ok": False, "why": f"no golden file {path.name}: make it "
                "with benchmark/make_golden.py", "probes": [],
                "compared": {"probes_outside_top": {"value": 1, "limit": 0}}}
    with open(path) as f:
        golden = json.load(f)
    by_name = {p["name"]: p for p in golden["probes"]}
    out, ok = [], True
    for row in s.probe_rows:
        g = by_name.get(row["id"])
        if g is None or not row["ids"]:
            out.append({"name": row["id"], "ok": False})
            ok = False
            continue
        verdict = reference.first_token_verdict(
            row["ids"][0], g["top"], float(golden["margin"])
        )
        out.append({"name": row["id"], "first_token": row["ids"][0],
                    "ttft_ms": metrics.ttft_ms(row), **verdict})
        ok = ok and verdict["ok"]
    # a probe's first token outside the reference's top ids has no deficit
    deficits = [p["deficit"] for p in out if p.get("deficit") is not None]
    compared = {
        "probes_outside_top": {"value": len(out) - len(deficits), "limit": 0},
        "probe_deficit_max": {"value": max(deficits, default=0.0),
                              "limit": float(golden["margin"])},
    }
    return {"ok": ok, "margin": golden["margin"], "probes": out,
            "compared": compared}


def in_flight_on_trace(rows: List[Dict[str, Any]], offset: float
                       ) -> List[Any]:
    """Intervals, on the trace's clock, in which some request was between
    its send and its last event."""
    ivals = []
    for r in rows:
        end = r["done_at"] or (r["t"][-1] if r["t"] else None)
        if r.get("sent") is not None and end is not None:
            ivals.append((r["sent"] + offset, end + offset))
    return trace_reduce.union(ivals)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--out", default=None,
                    help="directory for the run's detail file (default "
                         ".cache/benchmark/out)")
    args = ap.parse_args()
    faulthandler.dump_traceback_later(1150, exit=True)

    if not (spec.CHECKOUT / "distributed_gpu_inference_tpu").is_dir():
        raise RunFailed("no distributed_gpu_inference_tpu/ beside benchmark/: "
                        "run from the root of a checkout of the program")
    cell = spec.load_cell(args.workload)
    check_spec(cell)
    cfg, traffic = cell["_config"], cell["_traffic"]
    out_dir = spec.out_dir(args.out)
    traced = bool(args.trace)
    with open(HERE / "harness" / "peaks.json") as f:
        peaks_table = json.load(f)

    with Session(cell, T0) as s:
        peaks = peaks_table.get(s.device["kind"])
        if peaks is None and s.device["platform"] != "cpu":
            raise RunFailed(f"device_kind {s.device['kind']!r} is not in "
                            "harness/peaks.json")
        s.warm()
        probes = probe_check(s, cell)
        say(T0, "probes: " + ", ".join(
            f"{p['name']}:{'ok' if p['ok'] else 'BAD'}"
            f"(rank {p.get('rank')}, deficit {p.get('deficit')})"
            for p in probes["probes"]) + f" margin {probes.get('margin')}")
        if traced:
            s.annotate()

        plan = s.generator.generate(
            traffic["params"], cell.get("rate_rps"), args.seed, args.seconds
        )
        trace_dir = str(out_dir / "trace") if traced else None
        if trace_dir:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
        say(T0, f"set-up done: {s.timing}")
        win = run_window(
            s, plan, args.seconds, float(cell["drain_s"]), trace_dir,
            extra_params={"trace_id": f"b{args.seed}"} if traced else None,
        )
        if traced and s.annotation_errors:
            raise RunFailed(
                f"{len(s.annotation_errors)} engine calls could not be "
                "annotated, so the engine.* metrics have no source: "
                f"{s.annotation_errors[0]} (harness/session.py annotate())")
        setup_s = win["w0"] - T0
        memory_peak = s.memory_peak_bytes()

    # ------------------------------------------------------------------ #
    # from rows, counters and trace to the line
    # ------------------------------------------------------------------ #
    rows, w0, w1 = win["rows"], win["w0"], win["w1"]
    vocab = int(cfg["vocab_size"])
    summary = metrics.summarize(rows, w0, w1, vocab, cell.get("limits"))
    sample = [r for r in rows if w0 <= r["due"] < w1]
    compiles_in = s.compiles.between(w0, w1)
    compared = window_compared(s, win, plan, summary, vocab)
    compared.update(probes["compared"])
    checks = within(compared)
    correct = all(checks.values())

    red: Optional[Dict[str, Any]] = None
    structure: List[str] = []
    if traced:
        xplane = trace_reduce.find_xplane(trace_dir)
        if xplane is None:
            raise RunFailed("the profiler left no .xplane.pb")
        loaded = trace_reduce.load(xplane)
        structure = loaded["structure"]
        marked = trace_reduce.slice_of(loaded)
        red = trace_reduce.reduce(
            loaded,
            in_flight_on_trace(rows, marked[2]) if marked else None,
        )
        if not red.get("busy_s"):
            raise RunFailed("the trace shows no operation on the device: "
                            + "; ".join(structure))
        rounds = [m for m in red["modules"]
                  if any(part in m["name"] for part in ROUND_PROGRAMS)]
        if rounds and not any("call" in m for m in rounds):
            raise RunFailed(
                f"{len(rounds)} round programs ran in the slice and none "
                "inside a bench.* annotation: the engine.* metrics have no "
                "source (harness/session.py annotate())")

    e2e = {"setup_s": {"value": setup_s, "unit": "s"}}
    for name, entry in cell["end_to_end"].items():
        if name != "setup_s" and summary.get(name) is not None:
            e2e[name] = {"value": summary[name], "unit": entry["unit"]}
    notes: Dict[str, Any] = {}
    run_view = {
        "summary": summary, "rows": rows, "sample": sample, "win": win,
        "trace": red, "cell": cell, "config": cfg, "traffic": traffic,
        "geometry": s.geometry, "warmed": s.warmed, "peaks": peaks,
        "notes": notes,
    }
    per_layer, not_read = layers.read_all(run_view) if traced else ({}, [])

    device = dict(s.device, memory_peak_bytes=memory_peak)
    line: Dict[str, Any] = {
        "correct": correct, "attempted": summary["attempted"],
        "failed": summary["failed"],
        "metrics": per_layer if traced else e2e, "device": device,
    }
    if traced and red is not None:
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        line["breakdown"] = trace_reduce.breakdown(red)

    hist: Dict[int, int] = {}           # prompts by power of two, up to
    for r in sample:
        le = 1 << max(r["prompt_tokens"] - 1, 1).bit_length()
        hist[le] = hist.get(le, 0) + 1
    detail = {
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "rate_rps": cell.get("rate_rps"),
        "device": device, "checks": checks, "compared": compared,
        "summary": summary,
        "end_to_end": e2e, "per_layer": per_layer,
        "per_layer_not_read": not_read, "notes": notes,
        "timing": s.timing, "warmed": s.warmed, "geometry": s.geometry,
        "probes": probes, "prompt_length_histogram_le": hist,
        "compiles_in_window": compiles_in,
        "compiles": [{**r, "at": r["at"] - T0} for r in s.compiles.rows],
        "counters": {k: win[k] for k in ("before", "c0", "c1", "after")},
        "trace_structure": structure,
        "trace_reduced": None if red is None else {
            k: red[k] for k in ("devices", "window_s", "busy_s",
                                "busy_s_min", "per_device", "collective_s",
                                "idle_seconds")
        } | {"op_seconds_top": trace_reduce.breakdown(red, 40)["device_ops"],
             "modules": red["modules"][:2000]},
        "requests": detail_requests(rows, w0),
    }
    path = out_dir / f"{cell['name']}.seed{args.seed}.trace{args.trace}.json"
    with open(path, "w") as f:
        json.dump(detail, f)
    say(T0, f"detail: {path}")
    say(T0, f"checks: {checks}")
    say(T0, f"window: {summary['attempted']} requests due, "
        f"{summary['failed']} failed, {summary['refused']} refused; "
        "end to end: "
        + ", ".join(f"{k}={v['value']:.4g}" for k, v in e2e.items()))
    say(T0, "not judged here: " + ", ".join(
        [f"{k}={summary[k]:.4g}" for k in metrics.END_TO_END
         if k not in e2e and summary.get(k) is not None]
        + [f"slo_ok_share={summary.get('slo_ok_share')}"]))
    if traced:
        say(T0, "per layer: " + ", ".join(
            f"{k}={v['value']:.4g}" for k, v in per_layer.items()))
        if not_read:
            say(T0, "per layer, NOTHING TO READ in this slice: "
                + ", ".join(not_read))
    # each number compared beside its limit: last in the line, and the last
    # lines on standard error
    line["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name}={c['value']:.6g} limit={c['limit']:.6g}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except (RunFailed, spec.SpecError, ImportError) as exc:
        print(f"benchmark: FAILED — {exc}", file=sys.stderr, flush=True)
        code = 1
    sys.stdout.flush()
    sys.exit(code)
