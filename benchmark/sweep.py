#!/usr/bin/env python3
"""Find the knee of an open-loop cell, once, on the chip.

    python3 benchmark/sweep.py --workload <cell> [--rates 1,2,3] [--windows 3] [--write]

One process, one load of the model, ``--windows`` windows of ``--seconds``
(each with a seed of its own) at each of the cell's ``sweep_rates``, lowest
first. A window is sustained when at least 90 % of the requests sent met
both latency limits of the cell and the batcher's queue was no deeper at
the end of the window than in its middle (mean depth over the last fifth
against the mean over the middle fifth, half a request of slack). A rate is
sustained when the median window is: more than half of its windows. The
sweep stops at the first rate that is not, and
the knee is the rate before it: a sweep whose every rate was sustained, or
whose lowest was not, has found no knee and says so (``decide``). The cell
then runs at 0.8 x knee: ``--write`` puts that number into the cell file as
``rate_rps``, with the table of every window it came from. A benchmark
never searches for a rate while it measures; this is run when a cell is
defined and when a later benchmark PR finds the knee again.

``--keep-rows`` also leaves a detail file a window (its request rows with
every event's instant, as ``run.py`` does), for ``spread.py``: with one
rate, ``--seconds`` as long as a run and ten windows it is the cheap way
to see how a candidate statistic repeats, at one load of the model.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse             # noqa: E402
import json                 # noqa: E402
import sys                  # noqa: E402
from pathlib import Path    # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

from harness import metrics, spec  # noqa: E402
from harness.session import RunFailed, Session, check_spec, say  # noqa: E402
from harness.window import (  # noqa: E402
    delta, detail_requests, run_window, window_compared, within,
)

ATTAINMENT = 0.9
QUEUE_SLACK = 0.5
FRACTION_OF_KNEE = 0.8


def mean_depth(samples: List[Dict[str, float]], lo: float, hi: float
               ) -> float:
    xs = [x["queue_depth"] for x in samples if lo <= x["at"] < hi]
    return sum(xs) / len(xs) if xs else 0.0


def sustained(windows: List[Dict[str, Any]]) -> bool:
    """A rate is sustained when its median window is: more than half."""
    return 2 * sum(1 for w in windows if w["sustained"]) > len(windows)


def decide(table: List[Dict[str, Any]]) -> Dict[str, Any]:
    """From the windows of a sweep (rows with ``rate_rps`` and
    ``sustained``) to its knee: the highest rate below the first rate that
    was not sustained. ``knee_rps`` is None, and ``why`` says so, when no
    rate failed (the knee lies above the sweep) or the lowest did (below)."""
    by_rate: Dict[float, List[Dict[str, Any]]] = {}
    for row in table:
        by_rate.setdefault(float(row["rate_rps"]), []).append(row)
    rates = [{"rate_rps": r, "windows": len(ws),
              "windows_sustained": sum(1 for w in ws if w["sustained"]),
              "sustained": sustained(ws)} for r, ws in sorted(by_rate.items())]
    failed = next((k for k, r in enumerate(rates) if not r["sustained"]), None)
    if failed is None:
        knee, why = None, ("every rate was sustained: the knee lies above "
                           "the sweep and was NOT found; sweep higher rates")
    elif failed == 0:
        knee, why = None, ("the lowest rate was not sustained: the knee lies "
                           "below the sweep and was NOT found")
    else:
        knee = rates[failed - 1]["rate_rps"]
        why = (f"{knee} was sustained in {rates[failed - 1]['windows_sustained']}"
               f" of {rates[failed - 1]['windows']} windows, "
               f"{rates[failed]['rate_rps']} in "
               f"{rates[failed]['windows_sustained']} of "
               f"{rates[failed]['windows']}")
    return {"knee_rps": knee, "why": why, "rates": rates,
            "rate_rps": None if knee is None
            else round(FRACTION_OF_KNEE * knee, 2)}


def window_row(s: Session, cell: Dict[str, Any], rate: float, seed: int,
               seconds: float, keep_rows: Optional[Path]) -> Dict[str, Any]:
    """Offer one window at ``rate`` and reduce it to a row of the table."""
    vocab = int(cell["_config"]["vocab_size"])
    plan = s.generator.generate(cell["_traffic"]["params"], rate, seed,
                                seconds)
    if plan["loop"] != "open":
        raise RunFailed("only an open-loop cell has a knee")
    win = run_window(s, plan, seconds, float(cell["drain_s"]),
                     sample_every_s=0.25)
    sm = metrics.summarize(win["rows"], win["w0"], win["w1"], vocab,
                           cell["limits"])
    mid = mean_depth(win["samples"], 0.4 * seconds, 0.6 * seconds)
    end = mean_depth(win["samples"], 0.8 * seconds, seconds)
    rounds = delta(win, "batcher", "decode_rounds")
    compared = window_compared(s, win, plan, sm, vocab)
    row = {
        "rate_rps": rate, "seed": seed, "attempted": sm["attempted"],
        "failed": sm["failed"], "refused": sm["refused"],
        "slo_ok_share": sm.get("slo_ok_share"),
        "queue_mid": mid, "queue_end": end,
        "sustained": (sm.get("slo_ok_share") or 0.0) >= ATTAINMENT
        and end <= mid + QUEUE_SLACK,
        **{k: sm[k] for k in ("ttft_p50_ms", "ttft_p90_ms", "tpot_p50_ms",
                              "tpot_p90_ms", "itl_p99_ms", "gap_p90_ms",
                              "out_tok_s", "gen_late_p90_ms")},
        "occupancy": delta(win, "batcher", "occupancy_sum") / rounds
        if rounds else None,
        "ragged_share": delta(win, "batcher", "ragged_rounds") / rounds
        if rounds else None,
        "compiles_in_window": compared["compiles_in_window"]["value"],
    }
    if keep_rows is not None:
        detail = {"cell": cell["name"], "seed": seed, "seconds": seconds,
                  "trace": 0, "rate_rps": rate, "checks": within(compared),
                  "summary": sm, "in_session": True,
                  "requests": detail_requests(win["rows"], win["w0"])}
        path = keep_rows / f"{cell['name']}.rate{rate:g}.seed{seed}.json"
        path.write_text(json.dumps(detail))
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default=None,
                    help="comma-separated requests/s (default: the cell's "
                         "sweep_rates)")
    ap.add_argument("--windows", type=int, default=3,
                    help="windows a rate, each with its own seed")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--keep-rows", action="store_true",
                    help="leave a detail file a window in --out")
    ap.add_argument("--write", action="store_true",
                    help="write 0.8 x knee into the cell file as rate_rps")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cell = spec.load_cell(args.workload)
    check_spec(cell)
    rates = [float(x) for x in args.rates.split(",")] if args.rates \
        else [float(x) for x in cell["sweep_rates"]]
    out_dir = spec.out_dir(args.out)
    table: List[Dict[str, Any]] = []

    with Session(cell, T0) as s:
        s.warm()
        say(T0, f"set-up done: {s.timing}")
        for k, rate in enumerate(sorted(rates)):
            for j in range(max(args.windows, 1)):
                row = window_row(s, cell, rate, args.seed + 100 * k + j,
                                 args.seconds,
                                 out_dir if args.keep_rows else None)
                table.append(row)
                say(T0, "rate %(rate_rps).2f seed %(seed)d: ok "
                    "%(slo_ok_share)s, queue %(queue_mid).2f -> "
                    "%(queue_end).2f, ttft p50/p90 %(ttft_p50_ms)s/"
                    "%(ttft_p90_ms)s, tpot p90 %(tpot_p90_ms)s, itl p99 "
                    "%(itl_p99_ms)s, %(out_tok_s).1f tok/s, sustained "
                    "%(sustained)s" % row)
                # let the queue empty before the next window is offered
                end_wait = time.monotonic() + 60.0
                while time.monotonic() < end_wait:
                    st = s.llm.serving.get_stats()
                    if not st.get("queue_depth") \
                            and not st.get("active_slots"):
                        break
                    time.sleep(0.2)
            if not decide(table)["rates"][-1]["sustained"]:
                break
        device = dict(s.device, memory_peak_bytes=s.memory_peak_bytes())

    verdict = decide(table)
    knee = verdict["knee_rps"]
    result = {"cell": cell["name"], "device": device, "seconds": args.seconds,
              "windows_a_rate": args.windows, **verdict,
              "table": table, "timing": s.timing}
    path = out_dir / f"sweep.{cell['name']}.json"
    path.write_text(json.dumps(result, indent=1) + "\n")
    say(T0, f"{verdict['why']}: knee {knee} req/s -> rate_rps "
        f"{result['rate_rps']}; {path}")
    if args.write:
        if knee is None:
            raise RunFailed("the sweep did not bracket a knee; nothing "
                            "written")
        with open(cell["_path"]) as f:
            on_disk = json.load(f)
        on_disk["rate_rps"] = result["rate_rps"]
        on_disk["rate_note"] = (
            f"{FRACTION_OF_KNEE} x knee {knee} req/s, benchmark/sweep.py on "
            f"{device['kind']} x{device['count']}: {args.windows} windows of "
            f"{args.seconds:g} s a rate, {verdict['why']}"
        )
        on_disk["sweep_table"] = [
            {k: (round(v, 3) if isinstance(v, float) else v)
             for k, v in row.items()} for row in table]
        with open(cell["_path"], "w") as f:
            json.dump(on_disk, f, indent=1)
            f.write("\n")
    print(json.dumps({k: result[k] for k in ("cell", "knee_rps", "rate_rps")}))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except (RunFailed, spec.SpecError, ImportError) as exc:
        print(f"sweep: FAILED — {exc}", file=sys.stderr, flush=True)
        code = 1
    sys.exit(code)
