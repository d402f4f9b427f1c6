#!/usr/bin/env python3
"""Find the knee of an open-loop cell, once, on the chip.

    python3 benchmark/sweep.py --workload <cell> [--rates 1,2,3] [--write]

One process, one load of the model, one window of ``--seconds`` at each of
the cell's ``sweep_rates``, lowest first. The knee is the highest rate at
which at least 90 % of the requests sent met both latency limits of the
cell and the batcher's queue was no deeper at the end of the window than
in its middle (mean depth over the last fifth against the mean over the
middle fifth, half a request of slack), provided a higher rate of the
sweep was not sustained: a sweep whose every rate was sustained has found
no knee and says so. The cell then runs at 0.8 x knee:
``--write`` puts that number into the cell file as ``rate_rps``, with the
table it came from. A benchmark never searches for a rate while it
measures; this is run when a cell is defined and when a later benchmark PR
finds the knee again.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse             # noqa: E402
import json                 # noqa: E402
import sys                  # noqa: E402
from pathlib import Path    # noqa: E402
from typing import Any, Dict, List  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

from harness import metrics, spec  # noqa: E402
from harness.session import RunFailed, Session, check_spec, say  # noqa: E402
from harness.window import delta, run_window  # noqa: E402

ATTAINMENT = 0.9
QUEUE_SLACK = 0.5
FRACTION_OF_KNEE = 0.8


def mean_depth(samples: List[Dict[str, float]], lo: float, hi: float
               ) -> float:
    xs = [x["queue_depth"] for x in samples if lo <= x["at"] < hi]
    return sum(xs) / len(xs) if xs else 0.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default=None,
                    help="comma-separated requests/s (default: the cell's "
                         "sweep_rates)")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--write", action="store_true",
                    help="write 0.8 x knee into the cell file as rate_rps")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cell = spec.load_cell(args.workload)
    check_spec(cell)
    traffic = cell["_traffic"]
    rates = [float(x) for x in args.rates.split(",")] if args.rates \
        else [float(x) for x in cell["sweep_rates"]]
    out_dir = spec.out_dir(args.out)
    vocab = int(cell["_config"]["vocab_size"])
    table: List[Dict[str, Any]] = []

    with Session(cell, T0) as s:
        s.warm()
        say(T0, f"set-up done: {s.timing}")
        for k, rate in enumerate(sorted(rates)):
            plan = s.generator.generate(
                traffic["params"], rate, args.seed + k, args.seconds
            )
            if plan["loop"] != "open":
                raise RunFailed("only an open-loop cell has a knee")
            win = run_window(s, plan, args.seconds, float(cell["drain_s"]),
                             sample_every_s=0.25)
            sm = metrics.summarize(win["rows"], win["w0"], win["w1"], vocab,
                                   cell["limits"])
            mid = mean_depth(win["samples"], 0.4 * args.seconds,
                             0.6 * args.seconds)
            end = mean_depth(win["samples"], 0.8 * args.seconds, args.seconds)
            rounds = delta(win, "batcher", "decode_rounds")
            row = {
                "rate_rps": rate, "attempted": sm["attempted"],
                "failed": sm["failed"], "refused": sm["refused"],
                "slo_ok_share": sm.get("slo_ok_share"),
                "queue_mid": mid, "queue_end": end,
                "queue_steady": end <= mid + QUEUE_SLACK,
                "sustained": (sm.get("slo_ok_share") or 0.0) >= ATTAINMENT
                and end <= mid + QUEUE_SLACK,
                "ttft_p50_ms": sm["ttft_p50_ms"],
                "ttft_p90_ms": sm["ttft_p90_ms"],
                "tpot_p50_ms": sm["tpot_p50_ms"],
                "tpot_p90_ms": sm["tpot_p90_ms"],
                "gap_p90_ms": sm["gap_p90_ms"], "out_tok_s": sm["out_tok_s"],
                "gen_late_p90_ms": sm["gen_late_p90_ms"],
                "occupancy": delta(win, "batcher", "occupancy_sum") / rounds
                if rounds else None,
                "ragged_share": delta(win, "batcher", "ragged_rounds") / rounds
                if rounds else None,
                "compiles_in_window": len(
                    s.compiles.between(win["w0"], win["w1"])),
            }
            table.append(row)
            say(T0, "rate %(rate_rps).2f: ok %(slo_ok_share)s, queue "
                "%(queue_mid).2f -> %(queue_end).2f, ttft p50/p90 "
                "%(ttft_p50_ms)s/%(ttft_p90_ms)s, tpot p90 %(tpot_p90_ms)s, "
                "gap p90 %(gap_p90_ms)s, %(out_tok_s).1f tok/s, sustained "
                "%(sustained)s" % row)
            # let the queue empty before the next rate is offered
            end_wait = time.monotonic() + 60.0
            while time.monotonic() < end_wait:
                st = s.llm.serving.get_stats()
                if not st.get("queue_depth") and not st.get("active_slots"):
                    break
                time.sleep(0.2)
        device = dict(s.device, memory_peak_bytes=s.memory_peak_bytes())

    sustained = [r["rate_rps"] for r in table if r["sustained"]]
    knee = max(sustained) if sustained else None
    # a knee lies between a rate that was sustained and one that was not
    bracketed = knee is not None and knee < max(r["rate_rps"] for r in table)
    if knee is not None and not bracketed:
        say(T0, f"every rate up to {knee} was sustained: the knee lies above "
            "the sweep and was NOT found; sweep higher rates")
        knee = None
    result = {"cell": cell["name"], "device": device, "seconds": args.seconds,
              "knee_rps": knee,
              "rate_rps": None if knee is None
              else round(FRACTION_OF_KNEE * knee, 2),
              "table": table, "timing": s.timing}
    path = out_dir / f"sweep.{cell['name']}.json"
    path.write_text(json.dumps(result, indent=1) + "\n")
    say(T0, f"knee {knee} req/s -> rate_rps {result['rate_rps']}; {path}")
    if args.write:
        if knee is None:
            raise RunFailed("the sweep did not bracket a knee; nothing "
                            "written")
        with open(cell["_path"]) as f:
            on_disk = json.load(f)
        on_disk["rate_rps"] = result["rate_rps"]
        on_disk["rate_note"] = (
            f"{FRACTION_OF_KNEE} x knee {knee} req/s, benchmark/sweep.py on "
            f"{device['kind']} x{device['count']}"
        )
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
