#!/usr/bin/env python3
"""How closely a cell's runs repeat: the admission rule, computed.

    python3 benchmark/spread.py <dir or detail file> ... [--bound b] [--json out]

Reads the detail files runs have left (``run.py``'s
``<cell>.seed<n>.trace0.json`` and the windows ``sweep.py --keep-rows``
keeps), recomputes every statistic of ``harness/metrics.py`` ``END_TO_END``
from each run's request rows, and prints for every cell and statistic, over
the runs found: how many, the median, the quartiles
(``statistics.quantiles(values, n=4)``), the **spread** (quartile distance
over the median) and the **range** with the run farthest from the median
left out, over the median. A cell is admitted only if every metric it is
judged by spreads by at most half its bound and ranges by at most the
bound, over ten seeds or more (``README.md``). No chip is needed: the rows
hold every event's instant, so a statistic that is only a candidate is
computed from runs that were made before it was thought of: ``--bound b``
says ``admitted`` or ``NOT ADMITTED`` at ``b`` beside every statistic of
``END_TO_END`` the cell is not judged by, marked ``candidate``.

``setup_s`` and the phases of ``timing`` are reported over the runs whose
every compile request hit the cache (warm runs).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

from harness import metrics, spec  # noqa: E402

def spread_of(values: Sequence[float]) -> Optional[Dict[str, float]]:
    """Median, quartiles, spread and trimmed range of a metric's runs; None
    for fewer than two runs or a median of 0."""
    xs = [float(v) for v in values if v is not None]
    if len(xs) < 2:
        return None
    med = statistics.median(xs)
    if not med:
        return None
    q1, _, q3 = statistics.quantiles(xs, n=4)
    kept = sorted(xs, key=lambda v: abs(v - med))[:-1] if len(xs) > 2 else xs
    return {"n": len(xs), "median": med, "q1": q1, "q3": q3,
            "min": min(xs), "max": max(xs),
            "spread": (q3 - q1) / abs(med),
            "range_without_farthest": (max(kept) - min(kept)) / abs(med)}


def detail_files(paths: Iterable[str]) -> List[Path]:
    out: List[Path] = []
    for p in map(Path, paths):
        out += sorted(p.rglob("*.json")) if p.is_dir() else [p]
    return out


def load_runs(paths: Iterable[str]) -> Dict[str, List[Dict[str, Any]]]:
    """Untraced runs by cell, in the order of their seeds. A file that is
    not a run's detail is passed over."""
    by_cell: Dict[str, List[Dict[str, Any]]] = {}
    for path in detail_files(paths):
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(d, dict) or "requests" not in d or d.get("trace"):
            continue
        d["_path"] = str(path)
        by_cell.setdefault(d["cell"], []).append(d)
    for runs in by_cell.values():
        runs.sort(key=lambda d: (d["seed"], d["_path"]))
    return by_cell


def statistics_of(detail: Dict[str, Any], vocab: int,
                  limits: Optional[Dict[str, float]]) -> Dict[str, Any]:
    """``metrics.summarize`` over the run's window, from the rows it kept
    (their times count from the window's opening)."""
    return metrics.summarize(detail["requests"], 0.0, detail["seconds"],
                             vocab, limits)


def warm(detail: Dict[str, Any]) -> bool:
    return all(c.get("cache") == "hit" for c in detail.get("compiles", []))


def admitted(t: Dict[str, float], bound: float) -> bool:
    """The admission rule: spread at most half the bound, range without
    the farthest run at most the bound."""
    return t["spread"] <= bound / 2 and t["range_without_farthest"] <= bound


def report(by_cell: Dict[str, List[Dict[str, Any]]],
           candidate_bound: Optional[float] = None) -> Dict[str, Any]:
    """Every cell's table. A statistic the cell is judged by carries its
    ``bound`` and whether it is ``admitted``; with ``candidate_bound`` every
    other statistic of ``END_TO_END`` does too, marked ``candidate``."""
    result: Dict[str, Any] = {}
    for name, runs in sorted(by_cell.items()):
        try:
            cell = spec.load_cell(name)
            vocab, limits = int(cell["_config"]["vocab_size"]), \
                cell.get("limits")
            judged = {m: e.get("bound") for m, e in cell["end_to_end"].items()}
        except spec.SpecError:
            vocab, limits, judged = 1 << 31, None, {}
        sums = [statistics_of(d, vocab, limits) for d in runs]
        table: Dict[str, Any] = {}
        for m in metrics.END_TO_END + ("gap_p40_ms", "gap_p60_ms",
                                       "gen_late_p90_ms", "n_waits",
                                       "n_gaps", "attempted", "failed"):
            table[m] = spread_of([s.get(m) for s in sums])
        warm_runs = [d for d in runs if warm(d) and "end_to_end" in d]
        table["setup_s"] = spread_of(
            [d["end_to_end"]["setup_s"]["value"] for d in warm_runs])
        phases = sorted({k for d in warm_runs for k in d.get("timing", {})})
        for k in phases:
            table[f"timing.{k}"] = spread_of(
                [d["timing"].get(k) for d in warm_runs])
        for m in metrics.END_TO_END:
            bound = judged.get(m, candidate_bound)
            if table[m] is not None and bound is not None:
                table[m].update(bound=bound, admitted=admitted(table[m], bound),
                                candidate=m not in judged)
        result[name] = {
            "runs": len(runs),
            "seeds": [d["seed"] for d in runs],
            "rate_rps": sorted({d.get("rate_rps") for d in runs},
                               key=lambda x: (x is None, x)),
            "all_correct": all(all(d.get("checks", {}).values())
                               for d in runs),
            "values": {m: [s.get(m) for s in sums]
                       for m in metrics.END_TO_END},
            "table": table, "judged": judged,
        }
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("paths", nargs="+")
    ap.add_argument("--bound", type=float, default=None,
                    help="judge every unjudged statistic of END_TO_END at "
                         "this bound too, as a candidate")
    ap.add_argument("--json", default=None, help="also write the tables here")
    args = ap.parse_args()
    result = report(load_runs(args.paths), args.bound)
    for name, r in result.items():
        print(f"{name}: {r['runs']} runs, rate "
              f"{r['rate_rps']}, every check true: {r['all_correct']}")
        print(f"  {'statistic':24s} {'n':>3s} {'median':>10s} {'q1':>10s} "
              f"{'q3':>10s} {'spread':>7s} {'range-1':>7s}  bound")
        for m, t in r["table"].items():
            if t is None:
                continue
            verdict = ""
            if "admitted" in t:
                verdict = f"{t['bound']} " \
                    f"{'admitted' if t['admitted'] else 'NOT ADMITTED'}" \
                    f"{' candidate' if t['candidate'] else ''}"
            print(f"  {m:24s} {t['n']:3d} {t['median']:10.4f} {t['q1']:10.4f} "
                  f"{t['q3']:10.4f} {t['spread']:7.4f} "
                  f"{t['range_without_farthest']:7.4f}  {verdict}")
    if args.json:
        Path(args.json).write_text(json.dumps(result, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
