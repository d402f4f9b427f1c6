"""One measured window in a session: hand the plan to the generator, read
the program's counters at the window's two ends, trace a slice from its
middle when asked, and wait for the generator's rows.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from . import metrics
from .session import Session

# the traced run profiles this long a slice, not the whole window
TRACE_SLICE_S = 5.0
# an open loop's generator may send this late at its 90th percentile
MAX_GEN_LATE_P90_MS = 10.0


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def trace_slice(trace_dir: str, seconds: float) -> None:
    """Profile ``seconds`` from now. The host tracer keeps the annotations;
    the Python tracer stays off (a span per Python call would slow the
    serving threads far more than it tells)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.slice",
                                          mono=repr(time.monotonic())):
            time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()


def slice_start(plan: Dict[str, Any], seconds: float, length: float
                ) -> float:
    """Seconds into the window at which the traced slice opens: so that it
    sits in the middle of the window, or, in an open loop, at the first
    request due after that instant (a slice of a lull would show an idle
    chip and no round), and never past the window's end."""
    start = (seconds - length) / 2.0
    if plan["loop"] == "open":
        due = [r["due_s"] - plan["ramp_s"] for r in plan["requests"]]
        start = min((d for d in due if d >= start), default=start)
    return max(min(start, seconds - length), 0.0)


def run_window(s: Session, plan: Dict[str, Any], seconds: float,
               drain_s: float, trace_dir: Optional[str] = None,
               extra_params: Optional[Dict] = None,
               sample_every_s: float = 0.0) -> Dict[str, Any]:
    """Play ``plan`` with its window ``[w0, w1)`` opening ``ramp_s`` after
    its start; with ``trace_dir``, profile a slice of it. Returns the rows,
    the window, the counters at both ends and, with ``sample_every_s``, the
    batcher's queue depth over time."""
    t0 = time.monotonic() + 0.3
    w0 = t0 + float(plan["ramp_s"])
    w1 = w0 + seconds
    cmd = {k: v for k, v in plan.items() if k != "ramp_s"}
    cmd["stop_at"] = w1
    if extra_params:
        cmd["extra_params"] = extra_params
    before = s.counters()
    s.send_play(cmd, t0, w1 + drain_s)
    sleep_until(w0)
    c0 = s.counters()
    samples: List[Dict[str, float]] = []
    if trace_dir is not None:
        length = min(TRACE_SLICE_S, seconds / 2.0)
        sleep_until(w0 + slice_start(plan, seconds, length))
        trace_slice(trace_dir, length)
    while sample_every_s and time.monotonic() < w1:
        st = s.llm.serving.get_stats()
        samples.append({"at": time.monotonic() - w0,
                        "queue_depth": st.get("queue_depth", 0),
                        "in_flight": s.worker._serving_jobs})
        time.sleep(sample_every_s)
    sleep_until(w1)
    c1 = s.counters()
    rows = s.receive_play()
    after = s.counters()
    return {"rows": rows, "w0": w0, "w1": w1, "t0": t0, "c0": c0, "c1": c1,
            "before": before, "after": after, "samples": samples}


def window_compared(s: Session, win: Dict[str, Any], plan: Dict[str, Any],
                    summary: Dict[str, Any], vocab: int
                    ) -> Dict[str, Dict[str, float]]:
    """What one window has to show whatever the cell, each as a number
    beside the limit it may not pass: requests of the window not answered
    in full, rows of the whole plan not answered in full, the distance
    between the direct server's counters and the generator's, engine
    errors, compile requests inside the window, and how late the generator
    ran (a closed loop has no schedule to be late for)."""
    direct = win["after"]["direct"]
    served = direct.get("requests", 0) + direct.get("rejected", 0)
    numbers = {
        "failed": (summary["failed"], 0),
        "rows_incomplete": (sum(1 for r in win["rows"]
                                if not metrics.complete(r, vocab)), 0),
        "direct_count_gap": (
            abs(served - s.rows_sent)
            + abs(direct.get("rejected", 0) - s.refusals_seen), 0),
        "engine_errors": (delta(win, "batcher", "engine_errors",
                                whole=True), 0),
        "compiles_in_window": (
            len(s.compiles.between(win["w0"], win["w1"])), 0),
    }
    if plan["loop"] != "closed":
        numbers["gen_late_p90_ms"] = (summary["gen_late_p90_ms"] or 0.0,
                                      MAX_GEN_LATE_P90_MS)
    return {k: {"value": v, "limit": limit}
            for k, (v, limit) in numbers.items()}


def within(compared: Dict[str, Dict[str, float]]) -> Dict[str, bool]:
    """Each compared number against its limit."""
    return {k: c["value"] <= c["limit"] for k, c in compared.items()}


def detail_requests(rows: List[Dict[str, Any]], w0: float
                    ) -> List[Dict[str, Any]]:
    """The rows as a detail file keeps them: every event's instant counted
    from the window's opening, the token ids and timelines left out."""
    return [
        {k: v for k, v in r.items() if k not in ("ids", "timeline")}
        | {"due": r["due"] - w0, "sent": r["sent"] - w0,
           "t": [round(t - w0, 5) for t in r["t"]]}
        for r in rows
    ]


def delta(win: Dict[str, Any], part: str, key: str,
          whole: bool = False) -> float:
    """A counter's change over the window (or, with ``whole``, from before
    the plan to after its drain)."""
    a, b = (win["before"], win["after"]) if whole else (win["c0"], win["c1"])
    return float(b[part].get(key, 0) or 0) - float(a[part].get(key, 0) or 0)
