"""From a profiler trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU trace holds
one plane per chip (``/device:TPU:<n>``) with a line of XLA modules (one
event per execution of a jitted program, named ``jit_<function>(...)``) and
a line of XLA ops (one event per operation; an operation that holds others,
such as the ``while`` of a layer scan, spans its children). Host threads
are lines of the ``/host:CPU`` plane; ``jax.profiler.TraceAnnotation``
events land there with their keyword arguments as stats.

A CPU trace (the dry run) has no device plane: XLA runs its operations on
host threads and marks each event with the ``hlo_module`` it belongs to.
Those events then stand for the device, so that the dry run exercises every
reader; its last line names the CPU, and nobody reads its numbers.

``tests/test_trace_reduce.py`` holds this to a recorded trace of each kind.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # seconds on the trace's clock
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I,
)
ANNOTATION_PREFIX = "bench."


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return files[-1] if files else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` inside ``[lo, hi]``."""
    out, at = [], lo
    for a, b in clip(busy, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Seconds by name, each event counted without the events it spans
    (``while`` without its body), so that the sum is the busy time."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []   # [name, end, seconds of children, start]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, inner, start = stack.pop()
            out[name] = out.get(name, 0.0) + max(end - start - inner, 0.0)
            if stack:
                stack[-1][2] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, end, 0.0, start])
    close(float("inf"))
    return out


def short_name(name: str) -> str:
    """A TPU trace names an operation by its whole HLO instruction
    (``%fusion.157 = bf16[8,128,14336]{...} fusion(...)``): keep what stands
    before the ``=``. Other names stay as they are."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def _events(line: Any) -> List[Tuple[str, float, float, Dict[str, Any]]]:
    out = []
    for e in line.events:
        start = float(e.start_ns) * 1e-9
        out.append((short_name(e.name), start,
                    start + float(e.duration_ns) * 1e-9,
                    {k: v for k, v in e.stats}))
    return out


def load(path: str) -> Dict[str, Any]:
    """The trace as plain lists: per device the module events and the op
    events, and the host's annotation events. ``path`` is an ``.xplane.pb``
    as the profiler writes it, or one gzipped (the recorded test data)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: List[Dict[str, Any]] = []
    host_ops: Dict[Any, List[Any]] = {}
    notes: List[Tuple[str, float, float, Dict[str, Any]]] = []
    structure: List[str] = []
    for plane in data.planes:
        lines = list(plane.lines)
        structure.append(
            f"{plane.name}: " + ", ".join(
                f"{ln.name}[{sum(1 for _ in ln.events)}]" for ln in lines[:12]
            ) + (" ..." if len(lines) > 12 else "")
        )
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            by_name = {ln.name: ln for ln in lines}
            if "XLA Ops" not in by_name:
                continue
            devices.append({
                "name": plane.name,
                "ops": [e[:3] for e in _events(by_name["XLA Ops"])],
                "modules": [e[:3] for e in _events(by_name["XLA Modules"])]
                if "XLA Modules" in by_name else [],
            })
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in _events(ln):
                    if ev[0].startswith(ANNOTATION_PREFIX):
                        notes.append(ev)
                    elif "hlo_module" in ev[3] and not ev[0].startswith("end:"):
                        key = ev[3].get("device_ordinal", 0)
                        host_ops.setdefault(key, []).append(ev)
    if not devices and host_ops:
        # the dry run: XLA's CPU client ran the operations on host threads
        for key, evs in sorted(host_ops.items()):
            by_run: Dict[Any, List[Any]] = {}
            for ev in evs:
                by_run.setdefault(
                    (ev[3]["hlo_module"], ev[3].get("run_id")), []
                ).append(ev)
            devices.append({
                "name": f"/host:CPU as device {key}",
                "ops": [e[:3] for e in evs],
                "modules": [
                    (mod, min(e[1] for e in es), max(e[2] for e in es))
                    for (mod, _), es in by_run.items()
                ],
            })
    return {"devices": devices, "notes": sorted(notes, key=lambda e: e[1]),
            "structure": structure}


def slice_of(trace: Dict[str, Any]) -> Optional[Tuple[float, float, float]]:
    """The ``bench.slice`` annotation: its start and end on the trace's
    clock, and what must be added to a ``time.monotonic()`` reading to put
    it on that clock (the annotation carries the reading of its start)."""
    for name, a, b, st in trace["notes"]:
        if name == "bench.slice":
            return a, b, a - float(st["mono"]) if "mono" in st else 0.0
    return None


def reduce(trace: Dict[str, Any],
           in_flight: Optional[Sequence[Interval]] = None) -> Dict[str, Any]:
    """Busy and idle per device over the traced slice, time by operation,
    the round programs' executions, and each idle gap charged to what the
    host was doing.

    The slice is what the ``bench.slice`` annotation spans (the harness
    opens it when the profiler has started and closes it before it stops),
    or all of the trace without one. ``in_flight`` are the intervals, on
    the trace's clock, in which some request was in flight."""
    devices, notes = trace["devices"], trace["notes"]
    if not devices:
        return {"devices": 0}
    marked = slice_of(trace)
    if marked is not None:
        lo, hi = marked[0], marked[1]
    else:
        lo = min(e[1] for d in devices for e in d["ops"])
        hi = max(e[2] for d in devices for e in d["ops"])
    calls = [(name[len(ANNOTATION_PREFIX):], a, b, st)
             for name, a, b, st in notes
             if name != "bench.slice"]
    call_ivals = [(a, b) for _, a, b, _ in calls]

    per_device, op_seconds, idle_by = [], {}, {}
    for d in devices:
        busy = clip(union([(a, b) for _, a, b in d["ops"]]), lo, hi)
        per_device.append({"name": d["name"], "busy_s": total(busy)})
        for name, sec in self_times(
            [(n, max(a, lo), min(b, hi)) for n, a, b in d["ops"]
             if b > lo and a < hi]
        ).items():
            op_seconds[name] = op_seconds.get(name, 0.0) + sec
        for a, b in gaps(busy, lo, hi):
            for what, sec in _charge(a, b, calls, call_ivals,
                                     in_flight).items():
                idle_by[what] = idle_by.get(what, 0.0) + sec
    n = len(devices)
    collective = sum(s for name, s in op_seconds.items()
                     if COLLECTIVE.search(name))
    busy_sum = sum(p["busy_s"] for p in per_device)
    return {
        "devices": n, "window_s": hi - lo,
        "busy_s": busy_sum / n,
        "busy_s_min": min(p["busy_s"] for p in per_device),
        "per_device": per_device,
        "op_seconds": {k: v / n for k, v in op_seconds.items()},
        "idle_seconds": {k: v / n for k, v in idle_by.items()},
        "collective_s": collective / n,
        "modules": _modules(devices[0], calls, lo, hi),
    }


def _charge(a: float, b: float, calls: Sequence[Any],
            call_ivals: Sequence[Interval],
            in_flight: Optional[Sequence[Interval]]) -> Dict[str, float]:
    """Split the idle gap ``[a, b]`` by what the host was doing: inside an
    engine call (its host build, upload, readback), between engine calls
    with a request in flight (batcher, asyncio, SSE), or with none."""
    out: Dict[str, float] = {}
    inside = 0.0
    for (name, ca, cb, _st) in calls:
        if cb <= a:
            continue
        if ca >= b:
            break
        sec = min(b, cb) - max(a, ca)
        out[f"inside engine.{name} (host build, upload, readback)"] = \
            out.get(f"inside engine.{name} (host build, upload, readback)",
                    0.0) + sec
        inside += sec
    rest = (b - a) - inside
    if rest > 0:
        if in_flight is None:
            out["between engine calls"] = rest
        else:
            outside = gaps(union(call_ivals), a, b) if call_ivals \
                else [(a, b)]
            with_req = sum(
                total(clip(in_flight, x, y)) for x, y in outside
            )
            with_req = min(with_req, rest)
            if with_req > 0:
                out["between engine calls, a request in flight "
                    "(batcher, asyncio, SSE)"] = with_req
            if rest - with_req > 0:
                out["no request in flight"] = rest - with_req
    return out


def _modules(device: Dict[str, Any], calls: Sequence[Any], lo: float,
             hi: float) -> List[Dict[str, Any]]:
    """Executions of jitted programs on the first device inside the slice,
    each with the stats of the engine call (``bench.*`` annotation) that
    was open when it started."""
    out = []
    for name, a, b in device["modules"]:
        if a < lo or b > hi:
            continue
        stats: Dict[str, Any] = {}
        for cname, ca, cb, st in calls:
            if ca <= a <= cb:
                stats = {"call": cname, **st}
                break
        out.append({"name": name, "start": a, "seconds": b - a, **stats})
    return out


def breakdown(red: Dict[str, Any], k: int = 10) -> Dict[str, List[Any]]:
    """The contract's ``breakdown``: the device operations with most time
    and the idle time by what the host was doing, seconds per chip."""
    def topk(d: Dict[str, float]) -> List[Any]:
        return [[name, sec] for name, sec in
                sorted(d.items(), key=lambda kv: -kv[1])[:k]]
    return {"device_ops": topk(red.get("op_seconds", {})),
            "idle_gaps": topk(red.get("idle_seconds", {}))}
