"""Request flight recorder — per-request phase timelines, dependency-free.

Every job/stream that carries a ``trace_id`` accumulates monotonic phase
events across its whole path: server admission/route/claim, worker poll
pickup, batcher queue wait and admission-chunk rounds, first token,
preempt/resume, PD prefill → handoff begin/commit → decode adopt, and
completion. The recorder is ADVISORY end to end:

- the hot path is one ``time.monotonic()`` read + one list append
  (:class:`Timeline.note`); serialization happens only at result/heartbeat
  boundaries (:meth:`Timeline.wire`);
- a request without a trace id (or with ``DGI_FLIGHT=0``) gets the
  shared :data:`NULL_TIMELINE`, whose ``note`` is a no-op ``pass`` — the
  recorder-off path allocates nothing per request;
- the recorder can NEVER fail or reorder a request: events are bounded by
  :data:`FLIGHT_EVENT_CAP` (excess is counted, not raised), attrs are
  sanitized at wire time, and every consumer treats a malformed payload as
  a skipped sample.

Worker-side events ship to the control plane through the existing result
payload (``result["timeline"]``) and heartbeat (``engine_stats["flight"]``)
channels; ``server/flight_recorder.py`` merges the per-source lists into
one causally-ordered timeline per trace.

Per-ROUND spans (:func:`span`) are the other half: a request's timeline
says when it was admitted and by which round (``round=<n>``); the round's
``dgi.*`` spans say what the batcher and the engine did in it, on the
profiler's clock, beside the device's ops. docs/observability.md has the
table of both.

A TOKEN's way out is the third: the round that brought it is stamped once
as it returns (:class:`RoundStamp`, engine thread), the stamp rides the
stream's snapshot (:class:`Snapshot`) and its chunk through the loop, the
pump thread and the direct server, each of which adds its own instant
(:data:`EGRESS_KEY`), and the direct server sums the stages where it writes
the event.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, NamedTuple, Optional

# per-request event cap: a runaway event source (e.g. one chunk-round event
# per ragged round on a 100k-token prompt) saturates at the cap and counts
# the overflow instead of growing without bound
FLIGHT_EVENT_CAP = 256

# the last slice of the cap is reserved for phase-boundary events: a
# saturating repeater (chunk rounds) must not crowd out the terminal
# events every phase derivation hangs off — without the reserve, a
# capped timeline would END mid-prefill and e2e/ttft/decode would be
# silently wrong instead of merely truncated
FLIGHT_BOUNDARY_RESERVE = 16
BOUNDARY_EVENTS = frozenset((
    "batcher.first_token", "batcher.completed",
    "worker.done", "worker.stream.done",
    "pd.prefill.done", "pd.decode.done",
    "handoff.commit", "handoff.rx_commit", "handoff.failed",
    "server.completed",
))

# canonical phase names — the /metrics histogram label set and the bench
# attribution columns. Order is the documentation/reading order.
PHASES = ("queue_wait", "prefill", "ttft", "handoff", "decode", "e2e")


def flight_enabled() -> bool:
    """Process-wide recorder switch (default ON — the recorder is cheap
    enough to be always-on; per-request opt-in is the ``trace_id``)."""
    return os.environ.get("DGI_FLIGHT", "").strip().lower() not in (
        "0", "false", "off", "no",
    )


class _NullTimeline:
    """The recorder-off stand-in: every hook is a no-op, so hot paths call
    ``tl.note(...)`` unconditionally without branching on a flag."""

    __slots__ = ()
    enabled = False
    trace_id = ""
    events: List[Any] = []
    dropped = 0

    def note(self, name: str, **attrs: Any) -> None:
        pass

    def note_at(self, name: str, ts: float, **attrs: Any) -> None:
        pass

    def extend_at(self, events: Any) -> None:
        pass

    def wire(self, done: bool = False) -> Optional[Dict[str, Any]]:
        return None


NULL_TIMELINE = _NullTimeline()


# ---------------------------------------------------------------------------
# a token's way out: one stamp a round, carried with the snapshot
# ---------------------------------------------------------------------------

# the causes a stream's longest wait is counted under (``batcher.stats``
# ``longest_wait_<cause>``): the round that ended it carried one prompt
# piece, or several; was a scan raised while a request waited for a slot, or
# any other scan; or was none of these (a scan read back on its own, the
# first round after a preemption)
WAIT_CAUSES = ("ragged_1", "ragged_2plus", "scan_raised", "scan", "other")

# the chunk key an :class:`Egress` rides under from the pump thread to the
# direct server, which takes it off before the event is serialised
EGRESS_KEY = "_egress"


class RoundStamp(NamedTuple):
    """One per round, made where the round returns on the engine thread and
    shared by every row it served: ``ready`` is ``time.monotonic()`` with
    the round's tokens on the host and committed."""

    ready: float
    round: int
    kind: str       # ragged | scan | collect
    steps: int
    pieces: int     # prompt pieces a ragged round carried
    cause: str      # one of WAIT_CAUSES


class Egress(NamedTuple):
    """What a chunk carries of its way out when the pump thread yields it:
    the stamp of the round that brought its token, the instant the
    batcher's loop called the observer, the instant of the yield
    (``time.monotonic()`` both) and the batcher's request id."""

    stamp: RoundStamp
    notified: float
    pumped: float
    req: str


class Snapshot(list):
    """A stream's generated tokens after a round, as its observer gets
    them: a list, with the round's stamp and the instant the observer was
    called (``time.monotonic()``, loop thread)."""

    __slots__ = ("round", "notified")

    def __init__(self, tokens: Any, round: Optional[RoundStamp],
                 notified: float) -> None:
        super().__init__(tokens)
        self.round = round
        self.notified = notified


# ---------------------------------------------------------------------------
# round spans: the profiler's annotation + a time-busy counter
# ---------------------------------------------------------------------------

# jax.profiler.TraceAnnotation, looked up at the first span: this module is
# imported by the JAX-free control plane, which opens no span. None = not
# looked up yet, False = JAX is absent (spans then only count time).
_annotation: Any = None


def _annotation_class() -> Any:
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _annotation = TraceAnnotation
    return _annotation


class span:
    """``with span("dgi.<layer>.<what>", stats, key, **attrs) as sp:``

    Opens a ``jax.profiler.TraceAnnotation`` — while a profiler session
    runs (the benchmark's traced slice, an operator's capture) the span
    lands in the host plane of the same ``.xplane.pb``, on the same clock,
    as the device's ops; with no session it is a flag check — and on exit
    adds the elapsed ``time.perf_counter()`` seconds to ``stats[key]``
    when both are given (a time-busy counter, always on). The span's parent
    is the span open on its thread when it starts; spans of one round on
    different threads carry the same ``round=<n>``. ``sp.set(**attrs)``
    adds attributes found out while the span is open. Like the timelines,
    a span never fails what it measures: JAX absent means counters only."""

    __slots__ = ("_note", "_stats", "_key", "_t0")

    def __init__(self, name: str, stats: Optional[Dict[str, Any]] = None,
                 key: Optional[str] = None, **attrs: Any) -> None:
        cls = _annotation_class()
        self._note = cls(name, **attrs) if cls else None
        self._stats = stats if key is not None else None
        self._key = key

    def set(self, **attrs: Any) -> None:
        if self._note is not None:
            self._note.set_metadata(**attrs)

    def __enter__(self) -> "span":
        if self._note is not None:
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        if self._stats is not None:
            self._stats[self._key] = self._stats.get(self._key, 0.0) + (
                time.perf_counter() - self._t0)
        if self._note is not None:
            self._note.__exit__(*exc)


# the seconds of a start that reach ``/metrics``: the key of
# ``TPUEngine.get_stats()["startup"]`` (less its ``_s``) and the ``phase``
# label it has in ``worker_startup_seconds``
STARTUP_PHASES = {
    "init": "init", "params": "params", "kv_pools": "kv_pools",
    "jit_fns": "jit_fns", "load_model": "load_model",
    "graphs_trace": "graphs_trace", "graphs_lower": "graphs_lower",
    "graphs_backend": "graphs_backend", "worker_ready": "ready",
}


class phase(span):
    """``with phase("dgi.engine.init.params", startup, "params", **attrs):``

    A span of a start (docs/observability.md, "Start-up"): the seconds go
    to ``startup["<phase>_s"]`` and the instant it opened, as
    ``time.monotonic()``, to ``startup["at"]["<phase>"]``, so that the
    phases of one start can be laid beside whatever else reads that
    clock. A start has a few dozen of them; they are always on."""

    __slots__ = ("_phase",)

    def __init__(self, name: str, startup: Dict[str, Any], phase: str,
                 **attrs: Any) -> None:
        super().__init__(name, startup, phase + "_s", **attrs)
        self._phase = phase

    def __enter__(self) -> "phase":
        self._stats.setdefault("at", {})[self._phase] = time.monotonic()
        return super().__enter__()


def adopt_phases(startup: Dict[str, Any], outer: Dict[str, Any]) -> None:
    """Put the phases a caller timed round an engine's load (``outer``: the
    engine did not exist when the first of them opened) beside the
    engine's own in ``startup``."""
    startup.setdefault("at", {}).update(outer.get("at", {}))
    startup.update({k: v for k, v in outer.items() if k != "at"})


def _safe_attrs(attrs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """JSON-safe scalar attrs only — the wire rides job results and
    heartbeats, and one exotic value must not poison either channel."""
    if not attrs:
        return None
    out: Dict[str, Any] = {}
    for k, v in attrs.items():
        if v is None or isinstance(v, (bool, int, float)):
            out[str(k)] = v
        else:
            out[str(k)] = str(v)[:128]
    return out or None


class Timeline:
    """Per-request event accumulator (one per traced job/stream).

    Events are recorded as monotonic offsets from a wall-clock anchor
    captured at construction: intra-process ordering can never go
    backwards under a wall-clock step, while the wire format converts to
    wall-clock timestamps so timelines from different hosts merge on a
    shared (skew-tolerant, see ``merge_events``) axis.
    """

    __slots__ = ("trace_id", "source", "cap", "dropped",
                 "_wall0", "_mono0", "events")
    enabled = True

    def __init__(self, trace_id: str, source: str = "",
                 cap: int = FLIGHT_EVENT_CAP) -> None:
        self.trace_id = str(trace_id)
        self.source = str(source)
        self.cap = int(cap)
        self.dropped = 0
        self._wall0 = time.time()
        self._mono0 = time.monotonic()
        # [(name, wall_ts, attrs-or-None), ...]
        self.events: List[Any] = []

    # -- hot path ----------------------------------------------------------

    def _room_for(self, name: str) -> bool:
        n = len(self.events)
        if n >= self.cap:
            return False
        reserve = min(FLIGHT_BOUNDARY_RESERVE, self.cap // 2)
        if n >= self.cap - reserve and name not in BOUNDARY_EVENTS:
            return False
        return True

    def note(self, name: str, **attrs: Any) -> None:
        """Record one event NOW. List append + monotonic read; never
        raises (the recorder must never fail a request)."""
        if not self._room_for(name):
            self.dropped += 1
            return
        self.events.append(
            (name, self._wall0 + (time.monotonic() - self._mono0),
             attrs or None)
        )

    # -- boundary helpers --------------------------------------------------

    def note_at(self, name: str, ts: float, **attrs: Any) -> None:
        """Record one event at an explicit wall-clock timestamp (a
        boundary observed elsewhere — the poll pickup stamp, an engine
        slot's first-token time, a handoff receiver's commit)."""
        if not self._room_for(name):
            self.dropped += 1
            return
        try:
            self.events.append((name, float(ts), attrs or None))
        except (TypeError, ValueError):
            pass

    def at(self, mono: float) -> float:
        """The wall-clock timestamp this timeline gives the instant
        ``mono`` of ``time.monotonic()`` (for ``note_at``)."""
        return self._wall0 + (float(mono) - self._mono0)

    def extend_at(self, events: Any) -> None:
        """Adopt ``[(name, wall_ts), ...]`` pairs recorded by a component
        that has no timeline of its own (e.g. the HandoffReceiver, which
        knows only the session key). Malformed entries are skipped."""
        if not events:
            return
        for ev in events:
            try:
                self.note_at(str(ev[0]), float(ev[1]))
            except (TypeError, ValueError, IndexError):
                continue

    def wire(self, done: bool = False) -> Optional[Dict[str, Any]]:
        """Serialize for the result/heartbeat channel. Events are shipped
        as the FULL list each time — the server-side merge unions events
        per source keyed by (name, timestamp), so duplicate delivery (a
        heartbeat retried, a result replayed) is idempotent by
        construction. ``mono0`` / ``wall0`` are the clock anchor: an
        event's instant on ``time.monotonic()`` — one clock for every
        process of the machine — is ``ts - wall0 + mono0``."""
        if not self.events:
            return None
        out: Dict[str, Any] = {
            "trace_id": self.trace_id,
            "mono0": round(self._mono0, 6),
            "wall0": round(self._wall0, 6),
            "events": [
                [name, round(ts, 6), _safe_attrs(attrs) if attrs else None]
                for name, ts, attrs in self.events
            ],
        }
        if self.source:
            out["source"] = self.source
        if self.dropped:
            out["dropped"] = int(self.dropped)
        if done:
            out["done"] = True
        return out


def timeline_for(params: Any, source: str = "") -> Any:
    """A :class:`Timeline` for the request iff its params carry a
    ``trace_id`` and the process-wide recorder is enabled; the shared
    no-op :data:`NULL_TIMELINE` otherwise (zero per-request cost)."""
    if not isinstance(params, dict):
        return NULL_TIMELINE
    tid = params.get("trace_id")
    if not tid or not isinstance(tid, str) or not flight_enabled():
        return NULL_TIMELINE
    return Timeline(tid, source=source)


# ---------------------------------------------------------------------------
# merge + phase derivation (server-side, and the bench's client-side reader)
# ---------------------------------------------------------------------------


def merge_events(sources: Dict[str, List[Any]]) -> List[Dict[str, Any]]:
    """Merge per-source event lists into ONE causally-ordered timeline.

    Sort by wall timestamp (source name, then within-source order break
    ties deterministically), then clamp each timestamp to be >= its
    predecessor: the merged view is monotonically ordered even when the
    sources' clocks are skewed. Clamping is display-side only — the
    per-source lists keep their raw timestamps."""
    rows: List[Any] = []
    for src in sorted(sources):
        for i, ev in enumerate(sources[src] or []):
            try:
                name = str(ev[0])
                ts = float(ev[1])
            except (TypeError, ValueError, IndexError):
                continue
            attrs = ev[2] if len(ev) > 2 else None
            rows.append((ts, str(src), i, name, attrs))
    rows.sort(key=lambda r: (r[0], r[1], r[2]))
    out: List[Dict[str, Any]] = []
    prev = None
    for ts, src, _i, name, attrs in rows:
        if prev is not None and ts < prev:
            ts = prev
        prev = ts
        row: Dict[str, Any] = {"event": name, "ts": round(ts, 6),
                               "source": src}
        if isinstance(attrs, dict) and attrs:
            row["attrs"] = attrs
        out.append(row)
    return out


def _first(times: Dict[str, float], *names: str) -> Optional[float]:
    for n in names:
        if n in times:
            return times[n]
    return None


def phase_durations(merged: List[Dict[str, Any]]) -> Dict[str, float]:
    """Derive the canonical phase durations (seconds) from a merged
    timeline. Every phase is optional — only boundaries actually present
    yield a duration, and a nonsensical (negative) span is dropped rather
    than reported. The event names consumed here are the canonical table
    in docs/observability.md."""
    if not merged:
        return {}
    first: Dict[str, float] = {}
    last: Dict[str, float] = {}
    for ev in merged:
        name, ts = ev["event"], float(ev["ts"])
        first.setdefault(name, ts)
        last[name] = ts
    start = float(merged[0]["ts"])
    end = float(merged[-1]["ts"])
    out: Dict[str, float] = {}

    def put(phase: str, t0: Optional[float], t1: Optional[float]) -> None:
        if t0 is not None and t1 is not None and t1 >= t0:
            out[phase] = t1 - t0

    # queue wait: worker-side batcher wait preferred (the contended
    # resource), server-side submit→claim wait otherwise (queued path)
    put("queue_wait",
        _first(first, "batcher.enqueued", "server.submitted"),
        _first(first, "batcher.admitted", "server.claimed"))
    put("prefill",
        _first(first, "pd.prefill.start", "batcher.admitted"),
        _first(first, "pd.prefill.done", "batcher.first_token"))
    put("ttft", start,
        _first(first, "batcher.first_token", "pd.prefill.done"))
    # sender notes handoff.begin/commit, the receiving worker's data
    # plane notes handoff.rx_begin/rx_commit: the phase opens at the
    # FIRST begin either side observed and closes at the LAST commit
    h0 = _first(first, "handoff.begin", "handoff.rx_begin")
    h1 = _first(last, "handoff.commit", "handoff.rx_commit") \
        if ("handoff.commit" in last or "handoff.rx_commit" in last) \
        else None
    if h1 is not None and "handoff.commit" in last \
            and "handoff.rx_commit" in last:
        h1 = max(last["handoff.commit"], last["handoff.rx_commit"])
    put("handoff", h0, h1)
    put("decode",
        _first(first, "pd.decode.start", "batcher.first_token"),
        _first(last, "pd.decode.done", "batcher.completed"))
    put("e2e", start, end)
    return out
