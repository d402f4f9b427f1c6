"""Worker-hosted direct inference endpoint.

Behavioral parity with the reference's ``worker/direct_server.py`` (140 LoC,
FastAPI): ``/health``, ``/status``, and ``/inference`` which returns **503
while the worker is busy or draining** (:79-85) so clients fall back to the
control-plane queue. aiohttp here (the framework's one HTTP stack — same as
the control plane and the P2P data plane).

Discovery flow (reference SURVEY §3.2 direct-mode variant): clients find this
endpoint via the control plane's ``/api/v1/jobs/direct/nearest`` and POST
job params straight to ``/inference``, skipping the queue entirely.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import threading
import time
import uuid
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from aiohttp import web

from ..runtime.flight import EGRESS_KEY, Egress, span
from ..testing import faults as _faults

log = logging.getLogger(__name__)

# an event written this long after its round returned is a stalled one:
# counted, and logged with its stamps
EGRESS_STALL_S = 0.050


class _Written(NamedTuple):
    """One event of a traced stream as the direct server keeps it: its four
    stamps (``time.monotonic()`` seconds) and the round that brought it."""

    ready: float
    notified: float
    pumped: float
    written: float
    round: int
    cause: str


class _StreamWatch:
    """What the direct server keeps of a TRACED stream's writes (O(1)):
    its first token-bearing event, its last one, and the two either side of
    the longest stretch between two consecutive writes. They become the
    timeline events ``direct.first_write`` and ``direct.longest_wait``."""

    __slots__ = ("first", "last", "gap_s", "ended", "before")

    def __init__(self) -> None:
        self.first: Optional[_Written] = None
        self.last: Optional[_Written] = None
        self.gap_s = 0.0
        self.ended: Optional[_Written] = None
        self.before: Optional[_Written] = None

    def wrote(self, ev: _Written) -> None:
        if self.last is None:
            self.first = ev
        elif ev.written - self.last.written > self.gap_s:
            self.gap_s = ev.written - self.last.written
            self.ended, self.before = ev, self.last
        self.last = ev

    def events(self) -> List[Tuple[str, float, Dict[str, Any]]]:
        """``(name, time.monotonic() instant, attributes)`` of each."""
        out = []
        if self.first is not None:
            out.append(("direct.first_write", self.first.written,
                        self.first._asdict()))
        if self.ended is not None and self.before is not None:
            out.append(("direct.longest_wait", self.ended.written, {
                "wait_ms": round(self.gap_s * 1e3, 3), **self.ended._asdict(),
                **{f"prev_{k}": v
                   for k, v in self.before._asdict().items()}}))
        return out


def add_timeline_events(wire: Any, watch: _StreamWatch) -> None:
    """Add the watch's events to a serialised timeline (``Timeline.wire``),
    whose clock anchor puts their monotonic instants on its axis. The list
    is replaced, not grown: the heartbeat ring holds the same dict and may
    be serialising it on another thread."""
    if not isinstance(wire, dict) or "mono0" not in wire:
        return
    shift = float(wire["wall0"]) - float(wire["mono0"])
    wire["events"] = list(wire.get("events") or []) + [
        [name, round(mono + shift, 6), attrs]
        for name, mono, attrs in watch.events()]


class DirectServer:
    """Serves a Worker's engines over local HTTP (reference DirectServer)."""

    def __init__(self, worker: Any, host: str = "0.0.0.0",
                 port: int = 8471) -> None:
        self.worker = worker
        self.host = host
        self.port = port
        self._runner: Optional[web.AppRunner] = None
        self._thread: Optional[threading.Thread] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._started = threading.Event()
        self.stats: Dict[str, Any] = {
            "requests": 0, "rejected": 0, "hedge_cancels": 0,
            # seconds inside _parse_and_admit (span dgi.direct.admit)
            "admit_s": 0.0,
            # a token's way out, summed on this server's loop as each
            # event is written: events that carried a round's stamp, the
            # seconds from that round's return to the write and their
            # three stages (engine thread -> batcher loop -> pump thread
            # -> socket), and the events over EGRESS_STALL_S with their
            # seconds above it (docs/observability.md)
            "sse_events": 0, "egress_s": 0.0, "egress_notify_s": 0.0,
            "egress_pump_s": 0.0, "egress_write_s": 0.0,
            "egress_stalled": 0, "egress_stall_s": 0.0,
        }
        # health-telemetry accumulators, drained into each heartbeat by
        # wire_stats(): per-request wall latencies (ms) and served-5xx
        # counts since the last beat. Handlers run on the direct-server
        # loop thread while the heartbeat drains from the worker thread,
        # so the buffers take a lock.
        self._stats_lock = threading.Lock()
        self._recent_ms: list = []
        self._new_errors = 0
        # hedged dispatch: in-flight requests that registered a client
        # hedge key, cancellable at the next step boundary via
        # POST /inference/cancel — the losing racer's abort path
        self._cancels: Dict[str, threading.Event] = {}

    # -- handlers ------------------------------------------------------------

    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok", "ts": time.time()})

    async def _status(self, request: web.Request) -> web.Response:
        return web.json_response(self.worker.get_status())

    async def _parse_and_admit(self, request: web.Request,
                               require_stream: bool = False):
        """``_admit`` under the span ``dgi.direct.admit`` (its seconds:
        ``admit_s``); a traced request takes the instant it was accepted,
        before its body was parsed, to its timeline."""
        accepted = time.monotonic()
        with span("dgi.direct.admit", self.stats, "admit_s") as sp:
            out = await self._admit(request, require_stream)
            params = (out[1] or {}).get("params")
            if isinstance(params, dict) and params.get("trace_id"):
                params["_flight_accepted"] = accepted
                sp.set(trace_id=str(params["trace_id"])[:128])
        return out

    async def _admit(self, request: web.Request, require_stream: bool):
        """ONE admission pipeline for both inference endpoints (load-control
        caps must hold no matter which path the job takes): returns
        ``(engine, body, release, None)`` with the worker CLAIMED, or
        ``(None, None, None, error_response)``. On success the caller owns
        the claim and must call ``release(started)``.

        Claim kinds: an engine serving through a batcher front-end takes a
        SHARED serving claim (concurrent requests join the batch, capped by
        ``load_control.max_concurrent_jobs``); everything else keeps the
        exclusive IDLE→BUSY claim (engines without a batcher are never
        driven concurrently). Workers without the shared-claim surface
        (older shims, tests) always get the exclusive claim."""
        try:
            body = await request.json()
        except ValueError:
            return None, None, None, web.json_response(
                {"detail": "invalid JSON"}, status=400
            )
        if not isinstance(body, dict):
            return None, None, None, web.json_response(
                {"detail": "body must be a JSON object"}, status=400
            )
        task_type = body.get("type", "llm")
        engine = self.worker.engines.get(task_type)
        if engine is None:
            return None, None, None, web.json_response(
                {"detail": f"task type {task_type!r} not loaded"}, status=404
            )
        if require_stream and \
                getattr(engine, "stream_inference", None) is None:
            return None, None, None, web.json_response(
                {"detail": f"engine for {task_type!r} does not stream"},
                status=501,
            )
        # reserved internal key: the failover context is MINTED by this
        # server / the worker claim path, never accepted from a client —
        # a forged checkpoint would otherwise drive the resume path with
        # arbitrary state (bypassing request validation) and poison the
        # stream's control-plane checkpoints
        params = body.get("params")
        if isinstance(params, dict):
            params.pop("_failover_ctx", None)
            # flight recorder: the arrival stamps are worker-minted too —
            # a client-forged pickup time would skew phase attribution
            params.pop("_flight_picked_up_ts", None)
            params.pop("_flight_accepted", None)
            params.pop("_flight_tl", None)
            if params.get("trace_id"):
                # direct requests skip the queue: the "pickup" is the
                # moment this server admitted the request
                params["_flight_picked_up_ts"] = time.time()
        accept = getattr(self.worker, "should_accept_job", None)
        if accept is not None and not accept({"type": task_type}):
            self.stats["rejected"] += 1
            return None, None, None, web.json_response(
                {"detail": "declined by load control"}, status=503
            )
        serving = getattr(engine, "serving", None)
        begin_shared = getattr(self.worker, "try_begin_serving", None)
        is_pd = isinstance(params, dict) and params.get("pd_stage")
        if serving is not None and getattr(serving, "active", False) \
                and begin_shared is not None and not is_pd:
            # batcher-backed engine: shared claim — concurrent direct
            # requests land in the SAME continuous batch and share decode
            # rounds (PD stages keep the exclusive claim: they manage
            # engine slots out-of-band)
            if not begin_shared():
                self.stats["rejected"] += 1
                return None, None, None, web.json_response(
                    {"detail": f"worker {self.worker.state.value}"},
                    status=503,
                )
            end = self.worker.end_serving
        else:
            # atomically claim the worker (IDLE→BUSY): a second direct
            # request, or the queue poll loop, sees BUSY and backs off.
            # 503 → client falls back to the control-plane queue
            # (reference direct_server.py:79-85).
            if not self.worker.try_begin_job():
                self.stats["rejected"] += 1
                return None, None, None, web.json_response(
                    {"detail": f"worker {self.worker.state.value}"},
                    status=503,
                )
            end = self.worker.end_job
        self.stats["requests"] += 1

        def release(started: float) -> None:
            note = getattr(self.worker, "note_job_done", None)
            if note is not None:
                note(started)
            end()

        return engine, body, release, None

    def _fault_tag(self) -> str:
        """Per-worker context for the chaos seams: rules can target ONE
        replica of a fleet (``match={"worker": "w1"}``) instead of every
        engine in the process. Workers/shims opt in by setting
        ``fault_tag``; untagged workers match the empty string."""
        return str(getattr(self.worker, "fault_tag", "") or "")

    def _record_sample(self, latency_ms: Optional[float] = None,
                       error: bool = False) -> None:
        """Accumulate a health-telemetry observation for the next
        heartbeat. The sample buffer is bounded: if the heartbeat loop
        stalls, old samples drop rather than the buffer growing forever
        (the freshest window is what health scoring wants anyway)."""
        with self._stats_lock:
            if latency_ms is not None:
                self._recent_ms.append(float(latency_ms))
                if len(self._recent_ms) > 512:
                    del self._recent_ms[:-256]
            if error:
                self._new_errors += 1

    def wire_stats(self) -> Dict[str, Any]:
        """Heartbeat ``engine_stats["direct"]`` channel: drains the
        since-last-beat latency samples / served-5xx count (deltas), plus
        the CUMULATIVE counters the plane delta-anchors: hedge cancels
        into ``hedges_total{outcome="cancelled"}``, and a token's way out
        (``direct_sse_events_total``, ``direct_token_egress_seconds_total
        {stage}``, ``direct_egress_stalls_total``,
        ``direct_admit_seconds_total``)."""
        with self._stats_lock:
            recent = self._recent_ms
            self._recent_ms = []
            errors = self._new_errors
            self._new_errors = 0
        st = self.stats
        return {"recent_ms": recent, "new_errors": errors,
                "hedge_cancels": int(st["hedge_cancels"]),
                "sse_events": int(st["sse_events"]),
                "egress_stalled": int(st["egress_stalled"]),
                **{k: round(float(st[k]), 6) for k in (
                    "egress_notify_s", "egress_pump_s", "egress_write_s",
                    "admit_s")}}

    def _wrote(self, egress: Egress, written: float,
               watch: Optional[_StreamWatch]) -> None:
        """Count one event whose chunk carried ``egress``, written at
        ``written`` (``time.monotonic()``)."""
        rstamp, notified, pumped, req = egress
        st = self.stats
        total = written - rstamp.ready
        st["sse_events"] += 1
        st["egress_s"] += total
        st["egress_notify_s"] += notified - rstamp.ready
        st["egress_pump_s"] += pumped - notified
        st["egress_write_s"] += written - pumped
        if total > EGRESS_STALL_S:
            st["egress_stalled"] += 1
            st["egress_stall_s"] += total - EGRESS_STALL_S
            log.warning(
                "request %s: an event left %.3f s after round %d (%s, %d "
                "steps) returned: ready %.6f, notified +%.3f, pumped +%.3f, "
                "written +%.3f", req, total, rstamp.round, rstamp.kind,
                rstamp.steps, rstamp.ready, notified - rstamp.ready,
                pumped - rstamp.ready, total)
        if watch is not None:
            watch.wrote(_Written(rstamp.ready, notified, pumped, written,
                                 rstamp.round, rstamp.cause))

    async def _inference(self, request: web.Request) -> web.Response:
        t0 = time.time()   # BEFORE the fault seam: injected gray delay is
        # real service time and must land in the health latency samples
        reject = _faults.http_reject("worker.direct.request",
                                     worker=self._fault_tag())
        if reject == 0:
            # chaos seam: the worker "dies" on this request — hard-close
            # so the client sees a crashed process, not a clean error
            with contextlib.suppress(Exception):
                request.transport.close()
            raise ConnectionResetError("fault injected: request cut")
        if reject is not None:
            # gray flaky seam: the process is healthy, the answer is a 5xx
            self.stats["rejected"] += 1
            self._record_sample(error=True)
            return web.json_response(
                {"detail": "fault injected: flaky reply"}, status=reject
            )
        engine, body, release, err = await self._parse_and_admit(request)
        if err is not None:
            return err
        # hedged dispatch: a client that raced this request against another
        # replica registers a cancel key — the losing leg is aborted at the
        # next step boundary via POST /inference/cancel instead of burning
        # decode rounds to the end. The key is client-supplied but the
        # EVENT is server-minted (``_cancel_evt`` rides the reserved
        # underscore namespace _parse_and_admit strips from clients).
        params = body.get("params") or {}
        hedge_key = None
        if isinstance(params, dict):
            # the event slot is server-owned: a wire-supplied value would
            # reach the batcher's cancel hook as a non-Event and crash it
            params.pop("_cancel_evt", None)
            if params.get("hedge_key"):
                hedge_key = str(params.pop("hedge_key"))
                evt = threading.Event()
                params["_cancel_evt"] = evt
                self._cancels[hedge_key] = evt
        started = time.time()
        loop = asyncio.get_running_loop()
        try:
            result = await loop.run_in_executor(
                None, engine.inference, params
            )
        except Exception as exc:  # noqa: BLE001 - surface as a job error
            self._record_sample(error=True)
            return web.json_response({"detail": str(exc)}, status=500)
        finally:
            release(started)
            if hedge_key is not None:
                self._cancels.pop(hedge_key, None)
        self._record_sample(latency_ms=(time.time() - t0) * 1000.0)
        return web.json_response({"result": result})

    async def _inference_cancel(self, request: web.Request) -> web.Response:
        """Hedge-loser abort: flips the cancel event registered under the
        caller's ``hedge_key``, so the batcher releases the slot at the
        next step boundary. Idempotent; an unknown key (request already
        finished, or never started here) is a no-op 200 so racers never
        error out while tidying up."""
        try:
            body = await request.json()
        except ValueError:
            return web.json_response({"detail": "invalid JSON"}, status=400)
        key = str((body or {}).get("hedge_key") or "")
        evt = self._cancels.get(key) if key else None
        if evt is not None and not evt.is_set():
            evt.set()
            self.stats["hedge_cancels"] += 1
            return web.json_response({"cancelled": True})
        return web.json_response({"cancelled": False})

    async def _inference_stream(self, request: web.Request
                                ) -> web.StreamResponse:
        """SSE token streaming (reference SGLang SSE path,
        llm_sglang.py:358-416): each chunk is one ``data:`` event; the final
        event carries done/finish_reason/usage.

        Crash-safe streams: every event is stamped with the engine's
        monotonic token ``offset`` (mirrored into the SSE ``id:`` field —
        the Last-Event-ID idiom), and a ``resume`` body
        (``{"stream_id", "offset"}``) adopts the stream's control-plane
        checkpoint — possibly left by a DIFFERENT, now-dead worker — and
        splices the continuation at the client's offset: no token re-sent,
        none skipped.

        A token-bearing chunk comes with the stamps of its way here under
        ``EGRESS_KEY``; the key is taken off before the event is
        serialised (the bytes on the wire do not know of it), and the
        stages are summed once the write returns (``_wrote``). A traced
        stream's first write and longest wait between writes join its
        timeline in the closing event, which this server is the last to
        touch."""
        engine, body, release, err = await self._parse_and_admit(
            request, require_stream=True
        )
        if err is not None:
            return err
        started = time.time()
        params = dict(body.get("params") or {})
        resume = body.get("resume") if isinstance(body.get("resume"),
                                                  dict) else None
        stream_id = str(
            (resume or {}).get("stream_id") or body.get("stream_id")
            or uuid.uuid4().hex
        )
        if getattr(engine, "supports_failover", False):
            ctx: Dict[str, Any] = {"key": stream_id, "kind": "stream",
                                   "epoch": 0}
            if resume is not None:
                adopt = getattr(self.worker, "adopt_stream_checkpoint", None)
                adoption = None
                adopt_failed = adopt is None
                if adopt is not None:
                    loop = asyncio.get_running_loop()
                    try:
                        adoption = await loop.run_in_executor(
                            None, adopt, stream_id
                        )
                    except Exception:  # noqa: BLE001 — plane unreachable
                        adopt_failed = True
                if adoption is None:
                    release(started)
                    if adopt_failed:
                        # transient: the control plane was unreachable,
                        # NOT proof that no checkpoint exists — a 503
                        # keeps the client's resume budget alive (409
                        # would terminally fail a resumable stream)
                        return web.json_response(
                            {"detail": "checkpoint adoption failed "
                                       "(control plane unreachable)"},
                            status=503,
                        )
                    # no checkpoint to resume from: the client decides
                    # (fresh queued run only if it consumed nothing yet)
                    return web.json_response(
                        {"detail": f"no checkpoint for stream {stream_id}"},
                        status=409,
                    )
                ctx["checkpoint"] = adoption.get("checkpoint")
                ctx["epoch"] = int(adoption.get("epoch") or 0)
                ctx["offset"] = int(resume.get("offset") or 0)
                ctx["text_offset"] = int(resume.get("text_offset") or 0)
            params["_failover_ctx"] = ctx
        elif resume is not None:
            release(started)
            return web.json_response(
                {"detail": "engine does not support stream resume"},
                status=409,
            )
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Accel-Buffering": "no",
            }
        )
        await resp.prepare(request)
        watch = _StreamWatch() if params.get("trace_id") else None
        agen = engine.stream_inference(params)
        try:
            async for chunk in agen:
                if _faults.stream_cut("worker.direct.stream",
                                      stream_id=stream_id,
                                      worker=self._fault_tag()):
                    # chaos seam: the worker "dies" mid-stream — hard-close
                    # the socket so the client sees an abrupt drop, exactly
                    # like a crashed process
                    with contextlib.suppress(Exception):
                        request.transport.close()
                    raise ConnectionResetError("fault injected: stream cut")
                egress = chunk.pop(EGRESS_KEY, None)
                if watch is not None and chunk.get("done"):
                    add_timeline_events(chunk.get("timeline"), watch)
                with span("dgi.direct.write",
                          req=egress.req if egress else "",
                          round=egress.stamp.round if egress else -1):
                    evt = b""
                    if chunk.get("offset") is not None:
                        evt += f"id: {chunk['offset']}\n".encode()
                    evt += f"data: {json.dumps(chunk)}\n\n".encode()
                    await resp.write(evt)
                if egress is not None:
                    self._wrote(egress, time.monotonic(), watch)
        except ConnectionResetError:
            pass  # client went away mid-stream; aclose() below aborts the run
        finally:
            # closing the generator signals the pump thread to abort and
            # WAITS for it — the engine is quiet before the claim releases,
            # so the next request can never drive the engine concurrently
            await agen.aclose()
            release(started)
        with contextlib.suppress(ConnectionResetError):
            await resp.write_eof()
        return resp

    def make_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/health", self._health)
        app.router.add_get("/status", self._status)
        app.router.add_post("/inference", self._inference)
        app.router.add_post("/inference/cancel", self._inference_cancel)
        app.router.add_post("/inference/stream", self._inference_stream)
        return app

    def start(self) -> None:
        """Run in a background thread with a private event loop (the worker's
        main loop is a plain thread, reference main.py:386)."""

        def _run() -> None:
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            runner = web.AppRunner(self.make_app())
            loop.run_until_complete(runner.setup())
            self._runner = runner
            site = web.TCPSite(runner, self.host, self.port)
            loop.run_until_complete(site.start())
            self._started.set()
            loop.run_forever()
            loop.run_until_complete(runner.cleanup())
            loop.close()

        self._thread = threading.Thread(
            target=_run, name="direct-server", daemon=True
        )
        self._thread.start()
        if not self._started.wait(timeout=10.0):
            raise RuntimeError("direct server failed to start")

    def stop(self) -> None:
        if self._loop is not None and not self._loop.is_closed():
            self._loop.call_soon_threadsafe(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
