#!/usr/bin/env python
"""Worker-serving benchmark: the DEPLOYED path, not a harness.

Round 5 measured the latency-throughput frontier (p50 TTFT 270 ms
sustained at 1.5 req/s; 1,5xx tok/s at batch 32) with
``benchmarks/single_worker.py`` driving ``runtime/batcher.py`` directly —
a bench-only result (VERDICT r5 weak #1). This harness drives the REAL
production surface instead: open-loop Poisson arrivals (or a closed-loop
throughput sweep) POSTed over HTTP to a live ``worker/direct_server.py``
fronting a ``TPULLMEngine`` whose batcher front-end
(``worker/engines/llm.py`` serving mode, the deployed default) shares
decode rounds across the concurrent requests.

``--compare`` replays the SAME workload (same prompts, same arrival
schedule) against the in-process batcher — the bench-only configuration
the frontier was published from — and emits the deployed/bench ratios, so
"the frontier transferred to the worker path" is checkable on any
hardware: p50 TTFT within 15% and decode tok/s within 10% are the
acceptance bars.

``--compare-legacy`` (round 6) A/Bs the RAGGED serving path (the default:
admission appends prefill-chunk rows to the shared decode round — one
dispatch, no admission stall to shape) against the knob-tuned legacy
wave/chunk-interleaved path on the SAME live engine: the primary leg runs
ragged with the subwave/interleave/max-horizon knobs at their (ignored)
defaults, then ``serving.ragged=false`` is pushed to the live batcher
(the remote-config A/B path a fleet would use) and the identical workload
replays through the legacy machinery shaped by the CLI knob values.
Emits ragged/legacy TTFT p50/p95 and tok/s ratios — "the kernel beats
the hand-tuning it deletes" is checkable on any hardware.

``--spec`` (round 8) A/Bs speculative decoding ON (oracle draft: forced
acceptance at configurable rates — every cost real, only the decision
forced) against OFF through the same deployed path, publishing the
tok/s-vs-acceptance curve and the crossover rate where spec ON beats
spec OFF at equal p50 TTFT — the ROADMAP item 1 exit bar, measurable
without trained draft weights.

Usage (SLO row / throughput row / ragged-vs-knob-tuned):
    python -m benchmarks.worker_serving --arrival-rate 1.5 --requests 64 \
        --prompt-len 512 --max-tokens 128 --concurrency 16 \
        --subwave 2 --interleave 2 --max-horizon 4 \
        --compare
    python -m benchmarks.worker_serving --requests 64 --concurrency 32 \
        --prompt-len 128 --max-tokens 64 --compare
    python -m benchmarks.worker_serving --arrival-rate 2 --requests 64 \
        --prompt-len 512 --max-tokens 128 --concurrency 16 \
        --subwave 2 --interleave 1 --max-horizon 4 --compare-legacy
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time
import uuid
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import add_platform_arg, emit, percentiles, \
    resolve_backend_model


def synth_prompt_strings(n: int, prompt_len: int, shared_prefix: int,
                         seed: int = 0) -> List[str]:
    """ASCII prompts (ByteTokenizer: one token per character) with an
    optional shared system prefix — the string twin of
    ``benchmarks.common.synth_prompts``."""
    rng = np.random.default_rng(seed)
    letters = "abcdefghijklmnopqrstuvwxyz"
    shared_prefix = min(shared_prefix, prompt_len)
    prefix = "".join(
        letters[i] for i in rng.integers(0, 26, shared_prefix)
    )
    out = []
    for _ in range(n):
        rest = "".join(
            letters[i] for i in rng.integers(0, 26, prompt_len - shared_prefix)
        )
        out.append(prefix + rest)
    return out


class BenchWorker:
    """The claim surface DirectServer drives — shared serving claims with
    an effectively-unbounded cap (the batcher's queue_limit is the real
    backpressure here; the production Worker caps shared claims at
    load_control.max_concurrent_jobs)."""

    def __init__(self, llm_engine: Any) -> None:
        self.engines = {"llm": llm_engine}
        self.state = type("S", (), {"value": "idle"})()
        self._serving = 0

    def try_begin_serving(self) -> bool:
        self._serving += 1
        return True

    def end_serving(self) -> None:
        self._serving = max(0, self._serving - 1)

    def try_begin_job(self) -> bool:  # pragma: no cover — batcher path only
        return True

    def end_job(self) -> None:  # pragma: no cover
        pass

    def get_status(self) -> Dict[str, Any]:
        return {"state": "idle", "in_flight": self._serving}


def _warm(llm: Any, prompt_len: int, levels: Tuple[int, ...],
          concurrency: int) -> None:
    """Compile every graph the serving path will request OUTSIDE the
    measurement, mirroring single_worker's warmup: the prompt bucket at
    every power-of-2 wave width the batcher's submit_batch can produce
    (a cold batched-prefill compile mid-measurement would bill ~hundreds
    of ms to whichever path ran first), plus each quantized decode
    horizon. Then zero the warmed prefix-cache counters."""
    from benchmarks.common import make_request

    eng = llm.engine
    spec = getattr(eng.cfg, "speculative", None) is not None
    warm_ids = [((i * 13) % 26) + ord("a") for i in range(prompt_len)]
    warm_prompt = [llm.tokenizer.encode(chr(c))[0] for c in warm_ids]

    def _drain() -> None:
        while any(s is not None and s.finish_reason is None
                  for s in eng.slots):
            eng.decode_multi(levels[0])
        for i, s in enumerate(list(eng.slots)):
            if s is not None:
                eng.finish_slot(i, cache=False)

    def _run() -> None:
        w = 1
        while True:
            width = min(w, concurrency)
            eng.submit_batch([make_request(warm_prompt, 2)
                              for _ in range(width)])
            _drain()
            if width == concurrency:
                break
            w *= 2
        for T in levels:
            # a spec engine's decode dispatch is a rounds=min(T, budget)
            # scan: warm with a budget that reaches T (clamped to the
            # pool geometry) or the serving measurement pays the
            # full-depth compile on its first round
            budget = 2
            if spec:
                budget = max(2, min(T, eng.cfg.max_seq_len
                                    - len(warm_prompt) - 8))
            slot = eng.submit(make_request(warm_prompt, budget))
            while eng.slots[slot] is not None and \
                    eng.slots[slot].finish_reason is None:
                eng.decode_multi(T)
            eng.finish_slot(slot, cache=False)
        if getattr(eng, "supports_ragged", False):
            # ragged rounds compile one graph per chunk bucket width:
            # admit a prompt at every width an admission chunk row can
            # bucket to and run it through ragged_round, so the ragged
            # leg (the serving default) never bills a compile to TTFT.
            # Spec engines compile TWO graphs per width — admission-only
            # rounds delegate to the plain graph (no draft chain), and
            # rounds with a live decode slot run the spec verify graph —
            # plus the dedicated K+1 pure-verify width (short final
            # chunks), so warm admits each width twice: once alone, once
            # alongside a decoding slot.
            cap = min(max(int(eng.cfg.ragged_chunk), 1),
                      eng.cfg.prefill_buckets[-1], prompt_len)
            widths = {min(b, cap) for b in eng.cfg.prefill_buckets}
            if spec:
                widths.add(2)
            spec_legs = (False, True) if spec and len(eng.slots) > 1 \
                else (False,)
            bg_budget = max(2, min(32, eng.cfg.max_seq_len - 8))
            for width in sorted(widths):
                for with_live_decode in spec_legs:
                    if with_live_decode:
                        eng.submit(make_request(warm_prompt[:4], bg_budget))
                    adm = eng.submit_chunked_start(
                        make_request(warm_prompt[:width], 2)
                    )
                    while not adm.done:
                        eng.ragged_round([adm])
                    _drain()

    llm.serving.run_exclusive(_run)
    eng.manager.stats.prefix_queries = 0
    eng.manager.stats.prefix_hit_tokens = 0
    eng.manager.stats.prefix_total_tokens = 0


async def _drive(one, prompts: List[str], rate: Optional[float],
                 concurrency: int,
                 seed: int) -> Tuple[List[Dict[str, Any]], float, float]:
    """Shared arrival scaffolding for BOTH legs of ``--compare`` — one
    workload generator, so the deployed/bench ratio never compares two
    different arrival schedules. Open loop (rate set): seeded Poisson
    arrivals, no concurrency gate — TTFT includes queue wait, which is
    what an SLO means. Closed loop: semaphore at ``concurrency``.
    ``one(prompt, at)`` awaits until the arrival instant and performs a
    single request, returning {status, e2e_ms, ttft_ms?,
    completion_tokens?}."""
    t0 = time.perf_counter()
    if rate:
        gaps = np.random.default_rng(seed).exponential(
            1.0 / rate, len(prompts)
        )
        arrivals = np.cumsum(gaps)
        results = list(await asyncio.gather(
            *(one(p, a) for p, a in zip(prompts, arrivals))
        ))
        span = float(arrivals[-1])
    else:
        sem = asyncio.Semaphore(concurrency)

        async def gated(p: str) -> Dict[str, Any]:
            async with sem:
                return await one(p, None)

        results = list(await asyncio.gather(*(gated(p) for p in prompts)))
        span = 0.0
    return results, time.perf_counter() - t0, span


async def _drive_http(url: str, prompts: List[str], max_tokens: int,
                      rate: Optional[float], concurrency: int,
                      seed: int, extra_params: Optional[Dict[str, Any]] = None,
                      trace: bool = False, collect_text: bool = False,
                      ) -> Tuple[List[Dict[str, Any]], float, float]:
    """Drive the REAL direct server over HTTP. ``trace`` stamps a flight
    trace_id per request and collects the worker-side timeline off the
    result; ``collect_text`` keeps the generated text (recorder-on-vs-off
    byte-identity checks)."""
    import httpx

    async with httpx.AsyncClient(timeout=600.0) as client:

        async def one(p: str, at: Optional[float]) -> Dict[str, Any]:
            if at is not None:
                await asyncio.sleep(float(at))
            params = {"prompt": p, "max_new_tokens": max_tokens,
                      **(extra_params or {})}
            if trace:
                params["trace_id"] = f"bench-{uuid.uuid4().hex[:12]}"
            t0 = time.perf_counter()
            r = await client.post(url + "/inference", json={
                "type": "llm",
                "params": params,
            })
            e2e_ms = (time.perf_counter() - t0) * 1000.0
            out = {"status": r.status_code, "e2e_ms": e2e_ms}
            if r.status_code == 200:
                res = r.json().get("result") or {}
                out["ttft_ms"] = res.get("ttft_ms")
                out["completion_tokens"] = (
                    (res.get("usage") or {}).get("completion_tokens") or 0
                )
                if trace:
                    out["timeline"] = res.get("timeline")
                if collect_text:
                    out["text"] = res.get("text")
            return out

        return await _drive(one, prompts, rate, concurrency, seed)


async def _drive_inproc(llm: Any, prompts: List[str], max_tokens: int,
                        rate: Optional[float], concurrency: int,
                        seed: int) -> Tuple[List[Dict[str, Any]], float, float]:
    """The bench-only configuration (single_worker's shape): the SAME
    workload submitted straight to the batcher, skipping HTTP + claims.
    Requests are built at their arrival instant so the engine's TTFT clock
    includes queue wait, exactly like open_loop_drive."""
    from distributed_gpu_inference_tpu.worker.engines.base import (
        GenerationConfig,
    )

    def build(p: str):
        return llm._build_request(
            p, GenerationConfig.from_params({"max_new_tokens": max_tokens})
        )

    async def one(p: str, at: Optional[float]) -> Dict[str, Any]:
        if at is not None:
            await asyncio.sleep(float(at))
        t0 = time.perf_counter()
        resp = await asyncio.wrap_future(llm.serving.submit_async(build(p)))
        e2e_ms = (time.perf_counter() - t0) * 1000.0
        return {
            "status": 200 if resp.error is None else 500,
            "e2e_ms": e2e_ms,
            "ttft_ms": resp.ttft_ms,
            "completion_tokens": resp.completion_tokens,
        }

    return await _drive(one, prompts, rate, concurrency, seed)


def _timeline_attribution(results: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Per-phase latency attribution from per-request flight timelines:
    p50/p95 (ms) for each canonical phase. Accepts records carrying either
    a raw worker ``timeline`` wire (direct-path legs) or already-derived
    ``phases`` (queued/PD legs reading the plane's debug endpoint) — this
    is the table that replaces 'a single opaque TTFT number'."""
    from distributed_gpu_inference_tpu.runtime.flight import (
        PHASES,
        merge_events,
        phase_durations,
    )

    per_phase: Dict[str, List[float]] = {p: [] for p in PHASES}
    samples = 0
    for rec in results:
        phases = rec.get("phases")
        if not phases:
            wire = rec.get("timeline")
            if not isinstance(wire, dict):
                continue
            merged = merge_events({
                str(wire.get("source") or "worker"):
                    wire.get("events") or []
            })
            phases = phase_durations(merged)
        if not phases:
            continue
        samples += 1
        for p, v in phases.items():
            if p in per_phase:
                per_phase[p].append(float(v) * 1000.0)
    return {
        "samples": samples,
        "phase_ms": {p: percentiles(v)
                     for p, v in per_phase.items() if v},
    }


def _summarize(results: List[Dict[str, Any]], elapsed: float,
               span: float) -> Dict[str, Any]:
    ok = [r for r in results if r["status"] == 200]
    ttfts = [r["ttft_ms"] for r in ok if r.get("ttft_ms") is not None]
    decoded = sum(r.get("completion_tokens") or 0 for r in ok)
    return {
        "ok": len(ok),
        "rejected": len(results) - len(ok),
        "elapsed_s": round(elapsed, 3),
        "decode_tokens_per_s": round(decoded / elapsed, 2) if elapsed else 0,
        "ttft_ms": percentiles(ttfts),
        "e2e_ms": percentiles([r["e2e_ms"] for r in ok]),
        "offered_span_s": round(span, 3),
        "drain_s": round(elapsed - span, 3),
    }


# ---------------------------------------------------------------------------
# multi-worker fleet mode (round 7): cache-aware routing A/B over ≥2 live
# engines behind the REAL control plane — requests discover their worker
# through /jobs/direct/nearest (prefix-fingerprinted), workers advertise
# radix summaries over authenticated heartbeats, and the routing flag is
# flipped LIVE via the admin remote-config endpoint between legs.
# ---------------------------------------------------------------------------


class FleetMember:
    """One live engine + direct server, registered with the control plane
    and heartbeating radix summaries like a production worker."""

    def __init__(self, llm: Any, region: str = "us-west",
                 data_plane: bool = False) -> None:
        from distributed_gpu_inference_tpu.worker.direct_server import (
            DirectServer,
        )

        self.llm = llm
        self.region = region
        self.server = DirectServer(BenchWorker(llm), host="127.0.0.1",
                                   port=0)
        self.server.start()
        port = self.server._runner.addresses[0][1]
        self.url = f"http://127.0.0.1:{port}"
        # cluster-KV migration legs: a real /kv/transfer + /kv/export data
        # plane per member, so cold members PULL hot prefixes from peers
        self.pd_plane: Optional[Any] = None
        self.data_plane_url: Optional[str] = None
        if data_plane:
            from distributed_gpu_inference_tpu.comm.data_plane import (
                DataPlaneServer,
            )
            from distributed_gpu_inference_tpu.worker.main import (
                _PDReceiverShim,
            )

            self.pd_plane = DataPlaneServer(
                _PDReceiverShim(llm), host="127.0.0.1", port=0,
                kv_receiver=llm.kv_receiver, kv_exporter=llm.kv_export,
            )
            self.pd_plane.start()
            self.data_plane_url = (
                f"http://127.0.0.1:{self.pd_plane.bound_port}"
            )
        self.worker_id: Optional[str] = None
        self.token: Optional[str] = None

    def register(self, client: Any, plane_url: str) -> None:
        r = client.post(f"{plane_url}/api/v1/workers/register", json={
            "name": f"bench-{self.url.rsplit(':', 1)[-1]}",
            "region": self.region,
            "supported_types": ["llm"],
            "supports_direct": True,
            "direct_url": self.url,
            **({"data_plane_url": self.data_plane_url}
               if self.data_plane_url else {}),
        })
        r.raise_for_status()
        data = r.json()
        self.worker_id = data["worker_id"]
        self.token = data["auth_token"]

    def heartbeat(self, client: Any, plane_url: str) -> None:
        es: Dict[str, Any] = {}
        stats = self.llm.serving_stats() or {}
        es["batcher"] = {
            "active_slots": stats.get("active_slots", 0),
            "queue_depth": stats.get("queue_depth", 0),
            "avg_occupancy": stats.get("avg_occupancy", 0.0),
            "capacity": int(self.llm.engine.cfg.max_batch_size),
        }
        summary = self.llm.prefix_summary_wire()
        if summary is not None:
            es["prefix_summary"] = summary
        if self.llm.prefix_hot is not None:
            es["prefix_summary_live"] = True
        # mirror worker/main.py: ship the migrate counters and the flight
        # ring — the plane's calibration (round 20) learns pull bandwidth
        # and queue-wait/prefill rates from exactly these channels
        kvmig = self.llm.kv_migrate_wire_stats()
        if kvmig:
            es["kv_migrate"] = kvmig
        fl = self.llm.flight_wire_stats()
        if fl:
            es["flight"] = fl
        try:
            r = client.post(
                f"{plane_url}/api/v1/workers/{self.worker_id}/heartbeat",
                json={"status": "idle", "engine_stats": es},
                headers={"Authorization": f"Bearer {self.token}"},
            )
            if r.status_code == 200:
                # proactive replication (round 20): hand plane hints to
                # the engine's prefetch driver, like a production worker
                hints = r.json().get("kv_replicate")
                if hints:
                    try:
                        self.llm.kv_replicate(hints)
                    except Exception:  # noqa: BLE001 — advisory prefetch
                        pass
            if summary is not None:
                # mirror worker/main.py: ack ONLY on an explicit
                # "applied" answer — an absent key means the server never
                # processed the payload (acking would commit a phantom
                # base and route on stale summaries)
                if r.status_code == 200 and \
                        r.json().get("prefix_summary_resync") is False:
                    self.llm.prefix_summary_ack()
                else:
                    self.llm.prefix_summary_resync()
        except Exception:  # noqa: BLE001 — bench heartbeat loss is fine
            if summary is not None:
                self.llm.prefix_summary_resync()

    def reset_cache(self) -> None:
        """Cold-cache boundary between A/B legs: every leg starts with an
        empty prefix cache, an empty ADVERTISED summary (the first
        heartbeat round of the next leg ships the deletions, so no leg
        routes on the previous leg's summaries), and zeroed counters."""
        eng = self.llm.engine
        self.llm.serving.run_exclusive(
            lambda: eng.manager.clear_cached()
        )
        if self.llm.prefix_hot is not None:
            self.llm.prefix_hot.clear()
        # the wipe above may count as evictions; re-anchor so the next
        # wire() doesn't ALSO drop freshly-noted entries
        self.llm._prefix_evictions_seen = int(eng.manager.stats.evictions
                                              or 0)
        st = eng.manager.stats
        st.prefix_queries = 0
        st.prefix_hit_tokens = 0
        st.prefix_total_tokens = 0
        for k in self.llm.kv_migrate_stats:
            self.llm.kv_migrate_stats[k] = 0
        self.llm._kvmig_backoff.clear()
        rx = self.llm._handoff_rx
        if rx is not None:
            rx.stats["prefix_commits"] = 0

    def cache_stats(self) -> Dict[str, Any]:
        s = self.llm.engine.manager.stats
        return {
            "prefix_queries": s.prefix_queries,
            "prefix_hit_tokens": s.prefix_hit_tokens,
            "prefix_total_tokens": s.prefix_total_tokens,
        }

    def migrate_stats(self) -> Dict[str, int]:
        return dict(self.llm.kv_migrate_stats)

    def stop(self) -> None:
        self.server.stop()
        if self.pd_plane is not None:
            self.pd_plane.stop()
        self.llm.unload()


async def _drive_fleet(plane_url: str, members: List["FleetMember"],
                       workload: Any, hb_interval_s: float,
                       trace: Optional[str] = None,
                       ) -> Tuple[List[Dict[str, Any]], float]:
    """Replay one workload leg against the fleet: every request discovers
    its worker through the control plane (prefix-fingerprinted), honoring
    open-loop arrivals AND conversation turn dependencies."""
    import httpx

    from distributed_gpu_inference_tpu.utils.prefixes import (
        prefix_fingerprints,
    )

    done_events: Dict[str, asyncio.Event] = {
        r.id: asyncio.Event() for r in workload.requests
    }
    done_at: Dict[str, float] = {}
    t0 = time.perf_counter()
    async with httpx.AsyncClient(timeout=600.0) as client:
        stop_hb = asyncio.Event()

        async def hb_loop() -> None:
            # authenticated worker heartbeats on a thread (sync httpx via
            # to_thread keeps engine-side summary locks off the loop)
            sync_client = httpx.Client(timeout=30.0)
            try:
                while not stop_hb.is_set():
                    for m in members:
                        await asyncio.to_thread(
                            m.heartbeat, sync_client, plane_url
                        )
                    try:
                        await asyncio.wait_for(
                            stop_hb.wait(), hb_interval_s
                        )
                    except asyncio.TimeoutError:
                        pass
            finally:
                sync_client.close()

        async def one(req: Any) -> Dict[str, Any]:
            now = time.perf_counter() - t0
            if req.arrival_s > now:
                await asyncio.sleep(req.arrival_s - now)
            if req.depends_on is not None:
                await done_events[req.depends_on].wait()
                wait_until = done_at[req.depends_on] + req.think_s
                now = time.perf_counter() - t0
                if wait_until > now:
                    await asyncio.sleep(wait_until - now)
            fps = prefix_fingerprints(req.prompt)
            out: Dict[str, Any] = {"id": req.id, "tenant": req.tenant,
                                   "conversation": req.conversation}
            try:
                # one retry on transport errors: think-time gaps idle the
                # keep-alive connections, and the server closing one races
                # the client reusing it (greedy outputs are deterministic,
                # so a replayed inference is byte-identical)
                for attempt in (0, 1):
                    try:
                        t_req = time.perf_counter()
                        d = await client.get(
                            f"{plane_url}/api/v1/jobs/direct/nearest",
                            params={"prefix_fps": ",".join(fps)}
                            if fps else None,
                        )
                        if d.status_code != 200:
                            out["status"] = d.status_code
                            return out
                        disc = d.json()
                        r = await client.post(
                            disc["direct_url"] + "/inference", json={
                                "type": "llm",
                                "params": {"prompt": req.prompt,
                                           "max_new_tokens": req.max_tokens,
                                           "priority": req.priority,
                                           # flight-traced legs: the done
                                           # wire rides the heartbeat ring
                                           # into the recorder (and the
                                           # round-20 calibration sink);
                                           # the leg tag keeps trace ids
                                           # unique across A/B replays
                                           **({"trace_id":
                                               f"bench-{trace}-{req.id}"}
                                              if trace else {}),
                                           # router migrate-KV verdict: the
                                           # cold worker pulls the prefix
                                           # from the named peer before
                                           # admission
                                           **({"kv_migrate_from":
                                               disc["kv_migrate"]}
                                              if disc.get("kv_migrate")
                                              else {})},
                            })
                        break
                    except httpx.TransportError:
                        if attempt:
                            out["status"] = 599
                            return out
                out["status"] = r.status_code
                out["e2e_ms"] = (time.perf_counter() - t_req) * 1000.0
                out["worker_id"] = disc["worker_id"]
                if r.status_code == 200:
                    res = r.json().get("result") or {}
                    out["ttft_ms"] = res.get("ttft_ms")
                    out["text"] = res.get("text")
                    out["completion_tokens"] = (
                        (res.get("usage") or {}).get("completion_tokens")
                        or 0
                    )
                    if trace:
                        out["timeline"] = res.get("timeline")
            finally:
                done_at[req.id] = time.perf_counter() - t0
                done_events[req.id].set()
            return out

        # one COMPLETED heartbeat round before the first discovery, so
        # leg ON starts with this leg's summaries registered instead of
        # routing on whatever the previous leg left behind
        first_hb = httpx.Client(timeout=30.0)
        try:
            for m in members:
                await asyncio.to_thread(m.heartbeat, first_hb, plane_url)
        finally:
            first_hb.close()
        hb = asyncio.create_task(hb_loop())
        results = list(await asyncio.gather(
            *(one(r) for r in workload.requests)
        ))
        stop_hb.set()
        await hb
    return results, time.perf_counter() - t0


def _fleet_leg_summary(results: List[Dict[str, Any]], elapsed: float,
                       members: List["FleetMember"]) -> Dict[str, Any]:
    base = _summarize(results, elapsed, 0.0)
    ok = [r for r in results if r.get("status") == 200]
    ttfts = [r["ttft_ms"] for r in ok if r.get("ttft_ms") is not None]
    if ttfts:
        base["ttft_ms"]["mean"] = round(sum(ttfts) / len(ttfts), 2)
    hit = sum(m.cache_stats()["prefix_hit_tokens"] for m in members)
    total = sum(m.cache_stats()["prefix_total_tokens"] for m in members)
    by_worker: Dict[str, int] = {}
    for r in results:
        if r.get("worker_id"):
            by_worker[r["worker_id"]] = by_worker.get(r["worker_id"], 0) + 1
    base.update({
        "prefix_hit_rate": round(hit / total, 4) if total else 0.0,
        "re_prefill_tokens_saved": int(hit),
        "requests_by_worker": by_worker,
    })
    return base


def run_fleet(args: Any, backend: str, model: str) -> None:
    from distributed_gpu_inference_tpu.testing.harness import (
        LiveControlPlane,
    )
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    import httpx

    from benchmarks.workloads import generate

    wl = generate(args.scenario, args.seed, requests=args.requests,
                  max_tokens=args.max_tokens, rate=float(args.arrival_rate)
                  if args.arrival_rate else 2.0, burst=args.burst,
                  tenants=args.tenants)
    max_prompt = max(len(r.prompt) for r in wl.requests)
    members: List[FleetMember] = []
    with LiveControlPlane() as plane:
        client = httpx.Client(timeout=60.0)
        try:
            for _ in range(args.workers):
                llm = TPULLMEngine({
                    "model": model,
                    "max_batch_size": args.concurrency,
                    "max_seq_len": max_prompt + args.max_tokens + 16,
                    "quantization": args.quantization,
                    "serving": {
                        "queue_limit": max(4096, args.requests * 2),
                        "default_timeout_s": 600.0,
                    },
                })
                llm.load_model()
                m = FleetMember(llm)
                m.register(client, plane.url)
                members.append(m)

            def leg(label: str) -> Dict[str, Any]:
                for m in members:
                    m.reset_cache()
                results, elapsed = asyncio.run(_drive_fleet(
                    plane.url, members, wl,
                    hb_interval_s=args.fleet_heartbeat_s,
                ))
                out = _fleet_leg_summary(results, elapsed, members)
                out["outputs"] = {
                    r["id"]: r.get("text") for r in results
                    if r.get("status") == 200
                }
                return out

            # warmup replay: compile every graph both legs will use, so
            # neither leg bills XLA compiles to TTFT
            leg("warmup")
            routed = leg("routing_on")
            # the A/B flip a fleet operator would do: flip the LIVE
            # control plane's routing term via the admin endpoint —
            # workers untouched, summaries keep flowing
            client.put(f"{plane.url}/api/v1/admin/routing",
                       json={"enabled": False}).raise_for_status()
            blind = leg("routing_off")
            client.put(f"{plane.url}/api/v1/admin/routing",
                       json={"enabled": True}).raise_for_status()

            identical = routed.pop("outputs") == blind.pop("outputs")
            out = {
                "benchmark": "worker_serving_fleet",
                "path": "control_plane+direct_nearest+batcher_engines",
                "scenario": args.scenario, "seed": args.seed,
                "workers": args.workers, "model": model,
                "backend": backend, "requests": len(wl.requests),
                "concurrency": args.concurrency,
                "max_tokens": args.max_tokens,
                "routing_on": routed, "routing_off": blind,
                "outputs_identical": identical,
            }
            ratios: Dict[str, Any] = {}
            for pct in ("mean", "p50", "p95"):
                r_t = (routed["ttft_ms"] or {}).get(pct)
                b_t = (blind["ttft_ms"] or {}).get(pct)
                if r_t and b_t:
                    ratios[f"ttft_{pct}_routed_over_blind"] = round(
                        r_t / b_t, 3
                    )
            ratios["hit_rate_routed"] = routed["prefix_hit_rate"]
            ratios["hit_rate_blind"] = blind["prefix_hit_rate"]
            ratios["re_prefill_tokens_saved_delta"] = (
                routed["re_prefill_tokens_saved"]
                - blind["re_prefill_tokens_saved"]
            )
            out["routing_vs_blind"] = ratios
            emit(out)
        finally:
            client.close()
            for m in members:
                m.stop()


# ---------------------------------------------------------------------------
# --kv-migrate (round 13): cluster-wide KV migration vs PR 7's route-only
# baseline. Same fleet harness as --workers, plus a real /kv/transfer +
# /kv/export data plane per member so a cold worker PULLS a hot prefix from
# its peer instead of re-prefilling. The workload is the anti-affinity
# storm trace (benchmarks/workloads.py) — synchronized single-tenant bursts
# that saturate whichever worker is warm, exactly where advisory routing
# collapses — swept across offered rates: at low rate the warm worker
# absorbs its bursts and both legs tie; at high rate route-only spills cold
# and re-prefills while migrate-ON moves the KV to the spill target.
# ---------------------------------------------------------------------------


def run_kv_migrate(args: Any, backend: str, model: str) -> None:
    from distributed_gpu_inference_tpu.testing.harness import (
        LiveControlPlane,
    )
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    import httpx

    from benchmarks.workloads import generate

    rates = [float(r) for r in
             str(args.arrival_rate or "0.5,2.0").split(",")]
    workers = max(2, args.workers)
    wls = {
        rate: generate("storm", args.seed, requests=args.requests,
                       max_tokens=args.max_tokens, rate=rate,
                       burst=args.burst, tenants=args.tenants)
        for rate in rates
    }
    max_prompt = max(len(r.prompt) for wl in wls.values()
                     for r in wl.requests)
    members: List[FleetMember] = []
    with LiveControlPlane() as plane:
        client = httpx.Client(timeout=60.0)
        try:
            for _ in range(workers):
                llm = TPULLMEngine({
                    "model": model,
                    "max_batch_size": args.concurrency,
                    "max_seq_len": max_prompt + args.max_tokens + 16,
                    "quantization": args.quantization,
                    "serving": {
                        "queue_limit": max(4096, args.requests * 2),
                        "default_timeout_s": 600.0,
                    },
                })
                llm.load_model()
                m = FleetMember(llm, data_plane=True)
                m.register(client, plane.url)
                members.append(m)

            def routing(**kw: Any) -> None:
                client.put(f"{plane.url}/api/v1/admin/routing",
                           json=kw).raise_for_status()

            def leg(wl: Any) -> Dict[str, Any]:
                for m in members:
                    m.reset_cache()
                results, elapsed = asyncio.run(_drive_fleet(
                    plane.url, members, wl,
                    hb_interval_s=args.fleet_heartbeat_s,
                ))
                out = _fleet_leg_summary(results, elapsed, members)
                mig: Dict[str, int] = {}
                for m in members:
                    for k, v in m.migrate_stats().items():
                        mig[k] = mig.get(k, 0) + v
                out["kv_migrate"] = mig
                out["outputs"] = {
                    r["id"]: r.get("text") for r in results
                    if r.get("status") == 200
                }
                return out

            # compile every graph once (prompt lengths are identical
            # across rates, so one warmup serves every leg)
            routing(enabled=True, kv_migrate=True)
            leg(wls[rates[0]])

            out: Dict[str, Any] = {
                "benchmark": "worker_serving_kv_migrate",
                "path": "control_plane+direct_nearest+kv_export_pull",
                "scenario": "storm", "seed": args.seed,
                "workers": workers, "model": model, "backend": backend,
                "requests": args.requests, "burst": args.burst,
                "concurrency": args.concurrency,
                "max_tokens": args.max_tokens,
                "rates": {},
            }
            for rate in rates:
                wl = wls[rate]
                routing(enabled=True, kv_migrate=True)
                migrate_on = leg(wl)
                # the A/B flip: routing stays ON (PR 7 baseline), only the
                # migration cost model is disabled
                routing(kv_migrate=False)
                route_only = leg(wl)
                identical = (migrate_on.pop("outputs")
                             == route_only.pop("outputs"))
                entry: Dict[str, Any] = {
                    "migrate_on": migrate_on,
                    "route_only": route_only,
                    "outputs_identical": identical,
                    "hit_rate_migrate": migrate_on["prefix_hit_rate"],
                    "hit_rate_route_only": route_only["prefix_hit_rate"],
                }
                for pct in ("mean", "p50", "p95"):
                    m_t = (migrate_on["ttft_ms"] or {}).get(pct)
                    r_t = (route_only["ttft_ms"] or {}).get(pct)
                    if m_t and r_t:
                        entry[f"ttft_{pct}_migrate_over_route"] = round(
                            m_t / r_t, 3
                        )
                out["rates"][str(rate)] = entry
            routing(kv_migrate=False)
            emit(out)
        finally:
            client.close()
            for m in members:
                m.stop()


# ---------------------------------------------------------------------------
# --predictive (round 20): the serving-intelligence A/B. Two frontiers on a
# live fleet: (1) cost-model self-calibration under the storm workload —
# the SAME trace replayed with the static priors vs the learned per-worker
# EMAs, replayed `--predictive-repeats` times with calibration ON so the
# published predicted-vs-measured error's round-over-round FALL is the
# convergence evidence; (2) proactive prefix replication under the bursty
# workload — heartbeat-hinted prefetch pulls vs the purely reactive
# round-13 migrate path, measured as prefix hit-rate and TTFT. Greedy
# outputs predictor-on vs predictor-off are byte-identical in both halves:
# predictions move WHERE and WHEN work runs, never what it computes.
# ---------------------------------------------------------------------------


def run_predictive(args: Any, backend: str, model: str) -> None:
    from distributed_gpu_inference_tpu.testing.harness import (
        LiveControlPlane,
    )
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    import httpx

    from benchmarks.workloads import generate

    rate = float(args.arrival_rate or 2.0)
    workers = max(2, args.workers)
    repeats = max(2, args.predictive_repeats)
    storm = generate("storm", args.seed, requests=args.requests,
                     max_tokens=args.max_tokens, rate=rate,
                     burst=args.burst, tenants=args.tenants)
    bursty = generate("bursty", args.seed + 1, requests=args.requests,
                      max_tokens=args.max_tokens, rate=rate,
                      tenants=args.tenants)
    max_prompt = max(len(r.prompt) for wl in (storm, bursty)
                     for r in wl.requests)
    members: List[FleetMember] = []
    with LiveControlPlane() as plane:
        client = httpx.Client(timeout=60.0)
        try:
            for _ in range(workers):
                llm = TPULLMEngine({
                    "model": model,
                    "max_batch_size": args.concurrency,
                    "max_seq_len": max_prompt + args.max_tokens + 16,
                    "quantization": args.quantization,
                    "serving": {
                        "queue_limit": max(4096, args.requests * 2),
                        "default_timeout_s": 600.0,
                    },
                })
                llm.load_model()
                m = FleetMember(llm, data_plane=True)
                m.register(client, plane.url)
                members.append(m)

            def routing(**kw: Any) -> None:
                client.put(f"{plane.url}/api/v1/admin/routing",
                           json=kw).raise_for_status()

            def routing_state() -> Dict[str, Any]:
                r = client.get(f"{plane.url}/api/v1/admin/routing")
                r.raise_for_status()
                return r.json()

            def spillover_split(wl: Any,
                                results: List[Dict[str, Any]],
                                ) -> Dict[str, Any]:
                """TTFT split by placement continuity: a turn landing on
                the SAME worker as its conversation's previous turn rides
                the deep local prefix ('sticky'); one landing elsewhere
                ('spillover') starts from whatever that worker holds —
                the requests proactive replication exists to pre-warm."""
                conv_last: Dict[Any, Any] = {}
                sticky: List[float] = []
                spill: List[float] = []
                for req, rec in zip(wl.requests, results):
                    wid = rec.get("worker_id")
                    if wid is None:
                        continue
                    last = conv_last.get(req.conversation)
                    conv_last[req.conversation] = wid
                    t = rec.get("ttft_ms")
                    if last is None or t is None:
                        continue
                    (sticky if wid == last else spill).append(float(t))
                return {
                    "sticky_turns": len(sticky),
                    "spillover_turns": len(spill),
                    "sticky_ttft_ms": percentiles(sticky),
                    "spillover_ttft_ms": percentiles(spill),
                }

            def leg(wl: Any, tag: str) -> Dict[str, Any]:
                for m in members:
                    m.reset_cache()
                results, elapsed = asyncio.run(_drive_fleet(
                    plane.url, members, wl,
                    hb_interval_s=args.fleet_heartbeat_s,
                    trace=tag,   # traces feed the calibration sink
                ))
                out = _fleet_leg_summary(results, elapsed, members)
                out["placement"] = spillover_split(wl, results)
                mig: Dict[str, int] = {}
                for m in members:
                    for k, v in m.migrate_stats().items():
                        mig[k] = mig.get(k, 0) + v
                out["kv_migrate"] = mig
                out["outputs"] = {
                    r["id"]: r.get("text") for r in results
                    if r.get("status") == 200
                }
                if args.timeline:
                    out["timeline"] = _timeline_attribution(results)
                return out

            # compile every graph once before anything is measured
            routing(enabled=True, kv_migrate=True)
            leg(storm, "warm")

            out: Dict[str, Any] = {
                "benchmark": "worker_serving_predictive",
                "path": "control_plane+direct_nearest+kv_export_pull",
                "seed": args.seed, "workers": workers, "model": model,
                "backend": backend, "requests": args.requests,
                "rate": rate, "burst": args.burst,
                "concurrency": args.concurrency,
                "max_tokens": args.max_tokens, "repeats": repeats,
            }

            # -- half 1: cost-model self-calibration x storm ----------------
            routing(calibrate=False, calibrate_reset=True)
            static = leg(storm, "cal-off")
            routing(calibrate=True, calibrate_reset=True)
            err_by_round: List[Optional[float]] = []
            calibrated: Dict[str, Any] = {}
            for i in range(repeats):
                calibrated = leg(storm, f"cal-on-{i}")
                snap = routing_state().get("calibration") or {}
                err_by_round.append(snap.get("predicted_vs_measured"))
            cal_snapshot = routing_state().get("calibration") or {}
            routing(calibrate=False, calibrate_reset=True)
            errs = [e for e in err_by_round if e is not None]
            entry: Dict[str, Any] = {
                "static": static, "calibrated": calibrated,
                "outputs_identical": (static.pop("outputs")
                                      == calibrated.pop("outputs")),
                "predicted_vs_measured_by_round": err_by_round,
                "error_converged": (len(errs) >= 2
                                    and errs[-1] < errs[0]),
                "calibration": cal_snapshot,
            }
            for pct in ("mean", "p50", "p95"):
                c_t = (calibrated["ttft_ms"] or {}).get(pct)
                s_t = (static["ttft_ms"] or {}).get(pct)
                if c_t and s_t:
                    entry[f"ttft_{pct}_calibrated_over_static"] = round(
                        c_t / s_t, 3
                    )
            out["calibration_storm"] = entry

            # -- half 2: proactive replication x bursty ---------------------
            routing(replicate=False)
            reactive = leg(bursty, "rep-off")
            # hints must land within the burst windows: a short cooldown
            # and a 2-hit threshold fit bench-sized traffic
            routing(replicate=True, replicate_hot_threshold=2,
                    replicate_cooldown_s=5.0)
            proactive = leg(bursty, "rep-on")
            rep_snapshot = routing_state().get("replication") or {}
            routing(replicate=False)
            entry = {
                "reactive": reactive, "proactive": proactive,
                "outputs_identical": (reactive.pop("outputs")
                                      == proactive.pop("outputs")),
                "hit_rate_reactive": reactive["prefix_hit_rate"],
                "hit_rate_proactive": proactive["prefix_hit_rate"],
                "replication": rep_snapshot,
            }
            for pct in ("mean", "p50", "p95"):
                p_t = (proactive["ttft_ms"] or {}).get(pct)
                r_t = (reactive["ttft_ms"] or {}).get(pct)
                if p_t and r_t:
                    entry[f"ttft_{pct}_proactive_over_reactive"] = round(
                        p_t / r_t, 3
                    )
            out["replication_bursty"] = entry
            emit(out)
        finally:
            client.close()
            for m in members:
                m.stop()


# ---------------------------------------------------------------------------
# --chaos (round 9): the CLUSTER frontier and the brownout curve. Fleet mode
# gains chaos: LiveFleet (testing/harness.py — N REAL workers behind the
# live control plane) serves the same open-loop Poisson workload at 1/2/4
# replicas for the aggregate frontier, then a seeded kill/restart executes
# MID-WORKLOAD and the brownout leg publishes what the outage actually
# costs: SLO percentiles inside the kill window, goodput (token throughput
# during the window vs calm), and time-to-recover (restart → first request
# served by the rejoined replica). Greedy outputs chaos-on vs chaos-off are
# byte-identical — the failover machinery never changes WHAT is generated,
# only when and where.
# ---------------------------------------------------------------------------


async def _drive_fleet_direct(plane_url: str, prompts: List[str],
                              arrivals: List[float], max_tokens: int,
                              timeline: bool = False,
                              ) -> Tuple[List[Dict[str, Any]], float]:
    """Open-loop direct-path driver that SURVIVES chaos: each request
    discovers its worker per attempt, excludes workers it just watched
    die, and retries until it lands — the client behavior a production
    SDK implements, so brownout numbers measure the fleet, not a fragile
    driver. Records client e2e, engine TTFT, serving worker, and
    completion wall offset for window bucketing."""
    import httpx

    t0 = time.perf_counter()
    async with httpx.AsyncClient(timeout=600.0) as client:

        async def one(i: int, prompt: str, at: float) -> Dict[str, Any]:
            now = time.perf_counter() - t0
            if at > now:
                await asyncio.sleep(at - now)
            rec: Dict[str, Any] = {"i": i, "arrival_s": at, "status": 0}
            trace_id = (f"bench-{uuid.uuid4().hex[:12]}"
                        if timeline else None)
            t_req = time.perf_counter()
            exclude: List[str] = []
            # deadline-based retry: an open-loop client under brownout (or
            # plain oversubscription) keeps retrying — the SLO cost shows
            # up as e2e latency, not as failed requests
            while time.perf_counter() - t_req < 180.0:
                wid = None
                try:
                    query: Dict[str, str] = {}
                    if exclude:
                        query["exclude"] = ",".join(exclude)
                    if trace_id:
                        query["trace_id"] = trace_id
                    d = await client.get(
                        f"{plane_url}/api/v1/jobs/direct/nearest",
                        params=query or None,
                    )
                    if d.status_code != 200:
                        # fleet momentarily dark (sweep lag): back off
                        exclude = []
                        await asyncio.sleep(0.15)
                        continue
                    disc = d.json()
                    wid = disc["worker_id"]
                    params = {"prompt": prompt,
                              "max_new_tokens": max_tokens}
                    if trace_id:
                        params["trace_id"] = trace_id
                    r = await client.post(
                        disc["direct_url"] + "/inference", json={
                            "type": "llm",
                            "params": params,
                        })
                    if r.status_code == 200:
                        res = r.json().get("result") or {}
                        rec.update({
                            "status": 200,
                            "e2e_ms": (time.perf_counter() - t_req) * 1e3,
                            "done_s": time.perf_counter() - t0,
                            "ttft_ms": res.get("ttft_ms"),
                            "worker_id": wid,
                            "text": res.get("text"),
                            "completion_tokens": (res.get("usage") or {})
                            .get("completion_tokens") or 0,
                        })
                        if trace_id:
                            rec["timeline"] = res.get("timeline")
                        return rec
                    if r.status_code == 503:
                        await asyncio.sleep(0.1)   # busy: same worker frees up
                        continue
                    if wid and wid not in exclude:
                        exclude.append(wid)
                except httpx.TransportError:
                    # the worker died on us mid-request: exclude the corpse
                    if wid and wid not in exclude:
                        exclude.append(wid)
                    await asyncio.sleep(0.05)
            rec["status"] = 599
            return rec

        results = list(await asyncio.gather(
            *(one(i, p, a) for i, (p, a) in
              enumerate(zip(prompts, arrivals)))
        ))
    return results, time.perf_counter() - t0


def _fleet_leg(fleet: Any, prompts: List[str], arrivals: List[float],
               max_tokens: int, timeline: bool = False
               ) -> Tuple[List[Dict[str, Any]], float]:
    return asyncio.run(_drive_fleet_direct(
        fleet.url, prompts, arrivals, max_tokens, timeline=timeline
    ))


def _aggregate_summary(results: List[Dict[str, Any]],
                       elapsed: float) -> Dict[str, Any]:
    ok = [r for r in results if r["status"] == 200]
    toks = sum(r.get("completion_tokens") or 0 for r in ok)
    return {
        "ok": len(ok), "failed": len(results) - len(ok),
        "elapsed_s": round(elapsed, 3),
        "aggregate_tokens_per_s": round(toks / elapsed, 2) if elapsed
        else 0.0,
        "ttft_ms": percentiles(
            [r["ttft_ms"] for r in ok if r.get("ttft_ms") is not None]
        ),
        "e2e_ms": percentiles([r["e2e_ms"] for r in ok]),
        "requests_by_worker": {
            w: sum(1 for r in ok if r.get("worker_id") == w)
            for w in {r.get("worker_id") for r in ok if r.get("worker_id")}
        },
    }


def run_chaos_fleet(args: Any, backend: str, model: str) -> None:
    import numpy as _np

    from distributed_gpu_inference_tpu.testing.faults import (
        FleetEvent,
        FleetFaultPlan,
    )
    from distributed_gpu_inference_tpu.testing.harness import LiveFleet

    engine_config = {
        "model": model,
        "max_batch_size": args.concurrency,
        "max_seq_len": args.prompt_len + args.max_tokens + 16,
        "quantization": args.quantization,
        "serving": {
            "queue_limit": max(4096, args.requests * 2),
            "default_timeout_s": 600.0,
        },
    }
    prompts = synth_prompt_strings(args.requests, args.prompt_len,
                                   args.shared_prefix, seed=args.seed)
    rate = float(args.arrival_rate) if args.arrival_rate else 4.0
    gaps = _np.random.default_rng(args.seed).exponential(
        1.0 / rate, len(prompts)
    )
    arrivals = [float(a) for a in _np.cumsum(gaps)]
    span = arrivals[-1]

    out: Dict[str, Any] = {
        "benchmark": "worker_serving_fleet_chaos",
        "path": "control_plane+direct_nearest+live_fleet",
        "model": model, "backend": backend, "seed": args.seed,
        "requests": args.requests, "concurrency": args.concurrency,
        "prompt_len": args.prompt_len, "max_tokens": args.max_tokens,
        "arrival_rate_rps": rate,
    }

    # ---- cluster frontier: the same offered load at 1/2/4 replicas
    frontier = []
    for n in [int(x) for x in str(args.replicas).split(",") if x.strip()]:
        with LiveFleet(n=n, engine_config=engine_config) as fleet:
            _fleet_leg(fleet, prompts, arrivals, args.max_tokens)  # warm
            results, elapsed = _fleet_leg(fleet, prompts, arrivals,
                                          args.max_tokens)
            entry = {"replicas": n, **_aggregate_summary(results, elapsed)}
            frontier.append(entry)
    out["cluster_frontier"] = frontier

    # ---- brownout: seeded kill mid-workload at the chaos replica count
    n = int(args.chaos_replicas)
    t_kill = round(0.30 * span, 3)
    t_restart = round(0.60 * span, 3)
    with LiveFleet(n=n, engine_config=engine_config) as fleet:
        _fleet_leg(fleet, prompts, arrivals, args.max_tokens)      # warm
        calm_results, calm_elapsed = _fleet_leg(
            fleet, prompts, arrivals, args.max_tokens
        )
        calm = _aggregate_summary(calm_results, calm_elapsed)

        plan = FleetFaultPlan(args.seed, n_workers=n, duration_s=span)
        plan.events = [FleetEvent(t_kill, "kill", 0),
                       FleetEvent(t_restart, "restart", 0)]
        fleet.run_chaos(plan)
        try:
            # with --timeline the CHAOS leg is the traced one: per-phase
            # attribution of a brownout window, and the existing
            # chaos-vs-calm byte-identity doubles as recorder-on-vs-off
            chaos_results, chaos_elapsed = _fleet_leg(
                fleet, prompts, arrivals, args.max_tokens,
                timeline=args.timeline,
            )
        finally:
            fleet.wait_chaos()
        chaos = _aggregate_summary(chaos_results, chaos_elapsed)
        if args.timeline:
            chaos["timeline"] = _timeline_attribution(chaos_results)

        # schedule offsets as EXECUTED (the trace is wall-clock-stamped)
        kill_at = next(t for t, k, _ in plan.trace if k == "kill")
        restart_at = next(t for t, k, _ in plan.trace if k == "restart")
        killed_wid = fleet.members[0].worker_id

        ok = [r for r in chaos_results if r["status"] == 200]
        in_window = [r for r in ok
                     if kill_at <= r["arrival_s"] < restart_at]
        # goodput: token throughput the degraded fleet sustained during
        # the kill window, as a fraction of the calm leg's aggregate
        window_tokens = sum(
            r.get("completion_tokens") or 0 for r in ok
            if kill_at <= r.get("done_s", 0.0) < restart_at
        )
        window_s = max(1e-6, restart_at - kill_at)
        calm_tps = calm["aggregate_tokens_per_s"] or 1e-6
        # time-to-recover: restart → the rejoined replica serves again
        recovered = [r["done_s"] for r in ok
                     if r.get("worker_id") == killed_wid
                     and r.get("done_s", 0.0) >= restart_at]
        brownout = {
            "replicas": n,
            "kill_at_s": round(kill_at, 3),
            "restart_at_s": round(restart_at, 3),
            "killed_worker": killed_wid,
            "calm": calm,
            "chaos": chaos,
            "kill_window": {
                "offered": len([r for r in chaos_results
                                if kill_at <= r["arrival_s"] < restart_at]),
                "completed_ok": len(in_window),
                "ttft_ms": percentiles(
                    [r["ttft_ms"] for r in in_window
                     if r.get("ttft_ms") is not None]
                ),
                "e2e_ms": percentiles([r["e2e_ms"] for r in in_window]),
                "goodput_vs_calm": round(
                    (window_tokens / window_s) / calm_tps, 3
                ),
            },
            "time_to_recover_s": round(min(recovered) - restart_at, 3)
            if recovered else None,
        }
        chaos_texts = {r["i"]: r.get("text") for r in chaos_results
                       if r["status"] == 200}
        calm_texts = {r["i"]: r.get("text") for r in calm_results
                      if r["status"] == 200}
        brownout["outputs_identical"] = (
            len(chaos_texts) == len(calm_texts) == len(prompts)
            and chaos_texts == calm_texts
        )
        out["brownout"] = brownout
        out["chaos_trace"] = [list(t) for t in plan.trace]
    emit(out)


# ---------------------------------------------------------------------------
# --gray (round 18): what the gray-failure defenses buy. One replica of a
# 3-worker LiveFleet DEGRADES (alive, heartbeating, 0.3s/request slow) for
# the whole measured window while a mixed workload runs — half the requests
# carry deadline_s, half don't. Leg OFF is the round-17 build (health
# scoring disabled, no hedging); leg ON enables quarantine + hedge hints
# and the driver races deadline-carrying requests exactly like the SDK
# (fire primary, wait the plane's p95-derived delay, fire the hedge, first
# winner cancels the loser). Published: deadline-carrying p99 ON vs OFF,
# hedges fired/won, abandonment counts split by deadline-ness (the
# deadline-LESS count must be zero — abandonment is armed in both legs),
# and byte-identity of greedy outputs across legs.
# ---------------------------------------------------------------------------


async def _drive_gray(plane_url: str, prompts: List[str],
                      arrivals: List[float], max_tokens: int,
                      deadlines: List[Optional[float]], hedging: bool,
                      ) -> Tuple[List[Dict[str, Any]], float]:
    """Open-loop direct driver for the gray legs: per-request deadline_s
    rides the params, and (hedging=True) deadline-carrying requests opt
    into the plane's hedge hint and race two legs."""
    import httpx

    t0 = time.perf_counter()
    tidy: List[Any] = []   # loser-drain tasks; awaited before client close
    async with httpx.AsyncClient(timeout=600.0) as client:

        async def post_leg(url: str, params: Dict[str, Any],
                           key: str) -> Optional[Any]:
            try:
                return await client.post(url + "/inference", json={
                    "type": "llm",
                    "params": {**params, "hedge_key": key},
                })
            except httpx.TransportError:
                return None

        async def drain_loser(task: Any, url: str, key: str) -> None:
            # cancel releases the loser at the next step boundary; then
            # let its POST finish so nothing outlives the client
            try:
                await client.post(url + "/inference/cancel",
                                  json={"hedge_key": key})
            except httpx.TransportError:
                pass
            try:
                await asyncio.wait_for(task, timeout=30.0)
            except Exception:
                pass

        async def race(disc: Dict[str, Any], params: Dict[str, Any]
                       ) -> Tuple[Optional[Any], bool, bool, str]:
            """(response, hedge_fired, hedge_won, serving_worker)."""
            hint = disc["hedge"]
            kp, kh = uuid.uuid4().hex, uuid.uuid4().hex
            p_task = asyncio.create_task(
                post_leg(disc["direct_url"], params, kp))
            delay_s = max(0.0, float(hint.get("delay_ms") or 0.0)) / 1e3
            done, _ = await asyncio.wait({p_task}, timeout=delay_s)
            if p_task in done:
                return p_task.result(), False, False, disc["worker_id"]
            h_task = asyncio.create_task(
                post_leg(hint["direct_url"], params, kh))
            meta = {p_task: (disc["direct_url"], kp, disc["worker_id"]),
                    h_task: (hint["direct_url"], kh, hint["worker_id"])}
            pending = set(meta)
            while pending:
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                for t in done:
                    r = t.result()
                    if r is not None and r.status_code == 200:
                        for o in pending:
                            ourl, okey, _ = meta[o]
                            tidy.append(asyncio.create_task(
                                drain_loser(o, ourl, okey)))
                        return r, True, t is h_task, meta[t][2]
            # both legs failed: surface the primary's answer (may be None)
            return p_task.result(), True, False, disc["worker_id"]

        async def one(i: int, prompt: str, at: float) -> Dict[str, Any]:
            now = time.perf_counter() - t0
            if at > now:
                await asyncio.sleep(at - now)
            rec: Dict[str, Any] = {
                "i": i, "arrival_s": at, "status": 0,
                "deadline_s": deadlines[i],
                "hedged": False, "hedge_won": False, "abandoned": False,
            }
            params: Dict[str, Any] = {"prompt": prompt,
                                      "max_new_tokens": max_tokens}
            if deadlines[i] is not None:
                params["deadline_s"] = deadlines[i]
            t_req = time.perf_counter()
            exclude: List[str] = []
            while time.perf_counter() - t_req < 180.0:
                wid = None
                try:
                    query: Dict[str, str] = {}
                    if exclude:
                        query["exclude"] = ",".join(exclude)
                    if hedging and deadlines[i] is not None:
                        query["hedge"] = "1"
                    d = await client.get(
                        f"{plane_url}/api/v1/jobs/direct/nearest",
                        params=query or None,
                    )
                    if d.status_code != 200:
                        exclude = []
                        await asyncio.sleep(0.15)
                        continue
                    disc = d.json()
                    wid = disc["worker_id"]
                    if disc.get("hedge", {}).get("direct_url"):
                        r, fired, won, wid = await race(disc, params)
                        rec["hedged"] = rec["hedged"] or fired
                        rec["hedge_won"] = rec["hedge_won"] or won
                    else:
                        r = await client.post(
                            disc["direct_url"] + "/inference", json={
                                "type": "llm", "params": params,
                            })
                    if r is None:
                        if wid and wid not in exclude:
                            exclude.append(wid)
                        await asyncio.sleep(0.05)
                        continue
                    if r.status_code == 200:
                        res = r.json().get("result") or {}
                        rec.update({
                            "status": 200,
                            "e2e_ms": (time.perf_counter() - t_req) * 1e3,
                            "done_s": time.perf_counter() - t0,
                            "ttft_ms": res.get("ttft_ms"),
                            "worker_id": wid,
                            "text": res.get("text"),
                            "completion_tokens": (res.get("usage") or {})
                            .get("completion_tokens") or 0,
                        })
                        return rec
                    if r.status_code == 503:
                        await asyncio.sleep(0.1)
                        continue
                    detail = ""
                    try:
                        detail = str((r.json() or {}).get("detail") or "")
                    except ValueError:
                        pass
                    if "deadline exceeded" in detail:
                        # typed abandonment: hopeless by projection —
                        # retrying is exactly the waste the scan prevents
                        rec.update({"status": r.status_code,
                                    "abandoned": True, "error": detail})
                        return rec
                    if wid and wid not in exclude:
                        exclude.append(wid)
                except httpx.TransportError:
                    if wid and wid not in exclude:
                        exclude.append(wid)
                    await asyncio.sleep(0.05)
            rec["status"] = 599
            return rec

        results = list(await asyncio.gather(
            *(one(i, p, a) for i, (p, a) in
              enumerate(zip(prompts, arrivals)))
        ))
        if tidy:
            await asyncio.gather(*tidy, return_exceptions=True)
    return results, time.perf_counter() - t0


def _gray_subset(results: List[Dict[str, Any]],
                 with_deadline: bool) -> Dict[str, Any]:
    sub = [r for r in results
           if (r["deadline_s"] is not None) == with_deadline]
    ok = [r for r in sub if r["status"] == 200]
    return {
        "requests": len(sub), "ok": len(ok),
        "failed": len(sub) - len(ok),
        "abandoned": sum(1 for r in sub if r.get("abandoned")),
        "e2e_ms": percentiles([r["e2e_ms"] for r in ok]),
        "ttft_ms": percentiles(
            [r["ttft_ms"] for r in ok if r.get("ttft_ms") is not None]),
    }


def run_gray(args: Any, backend: str, model: str) -> None:
    import httpx
    import numpy as _np

    from distributed_gpu_inference_tpu.testing.faults import (
        FleetEvent,
        FleetFaultPlan,
        GRAY_CHAOS_KINDS,
    )
    from distributed_gpu_inference_tpu.testing.harness import LiveFleet

    engine_config = {
        "model": model,
        "max_batch_size": args.concurrency,
        "max_seq_len": args.prompt_len + args.max_tokens + 16,
        "quantization": args.quantization,
        "serving": {
            "queue_limit": max(4096, args.requests * 2),
            "default_timeout_s": 600.0,
            # armed in BOTH legs: the deadline-LESS abandonment count
            # must stay zero with the scan live, not with it off
            "abandon_deadlines": True,
            "deadline_grace_s": 0.5,
        },
    }
    prompts = synth_prompt_strings(args.requests, args.prompt_len,
                                   args.shared_prefix, seed=args.seed)
    rate = float(args.arrival_rate) if args.arrival_rate else 4.0
    gaps = _np.random.default_rng(args.seed).exponential(
        1.0 / rate, len(prompts))
    arrivals = [float(a) for a in _np.cumsum(gaps)]
    span = arrivals[-1]
    # every other request carries a generous deadline: eligible for
    # hedging, not in actual abandonment danger — so greedy outputs stay
    # comparable across legs
    deadlines: List[Optional[float]] = [
        float(args.gray_deadline_s) if i % 2 == 0 else None
        for i in range(len(prompts))
    ]

    def scrape(url: str, name: str) -> List[str]:
        body = httpx.get(f"{url}/metrics", timeout=10.0).text
        return [ln for ln in body.splitlines()
                if ln.startswith(name) and not ln.startswith("#")]

    def leg(defenses_on: bool) -> Dict[str, Any]:
        with LiveFleet(n=3, engine_config=engine_config) as fleet:
            if defenses_on:
                r = httpx.put(
                    f"{fleet.url}/api/v1/admin/health", json={
                        "enabled": True, "hedge": True,
                        "window_s": 30.0, "min_samples": 4,
                        "min_peers": 2, "suspect_ratio": 3.0,
                        "clear_ratio": 1.5, "grace_s": 0.2,
                        "probation_after_s": 300.0, "canary_budget": 2,
                    }, timeout=10.0)
                r.raise_for_status()
            # warm every engine calm (JIT compile must not eat the
            # degrade window) — also seeds the fast fleet baseline
            asyncio.run(_drive_gray(
                fleet.url, prompts, arrivals, args.max_tokens,
                [None] * len(prompts), hedging=False))
            plan = FleetFaultPlan(args.seed, n_workers=3,
                                  duration_s=span + 4.0,
                                  kinds=GRAY_CHAOS_KINDS)
            plan.events = [FleetEvent(0.0, "degrade", 0,
                                      duration_s=span + 3.0,
                                      delay_s=float(args.gray_degrade_s))]
            fleet.run_chaos(plan)
            try:
                results, elapsed = asyncio.run(_drive_gray(
                    fleet.url, prompts, arrivals, args.max_tokens,
                    deadlines, hedging=defenses_on))
            finally:
                fleet.wait_chaos()
            degraded_wid = fleet.members[0].worker_id
            ok = [r for r in results if r["status"] == 200]
            entry = {
                "defenses": "on" if defenses_on else "off",
                "elapsed_s": round(elapsed, 3),
                "degraded_worker": degraded_wid,
                "requests_on_degraded": sum(
                    1 for r in ok if r.get("worker_id") == degraded_wid),
                "with_deadline": _gray_subset(results, True),
                "deadline_less": _gray_subset(results, False),
                "hedges": {
                    "fired": sum(1 for r in results if r["hedged"]),
                    "won": sum(1 for r in results if r["hedge_won"]),
                },
                "health_metrics": {
                    "worker_health_state":
                        scrape(fleet.url, "worker_health_state"),
                    "health_transitions_total":
                        scrape(fleet.url, "health_transitions_total"),
                    "hedges_total": scrape(fleet.url, "hedges_total"),
                    "jobs_abandoned_total":
                        scrape(fleet.url, "jobs_abandoned_total"),
                },
            }
            texts = {r["i"]: r.get("text") for r in ok}
            return entry, texts

    out: Dict[str, Any] = {
        "benchmark": "worker_serving_gray",
        "path": "control_plane+direct_nearest+live_fleet+degrade",
        "model": model, "backend": backend, "seed": args.seed,
        "requests": args.requests, "concurrency": args.concurrency,
        "prompt_len": args.prompt_len, "max_tokens": args.max_tokens,
        "arrival_rate_rps": rate,
        "deadline_s": float(args.gray_deadline_s),
        "degrade_delay_s": float(args.gray_degrade_s),
    }
    off, off_texts = leg(False)
    on, on_texts = leg(True)
    p99_off = off["with_deadline"]["e2e_ms"]["p99"]
    p99_on = on["with_deadline"]["e2e_ms"]["p99"]
    out["gray"] = {
        "off": off, "on": on,
        "deadline_p99_ms_off": p99_off,
        "deadline_p99_ms_on": p99_on,
        "deadline_p99_improvement": round(p99_off / p99_on, 3)
        if p99_off and p99_on else None,
        "deadline_less_abandoned": (
            off["deadline_less"]["abandoned"]
            + on["deadline_less"]["abandoned"]
        ),
        "outputs_identical": (
            len(off_texts) == len(on_texts) == len(prompts)
            and off_texts == on_texts
        ),
    }
    emit(out)


# ---------------------------------------------------------------------------
# --io-chaos (round 19): what the per-tier IO breakers buy under a spill-
# tier brownout. A spill-tiered 2-replica LiveFleet (L2 host blocks + the
# in-process L3) serves the same open-loop workload three times: calm,
# then under a composed io_slow+io_error storm (every spill op pays a
# browning-out device's latency AND fails probabilistically) with the
# breakers armed (default), then the identical storm with
# DGI_IO_BREAKER_DISABLE=1 — the pre-round-19 behavior where every
# admission keeps paying the dying tier's latency for the whole window.
# Published: TTFT/e2e percentiles per leg, the ON/OFF latency ratios, the
# per-tier error/skip counters, and byte-identity of greedy outputs
# across all three legs — the spill tiers are an optimization, and
# fencing them off must never change WHAT is generated.
# ---------------------------------------------------------------------------


def run_io_chaos(args: Any, backend: str, model: str) -> None:
    import numpy as _np

    from distributed_gpu_inference_tpu.testing.faults import (
        FleetEvent,
        FleetFaultPlan,
        IO_CHAOS_SUITE_KINDS,
    )
    from distributed_gpu_inference_tpu.testing.harness import LiveFleet

    engine_config = {
        "model": model,
        "max_batch_size": args.concurrency,
        "max_seq_len": args.prompt_len + args.max_tokens + 16,
        "quantization": args.quantization,
        # the durable surfaces under test: a host spill tier + the
        # in-process remote tier, spill-on-evict implied. The device pool
        # is pinned SMALL (the default sizing rule would fit the whole
        # working set and spill only at the leg's tail) so evictions —
        # and therefore spill-tier IO — run continuously through the
        # storm window instead of clustering after it
        "num_blocks": 64,
        "kv_spill_host_blocks": 64,
        "kv_remote_url": "memory://",
        "serving": {
            "queue_limit": max(4096, args.requests * 2),
            "default_timeout_s": 600.0,
        },
    }
    # spill churn is the point of this leg: with the global default
    # --shared-prefix 64 and a 64-token prompt every request is the SAME
    # prompt — one cached prefix, zero evictions, a storm with nothing
    # to hit. Cap the shared prefix so suffixes stay distinct and the
    # working set actually cycles through the spill tiers.
    shared = min(args.shared_prefix, args.prompt_len // 4)
    prompts = synth_prompt_strings(args.requests, args.prompt_len,
                                   shared, seed=args.seed)
    # warm prompts are a DIFFERENT draw: warming compiles the graphs
    # without pre-filling the L1 prefix cache for the measured set, so
    # measured admissions actually probe the spill tiers
    warm_prompts = synth_prompt_strings(args.requests, args.prompt_len,
                                        shared, seed=args.seed + 1)
    rate = float(args.arrival_rate) if args.arrival_rate else 4.0
    gaps = _np.random.default_rng(args.seed).exponential(
        1.0 / rate, len(prompts))
    arrivals = [float(a) for a in _np.cumsum(gaps)]
    span = arrivals[-1]

    def spill_stats(fleet: Any) -> Dict[str, int]:
        agg: Dict[str, int] = {}
        for m in fleet.members:
            mgr = m.llm.engine.manager
            for k, v in mgr.spill_wire_stats().items():
                if k.endswith("_state"):
                    agg[k] = max(agg.get(k, 0), int(v))
                else:
                    agg[k] = agg.get(k, 0) + int(v)
        return agg

    def leg(storm: bool, breakers_on: bool) -> Dict[str, Any]:
        old = os.environ.get("DGI_IO_BREAKER_DISABLE")
        if not breakers_on:
            os.environ["DGI_IO_BREAKER_DISABLE"] = "1"
        try:
            with LiveFleet(n=2, engine_config=engine_config) as fleet:
                _fleet_leg(fleet, warm_prompts, arrivals,
                           args.max_tokens)               # compile warm
                if storm:
                    plan = FleetFaultPlan(
                        args.seed, n_workers=2, duration_s=span + 4.0,
                        kinds=IO_CHAOS_SUITE_KINDS)
                    # the browning-out device: spill ops fail at prob and
                    # the survivors pay the delay — the composed storm a
                    # dying disk/NIC actually produces. ORDER MATTERS:
                    # rule matching is first-match with prob-miss
                    # fallthrough, so io_error must arm FIRST — armed
                    # after the always-firing delay rule it would be
                    # shadowed and never raise
                    plan.events = [
                        FleetEvent(0.0, "io_error", -1,
                                   duration_s=span + 3.0,
                                   prob=float(args.io_error_prob)),
                        FleetEvent(0.0, "io_slow", -1,
                                   duration_s=span + 3.0,
                                   delay_s=float(args.io_delay_s)),
                    ]
                    fleet.run_chaos(plan)
                try:
                    results, elapsed = _fleet_leg(
                        fleet, prompts, arrivals, args.max_tokens)
                finally:
                    if storm:
                        fleet.wait_chaos()
                entry = _aggregate_summary(results, elapsed)
                entry["spill_io"] = spill_stats(fleet)
                texts = {r["i"]: r.get("text") for r in results
                         if r["status"] == 200}
                return entry, texts
        finally:
            if old is None:
                os.environ.pop("DGI_IO_BREAKER_DISABLE", None)
            else:
                os.environ["DGI_IO_BREAKER_DISABLE"] = old

    out: Dict[str, Any] = {
        "benchmark": "worker_serving_io_chaos",
        "path": "control_plane+direct_nearest+spill_tiers+io_storm",
        "model": model, "backend": backend, "seed": args.seed,
        "requests": args.requests, "concurrency": args.concurrency,
        "prompt_len": args.prompt_len, "max_tokens": args.max_tokens,
        "arrival_rate_rps": rate,
        "io_delay_s": float(args.io_delay_s),
        "io_error_prob": float(args.io_error_prob),
    }
    calm, calm_texts = leg(storm=False, breakers_on=True)
    on, on_texts = leg(storm=True, breakers_on=True)
    off, off_texts = leg(storm=True, breakers_on=False)
    ratios: Dict[str, Any] = {}
    for pct in ("p50", "p95"):
        o, f = (on["e2e_ms"] or {}).get(pct), (off["e2e_ms"] or {}).get(pct)
        if o and f:
            ratios[f"e2e_{pct}_on_over_off"] = round(o / f, 3)
        ot = (on["ttft_ms"] or {}).get(pct)
        ft = (off["ttft_ms"] or {}).get(pct)
        if ot and ft:
            ratios[f"ttft_{pct}_on_over_off"] = round(ot / ft, 3)
    out["io_chaos"] = {
        "calm": calm,
        "brownout_breakers_on": on,
        "brownout_breakers_off": off,
        "breakers_on_vs_off": ratios,
        "outputs_identical": (
            len(calm_texts) == len(on_texts) == len(off_texts)
            == len(prompts)
            and calm_texts == on_texts == off_texts
        ),
    }
    emit(out)


# ---------------------------------------------------------------------------
# --pd-split (round 11): the PD frontier. A LiveFleet split into a prefill
# fleet and a decode fleet (role-tagged registrations, every member running
# a real /kv/transfer data plane) serves pd-disaggregated jobs through the
# control plane — placement over roles, pinned stage children, streamed KV
# handoff, adopt_slot decode — against a DATA-PARALLEL baseline at EQUAL
# worker count (same engines, no roles, plain jobs). Then the handoff-
# brownout leg: a handoff partition window plus a seeded kill/restart of
# the prefill side mid-workload, publishing SLO-in-window, the re-prefill
# count, and time-to-recover. Greedy outputs are asserted byte-identical
# PD vs data-parallel and brownout vs calm — disaggregation and its
# recovery machinery never change WHAT is generated.
# ---------------------------------------------------------------------------


async def _drive_queued_jobs(plane_url: str, prompts: List[str],
                             arrivals: List[float], max_tokens: int,
                             pd: bool, timeline: bool = False,
                             ) -> Tuple[List[Dict[str, Any]], float]:
    """Open-loop queued-job driver (the PD path runs through /jobs, not
    the direct servers): submit at the arrival instant — riding out
    placement-capacity 503s/429s with the server's retry hint — then
    poll to completion. Records client e2e, engine ttft, completion wall
    offset, and the serving workers."""
    import httpx

    t0 = time.perf_counter()
    async with httpx.AsyncClient(timeout=600.0) as client:

        async def one(i: int, prompt: str, at: float) -> Dict[str, Any]:
            now = time.perf_counter() - t0
            if at > now:
                await asyncio.sleep(at - now)
            rec: Dict[str, Any] = {"i": i, "arrival_s": at, "status": 0}
            t_req = time.perf_counter()
            params: Dict[str, Any] = {
                "prompt": prompt, "max_tokens": max_tokens,
                "temperature": 0,
            }
            if pd:
                params["pd_disaggregated"] = True
            if timeline:
                params["trace_id"] = f"bench-{uuid.uuid4().hex[:12]}"
            job_id = None
            while time.perf_counter() - t_req < 180.0:
                try:
                    r = await client.post(
                        f"{plane_url}/api/v1/jobs",
                        json={"type": "llm", "params": params},
                    )
                except httpx.TransportError:
                    await asyncio.sleep(0.1)
                    continue
                if r.status_code == 201:
                    job_id = r.json()["job_id"]
                    break
                if r.status_code in (429, 503):
                    hint = 0.2
                    try:
                        hint = float(r.json().get("retry_after_s") or 0.2)
                    except (ValueError, KeyError):
                        pass
                    await asyncio.sleep(min(hint, 1.0))
                    continue
                rec["status"] = r.status_code
                return rec
            if job_id is None:
                rec["status"] = 599
                return rec
            while time.perf_counter() - t_req < 180.0:
                try:
                    j = (await client.get(
                        f"{plane_url}/api/v1/jobs/{job_id}"
                    )).json()
                except (httpx.TransportError, ValueError):
                    await asyncio.sleep(0.1)
                    continue
                if j.get("status") in ("completed", "failed", "cancelled"):
                    res = j.get("result") or {}
                    rec.update({
                        "status": 200 if j["status"] == "completed"
                        else 500,
                        "e2e_ms": (time.perf_counter() - t_req) * 1e3,
                        "done_s": time.perf_counter() - t0,
                        "ttft_ms": res.get("ttft_ms"),
                        "text": res.get("text"),
                        "prefill_worker": res.get("prefill_worker"),
                        "decode_worker": res.get("decode_worker"),
                        "migration_bytes": res.get("migration_bytes"),
                        "completion_tokens": (res.get("usage") or {})
                        .get("completion_tokens")
                        or res.get("completion_tokens") or 0,
                    })
                    if timeline:
                        # the plane merged server + both workers' events:
                        # read the derived phases off the debug endpoint.
                        # The recorder is eventually consistent BY DESIGN
                        # (job status commits before the flight fan-in so
                        # the recorder can never delay a completion) — a
                        # read racing the fan-in sees a pre-merge snapshot
                        # without worker events, so retry briefly until
                        # ``server.completed`` has landed
                        try:
                            for _ in range(40):
                                tr = await client.get(
                                    f"{plane_url}/api/v1/debug/requests/"
                                    f"{job_id}/timeline"
                                )
                                if tr.status_code != 200:
                                    break
                                tj = tr.json()
                                rec["phases"] = tj.get("phases")
                                rec["_timeline_detail"] = tj
                                evs = tj.get("events") or []
                                # complete ⇔ the LAST merged event is the
                                # completion note (a PD trace already holds
                                # the prefill child's server.completed while
                                # the decode fan-in is still in flight)
                                if evs and evs[-1].get("event") == \
                                        "server.completed":
                                    break
                                await asyncio.sleep(0.025)
                        except (httpx.TransportError, ValueError):
                            pass
                    return rec
                await asyncio.sleep(0.05)
            rec["status"] = 599
            return rec

        results = list(await asyncio.gather(
            *(one(i, p, a) for i, (p, a) in
              enumerate(zip(prompts, arrivals)))
        ))
    return results, time.perf_counter() - t0


def run_pd_split(args: Any, backend: str, model: str) -> None:
    import numpy as _np

    from distributed_gpu_inference_tpu.testing.faults import (
        FleetEvent,
        FleetFaultPlan,
    )
    from distributed_gpu_inference_tpu.testing.harness import LiveFleet

    try:
        n_prefill, n_decode = (int(x) for x in args.pd_split.split(":"))
    except ValueError:
        raise SystemExit("--pd-split takes P:D, e.g. 1:2")
    n = n_prefill + n_decode
    roles = ["prefill"] * n_prefill + ["decode"] * n_decode
    engine_config = {
        "model": model,
        "max_batch_size": args.concurrency,
        "max_seq_len": args.prompt_len + args.max_tokens + 16,
        "quantization": args.quantization,
        "pd_slot_ttl_s": 10.0,
        "serving": {
            "queue_limit": max(4096, args.requests * 2),
            "default_timeout_s": 600.0,
        },
    }
    prompts = synth_prompt_strings(args.requests, args.prompt_len,
                                   args.shared_prefix, seed=args.seed)
    rate = float(args.arrival_rate) if args.arrival_rate else 3.0
    gaps = _np.random.default_rng(args.seed).exponential(
        1.0 / rate, len(prompts)
    )
    arrivals = [float(a) for a in _np.cumsum(gaps)]
    span = arrivals[-1]

    def leg(fleet: Any, pd: bool, timeline: bool = False
            ) -> Tuple[List[Dict[str, Any]], float]:
        return asyncio.run(_drive_queued_jobs(
            fleet.url, prompts, arrivals, args.max_tokens, pd,
            timeline=timeline,
        ))

    out: Dict[str, Any] = {
        "benchmark": "worker_serving_pd_split",
        "path": "control_plane+pd_flow+streamed_handoff+adopt_slot",
        "model": model, "backend": backend, "seed": args.seed,
        "requests": args.requests, "concurrency": args.concurrency,
        "prompt_len": args.prompt_len, "max_tokens": args.max_tokens,
        "arrival_rate_rps": rate,
        "pd_split": f"{n_prefill}:{n_decode}", "workers": n,
    }

    # ---- PD leg + brownout on ONE fleet (warm once, reuse engines)
    with LiveFleet(n=n, roles=roles, pd_data_plane=True,
                   engine_config=engine_config) as fleet:
        sched = fleet.plane.state.pd_flow.scheduler
        # warm compiles first: cold-compile stalls can back up the PD
        # prefill slots (bounded by pd_slot_ttl_s) and fail requests,
        # which would poison a byte-identity comparator — so with
        # --timeline the recorder-OFF leg is a SEPARATE replay on the
        # warmed fleet. The plane mints a trace_id for every queued job
        # (always-on histograms), so "OFF" must be the process-wide kill
        # switch: the whole fleet runs in this process, and DGI_FLIGHT=0
        # darkens worker timelines AND the plane's recorder for the leg
        leg(fleet, pd=True)
        warm_results: List[Dict[str, Any]] = []
        if args.timeline:
            prev_flight = os.environ.get("DGI_FLIGHT")
            os.environ["DGI_FLIGHT"] = "0"
            try:
                warm_results, _ = leg(fleet, pd=True)
            finally:
                if prev_flight is None:
                    os.environ.pop("DGI_FLIGHT", None)
                else:
                    os.environ["DGI_FLIGHT"] = prev_flight
        # scheduler counters are cumulative across legs on the shared
        # fleet: every published stat is a per-leg DELTA
        affinity_before = sched.stats["affinity_hits"]
        pd_results, pd_elapsed = leg(fleet, pd=True,
                                     timeline=args.timeline)
        pd_summary = _aggregate_summary(pd_results, pd_elapsed)
        if args.timeline:
            # per-phase attribution for the PD leg (merged server + both
            # workers' events via the plane's debug endpoint) + the
            # recorder-on-vs-off byte-identity check against the untraced
            # warm leg's outputs
            attr = _timeline_attribution(pd_results)
            on_t = {r["i"]: r.get("text") for r in pd_results
                    if r["status"] == 200}
            off_t = {r["i"]: r.get("text") for r in warm_results
                     if r["status"] == 200}
            attr["outputs_identical_recorder_on_vs_off"] = (
                len(on_t) == len(off_t) == len(prompts) and on_t == off_t
            )
            if not attr["outputs_identical_recorder_on_vs_off"]:
                # name the divergent requests so a failed identity check
                # is attributable, not just a boolean
                attr["identity_mismatch"] = {
                    "on_ok": len(on_t), "off_ok": len(off_t),
                    "requests": sorted(
                        i for i in set(on_t) | set(off_t)
                        if on_t.get(i) != off_t.get(i)
                    )[:8],
                }
            # acceptance evidence: one merged PD timeline — causally
            # ordered, spanning server + prefill worker + decode worker,
            # with handoff begin/commit observed on BOTH sides
            detail = next((r.get("_timeline_detail") for r in pd_results
                           if r.get("_timeline_detail")), None)
            if detail:
                evs = detail.get("events") or []
                names = [e["event"] for e in evs]
                ts = [e["ts"] for e in evs]
                attr["example"] = {
                    "trace_id": detail.get("trace_id"),
                    "sources": detail.get("sources"),
                    "events": names,
                    "monotonic": ts == sorted(ts),
                    "handoff_events_both_workers": (
                        any(n in ("handoff.begin", "handoff.commit",
                                  "handoff.local") for n in names)
                        and any(n.startswith("handoff.rx_")
                                for n in names)
                    ) or any(n == "handoff.local" for n in names),
                }
            for r in pd_results:
                r.pop("_timeline_detail", None)
            out["timeline"] = attr
        pd_summary["handoff_bytes"] = sum(
            r.get("migration_bytes") or 0 for r in pd_results
        )
        pd_summary["affinity_hits"] = (
            sched.stats["affinity_hits"] - affinity_before
        )
        out["pd"] = pd_summary

        # ---- handoff brownout: partition the prefill side's pushes,
        # then kill/restart the prefill worker mid-workload
        flow = fleet.plane.state.pd_flow
        reprefills_before = flow.stats["reprefills"]
        rebalanced_before = sched.stats["role_rebalanced_prefill"]
        t_part = round(0.10 * span, 3)
        t_kill = round(0.35 * span, 3)
        t_restart = round(0.60 * span, 3)
        plan = FleetFaultPlan(args.seed, n_workers=n, duration_s=span,
                              kinds=("kill", "handoff_partition"))
        plan.events = [
            FleetEvent(t_part, "handoff_partition", 0,
                       duration_s=round(0.12 * span, 3)),
            FleetEvent(t_kill, "kill", 0),
            FleetEvent(t_restart, "restart", 0),
        ]
        fleet.run_chaos(plan)
        try:
            b_results, b_elapsed = leg(fleet, pd=True)
        finally:
            fleet.wait_chaos()
        for m in fleet.members:
            if not m.alive:
                m.start()
        brown = _aggregate_summary(b_results, b_elapsed)
        kill_at = next(t for t, k, _ in plan.trace if k == "kill")
        restart_at = next(t for t, k, _ in plan.trace if k == "restart")
        ok = [r for r in b_results if r["status"] == 200]
        in_window = [r for r in ok
                     if t_part <= r["arrival_s"] < restart_at]
        killed_wid = fleet.members[0].worker_id
        recovered = [r["done_s"] for r in ok
                     if r.get("prefill_worker") == killed_wid
                     and r.get("done_s", 0.0) >= restart_at]
        out["handoff_brownout"] = {
            "partition_at_s": t_part,
            "kill_at_s": round(kill_at, 3),
            "restart_at_s": round(restart_at, 3),
            "killed_prefill_worker": killed_wid,
            "summary": brown,
            "window": {
                "offered": len([r for r in b_results
                                if t_part <= r["arrival_s"] < restart_at]),
                "completed_ok": len(in_window),
                "ttft_ms": percentiles(
                    [r["ttft_ms"] for r in in_window
                     if r.get("ttft_ms") is not None]
                ),
                "e2e_ms": percentiles([r["e2e_ms"] for r in in_window]),
            },
            "reprefills": flow.stats["reprefills"] - reprefills_before,
            "role_rebalanced_prefill":
                sched.stats["role_rebalanced_prefill"] - rebalanced_before,
            "time_to_recover_s": round(min(recovered) - restart_at, 3)
            if recovered else None,
            "outputs_identical_vs_calm_pd": (
                {r["i"]: r.get("text") for r in ok}
                == {r["i"]: r.get("text") for r in pd_results
                    if r["status"] == 200}
                and len(ok) == len(prompts)
            ),
        }
        out["chaos_trace"] = [list(t) for t in plan.trace]

    # ---- data-parallel baseline at EQUAL worker count
    with LiveFleet(n=n, engine_config=engine_config) as fleet:
        leg(fleet, pd=False)                              # warm compiles
        dp_results, dp_elapsed = leg(fleet, pd=False)
    out["data_parallel"] = _aggregate_summary(dp_results, dp_elapsed)
    pd_texts = {r["i"]: r.get("text") for r in pd_results
                if r["status"] == 200}
    dp_texts = {r["i"]: r.get("text") for r in dp_results
                if r["status"] == 200}
    # completeness guard: equal PARTIAL dicts (both legs failing the same
    # requests) must not report a vacuous identity
    out["outputs_identical_pd_vs_dp"] = (
        pd_texts == dp_texts
        and len(pd_texts) == len(dp_texts) == len(prompts)
    )
    ratios: Dict[str, Any] = {}
    for pct in ("p50", "p95"):
        a = (pd_summary["ttft_ms"] or {}).get(pct)
        b = (out["data_parallel"]["ttft_ms"] or {}).get(pct)
        if a and b:
            ratios[f"ttft_{pct}_pd_over_dp"] = round(a / b, 3)
        a = (pd_summary["e2e_ms"] or {}).get(pct)
        b = (out["data_parallel"]["e2e_ms"] or {}).get(pct)
        if a and b:
            ratios[f"e2e_{pct}_pd_over_dp"] = round(a / b, 3)
    if out["data_parallel"]["aggregate_tokens_per_s"]:
        ratios["tokens_per_s_pd_over_dp"] = round(
            pd_summary["aggregate_tokens_per_s"]
            / out["data_parallel"]["aggregate_tokens_per_s"], 3
        )
    out["pd_vs_dp"] = ratios
    emit(out)


# ---------------------------------------------------------------------------
# --overload (round 12): the brownout ladder, measured. A LiveFleet serves
# steady PAID traffic while a 10x free-tier burst (the workloads.py bursty
# class, all-free) slams the plane. Three legs:
#   paid_baseline  — paid traffic alone, ladder ON (the SLO reference)
#   ladder_on      — paid + 10x free burst, admission ladder ON: free is
#                    clamped/shed (counted per tier), paid holds its SLO
#   ladder_off     — same composed load, admission OFF: the blanket
#                    backpressure 429s blindly — paid sheds too (the
#                    before picture the ladder exists to fix)
# plus an AUTOSCALER leg: a replica is killed mid-span (seeded
# FleetFaultPlan), the brownout-driven autoscaler restores capacity off
# the measured SLO window, and the leg reports the measured cold-start
# lead time and time-to-recover.
# ---------------------------------------------------------------------------


def _tiered_trace(seed: int, paid_n: int, free_n: int, rate: float,
                  max_tokens: int) -> List[Dict[str, Any]]:
    """Merged open-loop trace: steady paid rag traffic + the bursty class
    at 10x the paid rate, forced all-free (the misbehaving-tenant burst).
    Returns arrival-sorted dicts {at, tenant, tier, prompt, max_tokens}."""
    from benchmarks.workloads import generate

    paid = generate("rag", seed, requests=paid_n, rate=rate,
                    tenants=2, doc_len=96, query_len=24,
                    max_tokens=max_tokens)
    burst = generate("bursty", seed + 1, requests=free_n,
                     rate=rate * 10.0, tenants=3, system_len=64,
                     turn_len=16, max_tokens=max_tokens)
    span = max((r.arrival_s for r in paid.requests), default=1.0)
    out = []
    for r in paid.requests:
        out.append({"at": r.arrival_s, "tenant": f"paid-{r.tenant}",
                    "tier": "paid", "prompt": r.prompt,
                    "max_tokens": r.max_tokens})
    b_span = max((x.arrival_s for x in burst.requests), default=1.0)
    for r in burst.requests:
        # compress the burst into the middle 60% of the paid span so the
        # overload WINDOW is surrounded by calm paid-only traffic
        at = span * 0.2 + (r.arrival_s / b_span) * span * 0.6
        out.append({"at": round(at, 4), "tenant": f"burst-{r.tenant}",
                    "tier": "free", "prompt": r.prompt,
                    "max_tokens": r.max_tokens})
    out.sort(key=lambda d: d["at"])
    return out


async def _drive_tiered(plane_url: str, trace: List[Dict[str, Any]],
                        observe=None) -> List[Dict[str, Any]]:
    """Open-loop tiered driver: NOBODY retries a 429 — a shed is a shed
    (the burst models a misbehaving tenant; a paid shed is the failure
    the ladder must prevent, and riding it out would hide it)."""
    import httpx

    t0 = time.perf_counter()
    async with httpx.AsyncClient(timeout=600.0) as client:

        async def one(i: int, req: Dict[str, Any]) -> Dict[str, Any]:
            now = time.perf_counter() - t0
            if req["at"] > now:
                await asyncio.sleep(req["at"] - now)
            rec = {"i": i, "tier": req["tier"], "arrival_s": req["at"],
                   "status": 0}
            t_req = time.perf_counter()
            try:
                r = await client.post(f"{plane_url}/api/v1/jobs", json={
                    "type": "llm",
                    "params": {"prompt": req["prompt"],
                               "max_new_tokens": req["max_tokens"],
                               "tenant": req["tenant"],
                               "tier": req["tier"]},
                })
            except httpx.TransportError:
                rec["status"] = 599
                return rec
            if r.status_code != 201:
                rec["status"] = r.status_code
                if observe is not None and req["tier"] == "paid":
                    observe(in_slo=False)   # a paid shed IS an SLO miss
                return rec
            job_id = r.json()["job_id"]
            while time.perf_counter() - t_req < 180.0:
                try:
                    j = (await client.get(
                        f"{plane_url}/api/v1/jobs/{job_id}")).json()
                except (httpx.TransportError, ValueError):
                    await asyncio.sleep(0.1)
                    continue
                if j.get("status") in ("completed", "failed", "cancelled"):
                    res = j.get("result") or {}
                    e2e = (time.perf_counter() - t_req) * 1e3
                    rec.update({
                        "status": 200 if j["status"] == "completed"
                        else 500,
                        "e2e_ms": e2e,
                        "done_s": time.perf_counter() - t0,
                        "ttft_ms": res.get("ttft_ms"),
                        "worker_id": j.get("worker_id"),
                        "degraded": bool(
                            (j.get("params") or {}).get(
                                "degraded_max_tokens")),
                        "completion_tokens": (res.get("usage") or {})
                        .get("completion_tokens") or 0,
                    })
                    if observe is not None and req["tier"] == "paid":
                        observe(latency_ms=e2e)
                    return rec
                await asyncio.sleep(0.05)
            rec["status"] = 599
            return rec

        return list(await asyncio.gather(
            *(one(i, r) for i, r in enumerate(trace))
        ))


def _tier_summary(results: List[Dict[str, Any]]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for tier in ("paid", "free"):
        rs = [r for r in results if r["tier"] == tier]
        if not rs:
            continue
        ok = [r for r in rs if r["status"] == 200]
        out[tier] = {
            "offered": len(rs),
            "ok": len(ok),
            "shed_429": sum(1 for r in rs if r["status"] == 429),
            "failed": sum(1 for r in rs
                          if r["status"] not in (200, 429)),
            "degraded_clamped": sum(1 for r in ok if r.get("degraded")),
            "tokens": sum(r.get("completion_tokens") or 0 for r in ok),
            "ttft_ms": percentiles(
                [r["ttft_ms"] for r in ok
                 if r.get("ttft_ms") is not None]),
            "e2e_ms": percentiles([r["e2e_ms"] for r in ok]),
        }
    return out


def run_overload(args: Any, backend: str, model: str) -> None:
    from distributed_gpu_inference_tpu.server.autoscaler import (
        AutoscalerConfig,
        BrownoutAutoscaler,
    )
    from distributed_gpu_inference_tpu.testing.faults import (
        FleetEvent,
        FleetFaultPlan,
    )
    from distributed_gpu_inference_tpu.testing.harness import (
        FleetAutoscaler,
        LiveFleet,
    )

    engine_config = {
        "model": model,
        "max_batch_size": args.concurrency,
        "max_seq_len": 256 + args.max_tokens + 16,
        "quantization": args.quantization,
        "serving": {
            "queue_limit": 4096,
            "default_timeout_s": 600.0,
        },
    }
    rate = float(args.arrival_rate) if args.arrival_rate else 2.0
    paid_n, free_n = args.requests, args.requests * 6
    trace = _tiered_trace(args.seed, paid_n, free_n, rate,
                          args.max_tokens)
    queue_limit = 8
    admission = {
        "enabled": True, "degrade_at": 0.2, "no_spec_at": 0.4,
        "clamp_max_tokens": max(2, args.max_tokens // 4),
        "min_retry_after_s": 0.05,
    }
    fractions = {"paid": 1.0, "free": 0.5, "batch": 0.3}

    def configure(fleet: Any, enabled: bool) -> None:
        fleet.plane.state.admission.cfg.update(
            {**admission, "enabled": enabled})
        fleet.plane.state.worker_config._defaults.load_control \
            .tier_queue_fractions = dict(fractions)

    def admission_delta(fleet: Any, before: Dict[str, int]
                        ) -> Dict[str, int]:
        after = dict(fleet.plane.state.admission.stats)
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in set(after) | set(before)
                if after.get(k, 0) != before.get(k, 0)}

    out: Dict[str, Any] = {
        "benchmark": "worker_serving_overload",
        "path": "control_plane+admission_ladder+live_fleet",
        "model": model, "backend": backend, "seed": args.seed,
        "paid_requests": paid_n, "free_burst_requests": free_n,
        "paid_rate_rps": rate, "free_burst_rate_rps": rate * 10.0,
        "max_tokens": args.max_tokens,
        "submit_queue_limit": queue_limit,
        "tier_queue_fractions": fractions,
        "clamp_max_tokens": admission["clamp_max_tokens"],
        "replicas": int(args.chaos_replicas),
    }

    with LiveFleet(n=int(args.chaos_replicas),
                   engine_config=engine_config,
                   submit_queue_limit=queue_limit) as fleet:
        configure(fleet, enabled=True)
        paid_only = [r for r in trace if r["tier"] == "paid"]
        # short warm: compile the serving graphs, not a whole leg
        asyncio.run(_drive_tiered(fleet.url, paid_only[:4]))
        base = asyncio.run(_drive_tiered(fleet.url, paid_only))
        out["paid_baseline"] = _tier_summary(base)

        before = dict(fleet.plane.state.admission.stats)
        on = asyncio.run(_drive_tiered(fleet.url, trace))
        out["ladder_on"] = _tier_summary(on)
        out["ladder_on"]["admission_decisions"] = admission_delta(
            fleet, before)

        configure(fleet, enabled=False)
        off = asyncio.run(_drive_tiered(fleet.url, trace))
        out["ladder_off"] = _tier_summary(off)
        configure(fleet, enabled=True)

        p_on = out["ladder_on"].get("paid") or {}
        p_base = out["paid_baseline"].get("paid") or {}
        p_off = out["ladder_off"].get("paid") or {}
        verdict = {
            "paid_shed_ladder_on": p_on.get("shed_429", 0),
            "paid_shed_ladder_off": p_off.get("shed_429", 0),
            "free_shed_ladder_on":
                (out["ladder_on"].get("free") or {}).get("shed_429", 0),
            "free_clamped_ladder_on":
                (out["ladder_on"].get("free") or {})
                .get("degraded_clamped", 0),
        }
        for pct in ("p50", "p95"):
            a = (p_on.get("e2e_ms") or {}).get(pct)
            b = (p_base.get("e2e_ms") or {}).get(pct)
            if a and b:
                verdict[f"paid_e2e_{pct}_burst_over_baseline"] = round(
                    a / b, 3)
        out["verdict"] = verdict

    # ---- autoscaler leg: seeded kill mid-span, brownout-driven recovery.
    # The paid trace runs COMPRESSED (2x rate): the surviving replica must
    # actually fall behind after the kill, or there is no brownout to
    # scale out of.
    with LiveFleet(n=2, engine_config=engine_config) as fleet:
        wave = [{**r, "at": round(r["at"] / 2.0, 4)}
                for r in trace if r["tier"] == "paid"]
        w_span = max(r["at"] for r in wave)
        # two back-to-back waves: the kill browns out wave 1, the scaled-
        # out replica proves recovery by SERVING wave 2 (time-to-recover
        # is kill → first request completed by autoscaled capacity)
        paid_only = wave + [{**r, "at": round(r["at"] + w_span, 4)}
                            for r in wave]
        span = max(r["at"] for r in paid_only)
        asyncio.run(_drive_tiered(fleet.url, wave[:4]))        # warm
        asc = BrownoutAutoscaler(AutoscalerConfig(
            slo_latency_ms=float(args.overload_slo_ms),
            slo_target=0.9, window_s=max(2.0, span / 4.0),
            min_samples=4, scale_out_cooldown_s=5.0,
            max_replicas=3, default_cold_start_s=3.0,
        ), metrics=fleet.plane.state.metrics)
        driver = FleetAutoscaler(fleet, asc, tick_s=0.25).start()
        t_kill = round(0.30 * span, 3)
        plan = FleetFaultPlan(args.seed, n_workers=2, duration_s=span,
                              kinds=("kill",))
        plan.events = [FleetEvent(t_kill, "kill", 1),
                       FleetEvent(round(0.95 * span, 3), "restart", 1)]
        fleet.run_chaos(plan)
        try:
            scaled = asyncio.run(_drive_tiered(
                fleet.url, paid_only, observe=asc.observe))
        finally:
            fleet.wait_chaos()
            driver.stop()
            for m in fleet.members:
                if not m.alive:
                    m.start()
        scale_outs = [t for t, a in driver.actions if a == "scale_out"]
        new_workers = {m.worker_id for m in fleet.members[2:]}
        served_by_new = [r["done_s"] for r in scaled
                         if r["status"] == 200
                         and r.get("worker_id") in new_workers]
        out["autoscaler"] = {
            "kill_at_s": t_kill,
            "summary": _tier_summary(scaled),
            "scale_out_at_s": [round(t, 3) for t in scale_outs],
            "decisions": dict(asc.stats),
            "measured_cold_start_s": round(asc.cold_start_s, 3),
            # recovery: kill → first request served by autoscaled capacity
            "time_to_recover_s": round(min(served_by_new) - t_kill, 3)
            if served_by_new else None,
            "replicas_final": len(fleet.alive_members()),
        }
    emit(out)


# ---------------------------------------------------------------------------
# --spec (round 8): spec ON vs OFF on the SLO frontier with an ORACLE draft.
# Real 8B trained draft heads are environment-blocked (VERDICT r5 #3), but
# the win condition is testable without them: the oracle forces the
# acceptance rate while every cost stays real (draft chain, K+1-query
# verify, KV writes ahead of verification, commit + trim_reserved
# rollback). Sweeping the forced rate traces the tok/s-vs-acceptance curve
# through the DEPLOYED path — DirectServer + batcher + spec ragged rounds
# — and the crossover is the acceptance a trained draft must clear for
# spec ON to beat spec OFF at equal p50 TTFT.
# ---------------------------------------------------------------------------


def _build_serving_llm(args: Any, model: str, spec_k: int = 0,
                       adaptive: bool = False) -> Any:
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    cfg: Dict[str, Any] = {
        "model": model,
        "max_batch_size": args.concurrency,
        # identical pool geometry both legs: the spec verify window rides
        # inside the same max_seq_len margin
        "max_seq_len": args.prompt_len + args.max_tokens + 16
        + max(args.spec_k, 1) + 2,
        "quantization": args.quantization,
        "serving": {
            "queue_limit": max(4096, args.requests * 2),
            "default_timeout_s": 600.0,
        },
    }
    if args.kv_cache_dtype:
        cfg["kv_cache_dtype"] = args.kv_cache_dtype
    if spec_k > 0:
        cfg.update({
            "speculative_decode": True,
            "spec_num_draft_tokens": spec_k,
            "spec_adaptive": adaptive,
            # any valid rate — legs flip it live via set_spec_oracle
            "spec_oracle_accept": 1.0,
        })
    llm = TPULLMEngine(cfg)
    llm.load_model()
    return llm


# ---------------------------------------------------------------------------
# --plane-scale (round 15): control-plane replication, measured. A fleet of
# FAKE-engine workers (real APIClient protocol — signing, epoch-fenced
# completion, plane failover — no JAX engine, so the CONTROL PLANE is the
# bottleneck) drives two legs:
#   sweep     — open-loop submissions round-robin across P plane replicas
#               sharing one job store, for each P in --plane-counts:
#               claims/s (jobs brokered→completed per second), heartbeat
#               ingest rate, and p50/p99 admission latency (POST→201)
#   kill_one  — P=2, one plane hard-killed mid-stream: time-to-recover is
#               kill → first job submitted AFTER the kill completing
#               through the surviving plane, plus worker failover counts
# ---------------------------------------------------------------------------


async def _drive_plane_admissions(urls: List[str], n: int, rate: float,
                                  max_poll_s: float = 60.0,
                                  kill_after: Optional[Tuple[float, Any]]
                                  = None) -> List[Dict[str, Any]]:
    """Open-loop submissions spread round-robin over the plane cohort.

    Every record carries the admission latency (POST→answer) and the
    completion wall-clock; a transport error on one plane endpoint retries
    the next (the SDK's failover contract, inlined so the bench measures
    the raw HTTP path, not SDK backoff policy)."""
    import httpx

    t0 = time.perf_counter()
    fired = [False]
    async with httpx.AsyncClient(timeout=30.0) as client:

        async def one(i: int) -> Dict[str, Any]:
            at = i / rate
            now = time.perf_counter() - t0
            if at > now:
                await asyncio.sleep(at - now)
            if kill_after is not None and not fired[0] \
                    and (time.perf_counter() - t0) >= kill_after[0]:
                fired[0] = True
                kill_after[1]()
            rec: Dict[str, Any] = {"i": i, "submit_s": None,
                                   "admit_ms": None, "done_s": None,
                                   "status": 0}
            job_id = None
            for k in range(len(urls) * 2):
                url = urls[(i + k) % len(urls)]
                t_req = time.perf_counter()
                try:
                    r = await client.post(f"{url}/api/v1/jobs", json={
                        "type": "llm",
                        "params": {"prompt": f"plane-scale {i}",
                                   "max_new_tokens": 1},
                    })
                except httpx.TransportError:
                    continue          # dead plane: next endpoint
                rec["status"] = r.status_code
                if r.status_code == 201:
                    rec["submit_s"] = t_req - t0
                    rec["admit_ms"] = (time.perf_counter() - t_req) * 1e3
                    job_id = r.json()["job_id"]
                break
            if job_id is None:
                rec["status"] = rec["status"] or 599
                return rec
            while time.perf_counter() - t0 - rec["submit_s"] < max_poll_s:
                for k in range(len(urls)):
                    url = urls[(i + k) % len(urls)]
                    try:
                        j = (await client.get(
                            f"{url}/api/v1/jobs/{job_id}")).json()
                    except (httpx.TransportError, ValueError):
                        continue
                    if j.get("status") == "completed":
                        rec["done_s"] = time.perf_counter() - t0
                        return rec
                    break
                await asyncio.sleep(0.02)
            rec["status"] = 599
            return rec

        return list(await asyncio.gather(*(one(i) for i in range(n))))


def run_plane_scale(args: Any, backend: str, model: str) -> None:
    from distributed_gpu_inference_tpu.testing.harness import LiveFleet

    counts = [int(c) for c in str(args.plane_counts).split(",") if c]
    rate = float(args.arrival_rate) if args.arrival_rate else 120.0
    n_sub = args.requests
    workers = int(args.plane_workers)
    out: Dict[str, Any] = {
        "benchmark": "worker_serving_plane_scale",
        "path": "replicated_control_planes+fake_engine_fleet",
        "backend": backend, "seed": args.seed,
        "workers": workers, "submissions": n_sub,
        "submit_rate_rps": rate, "plane_counts": counts,
        "sweep": {},
    }

    for planes in counts:
        with LiveFleet(n=workers, fake_engines=True, n_planes=planes,
                       hb_interval_s=0.1) as fleet:
            urls = fleet.plane_urls
            # spread worker stickiness across the cohort: production
            # deployments start each worker with a rotated endpoint list,
            # the harness hands every member the same order
            for m in fleet.members:
                m.api._active = m.index % len(urls)
            # warm: compile nothing (fake engines), but settle the
            # registration burst before measuring
            asyncio.run(_drive_plane_admissions(urls, 8, rate))
            hb0 = sum(m.heartbeats for m in fleet.members)
            t0 = time.perf_counter()
            recs = asyncio.run(_drive_plane_admissions(urls, n_sub, rate))
            elapsed = time.perf_counter() - t0
            hb = sum(m.heartbeats for m in fleet.members) - hb0
            done = [r for r in recs if r["done_s"] is not None]
            stamped = fleet.any_plane().query(
                "SELECT plane_id, COUNT(*) AS c FROM jobs "
                "WHERE plane_id IS NOT NULL GROUP BY plane_id", ()
            )
            out["sweep"][str(planes)] = {
                "completed": len(done),
                "failed": len(recs) - len(done),
                "elapsed_s": round(elapsed, 3),
                "claims_per_s": round(len(done) / elapsed, 1),
                "heartbeat_ingest_per_s": round(hb / elapsed, 1),
                "admission_ms": percentiles(
                    [r["admit_ms"] for r in recs
                     if r["admit_ms"] is not None]),
                "claims_by_plane": {
                    r["plane_id"]: r["c"] for r in stamped
                },
            }

    # kill-one leg: 2 planes, one dies mid-stream
    with LiveFleet(n=workers, fake_engines=True, n_planes=2,
                   hb_interval_s=0.1) as fleet:
        urls = fleet.plane_urls
        for m in fleet.members:
            m.api._active = m.index % len(urls)
        asyncio.run(_drive_plane_admissions(urls, 8, rate))
        span = n_sub / rate
        t_kill = round(span * 0.35, 3)
        kill_state: Dict[str, float] = {}

        def kill_now() -> None:
            # from a side thread: plane teardown joins its server thread,
            # and blocking the driver's event loop on that would stall
            # every in-flight submission and poison the latency numbers
            import threading as _threading

            kill_state["at"] = time.perf_counter()
            _threading.Thread(target=fleet.planes[0].kill,
                              daemon=True).start()

        t0 = time.perf_counter()
        recs = asyncio.run(_drive_plane_admissions(
            urls, n_sub, rate, kill_after=(t_kill, kill_now)))
        kill_s = kill_state["at"] - t0
        done = [r for r in recs if r["done_s"] is not None]
        after = [r["done_s"] for r in done
                 if r["submit_s"] is not None and r["submit_s"] >= kill_s]
        fleet.planes[0].start()
        out["kill_one"] = {
            "planes": 2, "kill_at_s": round(kill_s, 3),
            "completed": len(done),
            "failed": len(recs) - len(done),
            # recovery: kill → first job submitted AFTER the kill done
            # through the surviving plane
            "time_to_recover_s": round(min(after) - kill_s, 3)
            if after else None,
            "worker_plane_failovers": sum(
                m.api.plane_failovers for m in fleet.members
                if m.api is not None),
            "admission_ms": percentiles(
                [r["admit_ms"] for r in recs
                 if r["admit_ms"] is not None]),
        }
    emit(out)


def run_spec_ab(args: Any, backend: str, model: str) -> None:
    from distributed_gpu_inference_tpu.worker.direct_server import (
        DirectServer,
    )

    rate = float(args.arrival_rate) if args.arrival_rate else None
    prompts = synth_prompt_strings(args.requests, args.prompt_len,
                                   args.shared_prefix)
    # ignore_eos: the oracle commits (garbage) draft tokens, and both legs
    # must generate IDENTICAL token counts for tok/s to be comparable
    extra = {"ignore_eos": True}

    def leg(llm: Any) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        worker = BenchWorker(llm)
        ds = DirectServer(worker, host="127.0.0.1", port=0)
        ds.start()
        url = f"http://127.0.0.1:{ds._runner.addresses[0][1]}"
        try:
            # on the engine-executor thread: a client-side timeout in the
            # previous sweep point can leave the batcher mid-round, and an
            # unsynchronized cache wipe would race its manager mutations
            llm.serving.run_exclusive(llm.engine.manager.clear_cached)
            summary = _summarize(*asyncio.run(_drive_http(
                url, prompts, args.max_tokens, rate, args.concurrency,
                args.seed, extra_params=extra,
            )))
            stats = llm.serving.get_stats()
            return summary, stats
        finally:
            ds.stop()

    out: Dict[str, Any] = {
        "benchmark": "worker_serving_spec",
        "path": "direct_server+batcher_engine+spec_ragged_rounds",
        "mode": "open_loop" if rate else "closed_loop",
        "model": model, "backend": backend,
        "requests": args.requests, "concurrency": args.concurrency,
        "prompt_len": args.prompt_len, "max_tokens": args.max_tokens,
        "arrival_rate_rps": rate, "seed": args.seed,
        "spec_k": args.spec_k, "spec_adaptive": bool(args.spec_adaptive),
        "kv_cache_dtype": args.kv_cache_dtype,
        "oracle": "forced-acceptance draft (real cost, forced decision)",
    }

    # ---- spec OFF baseline (identical engine minus the draft mode)
    llm_off = _build_serving_llm(args, model)
    try:
        _warm(llm_off, args.prompt_len, llm_off.serving.batcher._levels,
              args.concurrency)
        off_summary, off_stats = leg(llm_off)
    finally:
        llm_off.unload()
    out["spec_off"] = off_summary
    out["spec_off_batcher"] = {
        k: off_stats.get(k) for k in ("decode_rounds", "ragged_rounds",
                                      "ragged_admissions", "avg_occupancy")
    }
    off_tps = off_summary["decode_tokens_per_s"]
    off_p50 = (off_summary["ttft_ms"] or {}).get("p50")

    # ---- spec ON sweep over forced acceptance rates (live oracle flips —
    # the compiled graphs are identical across rates)
    rates = [float(r) for r in str(args.spec_accept).split(",") if r.strip()]
    llm_on = _build_serving_llm(args, model, spec_k=args.spec_k,
                                adaptive=bool(args.spec_adaptive))
    curve: List[Dict[str, Any]] = []
    try:
        _warm(llm_on, args.prompt_len, llm_on.serving.batcher._levels,
              args.concurrency)
        for r in rates:
            llm_on.serving.run_exclusive(llm_on.engine.set_spec_oracle, r)
            # per-LEG spec efficiency: the engine counters are cumulative
            # (warm + earlier sweep points), so rate/tokens-per-step must
            # come from this leg's deltas
            pre = {k: llm_on.engine.stats.get(k, 0)
                   for k in ("spec_accepted", "spec_drafted",
                             "spec_emitted", "spec_slot_steps")}
            on_summary, on_stats = leg(llm_on)
            post = llm_on.engine.stats
            d_drafted = post.get("spec_drafted", 0) - pre["spec_drafted"]
            d_steps = post.get("spec_slot_steps", 0) - pre["spec_slot_steps"]
            point = {
                "forced_accept_rate": r,
                "summary": on_summary,
                "measured_accept_rate": round(
                    (post.get("spec_accepted", 0) - pre["spec_accepted"])
                    / d_drafted, 4) if d_drafted else None,
                "tokens_per_step": round(
                    (post.get("spec_emitted", 0) - pre["spec_emitted"])
                    / d_steps, 3) if d_steps else None,
                "tokens_per_s_on_over_off": round(
                    on_summary["decode_tokens_per_s"] / off_tps, 3
                ) if off_tps else None,
            }
            p50 = (on_summary["ttft_ms"] or {}).get("p50")
            if p50 and off_p50:
                point["ttft_p50_on_over_off"] = round(p50 / off_p50, 3)
            curve.append(point)
    finally:
        llm_on.unload()
    out["spec_on_curve"] = curve

    # ---- crossover: smallest forced rate where spec ON beats OFF on
    # tok/s at equal p50 TTFT (<= 5% TTFT regression tolerated)
    crossover = None
    for point in sorted(curve, key=lambda p: p["forced_accept_rate"]):
        ratio = point.get("tokens_per_s_on_over_off") or 0.0
        t_ratio = point.get("ttft_p50_on_over_off")
        if ratio > 1.0 and (t_ratio is None or t_ratio <= 1.05):
            crossover = point["forced_accept_rate"]
            break
    out["crossover_accept_rate"] = crossover
    out["ttft_parity_tolerance"] = 1.05
    emit(out)


# ---------------------------------------------------------------------------
# --long-context (round 17): the mixed-traffic frontier. Three legs on ONE
# engine through the REAL DirectServer + batcher ragged rounds:
#   baseline    — the short-request stream alone (no long traffic)
#   unbudgeted  — same short stream + background --long-len prompts,
#                 prefill_budget=0 (a giant admission may claim the whole
#                 chunk bucket round after round)
#   budgeted    — same traffic, prefill_budget pushed LIVE via the serving
#                 remote-config path (the deployed knob, not a rebuild)
# The verdict metric is the SHORT requests' decode ITL p95: budgeted must
# land materially closer to baseline than unbudgeted. Outputs are asserted
# byte-identical budgeted vs unbudgeted (chunk widths change, tokens must
# not), and --timeline attributes where the long prefill time goes.
# ---------------------------------------------------------------------------


def _itl_ms(results: List[Dict[str, Any]]) -> List[float]:
    """Per-request mean inter-token latency: decode time spread over the
    tokens after the first. The tail of THIS distribution over short
    requests is what a monopolizing long prefill wrecks."""
    out = []
    for r in results:
        if r.get("status") == 200 and r.get("ttft_ms") is not None:
            n = r.get("completion_tokens") or 0
            if n > 1:
                out.append((r["e2e_ms"] - r["ttft_ms"]) / (n - 1))
    return out


def run_long_context(args: Any, backend: str, model: str) -> None:
    from distributed_gpu_inference_tpu.worker.direct_server import (
        DirectServer,
    )
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    rate = float(args.arrival_rate) if args.arrival_rate else 2.0
    long_len = int(args.long_len)
    n_long = max(1, int(args.long_requests))
    blocks = 16  # EngineConfig default block_size
    short_blocks = -(-(args.prompt_len + args.max_tokens + 16) // blocks)
    long_blocks = -(-(long_len + args.max_tokens + 16) // blocks)
    # chunk width of the unbudgeted rounds, and the width the budget caps
    # rounds to (floored at the short-prompt bucket so short admissions
    # never pad up to the full chunk)
    chunk = min(2048, long_len)
    bud_w = min(max(int(args.prefill_budget) or chunk,
                    args.prompt_len + 1), chunk)
    llm = TPULLMEngine({
        "model": model,
        "max_batch_size": args.concurrency,
        "max_seq_len": long_len + args.max_tokens + 16,
        # size the pool for the ACTUAL working set (shorts + the long
        # streams), not 1.5x batch x the 32k worst case — the default
        # sizing rule assumes every slot can be max_seq_len deep, which
        # at 32k is pure pad
        "num_blocks": args.concurrency
        * max(short_blocks, -(-(bud_w + 32) // blocks))
        + (n_long + 1) * long_blocks + 64,
        "quantization": args.quantization,
        # pin the compiled widths to exactly the two the legs dispatch —
        # the budget-capped chunk and the full chunk. Budget grants
        # bucket UP through prefill_buckets, so a free-form bucket
        # ladder would let the water-fill land widths no warmup
        # compiled and bill cold XLA compiles to the budgeted leg
        "prefill_buckets": tuple(sorted({bud_w, chunk})),
        "serving": {
            "queue_limit": max(4096, args.requests * 2),
            "default_timeout_s": 1800.0,
            "ragged_chunk": chunk,
        },
    })
    llm.load_model()
    worker = BenchWorker(llm)
    ds = DirectServer(worker, host="127.0.0.1", port=0)
    ds.start()
    url = f"http://127.0.0.1:{ds._runner.addresses[0][1]}"

    # warm the budget-capped width at full wave concurrency (the shorts
    # live there) and the full chunk width single-file (only the long
    # stream's ragged chunks dispatch it — a w-wide wave of full-chunk
    # prompts would need a pool sized for pure pad)
    _warm(llm, bud_w, llm.serving.batcher._levels, args.concurrency)
    if chunk != bud_w:
        _warm(llm, chunk, llm.serving.batcher._levels, 1)
    shorts = synth_prompt_strings(args.requests, args.prompt_len,
                                  args.shared_prefix, seed=args.seed)
    longs = synth_prompt_strings(n_long, long_len, 0, seed=args.seed + 1)

    async def leg_async(include_long: bool):
        st = _drive_http(url, shorts, args.max_tokens, rate,
                         args.concurrency, args.seed,
                         trace=args.timeline, collect_text=True)
        if include_long:
            # the long stream fires immediately and all at once (its own
            # closed loop) so the giant prefills overlap the short
            # stream's whole span; the SHORT arrival schedule (rate +
            # seed) is byte-identical across all three legs
            lt = _drive_http(url, longs, args.max_tokens, None, n_long,
                             args.seed + 1, trace=args.timeline,
                             collect_text=True)
            return await asyncio.gather(st, lt)
        return [await st, ([], 0.0, 0.0)]

    def leg(name: str, include_long: bool, budget: int) -> Dict[str, Any]:
        # push the budget through the REAL remote-config path, then fence
        # on the engine executor so the push (applied between rounds on
        # the loop thread) lands before the first measured request
        llm.apply_serving_config({"prefill_budget": budget})
        deadline = time.perf_counter() + 5.0
        while llm.serving.batcher.cfg.prefill_budget != budget \
                and time.perf_counter() < deadline:
            time.sleep(0.01)   # the push applies on the loop thread
        llm.serving.run_exclusive(llm.engine.manager.clear_cached)
        pre = {k: llm.serving.get_stats().get(k, 0)
               for k in ("budgeted_rounds", "budget_skipped_admissions",
                         "ragged_rounds")}
        (s_res, s_el, s_span), (l_res, _l_el, _l_span) = asyncio.run(
            leg_async(include_long)
        )
        stats = llm.serving.get_stats()
        out: Dict[str, Any] = {
            "prefill_budget": budget,
            "short": _summarize(s_res, s_el, s_span),
            "short_itl_ms": percentiles(_itl_ms(s_res)),
            "rounds": {k: stats.get(k, 0) - pre[k] for k in pre},
        }
        if include_long:
            ok_long = [r for r in l_res if r["status"] == 200]
            out["long"] = {
                "requests": n_long, "ok": len(ok_long),
                "prompt_len": long_len,
                "ttft_ms": percentiles(
                    [r["ttft_ms"] for r in ok_long
                     if r.get("ttft_ms") is not None]
                ),
                "e2e_ms": percentiles([r["e2e_ms"] for r in ok_long]),
            }
        if args.timeline:
            out["attribution_short"] = _timeline_attribution(s_res)
            if include_long:
                out["attribution_long"] = _timeline_attribution(l_res)
        out["_texts"] = (
            [r.get("text") for r in s_res] + [r.get("text") for r in l_res]
        )
        return out

    ragged_chunk = int(llm.engine.cfg.ragged_chunk)
    try:
        baseline = leg("baseline", False, 0)
        unbudgeted = leg("unbudgeted", True, 0)
        budgeted = leg("budgeted", True, int(args.prefill_budget))
    finally:
        ds.stop()
        llm.unload()

    identical = unbudgeted.pop("_texts") == budgeted.pop("_texts")
    baseline.pop("_texts")
    base_itl = (baseline["short_itl_ms"] or {}).get("p95")
    unb_itl = (unbudgeted["short_itl_ms"] or {}).get("p95")
    bud_itl = (budgeted["short_itl_ms"] or {}).get("p95")
    out = {
        "benchmark": "worker_serving_long_context",
        "path": "direct_server+batcher_engine+ragged_rounds",
        "model": model, "backend": backend,
        "requests": args.requests, "concurrency": args.concurrency,
        "prompt_len": args.prompt_len, "max_tokens": args.max_tokens,
        "arrival_rate_rps": rate, "seed": args.seed,
        "long_len": long_len, "long_requests": n_long,
        "prefill_budget": int(args.prefill_budget),
        "ragged_chunk": ragged_chunk,
        "baseline": baseline,
        "unbudgeted": unbudgeted,
        "budgeted": budgeted,
        "outputs_identical_budgeted_vs_unbudgeted": identical,
    }
    if base_itl and unb_itl and bud_itl:
        # how much of the long-prefill-induced short-ITL inflation the
        # budget claws back (1.0 = all the way to baseline)
        out["short_itl_p95"] = {
            "baseline": base_itl, "unbudgeted": unb_itl,
            "budgeted": bud_itl,
            "budget_recovery": round(
                (unb_itl - bud_itl) / (unb_itl - base_itl), 3
            ) if unb_itl > base_itl else None,
        }
    emit(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--concurrency", type=int, default=8,
                    help="engine slots; closed-loop client concurrency")
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-tokens", type=int, default=64)
    ap.add_argument("--shared-prefix", type=int, default=64)
    ap.add_argument("--arrival-rate", default=None,
                    help="open-loop Poisson req/s (comma-separated rates "
                    "sweep one engine); omit for the closed-loop "
                    "throughput row")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--subwave", type=int, default=0)
    ap.add_argument("--interleave", type=int, default=0)
    ap.add_argument("--max-horizon", type=int, default=64)
    ap.add_argument("--quantization", default=None)
    ap.add_argument("--compare", action="store_true",
                    help="also run the SAME workload through the "
                    "in-process batcher (the bench-only configuration) "
                    "and emit deployed/bench ratios")
    ap.add_argument("--compare-legacy", action="store_true",
                    help="A/B the ragged serving path (default, knobs "
                    "ignored) against the knob-tuned legacy admission "
                    "path on the same live engine (serving.ragged=false "
                    "pushed between legs) and emit ragged/legacy ratios")
    ap.add_argument("--spec", action="store_true",
                    help="A/B spec ON (oracle draft, forced acceptance "
                    "sweep) vs spec OFF through the deployed serving "
                    "path; emits the tok/s-vs-acceptance curve and the "
                    "crossover at equal p50 TTFT")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft depth K for the --spec ON legs")
    ap.add_argument("--spec-accept", default="0.0,0.25,0.5,0.75,1.0",
                    help="comma-separated forced acceptance rates "
                    "(fraction of the K drafts accepted per round)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="enable acceptance-adaptive draft depth in the "
                    "--spec ON legs")
    ap.add_argument("--kv-cache-dtype", default=None,
                    help="KV pool storage dtype for both --spec legs "
                    "(int8 composes with spec verify since round 8)")
    ap.add_argument("--workers", type=int, default=0,
                    help="≥2 stands up a FLEET behind a live control "
                    "plane and A/Bs cache-aware routing (admin flag "
                    "flipped live) on a seeded multi-tenant workload")
    ap.add_argument("--pd-split", default=None, metavar="P:D",
                    help="stand up a role-split LiveFleet (P prefill + D "
                    "decode workers, real /kv/transfer data planes) and "
                    "publish the PD frontier vs a data-parallel fleet at "
                    "equal worker count, plus a handoff-brownout leg "
                    "(handoff partition + prefill-side kill/restart: "
                    "SLO-in-window, re-prefill count, time-to-recover)")
    ap.add_argument("--overload", action="store_true",
                    help="brownout-ladder legs: steady paid traffic + a "
                    "10x free-tier burst through a LiveFleet with the "
                    "admission ladder ON vs OFF (paid SLO held vs blanket "
                    "429s), plus a brownout-driven autoscaler leg with a "
                    "seeded kill (measured cold-start lead time + "
                    "time-to-recover)")
    ap.add_argument("--overload-slo-ms", type=float, default=2000.0,
                    help="per-request e2e SLO bound the autoscaler leg "
                    "judges its window against")
    ap.add_argument("--plane-scale", action="store_true",
                    help="replicated control-plane legs on a fake-engine "
                    "fleet (real claim/heartbeat/completion protocol, no "
                    "JAX): claims/s, heartbeat ingest rate, and p99 "
                    "admission latency vs plane count, plus a 2-plane "
                    "kill-one leg with measured time-to-recover")
    ap.add_argument("--plane-counts", default="1,2,3",
                    help="comma-separated plane replica counts for the "
                    "--plane-scale sweep")
    ap.add_argument("--plane-workers", type=int, default=24,
                    help="fake-engine worker count for --plane-scale")
    ap.add_argument("--chaos", action="store_true",
                    help="cluster frontier + brownout mode: drive the "
                    "same open-loop workload through a LiveFleet at "
                    "--replicas counts, then replay it with a seeded "
                    "kill/restart mid-workload and publish SLO-in-window, "
                    "goodput, time-to-recover, and chaos-on/off "
                    "byte-identity")
    ap.add_argument("--gray", action="store_true",
                    help="gray-failure defense legs: one replica of a "
                    "3-worker LiveFleet degrades (alive, 0.3s/request "
                    "slow) under a mixed deadline/deadline-less workload "
                    "with quarantine+hedging ON vs OFF; publishes "
                    "deadline-carrying p99, hedges fired/won, abandonment "
                    "counts by deadline-ness, and output byte-identity")
    ap.add_argument("--gray-deadline-s", type=float, default=30.0,
                    help="deadline_s the deadline-carrying half of the "
                    "--gray workload requests carry")
    ap.add_argument("--gray-degrade-s", type=float, default=1.0,
                    help="per-request delay the degraded replica pays in "
                    "the --gray legs (gray failures are typically 10x+, "
                    "not marginal: below the fleet's queueing slack, "
                    "quarantining a third of the capacity costs more "
                    "than the slow replica does)")
    ap.add_argument("--io-chaos", action="store_true",
                    help="durable-tier brownout legs: a spill-tiered "
                    "2-worker LiveFleet under a composed io_slow+io_error "
                    "storm with the per-tier IO breakers ON (default) vs "
                    "DISABLED; publishes per-leg TTFT/e2e, the ON/OFF "
                    "latency ratios, spill error/skip counters, and "
                    "three-way output byte-identity")
    ap.add_argument("--io-delay-s", type=float, default=0.05,
                    help="per-op latency the browning-out spill device "
                    "pays during the --io-chaos storm")
    ap.add_argument("--io-error-prob", type=float, default=0.6,
                    help="per-op failure probability of the spill device "
                    "during the --io-chaos storm (what trips the "
                    "breakers; pure slowness never raises)")
    ap.add_argument("--replicas", default="1,2,4",
                    help="comma-separated replica counts for the --chaos "
                    "cluster frontier sweep")
    ap.add_argument("--chaos-replicas", type=int, default=2,
                    help="fleet size for the --chaos brownout leg "
                    "(one replica is killed and restarted)")
    ap.add_argument("--scenario", default="chat",
                    choices=["chat", "rag", "bursty", "storm", "priority"],
                    help="fleet-mode workload (benchmarks/workloads.py)")
    ap.add_argument("--kv-migrate", action="store_true",
                    help="cluster-wide KV migration A/B: migrate-ON vs "
                    "route-only under the anti-affinity storm workload, "
                    "swept over --arrival-rate (comma-separated storm "
                    "rates; default 0.5,2.0)")
    ap.add_argument("--predictive", action="store_true",
                    help="serving-intelligence A/B (round 20): cost-model "
                    "self-calibration ON vs static priors under the storm "
                    "workload (replayed --predictive-repeats times so the "
                    "predicted-vs-measured error trajectory shows "
                    "convergence), and proactive prefix replication ON vs "
                    "reactive-only under the bursty workload; per-leg "
                    "--timeline attribution and output byte-identity")
    ap.add_argument("--predictive-repeats", type=int, default=3,
                    help="calibrated-leg replays for the --predictive "
                    "convergence trajectory (min 2)")
    ap.add_argument("--burst", type=int, default=8,
                    help="requests per tenant storm (storm scenario / "
                    "--kv-migrate)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="workload tenant count (--workers fleet mode and "
                    "--kv-migrate)")
    ap.add_argument("--long-context", action="store_true",
                    help="mixed-traffic long-context frontier: short-"
                    "request ITL/TTFT with and without background "
                    "--long-len prompts, unbudgeted vs --prefill-budget "
                    "(pushed live), through DirectServer + ragged rounds")
    ap.add_argument("--long-len", type=int, default=32768,
                    help="background long-prompt length in tokens "
                    "(--long-context)")
    ap.add_argument("--long-requests", type=int, default=2,
                    help="number of background long prompts "
                    "(--long-context)")
    ap.add_argument("--prefill-budget", type=int, default=512,
                    help="per-round prefill token budget for the budgeted "
                    "leg (--long-context); 0 disables")
    ap.add_argument("--timeline", action="store_true",
                    help="flight-recorder attribution: stamp a trace_id "
                    "per request and publish per-phase p50/p95 "
                    "(queue_wait/prefill/ttft/handoff/decode/e2e) for the "
                    "measured leg instead of a single opaque TTFT number; "
                    "also asserts outputs byte-identical recorder on vs "
                    "off. Composes with the default, --pd-split, and "
                    "--chaos modes")
    ap.add_argument("--fleet-heartbeat-s", type=float, default=0.5,
                    help="fleet-mode worker heartbeat cadence (summaries "
                    "ride heartbeats; production uses 30s)")
    add_platform_arg(ap)
    args = ap.parse_args()

    backend, model = resolve_backend_model(args)

    if args.pd_split:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--pd-split takes a single --arrival-rate (the "
                     "comparison axis is PD vs data-parallel)")
        run_pd_split(args, backend, model)
        return

    if args.overload:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--overload takes a single --arrival-rate (the paid "
                     "rate; the burst is fixed at 10x)")
        run_overload(args, backend, model)
        return

    if args.plane_scale:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--plane-scale takes a single --arrival-rate (the "
                     "sweep axis is the plane count)")
        run_plane_scale(args, backend, model)
        return

    if args.chaos:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--chaos takes a single --arrival-rate (the sweep "
                     "axis is the replica count)")
        run_chaos_fleet(args, backend, model)
        return

    if args.gray:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--gray takes a single --arrival-rate (the "
                     "comparison axis is defenses ON vs OFF)")
        run_gray(args, backend, model)
        return

    if args.io_chaos:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--io-chaos takes a single --arrival-rate (the "
                     "comparison axis is breakers ON vs OFF)")
        run_io_chaos(args, backend, model)
        return

    if args.kv_migrate:
        run_kv_migrate(args, backend, model)
        return

    if args.predictive:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--predictive takes a single --arrival-rate (the "
                     "comparison axes are calibrated-vs-static and "
                     "proactive-vs-reactive)")
        run_predictive(args, backend, model)
        return

    if args.workers >= 2:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--workers fleet mode takes a single --arrival-rate "
                     "(rate sweeps are a single-engine mode feature)")
        run_fleet(args, backend, model)
        return

    if args.spec:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--spec takes a single --arrival-rate (the sweep "
                     "axis is the forced acceptance rate)")
        run_spec_ab(args, backend, model)
        return

    if args.long_context:
        if args.arrival_rate and "," in str(args.arrival_rate):
            ap.error("--long-context takes a single --arrival-rate (the "
                     "comparison axis is budgeted vs unbudgeted)")
        run_long_context(args, backend, model)
        return

    from distributed_gpu_inference_tpu.worker.direct_server import (
        DirectServer,
    )
    from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

    llm = TPULLMEngine({
        "model": model,
        "max_batch_size": args.concurrency,
        "max_seq_len": args.prompt_len + args.max_tokens + 16,
        "quantization": args.quantization,
        "serving": {
            "max_horizon": args.max_horizon,
            "subwave": args.subwave,
            "interleave": args.interleave,
            "queue_limit": max(4096, args.requests * 2),
            "default_timeout_s": 600.0,
        },
    })
    llm.load_model()
    worker = BenchWorker(llm)
    ds = DirectServer(worker, host="127.0.0.1", port=0)
    ds.start()
    port = ds._runner.addresses[0][1]
    url = f"http://127.0.0.1:{port}"

    _warm(llm, args.prompt_len, llm.serving.batcher._levels,
          args.concurrency)
    prompts = synth_prompt_strings(args.requests, args.prompt_len,
                                   args.shared_prefix)

    rates = (
        [float(r) for r in str(args.arrival_rate).split(",")]
        if args.arrival_rate else [None]
    )
    try:
        for i, rate in enumerate(rates):
            if i > 0:
                llm.engine.manager.clear_cached()
            dep_results, dep_elapsed, dep_span = asyncio.run(_drive_http(
                url, prompts, args.max_tokens, rate, args.concurrency,
                args.seed, trace=args.timeline, collect_text=args.timeline,
            ))
            deployed = _summarize(dep_results, dep_elapsed, dep_span)
            out = {
                "benchmark": "worker_serving",
                "path": "direct_server+batcher_engine",
                "mode": "open_loop" if rate else "closed_loop",
                "model": model, "backend": backend,
                "requests": args.requests,
                "concurrency": args.concurrency,
                "prompt_len": args.prompt_len,
                "max_tokens": args.max_tokens,
                "arrival_rate_rps": rate,
                "subwave": args.subwave, "interleave": args.interleave,
                "max_horizon": args.max_horizon,
                "deployed": deployed,
            }
            stats = llm.serving.get_stats()   # one snapshot: keys coherent
            out["batcher"] = {
                k: stats.get(k)
                for k in ("decode_rounds", "avg_occupancy", "horizon",
                          "chunked_admissions", "batched_waves",
                          "queue_peak", "ragged_mode", "ragged_rounds",
                          "ragged_admissions")
            }
            if args.timeline:
                # per-phase attribution off the traced deployed leg, plus
                # an UNTRACED replay of the identical workload: the
                # recorder must never change what is generated
                out["timeline"] = _timeline_attribution(dep_results)
                llm.engine.manager.clear_cached()
                off_results, _, _ = asyncio.run(_drive_http(
                    url, prompts, args.max_tokens, rate, args.concurrency,
                    args.seed, collect_text=True,
                ))
                on_texts = [r.get("text") for r in dep_results
                            if r["status"] == 200]
                off_texts = [r.get("text") for r in off_results
                             if r["status"] == 200]
                out["timeline"]["outputs_identical_recorder_on_vs_off"] = (
                    len(on_texts) == len(off_texts) == len(prompts)
                    and on_texts == off_texts
                )
            if args.compare_legacy:
                # flip the LIVE batcher to the legacy wave/chunk-
                # interleaved admission path (the remote-config A/B a
                # fleet would push), replay the identical workload, and
                # flip back. The CLI knob values shape the legacy leg;
                # the ragged leg above ignored them by construction.
                llm.engine.manager.clear_cached()
                llm.apply_serving_config({"ragged": False})
                legacy = _summarize(*asyncio.run(_drive_http(
                    url, prompts, args.max_tokens, rate, args.concurrency,
                    args.seed,
                )))
                # back to ragged for any following sweep rate (True ≡ the
                # auto default on this engine; reconfigure ignores None)
                llm.apply_serving_config({"ragged": True})
                out["legacy_knob_tuned"] = legacy
                ratios = {}
                for pct in ("p50", "p95"):
                    r_t = (deployed["ttft_ms"] or {}).get(pct)
                    l_t = (legacy["ttft_ms"] or {}).get(pct)
                    if r_t and l_t:
                        ratios[f"ttft_{pct}_ragged_over_legacy"] = round(
                            r_t / l_t, 3
                        )
                if legacy["decode_tokens_per_s"]:
                    ratios["tokens_per_s_ragged_over_legacy"] = round(
                        deployed["decode_tokens_per_s"]
                        / legacy["decode_tokens_per_s"], 3
                    )
                out["ragged_vs_legacy"] = ratios
            if args.compare:
                llm.engine.manager.clear_cached()
                bench = _summarize(*asyncio.run(_drive_inproc(
                    llm, prompts, args.max_tokens, rate, args.concurrency,
                    args.seed,
                )))
                out["bench_only"] = bench
                d50 = (deployed["ttft_ms"] or {}).get("p50")
                b50 = (bench["ttft_ms"] or {}).get("p50")
                if d50 and b50:
                    out["ttft_p50_ratio"] = round(d50 / b50, 3)
                if bench["decode_tokens_per_s"]:
                    out["tokens_per_s_ratio"] = round(
                        deployed["decode_tokens_per_s"]
                        / bench["decode_tokens_per_s"], 3
                    )
            emit(out)
    finally:
        ds.stop()
        llm.unload()


if __name__ == "__main__":
    main()
