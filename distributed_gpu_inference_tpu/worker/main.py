"""Worker runtime: register → load engines → heartbeat + poll → process jobs.

Behavioral parity with the reference's ``worker/main.py`` (Worker:28):

- ``_register``:83 — verify persisted credentials first, re-register when
  stale, then fetch server-pushed remote config (:151).
- ``_load_engines``:234 — one engine per supported task type from the
  registry; a task type whose engine cannot load is dropped, not fatal.
- ``_heartbeat_loop``:263 — background thread, every ``heartbeat_interval_s``;
  a ``config_changed`` flag in the response triggers a remote-config refetch
  (reference ``main.py:290-301``).
- ``_main_loop``:313 — poll every ``poll_interval_s``; fetch → process →
  complete; load-control gates (acceptance rate, hourly cap, working hours,
  cooldown — server-pushed, ``worker_config.py`` values win over local).
- ``request_shutdown``:444 — graceful drain: stop accepting, finish the
  running job, tell the server ``going-offline`` then ``offline`` (which
  requeues anything still assigned); SIGTERM/SIGINT wired (:410-411).

TPU-first deltas: capability probing reports a :class:`TpuTopology` from
``jax.devices()`` (chip generation, chip count, HBM) instead of nvidia-smi;
engines are the in-repo JAX engines, so "loading" compiles jitted graphs
rather than importing a CUDA backend.
"""

from __future__ import annotations

import logging
import random
import re
import signal
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..runtime.flight import STARTUP_PHASES, adopt_phases, phase
from ..utils.config import WorkerConfig
from ..utils.data_structures import TpuTopology, WorkerState
from ..utils.device import ChipSpec, chip_spec
from .api_client import APIClient, APIError
from .engines import EngineLoadError, create_engine
from .engines.base import JobMigrated
from .machine_id import MachineFingerprint

log = logging.getLogger("tpu_worker")


def probe_tpu_runtime() -> dict:
    """Environment-level TPU runtime probe — the analogue of the reference
    wizard's nvidia-smi/CUDA-version detection (``cli.py:77-133,298-651``),
    but for libtpu: works BEFORE any jax backend initializes (a probe that
    must first dial the chip cannot diagnose a broken runtime).

    Returns {libtpu, accel_devices, accelerator_type, worker_id, hosts} where
    ``accelerator_type`` is the platform-provided string (e.g.
    ``v5litepod-16``) GKE/GCE export via TPU_ACCELERATOR_TYPE.
    """
    import glob
    import importlib.util
    import os

    libtpu = bool(
        os.environ.get("TPU_LIBRARY_PATH")
        or importlib.util.find_spec("libtpu") is not None
        or glob.glob("/usr/lib/libtpu*")
        or glob.glob("/lib/libtpu*")
    )
    accel = sorted(glob.glob("/dev/accel*")) + sorted(glob.glob("/dev/vfio/*"))
    return {
        "libtpu": libtpu,
        "accel_devices": accel,
        "accelerator_type": os.environ.get("TPU_ACCELERATOR_TYPE")
        or os.environ.get("TPU_TYPE") or "",
        "worker_id": os.environ.get("TPU_WORKER_ID", ""),
        "hosts": (os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
                  if os.environ.get("TPU_WORKER_HOSTNAMES") else []),
    }


def _chip_topology(spec: ChipSpec, num_chips: int,
                   mesh_shape: tuple) -> TpuTopology:
    return TpuTopology(
        chip_type=spec.chip_type, num_chips=num_chips,
        hbm_gb_per_chip=spec.hbm_gb, mesh_shape=mesh_shape,
        mesh_axis_names=tuple(f"ici{i}" for i in range(len(mesh_shape)))
        if len(mesh_shape) > 1 else ("data",),
        ici_bandwidth_gbps=spec.ici_gbps,
        peak_bf16_tflops=spec.peak_bf16_tflops,
    )


def probe_topology() -> TpuTopology:
    """Describe local accelerators (the TPU analogue of the reference's
    nvidia-smi probe, ``cli.py:77``): jax device enumeration with physical
    mesh-shape discovery from device coords, figures from the published
    table keyed by ``device_kind`` (``utils/device.py``). The result rides
    in worker registration (``Worker.register`` → ``topology``) so
    schedulers see generation, chip count, HBM, and mesh shape.

    Nothing here guesses. An accelerator the table does not know is an
    error, not a v5e. A CPU backend — which JAX also falls into unasked
    when it finds no chip — is reported as ``cpu`` with no device figures.
    A backend that fails to start raises, unless the environment itself
    declares a TPU host (libtpu + accelerator type): that host registers
    as what the platform says it is, so a broken driver shows up as a TPU
    worker needing repair."""
    runtime = probe_tpu_runtime()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError:
        declared = runtime["accelerator_type"] if runtime["libtpu"] else ""
        spec = chip_spec(declared)
        if spec is None:
            raise
        m = re.search(r"-(\d+)$", declared)
        chips = int(m.group(1)) if m else 1
        return _chip_topology(spec, chips, (chips,))
    kind = devices[0].device_kind
    if kind.lower() == "cpu":
        return TpuTopology(
            chip_type="cpu", num_chips=len(devices), hbm_gb_per_chip=0.0,
            ici_bandwidth_gbps=0.0, dcn_bandwidth_gbps=0.0,
            peak_bf16_tflops=0.0,
        )
    spec = chip_spec(kind) or chip_spec(runtime["accelerator_type"])
    if spec is None:
        raise RuntimeError(
            f"unknown accelerator {kind!r}: no published figures for it in "
            "utils/device.py — add the chip there instead of borrowing "
            "another chip's numbers"
        )
    # physical mesh from device coords (bounding box of the slice); a flat
    # axis when the devices carry no coords or the box is not the slice
    dims: tuple = (len(devices),)
    coords = [getattr(d, "coords", None) for d in devices]
    if all(c is not None for c in coords):
        box = tuple(
            max(c[i] for c in coords) - min(c[i] for c in coords) + 1
            for i in range(len(coords[0]))
        )
        box = tuple(d for d in box if d > 1) or (len(devices),)
        if int(np.prod(box)) == len(devices):
            dims = box
    return _chip_topology(spec, len(devices), dims)


class _PDReceiverShim:
    """Stage adapter for a PD KV-receiving DataPlaneServer: only /health and
    /kv/transfer are served; pipeline-session endpoints 404."""

    def __init__(self, llm_engine: Any) -> None:
        self._eng = llm_engine

    def health(self) -> Dict[str, Any]:
        return {**self._eng.health(), "pd_kv_receiver": True}

    def create_session(self, *a: Any, **kw: Any) -> None:
        raise KeyError("not a pipeline stage (PD KV receiver only)")

    def close_session(self, *a: Any, **kw: Any) -> None:
        raise KeyError("not a pipeline stage (PD KV receiver only)")

    def forward(self, *a: Any, **kw: Any) -> None:
        raise KeyError("not a pipeline stage (PD KV receiver only)")


class Worker:
    """The volunteer/fleet worker process (reference ``Worker``, main.py:28)."""

    def __init__(
        self,
        config: WorkerConfig,
        api: Optional[APIClient] = None,
        on_credentials: Optional[Callable[[Dict[str, str]], None]] = None,
        topology: Optional[TpuTopology] = None,
    ) -> None:
        self.config = config
        self.api = api or APIClient(
            # plane cohort: primary + fallbacks become the failover list
            # (a single URL keeps the historical one-plane behavior)
            [config.server.url, *(config.server.fallback_urls or [])],
            worker_id=config.server.worker_id,
            auth_token=config.server.auth_token,
            refresh_token=config.server.refresh_token,
            signing_secret=config.server.signing_secret,
            timeout_s=config.server.request_timeout_s,
        )
        self._on_credentials = on_credentials
        self.topology = topology or probe_topology()
        self.engines: Dict[str, Any] = {}
        self.state = WorkerState.INITIALIZING
        self.current_job_id: Optional[str] = None

        self._shutdown = threading.Event()
        self._drained = threading.Event()
        self._direct: Optional[Any] = None
        # worker-measured round-trip of the PREVIOUS heartbeat (ms) —
        # shipped on the next beat as a control-path latency sample for
        # the plane's gray-failure health scoring
        self._hb_rtt_ms: Optional[float] = None
        # guards IDLE→BUSY transitions so the poll loop and the direct server
        # can never run engine.inference concurrently on the same engines
        self._state_lock = threading.Lock()
        # shared serving claims (batcher-backed engines): count of direct
        # requests / queued jobs currently sharing decode rounds — they
        # coexist with each other up to load_control.max_concurrent_jobs
        # but never with an exclusive claim (PD stages, legacy engines)
        self._serving_jobs = 0
        self._job_pool: Optional[Any] = None
        self._job_pool_width = 16
        self._pool_inflight = 0
        self._active_jobs: set = set()
        # exclusive-needing work (PD stage / non-llm) was fetched while
        # other shared claims were live: back off from polling until this
        # deadline (or until the shared load drains) instead of
        # claim/fetch/releasing the same head-of-queue job every interval
        self._exclusive_defer_until = 0.0
        self._heartbeat_thread: Optional[threading.Thread] = None
        self._hour_window: List[float] = []       # job-start times, rolling hour
        self._last_job_done_at = 0.0
        self._released_once: set = set()          # jobs we declined once
        self._rng = random.Random(0xC0FFEE)
        # per-PROCESS incarnation id: registration sends it so the plane
        # can tell a fast restart (new boot_id on the same fingerprint →
        # the old incarnation's RUNNING jobs requeue immediately) from a
        # credential-blip re-register by the same live process (same
        # boot_id → running work stays put)
        self.boot_id = uuid.uuid4().hex
        # plane cohort: identity of the control-plane replica that answered
        # our last heartbeat (None single-plane / pre-first-beat). A CHANGE
        # means we failed over — the new plane holds no ACKed base for our
        # prefix-summary delta chain, so a full snapshot must be resynced.
        self._last_plane_id: Optional[str] = None
        self.stats: Dict[str, Any] = {
            "jobs_completed": 0, "jobs_failed": 0, "jobs_rejected": 0,
            "jobs_migrated": 0,
            "heartbeats": 0, "config_refetches": 0,
        }

    # -- registration (reference main.py:83-165) -----------------------------

    def register(self) -> None:
        if self.api.worker_id and self.api.auth_token and \
                self.api.verify_credentials():
            log.info("existing credentials valid for %s", self.api.worker_id)
        else:
            info = {
                "name": self.config.name,
                "region": self.config.region,
                "machine_fingerprint": MachineFingerprint().get_or_create(),
                "supported_types": list(self.config.task_types),
                "topology": self.topology.to_dict(),
                # the row's own columns (worker list, remote-config HBM
                # caps) — the plane defaults them to one 16 GB chip
                "chip_generation": self.topology.chip_type,
                "num_chips": self.topology.num_chips,
                "hbm_gb_per_chip": self.topology.hbm_gb_per_chip,
                "mesh_shape": list(self.topology.mesh_shape),
                "supports_direct": self.config.direct.enabled,
                "direct_url": self.config.direct.public_url,
                "role": self.config.role,
                "data_plane_url": self.config.pd_data_plane_url,
                "boot_id": self.boot_id,
            }
            data = self.api.register(info)
            if self._on_credentials:
                self._on_credentials(
                    {
                        "worker_id": data["worker_id"],
                        "auth_token": data["auth_token"],
                        "refresh_token": data["refresh_token"],
                        "signing_secret": data["signing_secret"],
                    }
                )
            log.info("registered as %s", data["worker_id"])
        self._fetch_remote_config()

    def _fetch_remote_config(self) -> None:
        """Server-pushed load control wins over local values
        (reference main.py:151-165; worker_config.py:85-107)."""
        try:
            remote = self.api.fetch_remote_config()
        except APIError as exc:
            log.warning("remote config fetch failed: %s", exc)
            return
        self.stats["config_refetches"] += 1
        self.config.config_version = int(remote.get("version", 0))
        lc = remote.get("load_control") or {}
        for key in (
            "acceptance_rate", "max_concurrent_jobs", "max_jobs_per_hour",
            "hbm_limit_fraction", "cooldown_seconds",
        ):
            if key in lc and lc[key] is not None:
                setattr(self.config.load_control, key, lc[key])
        if lc.get("working_hours"):
            self.config.load_control.working_hours = tuple(lc["working_hours"])
        if lc.get("job_type_weights"):
            self.config.load_control.job_type_weights = dict(
                lc["job_type_weights"]
            )
        serving = remote.get("serving")
        if isinstance(serving, dict) and serving:
            # server-pushed SLO retune: batcher knobs (max_horizon,
            # max_wait_ms, queue limits) apply to LIVE batchers between
            # rounds — no engine reload, no dropped requests
            for eng in self.engines.values():
                apply = getattr(eng, "apply_serving_config", None)
                if apply is None:
                    continue
                try:
                    apply(dict(serving))
                except Exception:  # noqa: BLE001 — a bad push must not kill the worker
                    log.warning("serving config push rejected",
                                exc_info=True)

    # -- engines (reference main.py:234-261) ---------------------------------

    def load_engines(self) -> None:
        loaded: List[str] = []
        for task_type in list(self.config.task_types):
            try:
                cfg = self.config.engine_for(task_type)
                eng = create_engine(task_type, cfg.model_dump())
                eng.load_model()
                core = getattr(eng, "engine", None)
                if self.config.role != "hybrid" and core is not None and \
                        getattr(core, "stats", {}).get("kv_layout") in (
                            "latent", "latent+index", "hybrid", "kv+index",
                            "kv+window", "kv+state", "latent+window",
                            "latent+index+window"):
                    # a prefill / decode role hands K/V pages to a peer
                    # (runtime/kv_handoff.py require_kv_pages): refused
                    # here, where the worker is configured
                    raise EngineLoadError(
                        f"role {self.config.role!r}: the PD handoff carries "
                        f"K/V pages, {cfg.model} caches latent pages (and, "
                        "a hybrid model, state rows), index keys or state "
                        "rows beside its K/V pages, or pages per layer kind")
                self.engines[task_type] = eng
                loaded.append(task_type)
            except (EngineLoadError, KeyError) as exc:
                log.warning("dropping task type %s: %s", task_type, exc)
        self.config.task_types = loaded
        if not loaded:
            raise EngineLoadError("no engine loaded for any task type")

    # -- heartbeat (reference main.py:263-311) -------------------------------

    def _spec_engine_stats(self) -> Optional[Dict[str, Any]]:
        """Speculation-efficiency counters of any engine running the
        integrated speculative decode mode — ride the heartbeat so the
        control plane's ``/metrics`` surfaces accept-rate and tokens-per-
        step per worker. None when nothing speculates (no payload bloat)."""
        out: Dict[str, Any] = {}
        for eng in self.engines.values():
            core = getattr(eng, "engine", None)
            if core is None or \
                    getattr(getattr(core, "cfg", None), "speculative",
                            None) is None:
                continue
            s = core.get_stats()
            for k in ("spec_accepted", "spec_drafted", "spec_slot_steps",
                      "spec_emitted"):
                out[k] = out.get(k, 0) + int(s.get(k, 0) or 0)
        if not out:
            return None
        # rates derived from the SUMMED counters so the gauges always agree
        # with the counter ratios when several engines speculate
        out["spec_accept_rate"] = (
            out["spec_accepted"] / out["spec_drafted"]
            if out.get("spec_drafted") else 0.0
        )
        out["spec_tokens_per_step"] = (
            out["spec_emitted"] / out["spec_slot_steps"]
            if out.get("spec_slot_steps") else 0.0
        )
        return out

    def _pressure_engine_stats(self) -> Optional[Dict[str, Any]]:
        """KV-pressure recovery counters of every loaded paged engine
        (cumulative preemptions / resumes / pressure events) — ride the
        heartbeat so the control plane's ``/metrics`` shows which workers
        run their pools hot. None when no loaded engine exposes the
        counters (payload stays lean for non-LLM workers)."""
        out: Dict[str, int] = {}
        for eng in self.engines.values():
            core = getattr(eng, "engine", None)
            stats = getattr(core, "stats", None)
            if isinstance(stats, dict):
                for k in ("preemptions", "resumes", "kv_pressure_events"):
                    if k in stats:
                        out[k] = out.get(k, 0) + int(stats.get(k, 0) or 0)
            # abandoned streamed-handoff sessions purged by the engine's
            # HandoffReceiver → kv_handoff_sessions_purged_total
            purged = getattr(eng, "handoff_sessions_purged", None)
            if purged:
                out["kv_handoff_sessions_purged"] = (
                    out.get("kv_handoff_sessions_purged", 0) + int(purged)
                )
        return out or None

    def _pd_engine_stats(self) -> Optional[Dict[str, Any]]:
        """PD handoff lifecycle counters of every loaded engine (sender
        outcomes, piece retries, receiver abort/purge reasons) — nested
        under heartbeat ``engine_stats["pd"]`` so the control plane's
        ``/metrics`` surfaces ``pd_handoffs_total{outcome}`` and
        ``pd_handoff_bytes_total`` per worker. None when no engine has
        touched a handoff (payload stays lean off the PD path)."""
        out: Dict[str, int] = {}
        for eng in self.engines.values():
            fn = getattr(eng, "pd_wire_stats", None)
            if fn is None:
                continue
            try:
                s = fn()
            except Exception:  # noqa: BLE001 — never break the heartbeat
                continue
            for k, v in (s or {}).items():
                out[k] = out.get(k, 0) + int(v)
        return out or None

    def _kv_migrate_engine_stats(self) -> Optional[Dict[str, Any]]:
        """Cluster-KV migration counters of every loaded engine (pull
        outcomes, export service, bytes) — nested under heartbeat
        ``engine_stats["kv_migrate"]`` so the control plane's ``/metrics``
        surfaces ``kv_migrations_total{outcome}`` and
        ``kv_migration_bytes_total`` per worker. None when nothing ever
        migrated (payload stays lean)."""
        out: Dict[str, int] = {}
        for eng in self.engines.values():
            fn = getattr(eng, "kv_migrate_wire_stats", None)
            if fn is None:
                continue
            try:
                s = fn()
            except Exception:  # noqa: BLE001 — never break the heartbeat
                continue
            for k, v in (s or {}).items():
                out[k] = out.get(k, 0) + int(v)
        return out or None

    def _kv_spill_engine_stats(self) -> Optional[Dict[str, Any]]:
        """Spill-tier IO health of every loaded engine (put/get errors,
        corrupt-entry quarantines, breaker states/trips, refused corrupt
        checkpoints) — nested under heartbeat ``engine_stats["kv_spill"]``
        so the control plane's ``/metrics`` surfaces
        ``kv_spill_errors_total{tier}``, ``spill_quarantined_total`` and
        ``io_breaker_state{tier}`` per worker. None while every counter is
        zero and all breakers are closed (payload stays lean)."""
        out: Dict[str, int] = {}
        for eng in self.engines.values():
            fn = getattr(eng, "kv_spill_wire_stats", None)
            if fn is None:
                continue
            try:
                s = fn()
            except Exception:  # noqa: BLE001 — never break the heartbeat
                continue
            for k, v in (s or {}).items():
                if k.endswith("_state"):
                    # breaker state is a gauge: report the sickest engine
                    out[k] = max(out.get(k, 0), int(v))
                else:
                    out[k] = out.get(k, 0) + int(v)
        return out or None

    def _batcher_stats(self) -> Optional[Dict[str, Any]]:
        """Live batcher serving stats of every batcher-backed engine
        (occupancy, queue depth, chunked admissions, preemption counters)
        — nested under heartbeat ``engine_stats["batcher"]`` so the control
        plane's ``/metrics`` shows how hot each worker's batch runs. None
        when no engine serves through a batcher (payload stays lean)."""
        out: Dict[str, Any] = {}
        for eng in self.engines.values():
            fn = getattr(eng, "serving_stats", None)
            if fn is None:
                continue
            try:
                s = fn()
            except Exception:  # noqa: BLE001 — never break the heartbeat
                continue
            if not s:
                continue
            for k in ("submitted", "completed", "rejected", "admitted",
                      "decode_rounds", "preemptions", "resumes",
                      "preempted_too_often", "cancelled", "migrated",
                      "abandoned", "abandoned_predictive"):
                out[k] = out.get(k, 0) + int(s.get(k, 0) or 0)
            for k in ("queue_depth", "active_slots"):
                out[k] = out.get(k, 0) + int(s.get(k, 0) or 0)
            # round spans' counters (runtime/flight.py span): the loop's
            # seconds outside engine rounds, the scans by level, and from
            # the engine the seconds of each phase of a round and the
            # compile requests (the process's: not summed over engines;
            # after start they rise only at a shape nothing warmed)
            core = getattr(eng, "engine", None)
            es = core.get_stats() if core is not None else {}
            for k in ("compiles", "compile_s", "compile_trace_s",
                      "compile_lower_s", "compile_misses"):
                out[k] = max(out.get(k, 0), round(es.get(k, 0), 3))
            # what the start cost, a phase (the slowest engine's)
            started = es.get("startup") or {}
            for k, label in STARTUP_PHASES.items():
                if k + "_s" in started:
                    out[f"startup_{label}_s"] = max(
                        out.get(f"startup_{label}_s", 0),
                        round(started[k + "_s"], 3))
            for k in ("ragged_kv_path", "kv_layout"):
                if es.get(k):
                    out[k] = es[k]
            for k, src in (("between_rounds_s", s), ("admit_s", s),
                           ("deliver_s", s), ("round_host_exposed_s", s),
                           ("round_build_s", es),
                           ("round_dispatch_s", es),
                           ("round_readback_s", es), ("round_commit_s", es)):
                out[k] = round(out.get(k, 0.0) + float(src.get(k, 0) or 0), 6)
            # scans by level (scans_t<T>) and by why they got their length
            # (scans_<reason>), the row-steps they ran past a row's end,
            # the scans dispatched behind an unread one (scans_chained)
            # and why the others were read first (chain_breaks_<reason>);
            # fresh admissions and those that ran beside an unread scan,
            # the ragged rounds that went out behind one
            for k in s:
                if k in ("between_rounds", "scan_row_steps_masked",
                         "ragged_admissions", "admissions_ahead",
                         "ragged_rounds_chained") \
                        or k.startswith(("scans_", "chain_breaks_")):
                    out[k] = out.get(k, 0) + int(s[k] or 0)
                elif k.startswith("longest_wait_s_"):
                    # streams by the round that ended their longest wait
                    # (longest_wait_<cause>), and those waits' seconds
                    out[k] = round(out.get(k, 0.0) + float(s[k] or 0), 6)
                elif k.startswith("longest_wait_"):
                    out[k] = out.get(k, 0) + int(s[k] or 0)
            # the routed expert layers' counters (MoE engines only)
            # and a latent-attention engine's scan counters (mla_*); a
            # hybrid engine's state pool and what its kernels were handed
            for k in es:
                if k.startswith(("moe_", "mla_", "kda_", "ssd_")) or k in (
                        "state_binds", "prefix_hits_without_state") or (
                        k.startswith("index_") and k != "index_pool_bytes"):
                    out[k] = out.get(k, 0) + int(es[k] or 0)
                elif k in ("state_pool_bytes", "state_rows",
                           "index_pool_bytes"):
                    out[k] = int(es[k])
            if s.get("avg_occupancy") is not None:
                out["avg_occupancy"] = round(
                    float(s.get("avg_occupancy") or 0.0), 3
                )
            # the horizon rule's gauges: the level the host's cost is
            # amortised at, and the two measured times that choose it
            for k in ("horizon", "step_latency_ema_ms", "round_host_ema_ms"):
                if s.get(k) is not None:
                    out[k] = round(float(s[k]), 3)
        if out:
            # shared-claim ceiling: lets the scheduler GRADE this worker's
            # load (active + queued vs capacity) instead of reading the
            # binary BUSY flag that lies for concurrent batcher serving
            out["capacity"] = self.serving_capacity()
        return out or None

    def _flight_engine_stats(self) -> Optional[Dict[str, Any]]:
        """Flight-recorder payload of every loaded engine (cumulative
        timeline/drop counters + the bounded ring of recently-completed
        timelines) — nested under heartbeat ``engine_stats["flight"]``.
        The plane delta-anchors the counters and idempotently merges the
        ring (direct streams never pass complete_job, so this is their
        only route to the merged timeline store). None when nothing was
        ever traced (payload stays lean)."""
        out: Dict[str, Any] = {}
        recent: List[Dict[str, Any]] = []
        for eng in self.engines.values():
            fn = getattr(eng, "flight_wire_stats", None)
            if fn is None:
                continue
            try:
                s = fn()
            except Exception:  # noqa: BLE001 — never break the heartbeat
                continue
            if not s:
                continue
            for k in ("timelines", "events_dropped"):
                out[k] = out.get(k, 0) + int(s.get(k, 0) or 0)
            r = s.get("recent")
            if isinstance(r, list):
                recent.extend(r)
        if not out:
            return None
        if recent:
            out["recent"] = recent[-16:]
        return out

    def _prefix_summary_payload(self) -> Optional[tuple]:
        """(engine, wire payload) of the first engine advertising a radix
        summary this beat — None when every engine is already in sync
        with the control plane (no payload bloat)."""
        for eng in self.engines.values():
            fn = getattr(eng, "prefix_summary_wire", None)
            if fn is None:
                continue
            try:
                payload = fn()
            except Exception:  # noqa: BLE001 — never break the heartbeat
                continue
            if payload:
                return eng, payload
        return None

    def _collect_checkpoints(self) -> List[Dict[str, Any]]:
        """Portable checkpoints of every in-flight generation across loaded
        engines — piggybacked on heartbeats so a sequence survives this
        worker's death: the control plane attaches the latest checkpoint to
        the requeued job / adoptable stream and the replacement worker
        resumes instead of regenerating."""
        out: List[Dict[str, Any]] = []
        for eng in self.engines.values():
            fn = getattr(eng, "checkpoint_live", None)
            if fn is None:
                continue
            try:
                out.extend(fn() or [])
            except Exception:  # noqa: BLE001 — never break the heartbeat
                log.debug("checkpoint collection failed", exc_info=True)
        return out

    def _heartbeat_once(self) -> None:
        summary_eng = None
        for eng in self.engines.values():
            # PD housekeeping on the heartbeat cadence: adopted slots
            # whose decode stage never came (flow re-prefilled elsewhere)
            # age out instead of pinning KV until the next handoff message
            fn = getattr(eng, "pd_maintain", None)
            if fn is not None:
                try:
                    fn()
                except Exception:  # noqa: BLE001 — never break the beat
                    pass
        try:
            extra: Dict[str, Any] = {}
            engine_stats: Dict[str, Any] = {}
            spec_stats = self._spec_engine_stats()
            if spec_stats:
                engine_stats.update(spec_stats)
            pressure_stats = self._pressure_engine_stats()
            if pressure_stats:
                engine_stats.update(pressure_stats)
            batcher_stats = self._batcher_stats()
            if batcher_stats:
                engine_stats["batcher"] = batcher_stats
            pd_stats = self._pd_engine_stats()
            if pd_stats:
                engine_stats["pd"] = pd_stats
            kv_spill_stats = self._kv_spill_engine_stats()
            if kv_spill_stats:
                engine_stats["kv_spill"] = kv_spill_stats
            kvmig_stats = self._kv_migrate_engine_stats()
            if kvmig_stats:
                engine_stats["kv_migrate"] = kvmig_stats
            flight_stats = self._flight_engine_stats()
            if flight_stats:
                engine_stats["flight"] = flight_stats
            direct = self._direct
            if direct is not None:
                # gray-failure telemetry: per-request direct latencies /
                # served-5xx deltas feed the plane's health scoring; the
                # cumulative hedge-cancel counter delta-anchors
                # hedges_total{outcome="cancelled"}. Omitted while empty
                # so quiet beats stay byte-identical to pre-round ones.
                try:
                    ds = direct.wire_stats()
                except Exception:  # noqa: BLE001 — never break the beat
                    ds = None
                if ds and (ds.get("recent_ms") or ds.get("new_errors")
                           or ds.get("hedge_cancels")
                           or ds.get("sse_events")):
                    engine_stats["direct"] = ds
            summary = self._prefix_summary_payload()
            if summary is not None:
                # radix summary (full or delta) for cache-aware routing;
                # committed as server-known only after the round-trip
                # succeeds (deltas are diffed against an ACKed base)
                summary_eng, engine_stats["prefix_summary"] = summary
            if any(getattr(eng, "prefix_hot", None) is not None
                   for eng in self.engines.values()):
                # channel-alive marker: lets the server keep our advertised
                # summary fresh on payload-less beats (in sync) without
                # immortalizing summaries of workers that restarted with
                # the channel off
                engine_stats["prefix_summary_live"] = True
            if engine_stats:
                extra["engine_stats"] = engine_stats
            checkpoints = self._collect_checkpoints()
            if checkpoints:
                extra["checkpoints"] = checkpoints
            with self._state_lock:
                active = list(self._active_jobs)
                current_job_id = self.current_job_id
            if len(active) > 1:
                # concurrent shared jobs: current_job_id can only carry one
                # claim — report the full set so the server's stale-claim
                # guard covers every in-flight job, not an arbitrary one
                extra["active_job_ids"] = active
            if self._hb_rtt_ms is not None:
                # previous beat's measured round-trip: a worker whose
                # control path has gone gray (slow NIC, throttled host)
                # reports it here even when no direct traffic lands
                extra["hb_rtt_ms"] = round(self._hb_rtt_ms, 3)
            hb_t0 = time.perf_counter()
            resp = self.api.heartbeat(
                status=self.state.value,
                config_version=self.config.config_version,
                current_job_id=current_job_id,
                loaded_models=[
                    getattr(e, "model_name", None) or str(type(e).__name__)
                    for e in self.engines.values()
                ],
                stats={
                    k: self.stats[k]
                    for k in ("jobs_completed", "jobs_failed")
                },
                **extra,
            )
            self._hb_rtt_ms = (time.perf_counter() - hb_t0) * 1000.0
            self.stats["heartbeats"] += 1
            if summary_eng is not None:
                if resp.get("prefix_summary_applied") is False:
                    # statically un-ingestable (version/basis skew): stop
                    # shipping summaries this plane can never apply
                    summary_eng.prefix_summary_disable()
                elif resp.get("prefix_summary_resync") is False:
                    # explicit "applied": commit the pending snapshot
                    summary_eng.prefix_summary_ack()
                else:
                    # asked to resync, OR the server never answered for
                    # the payload (engine_stats dropped oversize, legacy
                    # plane): acking would commit a base the server does
                    # not hold — fall back to a full snapshot
                    summary_eng.prefix_summary_resync()
                summary_eng = None
            plane_id = resp.get("plane_id")
            if isinstance(plane_id, str) and plane_id:
                if self._last_plane_id is not None \
                        and plane_id != self._last_plane_id:
                    # plane failover: a DIFFERENT replica answered this
                    # beat. Its registry has no ACKed base for our delta
                    # chain (and may hold nothing at all for us) — force a
                    # full-snapshot resync now, even on in-sync beats that
                    # carry no payload, so affinity routing converges
                    # within one round-trip instead of staying blind until
                    # the next cache mutation. Runs AFTER the ack block:
                    # an ack from the new plane must not commit a base it
                    # only just learned.
                    log.info(
                        "control plane changed (%s -> %s); resyncing "
                        "prefix summary", self._last_plane_id, plane_id,
                    )
                    self.stats["plane_failovers"] = \
                        self.stats.get("plane_failovers", 0) + 1
                    for eng in self.engines.values():
                        fn = getattr(eng, "prefix_summary_resync", None)
                        if fn is not None:
                            try:
                                fn()
                            except Exception:  # noqa: BLE001 — advisory
                                pass
                self._last_plane_id = plane_id
            hints = resp.get("kv_replicate")
            if hints:
                # proactive prefix replication (round 20): the plane
                # predicts a storm for prefixes we don't hold — hand the
                # hints to the first migrate-capable engine, which pulls
                # on a daemon thread under the reactive driver's own
                # budget/backoff (never in this heartbeat loop)
                for eng in self.engines.values():
                    fn = getattr(eng, "kv_replicate", None)
                    if fn is None:
                        continue
                    try:
                        if fn(hints):
                            self.stats["kv_replicate_hints"] = \
                                self.stats.get("kv_replicate_hints", 0) \
                                + len(hints)
                            break
                    except Exception:  # noqa: BLE001 — advisory prefetch
                        pass
            if resp.get("stale_job") and self.current_job_id:
                # the server requeued our claim (we looked dead): the
                # in-flight inference cannot be cancelled mid-graph, but
                # flag it loudly — the eventual complete_job will hit the
                # 409/duplicate path and the result will be discarded
                log.warning(
                    "server reports job %s is no longer ours (requeued "
                    "after a heartbeat gap); finishing as zombie work",
                    self.current_job_id,
                )
                self.stats["stale_claims"] = \
                    self.stats.get("stale_claims", 0) + 1
            for jid in resp.get("stale_jobs") or []:
                log.warning(
                    "server reports job %s is no longer ours (requeued "
                    "after a heartbeat gap); finishing as zombie work",
                    jid,
                )
                self.stats["stale_claims"] = \
                    self.stats.get("stale_claims", 0) + 1
            if resp.get("config_changed"):
                self._fetch_remote_config()
        except APIError as exc:
            if summary_eng is not None:
                # the beat carrying our summary delta was lost: the server
                # never applied it, so the next delta's base would be wrong
                # — fall back to a full snapshot
                try:
                    summary_eng.prefix_summary_resync()
                except Exception:  # noqa: BLE001
                    pass
            if exc.status == 401:
                try:
                    self.api.refresh_credentials()
                except APIError:
                    log.error("token refresh failed; re-registering")
                    self.api.auth_token = None
                    try:
                        self.register()
                    except APIError as reg_exc:
                        log.error("re-registration failed: %s", reg_exc)
            else:
                log.warning("heartbeat failed: %s", exc)

    def _heartbeat_loop(self) -> None:
        while not self._shutdown.wait(self.config.heartbeat_interval_s):
            try:
                self._heartbeat_once()
            except Exception:  # noqa: BLE001 - the thread must survive
                # outages (even re-registration failing); next tick retries
                log.exception("heartbeat iteration failed")

    # -- load control (reference worker_config.py:195, main loop gates) ------

    def gates_open(self, now: Optional[float] = None) -> bool:
        """Job-independent load-control gates, checked BEFORE claiming a job
        so a gated worker never pulls work it will bounce back (working
        hours, cooldown, hourly cap, global acceptance sampling)."""
        lc = self.config.load_control
        now = time.time() if now is None else now
        if lc.working_hours:
            start_h, end_h = lc.working_hours
            hour = time.localtime(now).tm_hour
            inside = (
                start_h <= hour < end_h if start_h <= end_h
                else hour >= start_h or hour < end_h
            )
            if not inside:
                return False
        if lc.cooldown_seconds > 0 and \
                now - self._last_job_done_at < lc.cooldown_seconds:
            return False
        if lc.max_jobs_per_hour > 0:
            with self._state_lock:
                # prune + read under the lock: pool/direct threads append
                # concurrently via note_job_done, and a rebind would drop
                # their append on the floor
                self._hour_window = [
                    t for t in self._hour_window if now - t < 3600
                ]
                if len(self._hour_window) >= lc.max_jobs_per_hour:
                    return False
        if lc.acceptance_rate < 1.0 and self._rng.random() > lc.acceptance_rate:
            return False
        return True

    def should_accept_job(self, job: Dict[str, Any],
                          now: Optional[float] = None) -> bool:
        """Full admission check (gates + per-type weight). The type-weight
        throttle is one-shot per job: a job this worker already released once
        is accepted on re-encounter, so a probabilistic throttle can delay
        head-of-queue work but never starve it (release→re-claim ping-pong)."""
        if not self.gates_open(now=now):
            return False
        lc = self.config.load_control
        job_id = job.get("id")
        if job_id and job_id in self._released_once:
            return True
        weight = lc.job_type_weights.get(job.get("type", ""), 1.0)
        if weight < 1.0 and self._rng.random() > weight:
            return False
        return True

    def note_job_done(self, started: float) -> None:
        """Load-control bookkeeping shared by queued AND direct jobs —
        called from pool/direct threads concurrently."""
        with self._state_lock:
            self._last_job_done_at = time.time()
            self._hour_window.append(started)

    # -- busy-state acquisition (poll loop vs direct server) -----------------

    def try_begin_job(self) -> bool:
        """Atomically claim the worker for one EXCLUSIVE inference
        (IDLE→BUSY). Returns False when busy/draining — the caller must
        back off. Exclusive claims never coexist with shared serving
        claims (``try_begin_serving``), so engines without a batcher are
        never driven concurrently."""
        with self._state_lock:
            if self.state != WorkerState.IDLE:
                return False
            self.state = WorkerState.BUSY
            return True

    def end_job(self) -> None:
        with self._state_lock:
            if self.state == WorkerState.BUSY:
                self.state = WorkerState.IDLE

    def serving_capacity(self) -> int:
        """Concurrent shared-claim ceiling — server-pushed
        ``load_control.max_concurrent_jobs`` (the batcher's queue_limit
        guards depth beyond it)."""
        return max(1, int(self.config.load_control.max_concurrent_jobs or 1))

    def try_begin_serving(self) -> bool:
        """Claim ONE shared serving slot (batcher-backed engines): the
        request joins the engine's continuous batch instead of waiting for
        an idle worker. Shared claims coexist with each other up to
        :meth:`serving_capacity` but never with an exclusive claim, and a
        draining worker accepts nothing."""
        with self._state_lock:
            if self.state == WorkerState.IDLE:
                self.state = WorkerState.BUSY
                self._serving_jobs = 1
                return True
            if self.state == WorkerState.BUSY and self._serving_jobs > 0 \
                    and self._serving_jobs < self.serving_capacity():
                self._serving_jobs += 1
                return True
            return False

    def end_serving(self) -> None:
        with self._state_lock:
            if self._serving_jobs > 0:
                self._serving_jobs -= 1
                if self._serving_jobs == 0 and \
                        self.state == WorkerState.BUSY:
                    self.state = WorkerState.IDLE

    def _upgrade_serving_to_exclusive(self) -> bool:
        """Convert OUR shared claim into the exclusive claim — only
        possible when no other shared work is in flight (the poll loop
        uses this when a fetched job turns out to need exclusivity)."""
        with self._state_lock:
            if self.state == WorkerState.BUSY and self._serving_jobs == 1:
                self._serving_jobs = 0
                return True
            return False

    # -- job processing (reference main.py:335-402) --------------------------

    def _report_completion(self, job_id: str, success: bool,
                           result: Optional[Dict[str, Any]] = None,
                           error: Optional[str] = None,
                           deadline_s: float = 45.0,
                           **complete_kw: Any) -> Dict[str, Any]:
        """Report a terminal job outcome, riding out transient plane-side
        store brownouts (round 19): the plane answers a failed durable
        write with a retryable 503 (``store_unavailable`` + Retry-After),
        and the client's own 5xx ladder exhausts well inside a multi-
        second disk_full window — so keep re-reporting until
        ``deadline_s``. Safe to repeat: terminal completes are idempotent
        on the server (duplicates answer ``{"ok": true}``) and zombie
        results are epoch-fenced with a 409, which is NOT retried."""
        deadline = time.monotonic() + deadline_s
        while True:
            try:
                return self.api.complete_job(
                    job_id, success=success, result=result, error=error,
                    **complete_kw
                )
            except APIError as exc:
                if exc.status < 500 or self._shutdown.is_set() \
                        or time.monotonic() > deadline:
                    raise
                log.warning("completion report for %s bounced (%s); "
                            "retrying", job_id, exc)
                time.sleep(0.5)

    def process_job(self, job: Dict[str, Any],
                    release: Optional[Callable[[], None]] = None) -> None:
        """Run one claimed job. Caller must hold a claim: the exclusive
        BUSY state (``try_begin_job``, the default release) or a shared
        serving slot (``try_begin_serving`` — pass ``release=end_serving``).

        Failover-capable engines get a ``_failover_ctx`` (job id, assignment
        epoch, and the claim's server-held checkpoint, if any): they resume
        a requeued generation instead of regenerating, register it for
        heartbeat checkpointing, and — on graceful drain — freeze it and
        raise :class:`JobMigrated`, which hands the checkpoint back to the
        control plane WITHOUT burning a retry. Completions carry the
        assignment epoch so a zombie's late result is fenced with a 409."""
        job_id = job["id"]
        task_type = job.get("type", "llm")
        engine = self.engines.get(task_type)
        with self._state_lock:
            self._active_jobs.add(job_id)
            self.current_job_id = job_id
        started = time.time()
        epoch = int(job.get("assignment_epoch") or 0)
        fenced = "assignment_epoch" in job
        complete_kw: Dict[str, Any] = (
            {"assignment_epoch": epoch} if fenced else {}
        )
        try:
            if engine is None:
                raise RuntimeError(f"no engine loaded for type {task_type!r}")
            params = dict(job.get("params") or {})
            # reserved keys: never accept a client-submitted failover
            # context or flight stamps from job params — the worker mints
            # them below
            params.pop("_failover_ctx", None)
            params.pop("_flight_picked_up_ts", None)
            params.pop("_flight_tl", None)
            if params.get("trace_id"):
                # flight recorder: the poll-pickup instant (claim landed →
                # engine dispatched) — the engine adopts it into the
                # request's timeline, closing the server-side queue-wait
                # phase at the worker boundary
                params["_flight_picked_up_ts"] = time.time()
            if job.get("priority") is not None:
                # control-plane priority reaches the batcher's admission
                # heap (higher-priority jobs admit first, and KV-pressure
                # victims are picked lowest-priority-first)
                params.setdefault("priority", job.get("priority"))
            if getattr(engine, "supports_failover", False):
                params["_failover_ctx"] = {
                    "key": job_id, "kind": "job", "epoch": epoch,
                    "checkpoint": job.get("checkpoint"),
                }
            result = engine.inference(params)
            # the completion report gets its own fault domain: the result
            # is already computed, so a bounced POST (plane store
            # brownout → typed store_unavailable 503, or a raw 5xx past
            # the client's retry ladder) must NOT reclassify the JOB as
            # failed — ride out the window and report the success
            try:
                self._report_completion(
                    job_id, success=True, result=result, **complete_kw
                )
            except APIError:
                # window outlasted the deadline: leave the claim for the
                # sweeps/epoch fence to requeue — a rerun beats a
                # spuriously FAILED job with a perfectly good result
                log.error("could not report completion for job %s "
                          "(store brownout outlasted retries)", job_id)
            else:
                with self._state_lock:
                    self.stats["jobs_completed"] += 1
        except JobMigrated as mig:
            log.info("job %s migrated on drain (%d tokens checkpointed)",
                     job_id, mig.tokens)
            try:
                self.api.checkpoint_job(
                    job_id, epoch, mig.checkpoint, migrate=True
                )
            except APIError:
                # the server's offline requeue still reruns the job from
                # the last heartbeat-piggybacked checkpoint
                log.error("could not push drain checkpoint for %s", job_id)
            with self._state_lock:
                self.stats["jobs_migrated"] += 1
        except Exception as exc:  # noqa: BLE001 - job failure is a result
            if self._shutdown.is_set():
                # the worker is dying (hard kill / unload), not the job:
                # every in-flight batcher future resolves "batcher
                # stopped" and racing those reports against api.close()
                # used to let a few land as terminal FAILURES — marking
                # work failed that any other replica can run. Release the
                # claim instead (conditional RUNNING→QUEUED, retry_count
                # untouched); if the plane is already unreachable the
                # heartbeat-timeout sweep / boot_id fence requeues it
                # anyway. (Round-12 overload suite caught this: a kill
                # mid-burst failed the burst's tail.)
                log.warning("job %s aborted by shutdown (%s): releasing",
                            job_id, exc)
                try:
                    self.api.release_job(job_id)
                except Exception:  # noqa: BLE001 — the sweeps own it then
                    pass
                with self._state_lock:
                    self.stats["jobs_released_on_shutdown"] = \
                        self.stats.get("jobs_released_on_shutdown", 0) + 1
                return
            log.exception("job %s failed", job_id)
            code = getattr(exc, "error_code", None)
            if code:
                # machine-readable failure class (ServingError —
                # request_timeout vs shed_overload) rides the job result
                # next to the human-readable error text
                complete_kw["result"] = {"error_code": str(code)}
            try:
                self._report_completion(
                    job_id, success=False, error=str(exc), **complete_kw
                )
            except APIError:
                log.error("could not report failure for job %s", job_id)
            with self._state_lock:
                self.stats["jobs_failed"] += 1
        finally:
            self.note_job_done(started)
            with self._state_lock:
                self._active_jobs.discard(job_id)
                self.current_job_id = next(iter(self._active_jobs), None)
            (release or self.end_job)()

    def _llm_serving_active(self) -> bool:
        """True when the llm engine serves through a live batcher — queued
        llm jobs then run under SHARED claims and concurrent jobs share
        decode rounds."""
        serving = getattr(self.engines.get("llm"), "serving", None)
        return serving is not None and getattr(serving, "active", False)

    def _job_runs_shared(self, job: Dict[str, Any]) -> bool:
        """A fetched job may join the continuous batch iff it targets the
        batcher-backed llm engine. PD stage jobs ride shared claims too
        (round 11 — the split topology as a LIVE deployment mode): a
        decode-fleet worker co-batches many adopted sequences through
        ``batcher.adopt_slot``, and a prefill-fleet worker overlaps one
        job's KV push with the next job's prefill — an exclusive claim per
        stage would serialize the very fleets the split exists to scale.
        The engine work inside each stage is already serialized with live
        decode rounds (engine lock + ``run_exclusive``). Non-batcher
        engines keep the legacy exclusive claim."""
        if job.get("type", "llm") != "llm":
            return False
        return self._llm_serving_active()

    def _dispatch_shared(self, job: Dict[str, Any]) -> None:
        """Run a shared-claim job on the job pool: the poll loop returns to
        polling immediately, so several queued jobs decode concurrently in
        one batch (the claim was taken by the caller; process_job's finally
        releases it)."""
        if self._job_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._job_pool = ThreadPoolExecutor(
                max_workers=self._job_pool_width, thread_name_prefix="job"
            )
        with self._state_lock:
            self._pool_inflight += 1

        def run() -> None:
            try:
                self.process_job(job, release=self.end_serving)
            except Exception:  # noqa: BLE001 — pool thread must not die silently
                log.exception("shared job %s crashed", job.get("id"))
            finally:
                with self._state_lock:
                    self._pool_inflight -= 1

        self._job_pool.submit(run)

    def _poll_once(self) -> bool:
        """One poll iteration; returns True if a job was processed (or
        dispatched to the shared pool)."""
        if not self.gates_open():  # gated: don't even claim work
            return False
        shared_mode = self._llm_serving_active()
        if shared_mode:
            if self._pool_inflight >= self._job_pool_width:
                # every pool thread is busy: a further claim would start
                # its server-side clock while sitting unstarted in the
                # pool queue (stale-sweep requeue → duplicate compute)
                return False
            with self._state_lock:
                other_shared = self._serving_jobs > 0
            if other_shared and time.time() < self._exclusive_defer_until:
                # head-of-queue work needs exclusivity we cannot grant
                # while shared claims run: stop the claim/release churn
                # and give other workers (or our own drain) a window
                return False
            # claim a shared slot up front: queued jobs keep flowing while
            # direct streams (other shared claims) are in flight
            if not self.try_begin_serving():
                return False
            release = self.end_serving
        else:
            if not self.try_begin_job():  # direct inference in flight / draining
                return False
            release = self.end_job
        job = None
        try:
            job = self.api.fetch_next_job()
        except APIError as exc:
            log.warning("poll failed: %s", exc)
        if job is None:
            release()
            return False
        if not self.should_accept_job(job):
            self.stats["jobs_rejected"] += 1
            self._released_once.add(job["id"])
            try:
                # requeue, don't fail: another worker can run it, and WE will
                # take it if it comes back (one-shot throttle, no starvation)
                self.api.release_job(job["id"])
            except APIError:
                pass
            release()
            return False
        self._released_once.discard(job["id"])
        if not shared_mode:
            self.process_job(job)
            return True
        if self._job_runs_shared(job):
            self._dispatch_shared(job)   # claim travels with the job
            return True
        # the fetched job needs exclusivity (PD stage / non-llm engine):
        # upgrade — only possible when we hold the sole shared claim
        if self._upgrade_serving_to_exclusive():
            self.process_job(job)
            return True
        # other shared work in flight: hand the job back for another
        # worker rather than stalling the batch, and back off from
        # polling briefly (it would come straight back each interval)
        try:
            self.api.release_job(job["id"])
        except APIError:
            pass
        self.end_serving()
        self._exclusive_defer_until = time.time() + max(
            5.0, 5 * self.config.poll_interval_s
        )
        return False

    def _main_loop(self) -> None:
        while not self._shutdown.is_set():
            busy = self._poll_once()
            if not busy:
                self._shutdown.wait(self.config.poll_interval_s)
        self._drained.set()

    # -- lifecycle (reference main.py:404-496) -------------------------------

    def start(self, install_signal_handlers: bool = True,
              block: bool = True) -> None:
        # the worker's way to READY (docs/observability.md, "Start-up")
        outer: Dict[str, Any] = {}
        with phase("dgi.worker.start", outer, "worker_ready",
                   worker=self.config.name):
            with phase("dgi.worker.register", outer, "worker_register"):
                self.register()
            with phase("dgi.worker.load_engines", outer,
                       "worker_load_engines"):
                self.load_engines()
            for eng in self.engines.values():
                # stream-checkpoint cadence between heartbeats (llm
                # engine): admission + every checkpoint_interval_tokens
                if hasattr(eng, "checkpoint_sink"):
                    eng.checkpoint_sink = self.push_stream_checkpoint
            if self.config.direct.enabled:
                from .direct_server import DirectServer

                self._direct = DirectServer(
                    self, host=self.config.direct.host,
                    port=self.config.direct.port,
                )
                self._direct.start()
            if self.config.pd_data_plane_url and "llm" in self.engines:
                # decode-capable PD worker: run a data plane so prefill
                # peers can push KV handoffs (server/pd_flow.py stage 2)
                from urllib.parse import urlparse

                from ..comm.data_plane import DataPlaneServer

                llm_eng = self.engines["llm"]
                port = urlparse(self.config.pd_data_plane_url).port or 8472
                self._pd_plane = DataPlaneServer(
                    _PDReceiverShim(llm_eng), port=port,
                    kv_receiver=llm_eng.kv_receiver,
                    kv_exporter=getattr(llm_eng, "kv_export", None),
                )
                self._pd_plane.start()
        for eng in self.engines.values():
            stats = getattr(getattr(eng, "engine", None), "stats", None)
            if isinstance(stats, dict) and "startup" in stats:
                adopt_phases(stats["startup"], outer)
        log.info("ready in %.2fs (register %.2f, engines %.2f)",
                 outer["worker_ready_s"], outer["worker_register_s"],
                 outer["worker_load_engines_s"])
        self.state = WorkerState.IDLE
        if install_signal_handlers:
            try:
                signal.signal(signal.SIGTERM, self._signal_handler)
                signal.signal(signal.SIGINT, self._signal_handler)
            except ValueError:  # pragma: no cover - non-main thread
                pass
        self._heartbeat_thread = threading.Thread(
            target=self._heartbeat_loop, name="heartbeat", daemon=True
        )
        self._heartbeat_thread.start()
        self._heartbeat_once()
        if block:
            self._main_loop()
            self._finalize_shutdown()

    def _signal_handler(self, signum: int, frame: Any) -> None:  # pragma: no cover
        log.info("signal %s: graceful shutdown", signum)
        self.request_shutdown()

    def request_shutdown(self) -> None:
        """Graceful drain (reference main.py:444-463): stop accepting,
        MIGRATE the in-flight generation instead of finishing it (failover-
        capable engines freeze at the next step boundary and the checkpoint
        requeues the job — seconds instead of a full generation's tail),
        then notify the server."""
        if self._shutdown.is_set():
            return
        with self._state_lock:
            self.state = WorkerState.DRAINING
        for eng in self.engines.values():
            interrupt = getattr(eng, "interrupt_live", None)
            if interrupt is not None:
                try:
                    interrupt()
                except Exception:  # noqa: BLE001
                    pass
        try:
            self.api.going_offline()
        except APIError:
            pass
        self._shutdown.set()

    # -- stream failover (direct server drives these) ------------------------

    def adopt_stream_checkpoint(self, stream_id: str
                                ) -> Optional[Dict[str, Any]]:
        """Fetch-and-fence a dropped stream's checkpoint from the control
        plane (epoch bumps to this worker). None when no checkpoint exists
        — the direct server then answers the resume with a 409."""
        try:
            return self.api.adopt_stream(stream_id)
        except APIError as exc:
            if exc.status == 404:
                return None
            raise

    def push_stream_checkpoint(self, entry: Dict[str, Any]) -> None:
        """Checkpoint sink for the llm engine's stream cadence: push one
        stream checkpoint (or its ``done`` retirement) to the control
        plane. Job-kind entries only ride heartbeats — pushing them here
        would double-report."""
        if entry.get("kind") != "stream":
            return
        self.api.checkpoint_stream(
            entry["key"], int(entry.get("epoch") or 0),
            entry.get("state"), done=bool(entry.get("done")),
        )

    def _finalize_shutdown(self) -> None:
        if self._job_pool is not None:
            # shared queued jobs: interrupt_live (request_shutdown) already
            # told them to freeze at the next step boundary — wait for the
            # JobMigrated checkpoints to land before reporting offline
            self._job_pool.shutdown(wait=True)
        try:
            requeued = self.api.offline()
            if requeued:
                log.info("server requeued jobs: %s", requeued)
        except APIError:
            pass
        self.state = WorkerState.OFFLINE
        if getattr(self, "_direct", None) is not None:
            self._direct.stop()
        if getattr(self, "_pd_plane", None) is not None:
            self._pd_plane.stop()
        if self._heartbeat_thread is not None:
            self._heartbeat_thread.join(timeout=5.0)
        for eng in self.engines.values():
            try:
                eng.unload()
            except Exception:  # noqa: BLE001
                pass
        self.api.close()

    # -- introspection -------------------------------------------------------

    def get_status(self) -> Dict[str, Any]:
        return {
            "worker_id": self.api.worker_id,
            "state": self.state.value,
            "current_job_id": self.current_job_id,
            "task_types": list(self.config.task_types),
            "topology": self.topology.to_dict(),
            "stats": dict(self.stats),
        }


def main() -> None:  # pragma: no cover - manual entry point
    import argparse

    from ..utils.config import load_worker_config

    ap = argparse.ArgumentParser(description="TPU inference worker")
    ap.add_argument("--config", default="config.yaml")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    cfg = load_worker_config(args.config)
    Worker(cfg).start()


if __name__ == "__main__":  # pragma: no cover
    main()
