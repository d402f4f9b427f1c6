"""Gray-failure immunity (round 18): slow-worker quarantine, deadline
abandonment, and hedged dispatch.

The dangerous replica is ALIVE: it heartbeats on time while answering 10x
slow (``degrade``), noisily slow (``jitter``) or 5xx-at-probability
(``flaky``). This suite covers the whole defense in layers:

- **Schedules**: gray kinds live in their own tuple — historical fleet
  seeds stay bit-identical — and ``--replay SEED --gray`` reconstructs a
  failing suite seed's exact schedule.
- **HealthService units**: the healthy → suspect → quarantined →
  probation machine with injected clocks — relative scoring, hysteresis,
  grace, the quarantine-fraction cap, canary-budgeted re-admission, and
  the all-or-nothing live config push.
- **Plane integration** (no engines): quarantine excluded from discovery
  and claims, hedge hints offered to opted-in deadline traffic, the
  health gauges/counters, and the disabled path byte-identical to the
  pre-round-18 build.
- **Batcher abandonment units** (fake engine): the hopeless-work
  projection math and the typed ``deadline_abandoned`` resolution —
  NEVER for deadline-less requests, no-op when disabled.
- **DirectServer**: hedge-cancel exactly-once, the reserved
  ``_cancel_evt`` slot, and the heartbeat telemetry channel's
  drain-as-deltas contract.
- **SDK**: the hedged two-leg race — first winner cancels the loser,
  fast primaries never fire the hedge, deadline-less requests keep the
  single-POST path.
- **KV handoff wire**: deadlines cross the PD boundary as absolute
  times (omitted, not null, when unset).

Heavy replays carry ``slow`` + ``gray_chaos`` (HEAVY CI shard, ``pytest
-m gray_chaos``); everything else stays tier-1 unmarked.
"""

import asyncio
import contextlib
import threading
import time
from typing import Any, Dict, List, Optional

import httpx
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
)
from distributed_gpu_inference_tpu.runtime.kv_handoff import (
    KVHandoff,
    deserialize_handoff,
    serialize_handoff,
)
from distributed_gpu_inference_tpu.sdk.client import InferenceClient
from distributed_gpu_inference_tpu.server.health import (
    HEALTHY,
    PROBATION,
    QUARANTINED,
    SUSPECT,
    HealthConfig,
    HealthService,
)
from distributed_gpu_inference_tpu.testing.faults import (
    ALL_FLEET_EVENT_KINDS,
    FLEET_EVENT_KINDS,
    GRAY_CHAOS_KINDS,
    GRAY_CHAOS_WORKERS,
    GRAY_EVENT_KINDS,
    FleetEvent,
    FleetFaultPlan,
    _replay_main,
)
from distributed_gpu_inference_tpu.testing.harness import (
    DEFAULT_FLEET_ENGINE,
    LiveControlPlane,
    LiveFleet,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
    WorkerState,
)
from distributed_gpu_inference_tpu.worker.api_client import APIClient
from distributed_gpu_inference_tpu.worker.direct_server import DirectServer

N_SEEDS = 25


# ---------------------------------------------------------------------------
# schedule determinism + replay CLI (cheap, tier-1)
# ---------------------------------------------------------------------------


def _gray_plan(seed: int) -> FleetFaultPlan:
    return FleetFaultPlan(seed, n_workers=GRAY_CHAOS_WORKERS,
                          kinds=GRAY_CHAOS_KINDS)


def test_gray_plan_same_seed_same_schedule():
    for seed in range(N_SEEDS):
        a, b = _gray_plan(seed), _gray_plan(seed)
        assert a.events == b.events, seed
        assert a.events, seed


def test_gray_plan_covers_every_gray_kind_across_suite_seeds():
    kinds = set()
    for seed in range(N_SEEDS):
        kinds |= {e.kind for e in _gray_plan(seed).events}
    assert {"degrade", "jitter", "flaky", "kill"} <= kinds


def test_gray_kinds_are_separate_from_historical_tuples():
    """Adding gray kinds must not perturb a single historical seed: they
    live in their own tuple, and the default fleet generator never draws
    them."""
    assert not set(GRAY_EVENT_KINDS) & set(FLEET_EVENT_KINDS)
    assert set(GRAY_EVENT_KINDS) <= set(ALL_FLEET_EVENT_KINDS)
    for seed in range(40):
        for e in FleetFaultPlan(seed).events:
            assert e.kind not in GRAY_EVENT_KINDS, (seed, e)


def test_gray_plan_event_parameters_are_sane():
    """Degrade windows stretch to ≥ half the run (the persistent gray
    failure quarantine exists to catch); jitter/flaky probabilities stay
    in the generator's [0.25, 0.75] band."""
    saw_degrade = False
    for seed in range(60):
        plan = _gray_plan(seed)
        for e in plan.events:
            if e.kind == "degrade":
                saw_degrade = True
                assert e.duration_s >= plan.duration_s * 0.5 - 1e-9, (seed, e)
                assert e.delay_s > 0.0
            if e.kind in ("jitter", "flaky"):
                assert 0.25 <= e.prob <= 0.75, (seed, e)
            if e.kind == "jitter":
                assert e.delay_s > 0.0
    assert saw_degrade


def test_gray_replay_cli_reconstructs_suite_schedules(capsys):
    assert _replay_main(["--replay", "7", "--gray"]) == 0
    out = capsys.readouterr().out
    for line in _gray_plan(7).describe():
        assert line in out


def test_gray_replay_cli_rejects_mixed_suite_flags(capsys):
    with pytest.raises(SystemExit):
        _replay_main(["--replay", "1", "--gray", "--pd"])
    capsys.readouterr()


# ---------------------------------------------------------------------------
# HealthService: the state machine, hermetic (injected clocks)
# ---------------------------------------------------------------------------


def _svc(**over: Any):
    cfg = HealthConfig(enabled=True, min_samples=3, min_peers=2,
                       suspect_ratio=3.0, clear_ratio=1.5, grace_s=1.0,
                       probation_after_s=2.0, canary_budget=3)
    for k, v in over.items():
        setattr(cfg, k, v)
    transitions: List[tuple] = []
    svc = HealthService(cfg, on_transition=lambda w, f, t:
                        transitions.append((w, f, t)))
    return svc, transitions


def _feed(svc: HealthService, wid: str, ms: float, n: int,
          now: float) -> None:
    for _ in range(n):
        svc.observe(wid, ms, now=now)


def test_disabled_service_is_inert():
    svc = HealthService()          # default config: enabled=False
    svc.observe("a", 500.0)
    svc.observe_error("a", 10)
    svc.ingest("a", {"direct": {"recent_ms": [900.0], "new_errors": 3}},
               body={"hb_rtt_ms": 400.0})
    svc.evaluate()
    assert svc.states() == {}      # not even accumulating
    assert svc.snapshot()["workers"] == {}
    assert not svc.is_quarantined("a")
    assert svc.allow_canary("a")
    ids = ["a", "b"]
    assert svc.admissible(ids) is ids    # passthrough, untouched


def test_slow_worker_walks_the_full_state_machine_and_readmits():
    svc, trans = _svc()
    t0 = 1000.0
    for wid, ms in (("a", 10.0), ("b", 12.0), ("c", 300.0)):
        _feed(svc, wid, ms, 4, t0)
    svc.evaluate(now=t0)
    assert svc.state("c") == SUSPECT
    assert svc.state("a") == HEALTHY and svc.state("b") == HEALTHY
    # suspects still serve through the grace window
    assert not svc.is_quarantined("c")
    assert svc.allow_canary("c")
    svc.evaluate(now=t0 + 0.5)                 # grace not yet elapsed
    assert svc.state("c") == SUSPECT
    svc.evaluate(now=t0 + 1.0)                 # grace_s=1.0 elapsed
    assert svc.state("c") == QUARANTINED
    assert svc.is_quarantined("c")
    assert not svc.allow_canary("c")
    assert svc.admissible(["a", "b", "c"]) == ["a", "b"]
    svc.evaluate(now=t0 + 3.0)                 # probation_after_s=2.0
    assert svc.state("c") == PROBATION
    assert not svc.is_quarantined("c")         # routing gate is quarantine-only
    # canary evidence comes back fast → re-admitted
    _feed(svc, "c", 11.0, 3, t0 + 3.5)
    svc.evaluate(now=t0 + 4.0)
    assert svc.state("c") == HEALTHY
    assert trans == [("c", HEALTHY, SUSPECT),
                     ("c", SUSPECT, QUARANTINED),
                     ("c", QUARANTINED, PROBATION),
                     ("c", PROBATION, HEALTHY)]


def test_probation_requarantines_on_slow_canaries():
    svc, trans = _svc()
    t0 = 1000.0
    for wid, ms in (("a", 10.0), ("b", 12.0), ("c", 300.0)):
        _feed(svc, wid, ms, 4, t0)
    svc.evaluate(now=t0)
    svc.evaluate(now=t0 + 1.0)
    svc.evaluate(now=t0 + 3.0)
    assert svc.state("c") == PROBATION
    _feed(svc, "c", 400.0, 3, t0 + 3.5)        # canaries still slow
    svc.evaluate(now=t0 + 4.0)
    assert svc.state("c") == QUARANTINED
    assert trans[-1] == ("c", PROBATION, QUARANTINED)


def test_probation_canary_traffic_is_budget_bounded():
    svc, _ = _svc(canary_budget=2)
    t0 = 1000.0
    for wid, ms in (("a", 10.0), ("b", 12.0), ("c", 300.0)):
        _feed(svc, wid, ms, 4, t0)
    svc.evaluate(now=t0)
    svc.evaluate(now=t0 + 1.0)
    svc.evaluate(now=t0 + 3.0)
    assert svc.state("c") == PROBATION
    assert svc.allow_canary("c")
    assert svc.allow_canary("c")
    assert not svc.allow_canary("c")           # budget of 2 exhausted
    # ranking (admissible) never charges the budget — only selection does
    assert svc.admissible(["a", "c"]) == ["a", "c"]


def test_quarantine_cap_bounds_the_blast_radius():
    """At most max_quarantined_frac of the scored fleet quarantines at
    once: with 5 scored workers and the default 0.34, the cap is 1 — two
    simultaneous stragglers cannot take out 40% of the fleet."""
    svc, _ = _svc()
    t0 = 1000.0
    for wid, ms in (("a", 10.0), ("b", 11.0), ("e", 12.0),
                    ("c", 300.0), ("d", 320.0)):
        _feed(svc, wid, ms, 4, t0)
    svc.evaluate(now=t0)
    assert svc.state("c") == SUSPECT and svc.state("d") == SUSPECT
    svc.evaluate(now=t0 + 1.0)
    states = svc.states()
    held = [w for w in ("c", "d") if states[w] == QUARANTINED]
    assert len(held) == 1, states
    # the other straggler holds at suspect until headroom frees
    other = "d" if held == ["c"] else "c"
    assert states[other] == SUSPECT


def test_server_errors_score_as_synthetic_slow_samples():
    """A flaky replica failing FAST must not look healthy: each 5xx
    scores as error_sample_ms."""
    svc, _ = _svc()
    t0 = 1000.0
    _feed(svc, "a", 10.0, 4, t0)
    _feed(svc, "b", 12.0, 4, t0)
    svc.observe_error("c", count=4, now=t0)
    svc.evaluate(now=t0)
    assert svc.state("c") == SUSPECT
    snap = svc.snapshot(now=t0)
    assert snap["workers"]["c"]["p95_ms"] == svc.cfg.error_sample_ms
    # the synthetic-sample burst is capped (a counter glitch must not
    # flood the ring)
    svc.observe_error("d", count=10_000, now=t0)
    assert svc.snapshot(now=t0)["workers"]["d"]["samples"] <= 64


def test_no_baseline_without_enough_peers():
    """One worker alone is never judged — there is nothing to be
    relatively slow against."""
    svc, _ = _svc()
    t0 = 1000.0
    _feed(svc, "only", 5000.0, 10, t0)
    svc.evaluate(now=t0)
    assert svc.state("only") == HEALTHY
    assert svc.snapshot(now=t0)["baseline_p95_ms"] == 0.0


def test_admissible_falls_back_when_filter_would_empty():
    svc, _ = _svc()
    t0 = 1000.0
    for wid, ms in (("a", 10.0), ("b", 12.0), ("c", 300.0)):
        _feed(svc, wid, ms, 4, t0)
    svc.evaluate(now=t0)
    svc.evaluate(now=t0 + 1.0)
    assert svc.state("c") == QUARANTINED
    # availability beats purity: a slow answer over none
    assert svc.admissible(["c"]) == ["c"]
    assert svc.admissible(["a", "c"]) == ["a"]


def test_forget_clears_gray_state():
    svc, _ = _svc()
    _feed(svc, "a", 10.0, 4, 1000.0)
    assert "a" in svc.states()
    svc.forget("a")
    assert svc.states() == {}


def test_observe_rejects_garbage_samples():
    svc, _ = _svc()
    for bad in (float("nan"), float("inf"), -5.0, "abc", None):
        svc.observe("a", bad, now=1000.0)
    assert svc.snapshot(now=1000.0)["workers"] == {}


def test_ingest_reads_every_heartbeat_channel_and_never_raises():
    svc, _ = _svc()
    t0 = 1000.0
    svc.ingest("w", {"direct": {"recent_ms": [10.0, 20.0],
                                "new_errors": 2}},
               body={"hb_rtt_ms": 5.0}, now=t0)
    # 1 RTT + 2 direct latencies + 2 synthetic error samples
    assert svc.snapshot(now=t0)["workers"]["w"]["samples"] == 5
    # worker-supplied garbage degrades to skipped samples, never raises
    svc.ingest("w", {"direct": {"recent_ms": "zz", "new_errors": "x"}},
               body={"hb_rtt_ms": "bad"}, now=t0)
    svc.ingest("w", "not-a-dict", body=None, now=t0)
    assert svc.snapshot(now=t0)["workers"]["w"]["samples"] == 5


def test_config_update_validates_all_before_applying_any():
    cfg = HealthConfig()
    cfg.update({"suspect_ratio": 2.0, "clear_ratio": 1.2})
    assert cfg.suspect_ratio == 2.0 and cfg.clear_ratio == 1.2
    # hysteresis rails: clear must stay strictly below suspect
    with pytest.raises(ValueError, match="clear_ratio"):
        cfg.update({"clear_ratio": 5.0})
    assert cfg.clear_ratio == 1.2
    # all-or-nothing: the valid window_s must not land when min_samples
    # in the same push is rejected
    with pytest.raises(ValueError):
        cfg.update({"window_s": 120.0, "min_samples": 0})
    assert cfg.window_s == 60.0
    with pytest.raises(ValueError):
        cfg.update({"max_quarantined_frac": 1.5})
    # env/YAML tooling stringifies bools — coerce by content
    cfg.update({"enabled": "on", "hedge": "false"})
    assert cfg.enabled is True and cfg.hedge is False
    with pytest.raises(ValueError, match="not a boolean"):
        cfg.update({"enabled": "maybe"})
    assert cfg.enabled is True


def test_hedge_delay_derives_from_baseline_and_clamps():
    svc, _ = _svc()
    # no baseline yet: the clamp floor answers
    assert svc.hedge_delay_ms(now=1000.0) == svc.cfg.hedge_delay_min_ms
    _feed(svc, "a", 100.0, 4, 1000.0)
    _feed(svc, "b", 100.0, 4, 1000.0)
    assert svc.hedge_delay_ms(now=1000.0) == pytest.approx(150.0)  # 1.5x
    svc.cfg.hedge_delay_factor = 1000.0
    assert svc.hedge_delay_ms(now=1000.0) == svc.cfg.hedge_delay_max_ms


# ---------------------------------------------------------------------------
# plane integration: discovery, claims, hedge hints, metrics (no engines)
# ---------------------------------------------------------------------------


def _register(cp: LiveControlPlane, name: str) -> APIClient:
    api = APIClient(cp.url, backoff_s=0.0)
    api.register({"name": name, "region": "us-west",
                  "supported_types": ["llm"], "supports_direct": True,
                  "direct_url": f"http://{name}.example:8471"})
    return api


def _metric(cp: LiveControlPlane, name: str) -> str:
    text = httpx.get(f"{cp.url}/metrics").text
    return "\n".join(
        line for line in text.splitlines() if line.startswith(name)
    )


def _put_health(cp: LiveControlPlane, **cfg: Any) -> httpx.Response:
    return httpx.put(f"{cp.url}/api/v1/admin/health", json=cfg)


def _direct_samples(ms: float, n: int = 5) -> Dict[str, Any]:
    return {"direct": {"recent_ms": [ms] * n, "new_errors": 0,
                       "hedge_cancels": 0}}


def test_health_disabled_keeps_discovery_byte_identical():
    """The default-OFF contract: telemetry may arrive, nothing reads it;
    the nearest response carries the pre-round-18 fields exactly even
    when the client asks for a hedge; no health series render."""
    with LiveControlPlane() as cp:
        a = _register(cp, "a")
        b = _register(cp, "b")
        a.heartbeat(status="idle", engine_stats=_direct_samples(5.0),
                    hb_rtt_ms=1.0)
        b.heartbeat(status="idle", engine_stats=_direct_samples(900.0))
        r = httpx.get(f"{cp.url}/api/v1/jobs/direct/nearest",
                      params={"hedge": "1"})
        assert r.status_code == 200
        assert set(r.json().keys()) == {"worker_id", "direct_url",
                                        "region", "client_region"}
        assert cp.state.health.states() == {}       # nothing accumulated
        assert _metric(cp, "worker_health_state") == ""
        g = httpx.get(f"{cp.url}/api/v1/admin/health").json()
        assert g["enabled"] is False
        assert g["snapshot"]["workers"] == {}
        a.close()
        b.close()


def _quarantine_b(cp: LiveControlPlane):
    """3 workers; b ships slow direct samples until quarantined."""
    a, b, c = _register(cp, "a"), _register(cp, "b"), _register(cp, "c")
    a.heartbeat(status="idle", engine_stats=_direct_samples(10.0))
    c.heartbeat(status="idle", engine_stats=_direct_samples(12.0))
    b.heartbeat(status="idle", engine_stats=_direct_samples(500.0))
    # any beat re-evaluates; grace_s=0 lets suspect escalate on the next
    a.heartbeat(status="idle")
    assert cp.state.health.state(b.worker_id) == QUARANTINED
    return a, b, c


def test_quarantined_worker_excluded_from_discovery_and_claims():
    with LiveControlPlane() as cp:
        assert _put_health(cp, enabled=True, min_samples=3, min_peers=2,
                           grace_s=0.0, probation_after_s=600.0
                           ).status_code == 200
        a, b, c = _quarantine_b(cp)
        # discovery never hands out the quarantined replica
        for _ in range(6):
            r = httpx.get(f"{cp.url}/api/v1/jobs/direct/nearest")
            assert r.json()["worker_id"] != b.worker_id
        # the claim path is gated too: b polls and gets nothing, a claims
        job_id = cp.call(cp.state.store.create_job(
            {"type": "llm", "params": {"prompt": "x"}}
        ))
        assert b.fetch_next_job() is None
        job = a.fetch_next_job()
        assert job is not None and job["id"] == job_id
        # scrape-time gauges: per-worker state codes + the transition trail
        assert f'worker="{b.worker_id}"}} 2.0' in _metric(
            cp, "worker_health_state"
        )
        assert 'from="suspect",to="quarantined"' in _metric(
            cp, "health_transitions_total"
        )
        # fleet strength counts the quarantined replica as degraded:
        # 2 serving / 3 registered
        line = _metric(cp, "fleet_degraded")
        assert abs(float(line.split()[-1]) - 2.0 / 3.0) < 1e-6, line
        # a clean deregistration supersedes gray state
        r = httpx.delete(
            f"{cp.url}/api/v1/admin/workers/{b.worker_id}")
        assert r.status_code == 200
        assert b.worker_id not in cp.state.health.states()
        for api in (a, b, c):
            api.close()


def test_hedge_hint_offered_only_to_opted_in_requests():
    with LiveControlPlane() as cp:
        assert _put_health(cp, enabled=True, hedge=True, min_samples=3,
                           min_peers=2).status_code == 200
        a, b = _register(cp, "a"), _register(cp, "b")
        a.heartbeat(status="idle", engine_stats=_direct_samples(10.0))
        b.heartbeat(status="idle", engine_stats=_direct_samples(12.0))
        r = httpx.get(f"{cp.url}/api/v1/jobs/direct/nearest",
                      params={"hedge": "1"})
        j = r.json()
        assert "hedge" in j
        assert j["hedge"]["worker_id"] != j["worker_id"]
        assert j["hedge"]["direct_url"]
        assert j["hedge"]["delay_ms"] >= \
            cp.state.health.cfg.hedge_delay_min_ms
        assert 'outcome="offered"' in _metric(cp, "hedges_total")
        # no opt-in → no hedge field, even with both switches on
        r2 = httpx.get(f"{cp.url}/api/v1/jobs/direct/nearest")
        assert "hedge" not in r2.json()
        # hedge switch off → the opt-in is ignored
        assert _put_health(cp, hedge=False).status_code == 200
        r3 = httpx.get(f"{cp.url}/api/v1/jobs/direct/nearest",
                       params={"hedge": "1"})
        assert "hedge" not in r3.json()
        a.close()
        b.close()


def test_admin_health_put_rejects_bad_pushes_atomically():
    with LiveControlPlane() as cp:
        r = _put_health(cp, suspect_ratio=2.0, clear_ratio=5.0)
        assert r.status_code == 400
        g = httpx.get(f"{cp.url}/api/v1/admin/health").json()
        assert g["suspect_ratio"] == 3.0 and g["clear_ratio"] == 1.5
        assert _put_health(cp, enabled=True, window_s=30.0
                           ).status_code == 200
        g = httpx.get(f"{cp.url}/api/v1/admin/health").json()
        assert g["enabled"] is True and g["window_s"] == 30.0


# ---------------------------------------------------------------------------
# batcher: hopeless-work abandonment (fake engine, no decode loop)
# ---------------------------------------------------------------------------


class _PoolEngine:
    """The minimal engine surface an UNSTARTED batcher touches: items
    stay in the heap, so the deadline scan is exercised in isolation."""

    slots: List[Any] = []

    def request_fits_pool(self, request: InferenceRequest) -> bool:
        return True


def _mk_batcher(**over: Any) -> ContinuousBatcher:
    return ContinuousBatcher(
        _PoolEngine(), BatcherConfig(abandon_deadlines=True, **over)
    )


def _req(deadline_s: Optional[float], arrival_ago: float = 0.0,
         max_new: int = 64) -> InferenceRequest:
    return InferenceRequest(
        prompt_token_ids=[1, 2, 3],
        sampling=SamplingParams(max_new_tokens=max_new),
        arrival_time=time.time() - arrival_ago,
        deadline_s=deadline_s,
    )


def test_deadline_hopeless_projection_math():
    b = _mk_batcher(deadline_grace_s=0.5)
    b.stats["step_latency_ema_ms"] = 100.0
    now = 1000.0
    late = InferenceRequest(prompt_token_ids=[1],
                            sampling=SamplingParams(max_new_tokens=50),
                            arrival_time=now - 10.0, deadline_s=5.0)
    assert b._deadline_hopeless(late, 50, now)          # 5s past, 5s left
    assert not b._deadline_hopeless(late, 0, now)       # finishing frees 0
    # just past the deadline but 1 token lands inside the grace window
    close = InferenceRequest(prompt_token_ids=[1],
                             sampling=SamplingParams(max_new_tokens=100),
                             arrival_time=now - 0.1, deadline_s=0.0)
    assert not b._deadline_hopeless(close, 1, now)
    assert b._deadline_hopeless(close, 100, now)
    # before the deadline: never hopeless, whatever the projection
    early = InferenceRequest(prompt_token_ids=[1],
                             sampling=SamplingParams(max_new_tokens=100),
                             arrival_time=now, deadline_s=60.0)
    assert not b._deadline_hopeless(early, 10_000, now)
    # deadline-less: the explicit None guard, not just +inf arithmetic
    free = InferenceRequest(prompt_token_ids=[1],
                            sampling=SamplingParams(max_new_tokens=100),
                            arrival_time=now - 9999.0, deadline_s=None)
    assert not b._deadline_hopeless(free, 10_000, now)
    # feature off: not even a clock comparison
    b.cfg.abandon_deadlines = False
    assert not b._deadline_hopeless(late, 50, now)


def test_scan_abandons_hopeless_queued_work_with_typed_error():
    async def body():
        b = _mk_batcher()
        b.stats["step_latency_ema_ms"] = 200.0
        task = asyncio.ensure_future(
            b.submit(_req(deadline_s=5.0, arrival_ago=30.0)))
        await asyncio.sleep(0.01)          # enqueue runs; loop not started
        assert len(b._heap) == 1
        await b._scan_deadlines()
        resp = await asyncio.wait_for(task, 5.0)
        assert resp.error_code == "deadline_abandoned"
        assert resp.finish_reason == "abort"
        assert "grace" in (resp.error or "")
        assert b._heap == []
        assert b.stats["abandoned"] == 1
        assert b.stats["completed"] == 1

    asyncio.run(body())


def test_deadline_less_requests_are_never_abandoned():
    async def body():
        b = _mk_batcher()
        b.stats["step_latency_ema_ms"] = 1000.0
        hopeless = asyncio.ensure_future(
            b.submit(_req(deadline_s=1.0, arrival_ago=60.0)))
        free = asyncio.ensure_future(
            b.submit(_req(deadline_s=None, arrival_ago=60.0)))
        await asyncio.sleep(0.01)
        assert len(b._heap) == 2
        await b._scan_deadlines()
        resp = await asyncio.wait_for(hopeless, 5.0)
        assert resp.error_code == "deadline_abandoned"
        # the deadline-less neighbor is untouched, still queued
        assert len(b._heap) == 1
        assert not free.done()
        assert b.stats["abandoned"] == 1
        free.cancel()
        with contextlib.suppress(asyncio.CancelledError):
            await free

    asyncio.run(body())


def test_scan_is_a_noop_when_disabled():
    async def body():
        b = _mk_batcher()
        b.cfg.abandon_deadlines = False
        b.stats["step_latency_ema_ms"] = 1000.0
        task = asyncio.ensure_future(
            b.submit(_req(deadline_s=1.0, arrival_ago=60.0)))
        await asyncio.sleep(0.01)
        await b._scan_deadlines()
        assert len(b._heap) == 1           # expired, but the knob is off
        assert not task.done()
        assert b.stats["abandoned"] == 0
        task.cancel()
        with contextlib.suppress(asyncio.CancelledError):
            await task

    asyncio.run(body())


def test_abandonment_knobs_are_live_pushable():
    b = ContinuousBatcher(_PoolEngine(), BatcherConfig())
    assert b.cfg.abandon_deadlines is False          # default OFF
    assert b.cfg.deadline_grace_s == 0.5
    b.reconfigure(abandon_deadlines="true", deadline_grace_s="0.25")
    assert b.cfg.abandon_deadlines is True
    assert b.cfg.deadline_grace_s == 0.25
    b.reconfigure(abandon_deadlines="off")
    assert b.cfg.abandon_deadlines is False


def test_abandonment_knobs_ride_the_serving_remote_config():
    from distributed_gpu_inference_tpu.utils.config import ServingConfig
    from distributed_gpu_inference_tpu.worker.engines.llm import (
        SERVING_REMOTE_KEYS,
    )

    sv = ServingConfig()
    assert sv.abandon_deadlines is False and sv.deadline_grace_s == 0.5
    assert SERVING_REMOTE_KEYS["abandon_deadlines"] == "abandon_deadlines"
    assert SERVING_REMOTE_KEYS["deadline_grace_s"] == "deadline_grace_s"


# ---------------------------------------------------------------------------
# direct server: hedge cancel exactly-once + the telemetry channel
# ---------------------------------------------------------------------------


class _DSWorker:
    """FakeWorker with a blockable engine: ``wait_cancel`` requests park
    on the server-minted cancel event until /inference/cancel flips it."""

    def __init__(self, text: str = "ok", block: bool = False):
        self.state = WorkerState.IDLE
        self.engines = {"llm": self}
        self.text = text
        self.block = block
        self.seen: List[Dict[str, Any]] = []

    def try_begin_job(self):
        if self.state != WorkerState.IDLE:
            return False
        self.state = WorkerState.BUSY
        return True

    def end_job(self):
        if self.state == WorkerState.BUSY:
            self.state = WorkerState.IDLE

    def inference(self, params):
        self.seen.append(dict(params))
        evt = params.get("_cancel_evt")
        if self.block and evt is not None:
            cancelled = evt.wait(8.0)
            return {"text": "cancelled" if cancelled else "ran-to-end"}
        if params.get("boom"):
            raise RuntimeError("kaboom")
        return {"text": self.text}

    def get_status(self):
        return {"state": self.state.value, "task_types": ["llm"]}


async def _make_client(worker):
    ds = DirectServer(worker)
    client = TestClient(TestServer(ds.make_app()))
    await client.start_server()
    return client, ds


def test_hedge_cancel_is_exactly_once():
    async def body():
        w = _DSWorker(block=True)
        client, ds = await _make_client(w)
        t = asyncio.ensure_future(client.post(
            "/inference",
            json={"type": "llm", "params": {"hedge_key": "k1"}},
        ))
        for _ in range(200):
            if "k1" in ds._cancels:
                break
            await asyncio.sleep(0.01)
        assert "k1" in ds._cancels
        r1 = await client.post("/inference/cancel",
                               json={"hedge_key": "k1"})
        assert (await r1.json())["cancelled"] is True
        # the second racer tidying up sees False — the counter moves once
        r2 = await client.post("/inference/cancel",
                               json={"hedge_key": "k1"})
        assert (await r2.json())["cancelled"] is False
        resp = await asyncio.wait_for(t, 10.0)
        assert resp.status == 200
        assert (await resp.json())["result"]["text"] == "cancelled"
        assert ds.stats["hedge_cancels"] == 1
        # post-completion the key is unregistered: idempotent no-op 200
        r3 = await client.post("/inference/cancel",
                               json={"hedge_key": "k1"})
        assert r3.status == 200
        assert (await r3.json())["cancelled"] is False
        # the engine saw the server-minted Event, never the wire key
        seen = w.seen[0]
        assert "hedge_key" not in seen
        assert isinstance(seen.get("_cancel_evt"), threading.Event)
        await client.close()

    asyncio.run(body())


def test_cancel_unknown_key_and_bad_json():
    async def body():
        w = _DSWorker()
        client, ds = await _make_client(w)
        r = await client.post("/inference/cancel",
                              json={"hedge_key": "never-existed"})
        assert r.status == 200
        assert (await r.json())["cancelled"] is False
        r = await client.post("/inference/cancel", data=b"not json")
        assert r.status == 400
        assert ds.stats["hedge_cancels"] == 0
        await client.close()

    asyncio.run(body())


def test_wire_supplied_cancel_event_is_discarded():
    """``_cancel_evt`` is server-owned: a client smuggling one in must
    not reach the engine (it would crash the batcher's cancel hook)."""
    async def body():
        w = _DSWorker()
        client, _ = await _make_client(w)
        r = await client.post(
            "/inference",
            json={"type": "llm", "params": {"_cancel_evt": "evil"}},
        )
        assert r.status == 200
        assert "_cancel_evt" not in w.seen[0]
        await client.close()

    asyncio.run(body())


def test_direct_telemetry_drains_as_deltas():
    async def body():
        w = _DSWorker()
        client, ds = await _make_client(w)
        r = await client.post("/inference",
                              json={"type": "llm", "params": {}})
        assert r.status == 200
        r = await client.post("/inference",
                              json={"type": "llm",
                                    "params": {"boom": 1}})
        assert r.status == 500
        ws = ds.wire_stats()
        assert len(ws["recent_ms"]) == 1       # the success's wall time
        assert ws["recent_ms"][0] >= 0.0
        assert ws["new_errors"] == 1           # the engine 500
        assert ws["hedge_cancels"] == 0        # cumulative counter
        # drained: the next beat ships only what happened since
        ws2 = ds.wire_stats()
        assert ws2["recent_ms"] == [] and ws2["new_errors"] == 0
        await client.close()

    asyncio.run(body())


# ---------------------------------------------------------------------------
# SDK: the hedged two-leg race against two live direct servers
# ---------------------------------------------------------------------------


def _start_direct(worker: _DSWorker):
    ds = DirectServer(worker, host="127.0.0.1", port=0)
    ds.start()
    port = ds._runner.addresses[0][1]
    return ds, f"http://127.0.0.1:{port}"


def test_sdk_hedged_race_first_winner_cancels_loser():
    slow = _DSWorker(block=True)
    fast = _DSWorker(text="fast")
    ds_slow, url_slow = _start_direct(slow)
    ds_fast, url_fast = _start_direct(fast)
    c = InferenceClient("http://plane.invalid:9", backoff_s=0.0,
                        max_retries=0)
    try:
        c._get_nearest_worker = lambda **kw: {
            "worker_id": "p", "direct_url": url_slow, "region": "r",
            "hedge": {"worker_id": "h", "direct_url": url_fast,
                      "delay_ms": 30.0},
        }
        res = c._try_direct("llm", {"prompt": "x", "deadline_s": 5.0})
        assert res == {"text": "fast"}         # the hedge won the race
        # the losing primary was cancelled at the server, exactly once
        deadline = time.time() + 3.0
        while time.time() < deadline and \
                ds_slow.stats["hedge_cancels"] != 1:
            time.sleep(0.02)
        assert ds_slow.stats["hedge_cancels"] == 1
        # both legs carried the request; the keys never reached engines
        assert slow.seen and "hedge_key" not in slow.seen[0]
    finally:
        c.close()
        ds_slow.stop()
        ds_fast.stop()


def test_sdk_fast_primary_never_fires_the_hedge():
    primary = _DSWorker(text="primary")
    backup = _DSWorker(text="backup")
    ds_p, url_p = _start_direct(primary)
    ds_b, url_b = _start_direct(backup)
    c = InferenceClient("http://plane.invalid:9", backoff_s=0.0,
                        max_retries=0)
    try:
        c._get_nearest_worker = lambda **kw: {
            "worker_id": "p", "direct_url": url_p, "region": "r",
            "hedge": {"worker_id": "h", "direct_url": url_b,
                      "delay_ms": 500.0},
        }
        res = c._try_direct("llm", {"prompt": "x", "deadline_s": 5.0})
        assert res == {"text": "primary"}
        time.sleep(0.1)
        assert ds_b.stats["requests"] == 0     # hedge leg never fired
        assert ds_p.stats["hedge_cancels"] == 0
    finally:
        c.close()
        ds_p.stop()
        ds_b.stop()


def test_sdk_deadline_less_requests_keep_the_single_post_path():
    primary = _DSWorker(text="primary")
    backup = _DSWorker(text="backup")
    ds_p, url_p = _start_direct(primary)
    ds_b, url_b = _start_direct(backup)
    c = InferenceClient("http://plane.invalid:9", backoff_s=0.0,
                        max_retries=0)
    try:
        calls: Dict[str, Any] = {}

        def fake_nearest(**kw):
            calls.update(kw)
            return {"worker_id": "p", "direct_url": url_p, "region": "r",
                    "hedge": {"worker_id": "h", "direct_url": url_b,
                              "delay_ms": 1.0}}

        c._get_nearest_worker = fake_nearest
        res = c._try_direct("llm", {"prompt": "x"})
        assert res == {"text": "primary"}
        assert calls.get("hedge") is False     # discovery not asked to hedge
        assert ds_b.stats["requests"] == 0     # a stray hint is ignored
        # the unhedged POST carries the raw params — no cancel key minted
        assert "hedge_key" not in primary.seen[0]
        assert "_cancel_evt" not in primary.seen[0]
    finally:
        c.close()
        ds_p.stop()
        ds_b.stop()


def test_sdk_both_legs_failing_falls_back_to_queued_path():
    slow = _DSWorker(block=True)
    fast = _DSWorker()
    slow.state = WorkerState.BUSY              # both legs reject with 503
    fast.state = WorkerState.BUSY
    ds_s, url_s = _start_direct(slow)
    ds_f, url_f = _start_direct(fast)
    c = InferenceClient("http://plane.invalid:9", backoff_s=0.0,
                        max_retries=0)
    try:
        c._get_nearest_worker = lambda **kw: {
            "worker_id": "p", "direct_url": url_s, "region": "r",
            "hedge": {"worker_id": "h", "direct_url": url_f,
                      "delay_ms": 5.0},
        }
        assert c._try_direct("llm",
                             {"prompt": "x", "deadline_s": 5.0}) is None
    finally:
        c.close()
        ds_s.stop()
        ds_f.stop()


# ---------------------------------------------------------------------------
# KV handoff wire: deadlines cross the PD boundary as absolute times
# ---------------------------------------------------------------------------


def _mk_handoff(deadline_s: Optional[float],
                arrival_ago: float = 0.0) -> KVHandoff:
    req = InferenceRequest(
        prompt_token_ids=[1, 2, 3],
        sampling=SamplingParams(max_new_tokens=8),
        arrival_time=time.time() - arrival_ago,
        deadline_s=deadline_s,
    )
    return KVHandoff(
        request=req, model_name="m", block_size=4,
        token_ids=[1, 2, 3, 7], kv_len=3, pending_token=7,
        prompt_len=3, generated=[7], start_time=req.arrival_time,
        first_token_time=None,
        pages=np.zeros((1, 2, 2, 1, 4, 2), dtype=np.float32),
    )


def test_handoff_wire_carries_absolute_deadline():
    h = _mk_handoff(deadline_s=30.0, arrival_ago=2.0)
    data = serialize_handoff(h)
    assert b"deadline_at" in data
    out = deserialize_handoff(data)
    # re-derived against the receiver's fresh arrival_time, the ABSOLUTE
    # instant is preserved: elapsed handoff time stays spent
    assert out.request.deadline_s is not None
    assert out.request.deadline_s < 30.0
    assert out.request.deadline_at == pytest.approx(
        h.request.deadline_at, abs=1e-6)


def test_handoff_wire_omits_deadline_when_unset():
    h = _mk_handoff(deadline_s=None)
    data = serialize_handoff(h)
    # omitted, not null: deadline-less wires are byte-identical to the
    # pre-deadline format
    assert b"deadline_at" not in data
    out = deserialize_handoff(data)
    assert out.request.deadline_s is None
    assert out.request.deadline_at == float("inf")


def test_handoff_wire_clamps_already_missed_deadlines():
    h = _mk_handoff(deadline_s=1.0, arrival_ago=100.0)
    out = deserialize_handoff(serialize_handoff(h))
    assert out.request.deadline_s == 0.0       # missed, but never negative


def test_checkpoint_resume_keeps_edf_ordering_across_migration():
    """A failover-resumed job must re-enter the EDF heap ordered by its
    ORIGINAL absolute deadline — not with the fresh arrival's infinite
    (or re-anchored) slack."""
    from distributed_gpu_inference_tpu.runtime.engine import (
        PreemptedSequence,
    )

    orig = InferenceRequest(
        prompt_token_ids=[1, 2, 3],
        sampling=SamplingParams(max_new_tokens=8),
        arrival_time=time.time() - 5.0,
        deadline_s=8.0,
    )
    pre = PreemptedSequence(
        request=orig, prompt_len=3, generated=[7], slot_key=(0, 0),
        start_time=orig.arrival_time, first_token_time=None,
        cached_tokens=0,
    )
    resumed = PreemptedSequence.from_wire(pre.to_wire()).request
    # the absolute instant survives the wire; the 5s already elapsed on
    # the dead worker stays spent
    assert resumed.deadline_at == pytest.approx(orig.deadline_at,
                                                abs=1e-6)
    assert resumed.deadline_s == pytest.approx(3.0, abs=0.5)
    # EDF: the resumed request outranks a same-priority fresh
    # deadline-less arrival AND a fresh later-deadline one
    fresh_late = InferenceRequest(
        prompt_token_ids=[4], sampling=SamplingParams(max_new_tokens=8),
        deadline_s=60.0,
    )
    fresh_none = InferenceRequest(
        prompt_token_ids=[5], sampling=SamplingParams(max_new_tokens=8),
    )
    ranked = sorted(
        [fresh_none, fresh_late, resumed],
        key=lambda r: (-r.priority, r.deadline_at, r.arrival_time),
    )
    assert ranked[0] is resumed
    assert ranked[-1] is fresh_none


# ---------------------------------------------------------------------------
# the 25-seed composed suite (HEAVY: slow + gray_chaos)
# ---------------------------------------------------------------------------

GRAY_FLEET_ENGINE = {
    **DEFAULT_FLEET_ENGINE,
    "serving": {**DEFAULT_FLEET_ENGINE["serving"], "max_preemptions": 8},
}

# aggressive thresholds so a ~6s chaos window can walk the full state
# machine: judged after 4 samples, escalation after 0.3s of suspicion,
# probation opens 3s into quarantine
GRAY_HEALTH = dict(enabled=True, window_s=20.0, min_samples=4,
                   min_peers=2, suspect_ratio=3.0, clear_ratio=1.5,
                   grace_s=0.3, probation_after_s=3.0, canary_budget=4)


def _enable_health(plane: LiveControlPlane, **over: Any) -> None:
    r = httpx.put(f"{plane.url}/api/v1/admin/health",
                  json={**GRAY_HEALTH, **over})
    assert r.status_code == 200, r.text


@pytest.fixture(scope="module")
def gray_fleet():
    with LiveFleet(n=GRAY_CHAOS_WORKERS,
                   engine_config=GRAY_FLEET_ENGINE) as f:
        _enable_health(f.plane)
        yield f


def _health_state(plane: LiveControlPlane, wid: str) -> Optional[str]:
    r = httpx.get(f"{plane.url}/api/v1/admin/health")
    return (r.json()["snapshot"]["workers"].get(wid) or {}).get("state")


def _await_health_state(plane: LiveControlPlane, wid: str, want,
                        timeout_s: float) -> str:
    states = want if isinstance(want, (set, tuple)) else {want}
    deadline = time.time() + timeout_s
    seen = None
    while time.time() < deadline:
        seen = _health_state(plane, wid)      # GET re-evaluates server-side
        if seen in states:
            return seen
        time.sleep(0.05)
    raise AssertionError(f"worker {wid} never reached {states}: {seen}")


@pytest.mark.slow
@pytest.mark.gray_chaos
@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_gray_chaos_seeded(gray_fleet, seed):
    """One seeded gray replay: degrade/jitter/flaky composed with clean
    kills on a 3-replica fleet with quarantine live — nothing lost,
    exactly-once SSE offsets, outputs byte-identical to a calm replay."""
    from tests.test_fleet_chaos import (
        _assert_no_lost_or_duplicated_jobs,
        _await_quiet,
        _calm_reference,
        _drive_open_loop,
        _heal,
        _suite_prompts,
    )

    plan = _gray_plan(seed)
    assert plan.events == _gray_plan(seed).events      # determinism
    prompts = _suite_prompts(seed, 9)
    gray_fleet.run_chaos(plan)
    try:
        records = _drive_open_loop(gray_fleet, prompts, seed=seed,
                                   max_tokens=7)
    finally:
        gray_fleet.wait_chaos(timeout_s=180.0)
        _heal(gray_fleet)
    assert [k for _, k, _ in plan.trace] == [e.kind for e in plan.events]
    _await_quiet(gray_fleet)
    _assert_no_lost_or_duplicated_jobs(gray_fleet)
    _calm_reference(gray_fleet, records, max_tokens=7)
    assert all(m.alive for m in gray_fleet.members)


@pytest.mark.slow
@pytest.mark.gray_chaos
def test_degraded_worker_quarantined_then_readmitted_live():
    """The tentpole walk on a LIVE fleet: one replica degrades (alive,
    heartbeating, 0.3s/request slow); the plane quarantines it off the
    shipped latency samples, opens probation, and re-admits it once its
    fresh evidence comes back clean."""
    with LiveFleet(n=3, engine_config=GRAY_FLEET_ENGINE) as fl:
        _enable_health(fl.plane)
        target = fl.members[0]
        urls = [
            f"http://127.0.0.1:{m.server._runner.addresses[0][1]}"
            for m in fl.members
        ]
        # warm every engine BEFORE the chaos clock starts: first-request
        # JIT compile is seconds on CPU and would eat the degrade window
        with httpx.Client(timeout=30.0) as c:
            for u in urls:
                c.post(u + "/inference", json={
                    "type": "llm",
                    "params": {"prompt": "warm abcdef",
                               "max_new_tokens": 2},
                })
        plan = FleetFaultPlan(0, n_workers=3, duration_s=8.0,
                              kinds=GRAY_CHAOS_KINDS)
        plan.events = [FleetEvent(0.0, "degrade", 0, duration_s=6.0,
                                  delay_s=0.3)]
        fl.run_chaos(plan)
        try:
            # direct traffic on every replica: the degraded one's samples
            # arrive 0.3s slow while its peers set a fast baseline
            with httpx.Client(timeout=15.0) as c:
                for i in range(8):
                    for u in urls:
                        with contextlib.suppress(httpx.HTTPError):
                            c.post(u + "/inference", json={
                                "type": "llm",
                                "params": {"prompt": f"gray{i} abcdef",
                                           "max_new_tokens": 2},
                            })
            got = _await_health_state(
                fl.plane, target.worker_id,
                {SUSPECT, QUARANTINED, PROBATION}, timeout_s=10.0,
            )
            assert got, "degraded worker never flagged"
        finally:
            fl.wait_chaos()
        # the full escalation is in the transition trail even if polling
        # missed an intermediate state
        deadline = time.time() + 10.0
        while time.time() < deadline and 'to="quarantined"' not in \
                _metric(fl.plane, "health_transitions_total"):
            httpx.get(f"{fl.plane.url}/api/v1/admin/health")
            time.sleep(0.05)
        trail = _metric(fl.plane, "health_transitions_total")
        assert 'from="healthy",to="suspect"' in trail
        assert 'from="suspect",to="quarantined"' in trail
        # chaos over: fresh samples (heartbeat RTTs + fast direct
        # traffic) walk it through probation back to healthy
        with httpx.Client(timeout=15.0) as c:
            for i in range(4):
                with contextlib.suppress(httpx.HTTPError):
                    c.post(urls[0] + "/inference", json={
                        "type": "llm",
                        "params": {"prompt": f"calm{i} abcdef",
                                   "max_new_tokens": 2},
                    })
        assert _await_health_state(fl.plane, target.worker_id, HEALTHY,
                                   timeout_s=20.0) == HEALTHY
        trail = _metric(fl.plane, "health_transitions_total")
        assert 'from="quarantined",to="probation"' in trail
        assert 'from="probation",to="healthy"' in trail
        # the replica was never killed — alive and registered throughout
        assert target.alive
