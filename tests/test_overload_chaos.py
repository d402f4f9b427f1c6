"""SLO-native overload control under fire.

The round-12 tentpole suite: per-tenant admission budgets, the
degrade-before-reject ladder (clamp max_tokens → disable speculation →
429 free tier, paid last), deadline-EDF batcher ordering, and the
brownout-driven autoscaler — composed with seeded
:class:`FleetFaultPlan` kill/restart chaos on a :class:`LiveFleet`.

The 25-seed heavy suite throws a 10x free-tier burst at a small fleet
while a seeded kill/restart executes, and asserts the composed
invariants:

- **Paid-tier jobs are never shed while free-tier capacity exists** —
  structurally: the free tier's queue fraction closes admission to free
  traffic long before the queue can reach the paid limit.
- **No lost or duplicated jobs**: every ACCEPTED job completes exactly
  once (shed submissions never created a row).
- **Exactly-once SSE**: paid direct streams keep monotonic offsets and
  token-count==final-offset through the chaos.
- **Every shed/degrade decision observable**: the controller's decision
  counts reconcile with ``admission_decisions_total`` in ``/metrics``.
- **Byte-identical greedy outputs** for all completed jobs vs a calm
  (chaos-free, admission-off) replay at the same effective token
  budgets — degradation changes how MUCH is generated, never WHAT.

Heavy replays carry ``slow`` + ``overload`` (HEAVY CI shard, ``pytest
-m overload``); the ladder/EDF/Retry-After/cardinality/autoscaler unit
tests and one small fleet smoke stay tier-1.
"""

import asyncio
import math
import threading
import time
from typing import Any, Dict, List, Optional

import httpx
import pytest

from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
)
from distributed_gpu_inference_tpu.runtime.engine import PreemptedSequence
from distributed_gpu_inference_tpu.sdk.client import (
    InferenceClient,
    InferenceClientError,
)
from distributed_gpu_inference_tpu.server.admission import (
    TIER_PRIORITY_BOOST,
    AdmissionConfig,
    AdmissionController,
    estimate_cost_tokens,
    normalize_tier,
    tenant_of,
)
from distributed_gpu_inference_tpu.server.app import _json_error
from distributed_gpu_inference_tpu.server.autoscaler import (
    AutoscalerConfig,
    BrownoutAutoscaler,
)
from distributed_gpu_inference_tpu.server.observability import (
    HAVE_PROMETHEUS,
    MetricsCollector,
)
from distributed_gpu_inference_tpu.server.store import Store
from distributed_gpu_inference_tpu.server.usage import UsageService
from distributed_gpu_inference_tpu.server.worker_config import (
    DEFAULT_TIER_QUEUE_FRACTIONS,
    WorkerConfigService,
)
from distributed_gpu_inference_tpu.testing.faults import FleetFaultPlan
from distributed_gpu_inference_tpu.testing.harness import (
    DEFAULT_FLEET_ENGINE,
    FleetAutoscaler,
    LiveControlPlane,
    LiveFleet,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    JobStatus,
)

N_SEEDS = 25

FLEET_ENGINE = {
    **DEFAULT_FLEET_ENGINE,
    "serving": {**DEFAULT_FLEET_ENGINE["serving"], "max_preemptions": 8},
}

# the suite's admission geometry: with submit_queue_limit=10, free closes
# at 5 queued and batch at 3, while paid holds the full 10 — and since
# free admission stops at 5, the queue can only exceed 5 through paid
# jobs (≤4 in flight per seed), so it can NEVER reach 10: paid sheds are
# structurally impossible while free is being shed. Degrade rungs sit
# BELOW the free shed point so clamp/no-spec decisions actually occur.
SUITE_QUEUE_LIMIT = 10
SUITE_ADMISSION = {
    "enabled": True,
    "rate_tokens_per_s": 0.0,        # ladder driven by queue saturation
    "degrade_at": 0.2,               # clamp at ≥2 queued
    "no_spec_at": 0.4,               # vanilla decode at ≥4 queued
    "clamp_max_tokens": 4,
    "min_retry_after_s": 0.05,
}
SUITE_TIER_FRACTIONS = {"paid": 1.0, "free": 0.5, "batch": 0.3}


def _run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# admission ladder (cheap, tier-1 — no engines, no servers)
# ---------------------------------------------------------------------------


def _wc(limit: int, fractions: Optional[Dict[str, float]] = None
        ) -> WorkerConfigService:
    store = Store(":memory:")
    wc = WorkerConfigService(store)
    wc.set_submit_queue_limit(limit)
    if fractions:
        wc._defaults.load_control.tier_queue_fractions = dict(fractions)
    return wc


def test_admission_disabled_accepts_everything():
    wc = _wc(2)
    ac = AdmissionController(AdmissionConfig(enabled=False))
    for i in range(20):
        d = ac.decide(f"t{i}", "free", 1000, queued=99, active_workers=0,
                      worker_config=wc)
        assert d.action == "accept" and d.admitted


def test_admission_ladder_degrades_before_shedding():
    """Rungs in order as saturation climbs: accept → clamp → clamp+no-spec
    → shed; and the shed carries a retry hint."""
    wc = _wc(10)   # default fractions: free sheds at 8 (0.85 * 10)
    ac = AdmissionController(AdmissionConfig(
        enabled=True, degrade_at=0.3, no_spec_at=0.6, clamp_max_tokens=8,
    ))
    d = ac.decide("t", "free", 64, queued=1, active_workers=1,
                  worker_config=wc)
    assert d.action == "accept" and d.max_tokens is None
    d = ac.decide("t", "free", 64, queued=3, active_workers=1,
                  worker_config=wc)
    assert d.action == "degrade_clamp" and d.max_tokens == 8
    assert not d.disable_spec
    d = ac.decide("t", "free", 64, queued=6, active_workers=1,
                  worker_config=wc)
    assert d.action == "degrade_no_spec" and d.disable_spec
    assert d.max_tokens == 8
    d = ac.decide("t", "free", 64, queued=8, active_workers=1,
                  worker_config=wc)
    assert d.action == "shed" and not d.admitted
    assert d.retry_after_s >= 1.0


def test_admission_paid_sheds_last():
    """The tier shed order is batch → free → paid: at a queue depth where
    free/batch shed, paid still degrades-and-accepts; paid sheds only at
    the full limit (where everything sheds)."""
    wc = _wc(10)   # defaults: paid 10, free 8.5→8, batch 6
    ac = AdmissionController(AdmissionConfig(enabled=True))
    at9 = {t: ac.decide(f"x-{t}", t, 16, queued=9, active_workers=1,
                        worker_config=wc) for t in ("paid", "free", "batch")}
    assert at9["free"].action == "shed"
    assert at9["batch"].action == "shed"
    assert at9["paid"].admitted
    at10 = ac.decide("x-paid", "paid", 16, queued=10, active_workers=1,
                     worker_config=wc)
    assert at10.action == "shed"


def test_admission_budget_weighted_fair_share_and_paid_debt():
    """With a finite budget: a free tenant that burns its bucket sheds on
    budget alone (empty queue!), the paid tenant's fair-share rate is
    weight-proportionally larger, and paid is never shed on budget —
    it runs a bounded debt instead."""
    wc = _wc(0)    # no queue limit: budget is the only gate
    ac = AdmissionController(AdmissionConfig(
        enabled=True, rate_tokens_per_s=100.0, burst_s=1.0,
        tier_weights={"paid": 8.0, "free": 1.0, "batch": 0.25},
    ))
    now = 1000.0
    # activate both tenants so fair shares split the budget
    ac.decide("p", "paid", 1, 0, 1, wc, now=now)
    ac.decide("f", "free", 1, 0, 1, wc, now=now)
    assert ac.tenant_rate("paid", now=now) > 5 * ac.tenant_rate(
        "free", now=now)
    # drain the free bucket: repeated costly asks stop being accepted
    decisions = [ac.decide("f", "free", 200, 0, 1, wc, now=now + 0.01 * i)
                 for i in range(6)]
    sheds = [d for d in decisions if d.action == "shed"]
    assert sheds, [d.action for d in decisions]
    assert all(d.retry_after_s > 0 for d in sheds)
    # ... and the bucket REFILLS: after a couple of fair-share seconds
    # the degraded (clamped) ask is affordable again
    later = ac.decide("f", "free", 200, 0, 1, wc, now=now + 5.0)
    assert later.admitted and later.max_tokens is not None
    # paid with the same hammering never sheds (debt, then fairness)
    paid_actions = [ac.decide("p", "paid", 500, 0, 1, wc,
                              now=now + 0.01 * i).action for i in range(6)]
    assert "shed" not in paid_actions


def test_admission_bucket_lru_is_bounded():
    """A tenant-id-spraying client recycles bucket slots instead of
    growing plane memory."""
    wc = _wc(0)
    ac = AdmissionController(AdmissionConfig(
        enabled=True, rate_tokens_per_s=100.0, max_tenants=16,
    ))
    for i in range(500):
        ac.decide(f"spray-{i}", "free", 1, 0, 1, wc, now=1000.0 + i * 0.001)
    assert ac.tracked_tenants() <= 16


def test_admission_helpers_and_config_update():
    assert normalize_tier("PAID ") == "paid"
    assert normalize_tier("platinum") == "free"    # cannot invent a tier
    assert normalize_tier(None) == "free"
    assert tenant_of({"params": {"tenant": "a", "tier": "batch"}}) == \
        ("a", "batch")
    assert tenant_of({"tenant": "top", "params": {}}) == ("top", "free")
    assert tenant_of({}) == ("anonymous", "free")
    assert estimate_cost_tokens({"max_new_tokens": 8, "prompt": "x" * 40}) \
        == 18
    cfg = AdmissionConfig()
    cfg.update({"enabled": "true", "degrade_at": 0.25,
                "tier_weights": {"paid": 4}})
    assert cfg.enabled and cfg.degrade_at == 0.25
    # partial weight updates MERGE — the untouched tiers keep their
    # weights instead of falling onto the 1.0 lookup fallback
    assert cfg.tier_weights["paid"] == 4.0
    assert cfg.tier_weights["batch"] == 0.25
    with pytest.raises(ValueError):
        cfg.update({"nonsense_knob": 1})


def test_tier_queue_fractions_order_and_untiered_compat():
    """tier=None keeps the exact legacy blanket behavior; tier fractions
    are strictly ordered so shed order is batch → free → paid."""
    wc = _wc(10)
    assert DEFAULT_TIER_QUEUE_FRACTIONS["batch"] \
        < DEFAULT_TIER_QUEUE_FRACTIONS["free"] \
        < DEFAULT_TIER_QUEUE_FRACTIONS["paid"] == 1.0
    for queued in range(14):
        legacy_ok = queued < 10
        ok, retry = wc.should_accept_submission(queued, 1)
        assert ok == legacy_ok
        if not ok:
            assert retry >= 1.0
    # paid == untiered limit; free/batch close earlier
    assert wc.should_accept_submission(9, 1, tier="paid")[0]
    assert not wc.should_accept_submission(9, 1, tier="free")[0]
    assert not wc.should_accept_submission(6, 1, tier="batch")[0]
    assert wc.should_accept_submission(5, 1, tier="batch")[0]


def test_workload_tier_priorities_match_admission_boosts():
    """benchmarks/workloads.py must not drift from the server's tier →
    priority mapping (it cannot import server code)."""
    from benchmarks.workloads import TIER_PRIORITY

    assert TIER_PRIORITY == TIER_PRIORITY_BOOST


# ---------------------------------------------------------------------------
# metrics label cardinality (satellite: bounded tenant labels)
# ---------------------------------------------------------------------------


def test_tenant_label_cap_bounds_metric_cardinality():
    mc = MetricsCollector(tenant_label_cap=3)
    for i in range(50):
        mc.record_admission("free", "accept", tenant=f"t{i}")
    # the first 3 tenants keep their labels, the rest aggregate
    assert mc.tenant_label("t0") == "t0"
    assert mc.tenant_label("t49") == "other"
    assert mc.tenant_label("brand-new") == "other"
    if HAVE_PROMETHEUS:
        text = mc.render().decode()
        labels = {
            line.split('tenant="', 1)[1].split('"', 1)[0]
            for line in text.splitlines()
            if line.startswith("tenant_admission_decisions_total{")
        }
        assert len(labels) <= 4 and "other" in labels
        # the by-tier counter is unaffected by the spray
        assert 'admission_decisions_total{action="accept",' \
            'tenant_tier="free"} 50.0' in text


# ---------------------------------------------------------------------------
# Retry-After contract (satellite: app.py _json_error + shed paths)
# ---------------------------------------------------------------------------


def test_json_error_retry_after_ceil_and_body_agreement():
    for hint, header in ((1.2, "2"), (3.0, "3"), (0.2, "1"), (59.01, "60")):
        resp = _json_error(429, "x", retry_after_s=hint)
        assert resp.headers["Retry-After"] == header
        import json as _json

        body = _json.loads(resp.body)
        assert body["retry_after_s"] == round(hint, 3)
        assert int(resp.headers["Retry-After"]) == math.ceil(
            body["retry_after_s"])
    # no hint → no header, no body field
    resp = _json_error(404, "x")
    assert "Retry-After" not in resp.headers


def test_shed_paths_carry_retry_after_end_to_end():
    """A real control plane with admission enabled: free-tier sheds 429
    with header/body agreement; paid passes at the same depth; the admin
    endpoint flips the ladder live."""
    with LiveControlPlane(submit_queue_limit=4) as cp:
        # enable the ladder on the RUNNING plane via the admin endpoint
        r = httpx.put(f"{cp.url}/api/v1/admin/admission",
                      json={"enabled": True, "degrade_at": 1.0,
                            "no_spec_at": 1.0})
        assert r.status_code == 200 and r.json()["enabled"] is True
        assert httpx.put(f"{cp.url}/api/v1/admin/admission",
                         json={"bogus": 1}).status_code == 400

        def submit(tier: str) -> httpx.Response:
            return httpx.post(f"{cp.url}/api/v1/jobs", json={
                "type": "llm",
                "params": {"prompt": "p", "max_new_tokens": 4,
                           "tenant": f"ten-{tier}", "tier": tier},
            })

        # no workers: accepted jobs stay QUEUED. Free fraction 0.85*4→3:
        # the 4th free submission sheds while paid still enters.
        sheds: List[httpx.Response] = []
        for _ in range(6):
            r = submit("free")
            if r.status_code == 429:
                sheds.append(r)
        assert sheds, "free tier never shed"
        for r in sheds:
            body = r.json()
            assert body["retry_after_s"] > 0
            assert r.headers["Retry-After"] == str(
                math.ceil(body["retry_after_s"]))
        assert submit("paid").status_code == 201
        # every decision landed in /metrics
        text = httpx.get(f"{cp.url}/metrics").text
        assert 'admission_decisions_total{action="shed",' \
            'tenant_tier="free"}' in text
        snap = httpx.get(f"{cp.url}/api/v1/admin/admission").json()
        assert snap["snapshot"]["decisions"]["free:shed"] == len(sheds)


def test_degrade_clamps_job_params_and_boosts_tier_priority():
    """An admitted-but-degraded job row carries the clamped token budget
    and the tier priority boost — the worker and the batcher see exactly
    what the plane decided."""
    with LiveControlPlane(submit_queue_limit=100) as cp:
        httpx.put(f"{cp.url}/api/v1/admin/admission",
                  json={"enabled": True, "degrade_at": 0.0,
                        "no_spec_at": 0.0, "clamp_max_tokens": 3})
        r = httpx.post(f"{cp.url}/api/v1/jobs", json={
            "type": "llm", "priority": 1,
            "params": {"prompt": "q", "max_new_tokens": 64,
                       "tenant": "acme", "tier": "paid"},
        })
        assert r.status_code == 201
        job = cp.call(cp.state.store.get_job(r.json()["job_id"]))
        assert job["params"]["max_new_tokens"] == 3
        assert job["params"]["degraded_max_tokens"] == 3
        assert job["params"]["speculative"] is False
        assert job["params"]["tenant"] == "acme"
        assert job["params"]["tier"] == "paid"
        assert job["priority"] == 1 + TIER_PRIORITY_BOOST["paid"]


# ---------------------------------------------------------------------------
# usage metering carries the admitted tenant/tier (store v8)
# ---------------------------------------------------------------------------


def test_usage_records_tenant_and_tier():
    async def run():
        store = Store(":memory:")
        usage = UsageService(store)
        job = {
            "id": "j1", "type": "llm", "worker_id": "w1",
            "params": {"tenant": "acme", "tier": "paid"},
            "result": {"usage": {"total_tokens": 12}},
        }
        rec = await usage.record_job_usage(job)
        assert rec["tenant"] == "acme" and rec["tier"] == "paid"
        rows = await store.query(
            "SELECT tenant, tier, units FROM usage_records", ())
        assert rows == [{"tenant": "acme", "tier": "paid", "units": 12.0}]
        summary = await usage.tenant_summary()
        assert summary[0]["tenant"] == "acme"
        assert summary[0]["units"] == 12.0
        store.close()

    _run(run())


# ---------------------------------------------------------------------------
# deadline-EDF batcher ordering + error codes (engine-free: the batcher
# never starts, so no jax graph is ever built)
# ---------------------------------------------------------------------------


class _StubEngineCfg:
    prefill_buckets = (32,)
    speculative = None
    max_seq_len = 128


class _StubEngine:
    cfg = _StubEngineCfg()
    num_active = 0

    def __init__(self) -> None:
        self.slots: List[Any] = [None] * 4
        self.preempted: List[int] = []

    def free_slots(self) -> List[int]:
        return []

    def request_fits_pool(self, request: Any) -> bool:
        return True

    def preempt_slot(self, slot: int) -> PreemptedSequence:
        self.preempted.append(slot)
        s = self.slots[slot]
        return PreemptedSequence(
            request=s.request, prompt_len=0, generated=[],
            slot_key=(0, 0), start_time=0.0, first_token_time=None,
            cached_tokens=0,
        )


def _req(prompt: str, priority: int = 0,
         deadline_s: Optional[float] = None,
         arrival: float = 100.0) -> InferenceRequest:
    return InferenceRequest(
        prompt_token_ids=[ord(c) % 256 for c in prompt],
        priority=priority, deadline_s=deadline_s, arrival_time=arrival,
    )


def test_batcher_edf_orders_within_priority_band():
    async def run():
        b = ContinuousBatcher(_StubEngine(), BatcherConfig(queue_limit=64))
        reqs = [
            _req("a", priority=0, deadline_s=9.0, arrival=100.0),
            _req("b", priority=0, deadline_s=2.0, arrival=101.0),
            _req("c", priority=0, arrival=99.0),          # no deadline
            _req("d", priority=5, deadline_s=50.0, arrival=102.0),
        ]
        tasks = [asyncio.ensure_future(b.submit(r, timeout_s=5.0))
                 for r in reqs]
        await asyncio.sleep(0.01)
        order = [it.request.prompt_token_ids[0]
                 for it in b._admission_order()]
        # priority 5 leads regardless of deadline; inside the 0-band EDF
        # wins: deadline 2 (b) before deadline 9 (a) before none (c)
        assert order == [ord("d"), ord("b"), ord("a"), ord("c")]
        for t in tasks:
            t.cancel()

    _run(run())


def test_batcher_order_byte_identical_without_deadlines():
    """Acceptance bar: with no deadlines set, admission order must equal
    the pre-EDF batcher's (-priority, arrival, seq) order exactly."""
    async def run():
        b = ContinuousBatcher(_StubEngine(), BatcherConfig(queue_limit=64))
        reqs = [_req(chr(97 + i), priority=i % 3, arrival=100.0 + (i * 7) % 5)
                for i in range(12)]
        tasks = [asyncio.ensure_future(b.submit(r, timeout_s=5.0))
                 for r in reqs]
        await asyncio.sleep(0.01)
        got = [it.request for it in b._admission_order()]
        legacy = sorted(
            ((-r.priority, r.arrival_time, i) for i, r in enumerate(reqs)),
        )
        want = [reqs[i] for _, _, i in legacy]
        assert got == want
        for t in tasks:
            t.cancel()

    _run(run())


def test_batcher_victim_policy_is_deadline_aware():
    async def run():
        eng = _StubEngine()
        b = ContinuousBatcher(eng, BatcherConfig(queue_limit=64))

        class _Slot:
            finish_reason = None
            prefilling = False

            def __init__(self, request: Any) -> None:
                self.request = request

        loop = asyncio.get_running_loop()
        items = {}
        specs = [("tight", 1.0), ("loose", 30.0), ("none", None)]
        for slot, (name, dl) in enumerate(specs):
            r = _req(name[0], priority=0, deadline_s=dl)
            eng.slots[slot] = _Slot(r)
            from distributed_gpu_inference_tpu.runtime.batcher import (
                _QueueItem,
            )

            items[slot] = _QueueItem(
                sort_key=(0, r.deadline_at, r.arrival_time, slot),
                request=r, future=loop.create_future(),
            )
        b._slot_items = dict(items)
        b._admit_stamp = {0: 10, 1: 11, 2: 12}
        await b._preempt_victim(mandatory=True)
        # most slack first: the deadline-less slot is the victim
        assert eng.preempted == [2]
        # next victim: the LOOSE deadline, not the tight one (the batcher
        # already removed the first victim from _slot_items; clear only
        # its engine slot)
        b._slot_items.pop(2, None)
        eng.slots[2] = None
        await b._preempt_victim(mandatory=True)
        assert eng.preempted == [2, 1]
        # all-no-deadline regression: LIFO by admission stamp (the
        # pre-deadline policy, byte-identical)
        eng2 = _StubEngine()
        b2 = ContinuousBatcher(eng2, BatcherConfig(queue_limit=64))
        for slot in range(3):
            r = _req(chr(97 + slot))
            eng2.slots[slot] = _Slot(r)
            b2._slot_items[slot] = _QueueItem(
                sort_key=(0, r.deadline_at, r.arrival_time, slot),
                request=r, future=loop.create_future(),
            )
        b2._admit_stamp = {0: 5, 1: 9, 2: 7}
        await b2._preempt_victim(mandatory=True)
        assert eng2.preempted == [1]      # youngest admission

    _run(run())


def test_error_codes_request_timeout_vs_shed_overload():
    async def run():
        b = ContinuousBatcher(_StubEngine(), BatcherConfig(queue_limit=1))
        # never started: the first submit waits, the second overflows
        first = asyncio.ensure_future(b.submit(_req("x"), timeout_s=0.2))
        await asyncio.sleep(0.01)
        second = await b.submit(_req("y"), timeout_s=0.2)
        assert second.error == "queue full"
        assert second.error_code == "shed_overload"
        r1 = await first
        assert r1.error_code == "request_timeout"
        assert "timeout" in r1.error

    _run(run())


def test_serving_error_carries_code_to_sse_and_job_result():
    """The machine-readable class survives the two surfacing paths: the
    SSE pump copies it onto the error chunk, worker/main attaches it to
    the failure result."""
    from distributed_gpu_inference_tpu.utils.data_structures import (
        InferenceResponse,
    )
    from distributed_gpu_inference_tpu.worker.engines.base import (
        ServingError,
    )
    from distributed_gpu_inference_tpu.worker.engines.llm import (
        _raise_serving,
    )

    resp = InferenceResponse(request_id="r", error="timeout after 1s",
                             error_code="request_timeout")
    with pytest.raises(ServingError) as exc:
        _raise_serving(resp)
    assert exc.value.error_code == "request_timeout"
    # a generic exception has no code — surfaces stay backward compatible
    assert getattr(RuntimeError("x"), "error_code", None) is None


# ---------------------------------------------------------------------------
# autoscaler unit behavior (tier-1)
# ---------------------------------------------------------------------------


def test_autoscaler_projects_slo_and_scales_out():
    a = BrownoutAutoscaler(AutoscalerConfig(
        window_s=4.0, min_samples=4, scale_out_cooldown_s=0.0,
        default_cold_start_s=2.0, slo_target=0.9,
    ))
    t = 1000.0
    assert a.tick(1, 0.5, now=t) == "hold"      # min_samples gate
    for i in range(8):
        a.observe(in_slo=(i < 4), now=t + i * 0.4)   # worsening trend
    assert a.projected_slo(now=t + 3.2) < a.slo_in_window(now=t + 3.2)
    assert a.tick(1, 0.9, now=t + 3.2) == "scale_out"
    assert a.stats["scale_out"] == 1
    # max_replicas bound
    b = BrownoutAutoscaler(AutoscalerConfig(
        window_s=4.0, min_samples=2, scale_out_cooldown_s=0.0,
        max_replicas=2,
    ))
    for i in range(4):
        b.observe(in_slo=False, now=t + i * 0.2)
    assert b.tick(2, 1.0, now=t + 1.0) == "hold"


def test_autoscaler_scale_in_needs_sustained_headroom():
    a = BrownoutAutoscaler(AutoscalerConfig(
        window_s=4.0, min_samples=3, headroom_ticks=3,
        scale_in_cooldown_s=0.0, min_replicas=1,
    ))
    t = 2000.0

    def tick(util: float, now: float) -> str:
        # traffic keeps flowing (all in SLO) so the window never empties
        a.observe(in_slo=True, now=now)
        a.observe(in_slo=True, now=now)
        a.observe(in_slo=True, now=now)
        return a.tick(3, util, now=now)

    for i in range(8):
        a.observe(in_slo=True, now=t + i * 0.4)
    now = t + 3.5
    assert tick(0.1, now) == "hold"          # streak 1
    assert tick(0.1, now + 1) == "hold"      # streak 2
    assert tick(0.9, now + 2) == "hold"      # busy tick resets the streak
    assert tick(0.1, now + 3) == "hold"
    assert tick(0.1, now + 4) == "hold"
    assert tick(0.1, now + 5) == "scale_in"
    # never below min_replicas
    a.observe(in_slo=True, now=now + 20)
    a.observe(in_slo=True, now=now + 20)
    a.observe(in_slo=True, now=now + 20)
    assert a.tick(1, 0.0, now=now + 20) != "scale_in"


def test_autoscaler_measures_cold_start():
    a = BrownoutAutoscaler(AutoscalerConfig(default_cold_start_s=4.0,
                                            cold_start_ema=0.5))
    a.note_scale_out_started(now=100.0)
    a.note_replica_serving(now=102.0)
    assert a.cold_start_s == pytest.approx(3.0)
    a.note_scale_out_started(now=200.0)
    a.note_replica_serving(now=201.0)
    assert a.cold_start_s == pytest.approx(2.0)
    assert a.stats["cold_starts_measured"] == 2
    # unpaired serving note is a no-op
    a.note_replica_serving(now=300.0)
    assert a.stats["cold_starts_measured"] == 2


# ---------------------------------------------------------------------------
# the live-fleet overload machinery
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet():
    with LiveFleet(n=2, engine_config=FLEET_ENGINE,
                   submit_queue_limit=SUITE_QUEUE_LIMIT) as f:
        f.plane.state.admission.cfg.update(SUITE_ADMISSION)
        f.plane.state.worker_config._defaults.load_control \
            .tier_queue_fractions = dict(SUITE_TIER_FRACTIONS)
        yield f


def _admission_stats(fl: LiveFleet) -> Dict[str, int]:
    return dict(fl.plane.state.admission.stats)


def _metric_value(fl: LiveFleet, name: str, **labels: str) -> float:
    text = httpx.get(f"{fl.plane.url}/metrics").text
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def _create_no_429_retry(c: InferenceClient, params: Dict[str, Any]
                         ) -> str:
    """create_job that retries TRANSPORT blips only (idle keep-alive
    connections race the server closing them — the same artifact every
    fleet driver in this repo retries) but lets 429s surface: a shed must
    be observed, not ridden out."""
    for attempt in range(4):
        try:
            return c.create_job("llm", params)
        except InferenceClientError as exc:
            if exc.status == 599 and attempt < 3:
                time.sleep(0.05)
                continue
            raise


def _free_burst(fl: LiveFleet, seed: int, n: int,
                out: Dict[str, Any]) -> None:
    """The 10x burst: n free-tier jobs fired as fast as the plane answers
    (no pacing — this IS the overload). Sheds are collected, accepted
    job ids recorded; 429s never retry (the burst models a misbehaving
    tenant, not a polite SDK)."""
    c = InferenceClient(fl.url, backoff_s=0.0, max_retries=0)
    try:
        for i in range(n):
            try:
                jid = _create_no_429_retry(c, {
                    "prompt": f"free s{seed} r{i} aaaa",
                    "max_new_tokens": 8,
                    "tenant": f"burst-{seed % 3}", "tier": "free",
                })
                out["accepted"].append(jid)
            except InferenceClientError as exc:
                assert exc.status == 429, exc
                assert exc.retry_after_s is not None \
                    and exc.retry_after_s > 0
                out["shed"] += 1
    finally:
        c.close()


def _paid_traffic(fl: LiveFleet, seed: int, n: int, span_s: float,
                  out: Dict[str, Any],
                  errors: List[BaseException]) -> None:
    """Paid-tier jobs spaced across the burst window. Paid clients do NOT
    retry either — a single 429 on a paid job is an invariant violation,
    and we want to see it, not ride it out."""
    c = InferenceClient(fl.url, backoff_s=0.0, max_retries=0)
    try:
        for i in range(n):
            time.sleep(span_s / max(1, n))
            jid = _create_no_429_retry(c, {
                "prompt": f"paid s{seed} r{i} bbbb",
                "max_new_tokens": 6,
                "tenant": "enterprise", "tier": "paid",
            })
            out["paid_accepted"].append(jid)
    except BaseException as exc:  # noqa: BLE001 — surfaced by the caller
        errors.append(exc)
    finally:
        c.close()


def _paid_stream(fl: LiveFleet, seed: int, out: Dict[str, Any],
                 errors: List[BaseException]) -> None:
    """One paid direct SSE stream riding through the chaos window —
    exactly-once offsets asserted exactly like the fleet-chaos suite."""
    c = InferenceClient(fl.url, backoff_s=0.05)
    try:
        chunks = list(c.stream_chat(prompt=f"stream s{seed} cccc",
                                    max_new_tokens=6, timeout_s=90.0,
                                    max_stream_resumes=6))
        assert chunks[-1].get("done") is True, chunks[-1:]
        offs = [int(ch["offset"]) for ch in chunks
                if ch.get("offset") is not None]
        assert offs == sorted(offs), offs
        toks = [t for ch in chunks[:-1] for t in ch.get("token_ids") or []]
        if offs:
            assert len(toks) == offs[-1], (len(toks), offs)
        out["stream_text"] = "".join(
            ch.get("text_delta") or "" for ch in chunks[:-1]
        )
    except BaseException as exc:  # noqa: BLE001 — surfaced by the caller
        errors.append(exc)
    finally:
        c.close()


def _wait_jobs(fl: LiveFleet, job_ids: List[str],
               timeout_s: float = 120.0) -> Dict[str, Dict[str, Any]]:
    c = InferenceClient(fl.url, backoff_s=0.05)
    done = {}
    try:
        for jid in job_ids:
            job = c.wait_for_job(jid, timeout_s=timeout_s, poll_s=0.05)
            assert job["status"] == "completed", (jid, job)
            done[jid] = job
    finally:
        c.close()
    return done


def _calm_replay_identical(fl: LiveFleet,
                           done: Dict[str, Dict[str, Any]]) -> None:
    """Replay every completed job on the healed fleet with the ladder OFF
    at the SAME effective token budget (the clamp is part of the job's
    contract once admitted) — greedy text must match byte for byte."""
    fl.plane.state.admission.cfg.enabled = False
    c = InferenceClient(fl.url, backoff_s=0.05)
    try:
        for jid, job in done.items():
            params = job["params"]
            rid = c.create_job("llm", {
                "prompt": params["prompt"],
                "max_new_tokens": params["max_new_tokens"],
            })
            calm = c.wait_for_job(rid, timeout_s=90.0, poll_s=0.05)
            assert calm["status"] == "completed", (jid, calm)
            assert calm["result"]["text"] == job["result"]["text"], jid
    finally:
        c.close()
        fl.plane.state.admission.cfg.enabled = True


def _heal(fl: LiveFleet) -> None:
    for m in fl.members:
        if not m.alive:
            m.start()


def _overload_round(fl: LiveFleet, seed: int, free_n: int, paid_n: int,
                    chaos: bool) -> Dict[str, Any]:
    """One composed round: the 10x free burst + paced paid traffic + one
    paid SSE stream, optionally under a seeded kill/restart plan."""
    before = _admission_stats(fl)
    out: Dict[str, Any] = {"accepted": [], "paid_accepted": [],
                           "shed": 0, "stream_text": None}
    errors: List[BaseException] = []
    span = 2.0
    plan = None
    if chaos:
        plan = FleetFaultPlan(seed, n_workers=2, duration_s=span + 1.0,
                              kinds=("kill",))
        fl.run_chaos(plan)
    threads = [
        threading.Thread(target=_paid_traffic,
                         args=(fl, seed, paid_n, span, out, errors),
                         daemon=True),
        threading.Thread(target=_paid_stream, args=(fl, seed, out, errors),
                         daemon=True),
    ]
    for t in threads:
        t.start()
    try:
        _free_burst(fl, seed, free_n, out)
    finally:
        for t in threads:
            t.join(timeout=120.0)
        if plan is not None:
            fl.wait_chaos(timeout_s=180.0)
            _heal(fl)
    if errors:
        raise errors[0]
    after = _admission_stats(fl)
    out["delta"] = {k: after.get(k, 0) - before.get(k, 0)
                    for k in set(after) | set(before)}
    return out


def _assert_overload_invariants(fl: LiveFleet, out: Dict[str, Any],
                                seed: Any) -> None:
    delta = out["delta"]
    # paid never shed (structural: free admission closes at 5 queued, so
    # the queue cannot reach paid's limit of 10)
    assert delta.get("paid:shed", 0) == 0, (seed, delta)
    assert len(out["paid_accepted"]) > 0, seed
    # decisions → /metrics reconciliation (cumulative counters equal the
    # controller's cumulative stats)
    stats = _admission_stats(fl)
    for key, count in stats.items():
        tier, action = key.split(":")
        assert _metric_value(
            fl, "admission_decisions_total",
            tenant_tier=tier, action=action,
        ) == float(count), (seed, key)
    # accepted jobs all complete exactly once; shed jobs never created
    done = _wait_jobs(fl, out["accepted"] + out["paid_accepted"])
    rows = fl.plane.query(
        "SELECT id, status FROM jobs WHERE status != ?",
        (JobStatus.COMPLETED.value,),
    )
    assert not rows, (seed, rows)
    # degraded jobs honored their clamp
    clamp = fl.plane.state.admission.cfg.clamp_max_tokens
    for jid, job in done.items():
        if job["params"].get("degraded_max_tokens"):
            usage = job["result"]["usage"]
            assert usage["completion_tokens"] <= clamp, (seed, jid)
    # byte-identical outputs vs a calm, ladder-off replay
    _calm_replay_identical(fl, done)


# one cheap smoke stays tier-1: burst + shed + degrade + invariants, no
# chaos, small counts
def test_overload_smoke_free_burst_degrades_paid_holds(fleet):
    out = _overload_round(fleet, seed=0, free_n=14, paid_n=3, chaos=False)
    assert out["shed"] >= 1, "free tier never shed under the burst"
    assert out["delta"].get("free:shed", 0) == out["shed"]
    degrades = sum(v for k, v in out["delta"].items()
                   if k.endswith(":degrade_clamp")
                   or k.endswith(":degrade_no_spec"))
    assert degrades >= 1, out["delta"]
    _assert_overload_invariants(fleet, out, seed="smoke")


# ---------------------------------------------------------------------------
# the 25-seed composed suite (HEAVY: slow + overload)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.overload
@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_overload_chaos_seeded(fleet, seed):
    """A 10x free-tier burst composed with a seeded kill/restart plan:
    the ladder invariants, exactly-once SSE, metrics reconciliation, and
    calm-replay byte-identity all hold while a worker dies and rejoins
    mid-burst."""
    plan_probe = FleetFaultPlan(seed, n_workers=2, duration_s=3.0,
                                kinds=("kill",))
    assert plan_probe.events == FleetFaultPlan(
        seed, n_workers=2, duration_s=3.0, kinds=("kill",)).events
    out = _overload_round(fleet, seed, free_n=16, paid_n=4, chaos=True)
    _assert_overload_invariants(fleet, out, seed)
    assert all(m.alive for m in fleet.members)


@pytest.mark.slow
@pytest.mark.overload
def test_free_tier_sheds_across_suite_seeds(fleet):
    """Aggregate guarantee over a few chaos rounds: the burst DOES shed
    free-tier traffic (the suite would be vacuous if the queue never
    saturated) while paid sheds stay zero."""
    sheds = 0
    for seed in (101, 102, 103):
        out = _overload_round(fleet, seed, free_n=16, paid_n=3, chaos=True)
        sheds += out["shed"]
        assert out["delta"].get("paid:shed", 0) == 0
    assert sheds >= 3


# ---------------------------------------------------------------------------
# autoscaler on a live fleet, composed with chaos (HEAVY)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.overload
def test_autoscaler_scales_out_live_fleet_under_chaos():
    """A 2-replica fleet loses one to a kill while paced traffic runs;
    the SLO window degrades, the autoscaler adds a cold replica (timed —
    the measured cold start feeds the projection), and the fleet ends
    ABOVE its starting strength with every job completed."""
    with LiveFleet(n=2, engine_config=FLEET_ENGINE) as fl:
        asc = BrownoutAutoscaler(
            AutoscalerConfig(
                slo_latency_ms=400.0, slo_target=0.9, window_s=3.0,
                min_samples=4, scale_out_cooldown_s=5.0,
                max_replicas=3, default_cold_start_s=3.0,
            ),
            metrics=fl.plane.state.metrics,
        )
        driver = FleetAutoscaler(fl, asc, tick_s=0.25).start()
        c = InferenceClient(fl.url, backoff_s=0.05)
        job_ids: List[str] = []
        try:
            fl.members[1].kill()
            fl.plane.state.metrics.record_chaos_event("kill")
            for i in range(12):
                t0 = time.perf_counter()
                jid = c.create_job("llm", {
                    "prompt": f"asc r{i} dddd", "max_new_tokens": 6,
                })
                job = c.wait_for_job(jid, timeout_s=90.0, poll_s=0.02)
                assert job["status"] == "completed", job
                job_ids.append(jid)
                asc.observe(
                    latency_ms=(time.perf_counter() - t0) * 1000.0)
        finally:
            c.close()
            driver.stop()
            _heal(fl)
        assert asc.stats["scale_out"] >= 1, asc.stats
        assert asc.stats["cold_starts_measured"] >= 1
        assert asc.cold_start_s > 0.0
        assert len(fl.members) >= 3          # a replica was really added
        assert len(fl.alive_members()) >= 2
        # decisions visible in /metrics
        text = httpx.get(f"{fl.plane.url}/metrics").text
        assert 'autoscaler_decisions_total{action="scale_out"}' in text
        assert "autoscaler_cold_start_seconds" in text


@pytest.mark.slow
@pytest.mark.overload
def test_fleet_scale_in_retires_youngest():
    with LiveFleet(n=1, engine_config=FLEET_ENGINE) as fl:
        assert fl.scale_in() is None          # never below one replica
        m = fl.scale_out()
        assert m.alive and len(fl.alive_members()) == 2
        victim = fl.scale_in()
        assert victim is m and not m.alive
        assert len(fl.alive_members()) == 1
