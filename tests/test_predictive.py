"""Serving intelligence (round 20): cost-model self-calibration,
proactive prefix replication, and predictive PD/fleet rebalance.

Everything predictive in this round is ADVISORY and OFF by default —
these tests pin both halves of that contract:

- **Estimator units**: EMA convergence with a falling predicted-vs-
  measured error, outlier clamping once warm, NaN/inf rejection, and the
  None-below-min-samples gate.
- **Calibration ingest**: flight-trace queue-wait/prefill samples with
  per-(trace, worker) dedup; per-tier bandwidth from delta-anchored
  cumulative wire counters, restart re-anchor included.
- **Byte-identity**: ``decide_kv_route`` at default (uncalibrated)
  parameters reproduces the PR 13 static cost arithmetic EXACTLY over a
  parameter grid, and every round-18 knob defaults off.
- **In-flight pull pricing** (the satellite fix): a cold target already
  running its migrate budget stops pricing as idle and the decision
  flips to recompute; tracker entries expire with the window.
- **Replication planner**: hot-threshold velocity gate, per-beat hint
  budget, per-(worker, prefix) cooldown, already-warm skip, and source
  selection from live exporters only.
- **Predictive rebalance**: projected-SLO misses preflip a donor worker
  to HYBRID and suggest the starved role for scale-out; recovery past
  the hysteresis restores configured roles; capability refreshes
  preserve the preflip.
- **Predictive abandonment**: a pre-deadline hopeless request abandons
  typed and counted (``abandoned_predictive``) only when the flag is on.

Select with ``pytest -m predictive``.
"""

import asyncio
import contextlib
import time
from typing import Any, Optional

import pytest

from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
)
from distributed_gpu_inference_tpu.runtime.kv_handoff import (
    pack_export_request,
    unpack_export_request,
)
from distributed_gpu_inference_tpu.runtime.prefix_summary import (
    PrefixHotSet,
)
from distributed_gpu_inference_tpu.server.autoscaler import (
    AutoscalerConfig,
    BrownoutAutoscaler,
    PredictiveRebalanceConfig,
    PredictiveRebalancer,
)
from distributed_gpu_inference_tpu.server.calibration import (
    CostCalibration,
    Estimator,
    MigrateHintTracker,
)
from distributed_gpu_inference_tpu.server.pd_scheduler import (
    PrefillDecodeScheduler,
    WorkerCapability,
)
from distributed_gpu_inference_tpu.server.prefix_routing import (
    MIGRATE_TIER_COST,
    PrefixRegistry,
    RoutingConfig,
    decide_kv_route,
)
from distributed_gpu_inference_tpu.server.replication import (
    ReplicationPlanner,
)
from distributed_gpu_inference_tpu.utils.config import ServingConfig
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
    WorkerRole,
)

pytestmark = pytest.mark.predictive


# ---------------------------------------------------------------------------
# estimator units
# ---------------------------------------------------------------------------


def test_estimator_converges_and_error_falls():
    est = Estimator(alpha=0.3, clamp=5.0, min_samples=3)
    # alternating noise around 100: the EMA settles near the mean and the
    # relative-error EMA falls as the estimate locks on
    series = [80.0, 120.0, 95.0, 105.0, 99.0, 101.0, 100.0, 100.0,
              100.0, 100.0, 100.0, 100.0]
    errs = []
    for s in series:
        est.observe(s)
        if est.err_ema is not None:
            errs.append(est.err_ema)
    assert 90.0 < est.value < 110.0
    assert est.warm
    # convergence: the published error is lower at the end than when the
    # estimator first had an error at all
    assert errs[-1] < errs[0]


def test_estimator_clamps_outliers_once_warm():
    est = Estimator(alpha=0.5, clamp=5.0, min_samples=2)
    est.observe(100.0)
    est.observe(100.0)
    assert est.warm
    est.observe(1e6)   # one GC pause / cold pull: clamped to value*clamp
    # blended sample was at most 500 → value at most 100 + 0.5*400 = 300
    assert est.value <= 300.0
    # BELOW min_samples the clamp is off (the second sample may legally
    # be far from the seed — two samples are not a consensus)
    fresh = Estimator(alpha=0.5, clamp=5.0, min_samples=3)
    fresh.observe(1.0)
    fresh.observe(1000.0)
    assert fresh.value > 100.0


def test_estimator_rejects_degenerate_and_gates_below_min_samples():
    est = Estimator(alpha=0.3, clamp=5.0, min_samples=3)
    est.observe(float("nan"))
    est.observe(float("inf"))
    assert est.n == 0 and est.get() is None
    est.observe(10.0)
    est.observe(12.0)
    assert est.get() is None          # 2 < min_samples: keep the prior
    est.observe(11.0)
    assert est.get() is not None


# ---------------------------------------------------------------------------
# calibration ingest
# ---------------------------------------------------------------------------


def _cal(**over: Any) -> CostCalibration:
    cfg = RoutingConfig()
    for k, v in over.items():
        setattr(cfg, k, v)
    return CostCalibration(cfg)


def _trace_events(enq: float, adm: float, ftk: float, tokens: int):
    return [
        ("batcher.enqueued", enq, {}),
        ("batcher.admitted", adm, {"tokens": tokens}),
        ("batcher.first_token", ftk, {}),
    ]


def test_ingest_trace_extracts_queue_wait_and_prefill_tps():
    cal = _cal(calibrate=True, calibrate_min_samples=1)
    landed = cal.ingest_trace("w1", "t1",
                              _trace_events(10.0, 10.5, 11.0, 2000))
    assert landed
    assert cal.queue_wait_s("w1") == pytest.approx(0.5)
    assert cal.prefill_tps("w1") == pytest.approx(2000 / 0.5)
    # duplicate delivery (flight rings re-ship): idempotent per
    # (trace, worker)
    assert not cal.ingest_trace("w1", "t1",
                                _trace_events(10.0, 10.9, 11.0, 2000))
    assert cal.queue_wait_s("w1") == pytest.approx(0.5)


def test_ingest_kv_migrate_delta_anchored_with_restart_reanchor():
    cal = _cal(calibrate=True, calibrate_min_samples=1)
    # first reading ANCHORS (delta vs 0 is itself a sample): 1 MB in 1 s
    cal.ingest_kv_migrate("w1", {"pull_bytes_dev": 1_000_000,
                                 "pull_ms_dev": 1000})
    assert cal.bandwidth("w1", "dev") == pytest.approx(1e6)
    # second reading: +2 MB in +1 s → 2 MB/s sample blends in
    cal.ingest_kv_migrate("w1", {"pull_bytes_dev": 3_000_000,
                                 "pull_ms_dev": 2000})
    bw = cal.bandwidth("w1", "dev")
    assert bw is not None and 1e6 < bw < 2e6
    # restart: counters regress → re-anchor, NO negative/zero sample
    cal.ingest_kv_migrate("w1", {"pull_bytes_dev": 500_000,
                                 "pull_ms_dev": 400})
    assert cal.bandwidth("w1", "dev") == pytest.approx(bw)
    # next delta after the re-anchor lands normally
    cal.ingest_kv_migrate("w1", {"pull_bytes_dev": 1_500_000,
                                 "pull_ms_dev": 1400})
    assert cal.bandwidth("w1", "dev") != pytest.approx(bw)


def test_calibration_reads_gated_on_flag_and_reset():
    cal = _cal(calibrate=False, calibrate_min_samples=1)
    cal.ingest_trace("w1", "t1", _trace_events(0.0, 1.0, 2.0, 1000))
    cal.ingest_kv_migrate("w1", {"pull_bytes_host": 10_000,
                                 "pull_ms_host": 10})
    # ingestion accumulated (visible in the snapshot)...
    assert cal.snapshot()["workers"]
    # ...but decide-time reads answer None while the flag is off
    assert cal.queue_wait_s("w1") is None
    assert cal.prefill_tps("w1") is None
    assert cal.bandwidth("w1", "host") is None
    cal.cfg.calibrate = True
    assert cal.queue_wait_s("w1") is not None
    # the A/B hard half: reset drops learned state AND the delta anchors
    cal.reset()
    assert cal.queue_wait_s("w1") is None
    assert cal.snapshot()["workers"] == {}


def test_bandwidth_tier_cost_cancels_in_decide():
    """The estimator measures the tier-INCLUSIVE effective rate;
    decide_kv_route multiplies transfer by the tier cost after dividing
    by the bandwidth — bandwidth() pre-multiplies so the prediction
    equals bytes / measured rate exactly."""
    cal = _cal(calibrate=True, calibrate_min_samples=1)
    cal.ingest_kv_migrate("w1", {"pull_bytes_spill": 2_000_000,
                                 "pull_ms_spill": 1000})   # 2 MB/s measured
    cfg = cal.cfg
    bw = cal.bandwidth("w1", "spill")
    assert bw == pytest.approx(2e6 * MIGRATE_TIER_COST["spill"])
    d = decide_kv_route(cfg, request_blocks=4, matched_blocks=4,
                        tier="spill", warm_headroom=1.0, cold_headroom=1.0,
                        migrate_bandwidth=bw)
    matched_bytes = 4 * cfg.block_chars * cfg.migrate_bytes_per_token
    # idle cold side: migrate cost is pure transfer at the measured rate
    assert d["costs"]["migrate"] == pytest.approx(matched_bytes / 2e6)


# ---------------------------------------------------------------------------
# byte-identity: defaults reproduce the static cost model
# ---------------------------------------------------------------------------


def _static_costs(cfg: RoutingConfig, request_blocks: int,
                  matched_blocks: int, tier: str, warm_headroom: float,
                  cold_headroom: float) -> dict:
    """The PR 13 cost arithmetic, restated independently."""
    bc = max(1, cfg.block_chars)
    total = max(request_blocks, matched_blocks, 1) * bc
    matched = max(0, matched_blocks) * bc

    def wait(h: float) -> float:
        return (1.0 - max(0.0, min(1.0, h))) * cfg.migrate_queue_wait_s

    def prefill(tokens: float) -> float:
        return max(0.0, tokens) / cfg.migrate_prefill_tokens_per_s

    transfer = (matched * cfg.migrate_bytes_per_token
                * MIGRATE_TIER_COST.get(tier, 1.0)
                / cfg.migrate_bandwidth_bytes_per_s)
    return {
        "warm": wait(warm_headroom) + prefill(total - matched),
        "migrate": wait(cold_headroom) + prefill(total - matched) + transfer,
        "recompute": wait(cold_headroom) + prefill(total),
    }


def test_decide_kv_route_defaults_are_byte_identical_to_static_model():
    cfg = RoutingConfig()
    for rb in (1, 4, 16, 32):
        for mb in (0, 1, 2, 8, 32):
            for tier in ("dev", "host", "spill"):
                for wh, ch in ((1.0, 1.0), (0.0, 1.0), (0.3, 0.7),
                               (1.0, 0.0)):
                    got = decide_kv_route(
                        cfg, request_blocks=rb, matched_blocks=mb,
                        tier=tier, warm_headroom=wh, cold_headroom=ch,
                    )
                    want = _static_costs(cfg, rb, mb, tier, wh, ch)
                    for k in ("warm", "migrate", "recompute"):
                        assert got["costs"][k] == want[k], (rb, mb, tier,
                                                           wh, ch, k)


def test_round18_knobs_default_off():
    cfg = RoutingConfig()
    assert cfg.calibrate is False
    assert cfg.replicate is False
    assert BatcherConfig().predictive_abandon is False
    assert ServingConfig().predictive_abandon is False
    assert PredictiveRebalanceConfig().enabled is False


def test_routing_config_update_validates_round18_knobs():
    cfg = RoutingConfig()
    cfg.update({"calibrate": True, "calibrate_alpha": 0.5,
                "replicate": True, "replicate_hot_threshold": 5,
                "migrate_hint_window_s": 3.0})
    assert cfg.calibrate and cfg.replicate
    assert cfg.calibrate_alpha == 0.5
    assert cfg.replicate_hot_threshold == 5
    with pytest.raises(ValueError):
        cfg.update({"calibrate_alpha": 2.0})
    with pytest.raises(ValueError):
        cfg.update({"replicate_max_hints": 0})
    with pytest.raises(ValueError):
        cfg.update({"calibrate_clamp": 0.5})
    d = cfg.to_dict()
    for key in ("calibrate", "calibrate_alpha", "calibrate_clamp",
                "calibrate_min_samples", "migrate_hint_window_s",
                "replicate", "replicate_hot_threshold",
                "replicate_window_s", "replicate_max_hints",
                "replicate_cooldown_s"):
        assert key in d


# ---------------------------------------------------------------------------
# in-flight pull pricing (the satellite fix)
# ---------------------------------------------------------------------------


def test_inflight_pulls_flip_migrate_to_recompute():
    cfg = RoutingConfig()
    kw = dict(request_blocks=8, matched_blocks=8, tier="dev",
              warm_headroom=0.0, cold_headroom=1.0)
    idle = decide_kv_route(cfg, **kw)
    assert idle["choice"] == "migrate"   # deep match, saturated warm side
    busy = decide_kv_route(cfg, cold_inflight_pulls=3, **kw)
    # three pulls already serialize ahead on the target's budget: the
    # queued transfers now cost more than re-prefilling from scratch
    assert busy["costs"]["migrate"] > idle["costs"]["migrate"]
    assert busy["choice"] == "recompute"


def test_migrate_hint_tracker_window_expiry():
    cfg = RoutingConfig()
    cfg.migrate_hint_window_s = 5.0
    tr = MigrateHintTracker(cfg)
    t0 = 1000.0
    assert tr.inflight("w1", now=t0) == 0
    tr.note("w1", now=t0)
    tr.note("w1", now=t0 + 1.0)
    assert tr.inflight("w1", now=t0 + 2.0) == 2
    # the first hint ages past the window; the second survives
    assert tr.inflight("w1", now=t0 + 5.5) == 1
    assert tr.inflight("w1", now=t0 + 7.0) == 0
    assert tr.inflight("other", now=t0) == 0


# ---------------------------------------------------------------------------
# replication planner
# ---------------------------------------------------------------------------


def _planner(**over: Any):
    cfg = RoutingConfig()
    cfg.replicate = True
    for k, v in over.items():
        setattr(cfg, k, v)
    reg = PrefixRegistry(cfg)
    return ReplicationPlanner(cfg, reg), reg, cfg


def _advertise(reg: PrefixRegistry, cfg: RoutingConfig, worker_id: str,
               fps, now: float) -> None:
    res = reg.ingest(worker_id, {
        "v": 1, "seq": 1, "block_chars": cfg.block_chars,
        "full": [[fp, i + 1, "dev"] for i, fp in enumerate(fps)],
    }, now=now)
    assert res.applied


SRC = {"id": "warm", "data_plane_url": "http://warm:9009"}
COLD = "cold"


def test_hot_threshold_gates_hints():
    pl, reg, cfg = _planner(replicate_hot_threshold=3,
                            replicate_window_s=10.0)
    now = 1000.0
    fps = ["aa", "bb", "cc"]
    _advertise(reg, cfg, "warm", fps, now)
    pl.note_query(fps, now=now)
    pl.note_query(fps, now=now + 1)
    # two hits inside the window: below threshold, no hint
    assert pl.hints_for(COLD, [SRC], now=now + 2) == []
    pl.note_query(fps, now=now + 2)
    hints = pl.hints_for(COLD, [SRC], now=now + 3)
    assert len(hints) == 1
    h = hints[0]
    assert h["worker_id"] == "warm"
    assert h["data_plane_url"] == SRC["data_plane_url"]
    assert h["fps"] == fps
    assert h["tier"] == "dev"
    # hits outside the window expire: the same prefix goes cold again
    pl2, reg2, cfg2 = _planner(replicate_hot_threshold=3,
                               replicate_window_s=10.0)
    _advertise(reg2, cfg2, "warm", fps, now)
    for i in range(3):
        pl2.note_query(fps, now=now + i)
    assert pl2.hints_for(COLD, [SRC], now=now + 30) == []


def test_hint_budget_and_cooldown_bound_fanout():
    pl, reg, cfg = _planner(replicate_hot_threshold=1,
                            replicate_max_hints=2,
                            replicate_cooldown_s=30.0)
    now = 1000.0
    chains = [[f"p{i}a", f"p{i}b"] for i in range(4)]
    # one combined snapshot — a later full snapshot would REPLACE the map
    res = reg.ingest("warm", {
        "v": 1, "seq": 1, "block_chars": cfg.block_chars,
        "full": [[fp, i + 1, "dev"]
                 for chain in chains for i, fp in enumerate(chain)],
    }, now=now)
    assert res.applied
    # heat them unevenly so the budget goes hottest-first
    for i, fps in enumerate(chains):
        for _ in range(i + 1):
            pl.note_query(fps, now=now)
    hints = pl.hints_for(COLD, [SRC], now=now + 1)
    assert len(hints) == 2               # per-beat budget
    assert hints[0]["fps"] == chains[3]  # hottest first
    assert hints[1]["fps"] == chains[2]
    # cooldown: the SAME worker is not re-hinted for those prefixes, so
    # the budget moves down the heat ranking
    again = pl.hints_for(COLD, [SRC], now=now + 2)
    assert [h["fps"] for h in again] == [chains[1], chains[0]]
    # past the cooldown the hottest prefixes are hintable again
    later = pl.hints_for(COLD, [SRC], now=now + 40)
    assert later == []   # hits expired with the window — honest cold


def test_chain_heating_hints_deepest_recurring_boundary():
    """A chat conversation extends its chain every turn — each query has
    a FRESH deepest fp, but the shared head recurs. Heat accrues to
    every traversed boundary, and the hint ships the deepest still-hot
    chain (one per lineage, never an ancestor a deeper hot entry
    covers)."""
    pl, reg, cfg = _planner(replicate_hot_threshold=3)
    now = 1000.0
    # three turns of one conversation: sys → sys+t1 → sys+t1+t2
    pl.note_query(["sys"], now=now)
    pl.note_query(["sys", "t1"], now=now + 1)
    pl.note_query(["sys", "t1", "t2"], now=now + 2)
    _advertise(reg, cfg, "warm", ["sys", "t1", "t2"], now)
    hints = pl.hints_for(COLD, [SRC], now=now + 3)
    # "sys" has 3 hits (hot), "t1" has 2, "t2" has 1 — but "sys" would
    # be covered if a deeper boundary were hot too; here it is the
    # deepest HOT one, so the hint is exactly the recurring head
    assert len(hints) == 1
    assert hints[0]["fps"] == ["sys"]
    # one more turn: now "t1" crosses the threshold and supersedes "sys"
    pl.note_query(["sys", "t1", "t3"], now=now + 3)
    hints = pl.hints_for("cold2", [SRC], now=now + 4)
    assert len(hints) == 1
    assert hints[0]["fps"] == ["sys", "t1"]


def test_no_hint_when_worker_already_advertises_prefix():
    pl, reg, cfg = _planner(replicate_hot_threshold=1)
    now = 1000.0
    fps = ["aa", "bb"]
    _advertise(reg, cfg, "warm", fps, now)
    _advertise(reg, cfg, COLD, fps[:1], now)   # holds a PARTIAL overlap
    pl.note_query(fps, now=now)
    assert pl.hints_for(COLD, [SRC], now=now + 1) == []


def test_no_hint_without_live_exporter():
    pl, reg, cfg = _planner(replicate_hot_threshold=1)
    now = 1000.0
    fps = ["aa", "bb"]
    pl.note_query(fps, now=now)
    # nobody advertises it → no source → no hint
    assert pl.hints_for(COLD, [SRC], now=now + 1) == []
    _advertise(reg, cfg, "warm", fps, now)
    # the heartbeating worker itself is never its own source
    assert pl.hints_for("warm", [SRC], now=now + 1) == []
    # a source without a data plane cannot serve a pull
    assert pl.hints_for(COLD, [{"id": "warm"}], now=now + 1) == []
    assert len(pl.hints_for(COLD, [SRC], now=now + 1)) == 1


# ---------------------------------------------------------------------------
# prefix hot-set: note_fingerprints ≡ note
# ---------------------------------------------------------------------------


def test_note_fingerprints_matches_note():
    from distributed_gpu_inference_tpu.utils.prefixes import (
        canonical_prompt_text,
        prefix_fingerprints,
    )

    prompt = "x" * 2048
    a = PrefixHotSet(top_n=16)
    b = PrefixHotSet(top_n=16)
    a.note(prompt)
    fps = prefix_fingerprints(canonical_prompt_text(prompt),
                              b.block_chars, b.max_blocks)
    b.note_fingerprints(fps)
    assert a.snapshot() == b.snapshot()
    assert b.note_fingerprints([]) == 0
    # a replication pull advertising adopted KV lands at its tier
    c = PrefixHotSet(top_n=16)
    c.note_fingerprints(["f1", "f2"], tier="host")
    assert c.snapshot() == {"f1": (1, "host"), "f2": (2, "host")}


# ---------------------------------------------------------------------------
# predictive PD rebalance
# ---------------------------------------------------------------------------


def _pd_pool() -> PrefillDecodeScheduler:
    pd = PrefillDecodeScheduler()
    pd.register_worker(WorkerCapability(
        worker_id="p1", role=WorkerRole.PREFILL, max_prefill_batch=4))
    pd.register_worker(WorkerCapability(
        worker_id="d1", role=WorkerRole.DECODE, max_decode_batch=8))
    return pd


def _miss_autoscaler(now: float, in_slo: bool) -> BrownoutAutoscaler:
    auto = BrownoutAutoscaler(AutoscalerConfig(min_samples=3,
                                               window_s=10.0))
    for i in range(6):
        auto.observe(in_slo=in_slo, now=now - 1.0 + i * 0.1)
    return auto


def test_projected_miss_preflips_donor_and_suggests_starved_role():
    now = 1000.0
    auto = _miss_autoscaler(now, in_slo=False)
    pd = _pd_pool()
    # starve the prefill side: every slot busy, decode side idle
    pd.worker("p1").active_prefill = 4
    reb = PredictiveRebalancer(
        auto, pd, PredictiveRebalanceConfig(enabled=True))
    suggested = reb.tick(now=now)
    assert suggested == "prefill"
    # the decode worker donated: it now also accepts prefill work
    assert pd.worker("d1").cap.role is WorkerRole.HYBRID
    assert pd._preflipped == {"d1": WorkerRole.DECODE}
    assert pd.stats["preflipped"] == 1
    # max_preflips=1: while the projection still misses and prefill is
    # still the short side (the donated slots fill too), the rebalancer
    # keeps suggesting but cannot convert the whole donor side
    pd.worker("d1").active_prefill = 2
    assert reb.tick(now=now + 1.0) == "prefill"
    assert pd.stats["preflipped"] == 1
    assert pd._preflipped == {"d1": WorkerRole.DECODE}


def test_recovery_past_hysteresis_restores_roles():
    now = 1000.0
    auto = _miss_autoscaler(now, in_slo=False)
    pd = _pd_pool()
    pd.worker("p1").active_prefill = 4
    reb = PredictiveRebalancer(
        auto, pd, PredictiveRebalanceConfig(enabled=True))
    reb.tick(now=now)
    assert pd.worker("d1").cap.role is WorkerRole.HYBRID
    # the window refills with healthy samples → projection recovers
    for i in range(20):
        auto.observe(in_slo=True, now=now + 20.0 + i * 0.1)
    assert reb.tick(now=now + 23.0) is None
    assert pd.worker("d1").cap.role is WorkerRole.DECODE
    assert pd._preflipped == {}
    assert pd.stats["preflip_restored"] == 1


def test_rebalancer_disabled_and_balanced_pools_are_noops():
    now = 1000.0
    auto = _miss_autoscaler(now, in_slo=False)
    pd = _pd_pool()
    pd.worker("p1").active_prefill = 4
    off = PredictiveRebalancer(auto, pd, PredictiveRebalanceConfig())
    assert off.tick(now=now) is None
    assert pd.worker("d1").cap.role is WorkerRole.DECODE
    # balanced shortage (both sides equally free) is scale-out territory,
    # not a role imbalance
    pd2 = PrefillDecodeScheduler()
    pd2.register_worker(WorkerCapability(
        worker_id="p1", role=WorkerRole.PREFILL, max_prefill_batch=4))
    pd2.register_worker(WorkerCapability(
        worker_id="d1", role=WorkerRole.DECODE, max_decode_batch=4))
    on = PredictiveRebalancer(
        auto, pd2, PredictiveRebalanceConfig(enabled=True))
    assert on.tick(now=now) is None
    assert pd2._preflipped == {}


def test_refresh_worker_preserves_preflip_and_active_counts():
    pd = _pd_pool()
    pd.worker("d1").active_decode = 3
    assert pd.preflip_role("prefill") == "d1"
    assert pd.worker("d1").cap.role is WorkerRole.HYBRID
    # a placement sync refreshes the capability from the store row (which
    # still says DECODE): the preflip must survive, the restore target
    # follows the store, and live counters stay bound
    pd.refresh_worker(WorkerCapability(
        worker_id="d1", role=WorkerRole.DECODE, max_decode_batch=16))
    w = pd.worker("d1")
    assert w.cap.role is WorkerRole.HYBRID
    assert w.cap.max_decode_batch == 16
    assert w.active_decode == 3
    assert pd._preflipped == {"d1": WorkerRole.DECODE}
    pd.restore_preflips()
    assert pd.worker("d1").cap.role is WorkerRole.DECODE
    # refresh of an unknown worker registers it
    pd.refresh_worker(WorkerCapability(worker_id="new",
                                       role=WorkerRole.HYBRID))
    assert pd.worker("new") is not None
    # removal drops any preflip bookkeeping
    pd.preflip_role("prefill")
    pd.remove_worker("d1")
    assert "d1" not in pd._preflipped


# ---------------------------------------------------------------------------
# predictive deadline abandonment (fake engine, no decode loop)
# ---------------------------------------------------------------------------


class _PoolEngine:
    max_num_seqs = 8

    def request_fits_pool(self, request: InferenceRequest) -> bool:
        return True


def _req(deadline_s: Optional[float], arrival_ago: float,
         max_new: int = 64) -> InferenceRequest:
    return InferenceRequest(
        prompt_token_ids=[1, 2, 3],
        sampling=SamplingParams(max_new_tokens=max_new),
        arrival_time=time.time() - arrival_ago,
        deadline_s=deadline_s,
    )


def test_predictive_abandon_fires_before_the_deadline():
    b = ContinuousBatcher(_PoolEngine(), BatcherConfig(
        abandon_deadlines=True, predictive_abandon=True,
        deadline_grace_s=0.5))
    b.stats["step_latency_ema_ms"] = 1000.0
    now = 1000.0
    # deadline 5 s out, but 100 tokens at 1 s/token can never land
    doomed = InferenceRequest(prompt_token_ids=[1],
                              sampling=SamplingParams(max_new_tokens=100),
                              arrival_time=now, deadline_s=5.0)
    assert b._deadline_hopeless(doomed, 100, now)
    # the same projection with room to finish stays admitted
    fine = InferenceRequest(prompt_token_ids=[1],
                            sampling=SamplingParams(max_new_tokens=3),
                            arrival_time=now, deadline_s=5.0)
    assert not b._deadline_hopeless(fine, 3, now)
    # reactive mode never fires pre-deadline — the round-18 OFF contract
    b.cfg.predictive_abandon = False
    assert not b._deadline_hopeless(doomed, 100, now)


def test_predictive_abandon_counted_and_typed():
    async def body():
        b = ContinuousBatcher(_PoolEngine(), BatcherConfig(
            abandon_deadlines=True, predictive_abandon=True,
            deadline_grace_s=0.5))
        b.stats["step_latency_ema_ms"] = 1000.0
        # deadline is still 60 s away — only the projection condemns it
        task = asyncio.ensure_future(
            b.submit(_req(deadline_s=60.0, arrival_ago=0.0, max_new=500)))
        await asyncio.sleep(0.01)
        assert len(b._heap) == 1
        await b._scan_deadlines()
        resp = await asyncio.wait_for(task, 5.0)
        assert resp.error_code == "deadline_abandoned"
        assert resp.finish_reason == "abort"
        assert b.stats["abandoned"] == 1
        assert b.stats["abandoned_predictive"] == 1

    asyncio.run(body())


def test_reactive_abandon_does_not_count_predictive():
    async def body():
        b = ContinuousBatcher(_PoolEngine(), BatcherConfig(
            abandon_deadlines=True, deadline_grace_s=0.5))
        b.stats["step_latency_ema_ms"] = 200.0
        task = asyncio.ensure_future(
            b.submit(_req(deadline_s=5.0, arrival_ago=30.0)))
        await asyncio.sleep(0.01)
        await b._scan_deadlines()
        resp = await asyncio.wait_for(task, 5.0)
        assert resp.error_code == "deadline_abandoned"
        assert b.stats["abandoned"] == 1
        assert b.stats["abandoned_predictive"] == 0
        # a pre-deadline request is untouched with the flag off
        live = asyncio.ensure_future(
            b.submit(_req(deadline_s=60.0, arrival_ago=0.0, max_new=500)))
        await asyncio.sleep(0.01)
        await b._scan_deadlines()
        assert not live.done()
        live.cancel()
        with contextlib.suppress(asyncio.CancelledError):
            await live

    asyncio.run(body())


# ---------------------------------------------------------------------------
# fp-keyed export requests (replication pull wire form)
# ---------------------------------------------------------------------------


def test_pack_export_request_with_fp_round_trips_on_version_1():
    raw = pack_export_request(key="k", token_ids=[], model_name="m",
                              block_size=16, int8_kv=False, fp="deadbeef")
    req = unpack_export_request(raw)
    assert req["v"] == 1               # old exporters still parse it
    assert req["fp"] == "deadbeef"
    assert req["token_ids"] == []      # they just see no tokens → no body
    # the classic form carries no fp key at all — byte-compatible
    legacy = unpack_export_request(pack_export_request(
        key="k", token_ids=[1, 2], model_name="m",
        block_size=16, int8_kv=False))
    assert "fp" not in legacy
