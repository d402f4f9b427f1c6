"""One scan kept in flight (PR 32): the batcher dispatches the next decode
scan before the last one is read back (``TPUEngine.decode_multi(T,
ahead=True)``), so its round work runs while the device does.

The same work: a chain of length zero is the loop as it always was, and the
token streams are the same bytes either way. Everything here runs the tiny
models on the CPU; ``supports_scan_ahead = False`` on the engine instance is
the unchained control (the batcher asks per call, from what it observes)."""

import asyncio
import inspect
import threading
import types

import numpy as np
import pytest

from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.runtime import batcher as batcher_mod
from distributed_gpu_inference_tpu.runtime.batcher import (
    _CHAIN_BREAKS,
    BatcherConfig,
    ContinuousBatcher,
)
from distributed_gpu_inference_tpu.runtime.engine import EngineConfig, TPUEngine
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)
from tests.test_scan_level_rule import _batcher, _feed

PROMPTS = [list(range(5, 17)), list(range(40, 59)), list(range(90, 97))]
BUDGETS = [9, 23, 30]


def _engine(model, **kw):
    cfg = dict(max_batch_size=4, max_seq_len=128, dtype="float32",
               prefill_buckets=(16, 32, 64), multi_step=4,
               enable_prefix_cache=False)
    cfg.update(kw)
    return TPUEngine(get_model_config(model, dtype="float32"),
                     EngineConfig(**cfg), seed=0)


@pytest.fixture(scope="module")
def engines():
    return {"dense": _engine("llama3-tiny"), "olmoe": _engine("olmoe-tiny")}


def _req(prompt, max_new, temp=0.0, seed=None, stop=(), **kw):
    return InferenceRequest(
        prompt_token_ids=list(prompt),
        sampling=SamplingParams(max_new_tokens=max_new, temperature=temp,
                                seed=seed, stop_token_ids=list(stop)),
        **kw)


def _requests(temp, stop=()):
    return [_req(p, n, temp, seed=7 + i, stop=stop)
            for i, (p, n) in enumerate(zip(PROMPTS, BUDGETS))]


def _serve(engine, requests, steps, chained=True, during=None, **cfg):
    """The requests through a fresh batcher at a fixed scan length;
    returns (responses, stats). ``during(batcher)`` is a coroutine run
    beside them."""
    engine.supports_scan_ahead = chained

    async def go():
        b = ContinuousBatcher(engine, BatcherConfig(
            max_wait_ms=1, adaptive=False, multi_step=steps,
            max_multi_step=max(steps, 4), **cfg))
        b.start()
        work = [b.submit(r) for r in requests]
        if during is not None:
            work.append(during(b))
        out = await asyncio.gather(*work)
        stats = b.get_stats()
        await b.stop()
        return out[:len(requests)], stats

    try:
        return asyncio.run(go())
    finally:
        engine.supports_scan_ahead = True


def _scans(stats):
    return sum(v for k, v in stats.items()
               if k.startswith("scans_t") and k[7:].isdigit())


def _breaks(stats):
    return sum(stats[f"chain_breaks_{why}"] for why in _CHAIN_BREAKS)


# --------------------------------------------------------------------- #
# (1) chained against unchained: the same bytes
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("end", ["budget", "stop"])
@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "seeded"])
@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("model", ["dense", "olmoe"])
def test_chained_and_unchained_streams_are_the_same_bytes(
        engines, model, steps, temp, end):
    eng = engines[model]
    stop = ()
    if end == "stop":
        # a token the longest stream emits mid-way, as everyone's stop id:
        # rows then end inside a scan at a step the host cannot foresee
        plain, _ = _serve(eng, _requests(temp), steps, chained=False)
        stop = (plain[2].token_ids[len(plain[2].token_ids) // 2],)
    routed = [eng.stats.get("moe_assignments_scan", 0)]
    want, base = _serve(eng, _requests(temp, stop), steps, chained=False)
    routed.append(eng.stats.get("moe_assignments_scan", 0))
    got, stats = _serve(eng, _requests(temp, stop), steps, chained=True)
    routed.append(eng.stats.get("moe_assignments_scan", 0))
    # the experts' counters of a scan read for another entry are counted
    assert routed[2] - routed[1] == routed[1] - routed[0]
    assert (routed[1] > routed[0]) == (model == "olmoe")
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    assert [r.finish_reason for r in got] == [r.finish_reason for r in want]
    if end == "stop":
        assert "stop" in [r.finish_reason for r in got]
    else:
        assert [len(r.token_ids) for r in got] == BUDGETS
    # the control never chained, the other did, and rows ended mid-chain
    assert base["scans_chained"] == 0 and _breaks(base) == 0
    assert stats["scans_chained"] > 0
    assert stats["chain_breaks_row_end"] + stats["chain_breaks_idle"] > 0
    assert eng.manager.get_stats()["free_blocks"] == eng.num_blocks - 1


# --------------------------------------------------------------------- #
# (2) a row that hits a stop id inside scan n: nothing in scan n+1
# --------------------------------------------------------------------- #

def test_a_row_stopped_inside_a_scan_emits_nothing_in_the_chained_one(
        engines):
    eng = engines["dense"]
    free = eng.manager.get_stats()["free_blocks"]
    ref = eng.generate([_req(PROMPTS[1], 24)], use_multi_step=True)[0]
    stop = ref.token_ids[5]
    assert stop not in ref.token_ids[:5]
    slot = eng.submit(_req(PROMPTS[1], 24, stop=(stop,)))
    other = eng.submit(_req(PROMPTS[0], 24))
    # token 0 came with the prefill; scan 1 emits tokens 1-4, scan 2 hits
    # the stop id at its first step, scan 3 goes out behind scan 2 unread
    assert eng.decode_multi(4, ahead=True) == {}
    first = eng.decode_multi(4, ahead=True)
    assert first[slot] == ref.token_ids[1:5] and eng.scan_unread
    second = eng.decode_multi(4, ahead=True)         # reads scan 2
    assert second[slot] == [stop] and len(second[other]) == 4
    assert eng.slots[slot].finish_reason == "stop"
    third = eng.collect_scan()                       # scan 3: masked row
    assert third[slot] == [] and len(third[other]) == 4
    assert not eng.scan_unread and eng.collect_scan() == {}
    resp = eng.finish_slot(slot)
    assert resp.token_ids == ref.token_ids[:5]
    eng.finish_slot(other)
    assert eng.manager.get_stats()["free_blocks"] == free


# --------------------------------------------------------------------- #
# (3) an arrival, a cancel, a deadline mid-chain
# --------------------------------------------------------------------- #

async def _mid_chain(b):
    while b.stats["scans_chained"] < 3:
        await asyncio.sleep(0.0005)


@pytest.mark.parametrize("what", ["arrival", "cancel", "deadline"])
def test_mid_chain_events_break_the_chain_within_a_scan(engines, what):
    eng = engines["dense"]
    cancel = threading.Event()
    seen = {}
    long = _req(PROMPTS[0], 90)
    cfg = {}
    if what == "deadline":
        long = _req(PROMPTS[0], 90, deadline_s=0.0)
        cfg = dict(abandon_deadlines=True, deadline_grace_s=3600.0)
    real_ragged = eng.ragged_round

    def ragged_round(admissions, *a, **kw):
        # scans dispatched by the time the arrival's prompt goes out
        seen.setdefault("admitted_at", None)
        if seen.get("sent_at") is not None and seen["admitted_at"] is None:
            seen["admitted_at"] = _scans(seen["b"].stats)
        return real_ragged(admissions, *a, **kw)

    async def during(b):
        seen["b"] = b
        await _mid_chain(b)
        seen["sent_at"] = _scans(b.stats)
        if what == "arrival":
            return await b.submit(_req(PROMPTS[2], 3))
        if what == "cancel":
            cancel.set()
        else:
            b.cfg.deadline_grace_s = 0.0        # hopeless from now on
        while b._slot_items:
            await asyncio.sleep(0.0005)
        seen["gone_at"] = _scans(b.stats)

    eng.ragged_round = ragged_round
    try:
        async def go():
            b = ContinuousBatcher(eng, BatcherConfig(
                max_wait_ms=1, adaptive=False, multi_step=1,
                max_multi_step=4, **cfg))
            b.start()
            out = await asyncio.gather(
                b.submit(long, cancel=cancel), during(b))
            stats = b.get_stats()
            await b.stop()
            return out, stats
        (resp, extra), stats = asyncio.run(go())
    finally:
        del eng.ragged_round
    if what == "arrival":
        assert extra.ok and len(extra.token_ids) == 3
        assert len(resp.token_ids) == 90
        # (its round went out behind the scan that was out, or after its
        # read)
        assert stats["chain_breaks_admission"] \
            + stats["ragged_rounds_chained"] >= 1
        # the scan that was out when it came, and at most the one whose
        # dispatch was under way
        assert seen["admitted_at"] - seen["sent_at"] <= 2
    else:
        assert resp.finish_reason == "abort" and len(resp.token_ids) < 90
        assert stats["chain_breaks_signal"] >= 1
        assert seen["gone_at"] - seen["sent_at"] <= 2
        assert stats["cancelled" if what == "cancel" else "abandoned"] == 1
    assert not eng.scan_unread
    assert _scans(stats) == stats["scans_chained"] + _breaks(stats)


def test_out_of_band_engine_work_runs_on_current_mirrors(engines):
    """``BatcherServing.run_exclusive`` (PD stages, handoff, export): the
    callable finds no scan unread and mirrors that agree with each other,
    mid-chain; the loop counts the chain as broken and serves on."""
    import time

    from distributed_gpu_inference_tpu.runtime.batcher import BatcherServing

    eng = engines["dense"]
    serving = BatcherServing(eng, BatcherConfig(
        max_wait_ms=1, adaptive=False, multi_step=1, max_multi_step=4))
    try:
        fut = serving.submit_async(_req(PROMPTS[0], 100))
        while serving.get_stats()["scans_chained"] < 3:
            time.sleep(0.0005)

        def look():
            slot = next((i for i, s in enumerate(eng.slots)
                         if s is not None), None)
            if slot is None:
                return None                 # the request is done
            s = eng.slots[slot]
            return (eng.scan_unread, len(s.generated),
                    int(eng._kv_lens[slot]),
                    len(eng.manager.seq_tokens[s.seq_id]))

        for i in range(6):      # at whatever point of a round it lands
            seen = serving.run_exclusive(look)
            if seen is None and i:
                break
            unread, generated, kv_len, managed = seen
            assert not unread
            # every token but the pending one is committed, the manager's
            # list holds the pending one too
            assert kv_len == len(PROMPTS[0]) + generated - 1 == managed - 1
            time.sleep(0.003)
        resp = fut.result(timeout=60)
        stats = serving.get_stats()
    finally:
        serving.stop()
    assert len(resp.token_ids) == 100
    assert stats["chain_breaks_signal"] >= 1
    assert _scans(stats) == stats["scans_chained"] + _breaks(stats)


# --------------------------------------------------------------------- #
# (4) no room to reserve ahead: no chain, the pressure path as it was
# --------------------------------------------------------------------- #

def test_out_of_blocks_while_reserving_ahead_reads_first_and_leaks_nothing():
    # 16 tokens a block; two rows of 14-token prompts hold a block each,
    # scan 1 (16 steps) takes each into its second, and the pool (pad +
    # 5) has one block left where the scan behind it needs two more
    eng = _engine("llama3-tiny", max_batch_size=2, num_blocks=6,
                  max_seq_len=64, multi_step=16)
    free = eng.manager.get_stats()["free_blocks"]
    ref = _engine("llama3-tiny", max_batch_size=2, max_seq_len=64,
                  multi_step=16)
    want = ref.generate([_req(range(3, 17), 40), _req(range(50, 64), 40)],
                        use_multi_step=True)
    a = eng.submit(_req(range(3, 17), 40))
    b = eng.submit(_req(range(50, 64), 40))
    assert eng.decode_multi(16, ahead=True) == {}
    assert eng.take_pressure() is None
    got = eng.decode_multi(16, ahead=True)      # cannot reserve ahead
    assert [len(got[a]), len(got[b])] == [16, 16]
    # the first scan was read before anything else; the second went out by
    # the path a call that reads its own scan takes: one row frozen, the
    # pressure signalled, nothing raised
    pressure = eng.take_pressure()
    assert pressure is not None and pressure.source == "decode"
    assert len(pressure.slots) == 1
    more = eng.collect_scan()
    frozen = pressure.slots[0]
    assert more.get(frozen, []) == [] and len(more[a + b - frozen]) == 16
    for slot, resp in ((a, want[0]), (b, want[1])):
        n = len(eng.slots[slot].generated)
        assert eng.slots[slot].generated == resp.token_ids[:n]
        eng.finish_slot(slot)
    assert eng.manager.get_stats()["free_blocks"] == free


def test_reserve_ahead_raises_nothing_through_the_batcher():
    """The same pool through the loop: both requests complete (the frozen
    row by preemption and resume), with the control's tokens."""
    reqs = lambda: [_req(range(3, 17), 40), _req(range(50, 64), 40)]  # noqa
    kw = dict(max_batch_size=2, num_blocks=6, max_seq_len=64, multi_step=16)
    want, base = _serve(_engine("llama3-tiny", **kw), reqs(), 16,
                        chained=False)
    eng = _engine("llama3-tiny", **kw)
    got, stats = _serve(eng, reqs(), 16, chained=True)
    assert all(r.ok for r in got)
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    assert stats["preemption_block_pressure"] >= 1
    assert stats["chain_breaks_pressure"] >= 1
    assert base["preemption_block_pressure"] >= 1
    assert eng.manager.get_stats()["free_blocks"] == eng.num_blocks - 1


# --------------------------------------------------------------------- #
# (5) the counters account for every scan; (7) the harness's contract
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("steps", [1, 4])
def test_every_scan_is_chained_or_broken_and_wrapped_once(engines, steps):
    """``benchmark/harness/session.py annotate()`` replaces
    ``decode_multi`` on the instance with a wrapper that reads
    ``num_steps``: one wrapper call a dispatched scan, each carrying that
    scan's length, and no scan dispatched outside one."""
    eng = engines["dense"]
    inner = eng.decode_multi
    assert list(inspect.signature(inner).parameters)[:1] == ["num_steps"]
    calls = []

    def outer(*a, **kw):
        before = eng.stats["decode_calls"]
        out = inner(*a, **kw)
        calls.append((a[0], eng.stats["decode_calls"] - before))
        return out

    eng.decode_multi = outer
    try:
        got, stats = _serve(eng, _requests(0.0), steps)
    finally:
        del eng.decode_multi
    assert [len(r.token_ids) for r in got] == BUDGETS
    # each call dispatched exactly the steps it was asked for
    assert calls and all(asked == ran == steps for asked, ran in calls)
    assert len(calls) == _scans(stats) == stats[f"scans_t{steps}"]
    assert _scans(stats) == stats["scans_chained"] + _breaks(stats)
    assert stats["scans_chained"] >= _scans(stats) // 2
    assert 0.0 < stats["round_host_exposed_s"]
    assert eng.stats["round_host_exposed_s"] <= sum(
        eng.stats[f"round_{p}_s"] for p in ("build", "dispatch", "commit"))


# --------------------------------------------------------------------- #
# (6) the horizon rule on the exposed host time
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("s_ms,h_ms", [(11.4, 8.0), (5.7, 5.0), (30.6, 6.0)],
                         ids=["mistral", "olmoe", "mixtral-tp4"])
def test_hidden_host_time_settles_at_the_lowest_level(s_ms, h_ms):
    b = _batcher()
    for _ in range(40):                 # every round's host time exposed
        _feed(b, s_ms, h_ms)
    start = b._levels[b._level]
    for _ in range(200):                # hidden behind the scan before
        _feed(b, s_ms, 0.05)
    assert b._levels[b._level] == 1 <= start
    assert b.stats["round_host_ema_ms"] < s_ms / 4


@pytest.mark.parametrize("s_ms", [2.0, 5.7])
def test_exposed_host_time_over_a_quarter_of_the_scan_climbs(s_ms):
    """A chained T=1 round whose host work exceeds a step leaves the chip
    idle by the excess: that is exposed ``h``, and the rule climbs."""
    b = _batcher()
    # what a round costs the host, hidden or not (``_scan_measured`` keeps
    # it): under a step, so a T=1 scan hides it and the rule goes down
    b._cost_ms = {4: 0.8 * s_ms}
    for _ in range(200):
        _feed(b, s_ms, 0.05)
    assert b._levels[b._level] == 1
    # the host slows to a step and a half a round: over half a step of
    # every T=1 round is exposed, and a round at T=4 costs as much
    b._cost_ms = {1: 1.5 * s_ms, 4: 1.5 * s_ms}
    seen = {_feed(b, s_ms, s_ms * 0.6) for _ in range(200)}
    # it climbs, and the small figure of the level above does not fetch it
    # straight back: a T=1 scan would not cover what a round costs now
    assert b._levels[b._level] == 4 and seen == {1, 4}
    # the host recovers: what the level above measures says so
    b._cost_ms[4] = 0.8 * s_ms
    for _ in range(50):
        _feed(b, s_ms, 0.05)
    assert b._levels[b._level] == 1


class _Chip:
    """A device on a scripted clock behind the engine's chaining surface:
    a scan of T steps takes ``T * step_s`` from when it is dispatched or
    the scan before it ends, whichever is later; the host pays ``build_s``
    before a dispatch and ``commit_s`` after a readback."""

    supports_scan_ahead = True
    step_s, build_s, commit_s = 0.010, 0.003, 0.001

    def __init__(self, clock):
        self.clock, self.free_at, self.unread = clock, 0.0, None
        self.slots = [None] * 2
        self.cfg = types.SimpleNamespace()
        self.pressure_pending = False
        self.stats = {"rounds": 0, "round_build_s": 0.0,
                      "round_dispatch_s": 0.0, "round_commit_s": 0.0,
                      "round_readback_s": 0.0, "round_host_exposed_s": 0.0}

    scan_unread = property(lambda self: self.unread is not None)
    scan_in_flight = property(
        lambda self: self.unread is not None and self.clock[0] < self.unread)

    def decode_budgets(self):
        return np.array([1000, 1000], dtype=np.int32)

    def _read(self, end):
        wait = max(0.0, end - self.clock[0])
        self.scan_read_running = wait > 0.0
        self.clock[0] += wait + self.commit_s
        self.stats["round_readback_s"] += wait
        self.stats["round_commit_s"] += self.commit_s
        if self.unread is None:
            self.stats["round_host_exposed_s"] += self.commit_s

    def decode_multi(self, steps, ahead=False):
        self.stats["rounds"] += 1
        hidden = self.scan_in_flight
        self.clock[0] += self.build_s
        self.stats["round_build_s"] += self.build_s
        if not hidden:
            self.stats["round_host_exposed_s"] += self.build_s
        prev = self.unread
        self.unread = self.free_at = \
            max(self.clock[0], self.free_at) + steps * self.step_s
        if prev is not None:
            self._read(prev)
        if not ahead:
            end, self.unread = self.unread, None
            self._read(end)
        return {0: [1] * steps, 1: [1] * steps} \
            if prev is not None or not ahead else {}

    def collect_scan(self):
        end, self.unread = self.unread, None
        self._read(end)
        return {0: [1], 1: [1]}


@pytest.mark.parametrize("gap_s,want_ms,within", [
    (0.002, 0.0, 0.05), (0.012, 6.0, 0.05),
], ids=["host-keeps-up", "host-outlasts-the-scan"])
def test_the_loop_hands_retune_the_exposed_host_time(
        monkeypatch, gap_s, want_ms, within):
    """On a scripted clock: read by its own call, a T=1 scan's gap, build
    and commit are all the chip's idle time; chained, none of it is while
    the host keeps up (2 + 3 + 1 ms under a 10 ms step), and what the
    host's round has over a step where it does not (12 + 3 + 1 - 10: no
    clock of the host's sees a scan end that it did not wait for, so that
    is the round less the step as rounds that did wait measured it, and
    such rounds leave the step's figure alone)."""
    clock = [100.0]
    monkeypatch.setattr(batcher_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: clock[0], time=lambda: clock[0],
        monotonic=lambda: clock[0]))
    eng = _Chip(clock)
    b = ContinuousBatcher(eng, BatcherConfig(adaptive=False, multi_step=1))

    def rounds(n):
        out = []
        for _ in range(n):
            clock[0] += gap_s
            out.append(b._engine_round())
            if out[-1]:
                b._retune(*out[-1])         # as the loop does: keeps ``s``
        return out

    eng.supports_scan_ahead = False
    for steps, scan_s, host_s in rounds(4)[1:]:
        assert (steps, scan_s) == (1, pytest.approx(eng.step_s))
        assert host_s == pytest.approx(gap_s + eng.build_s + eng.commit_s)
    eng.supports_scan_ahead = True
    first, *chained = rounds(12)
    assert first is None and b.stats["scans_chained"] == 11
    for steps, scan_s, host_s in chained[2:]:
        assert steps == 1 and scan_s == pytest.approx(eng.step_s, abs=1e-4)
        assert host_s * 1e3 == pytest.approx(want_ms, abs=within)
    assert b.stats["step_latency_ema_ms"] == pytest.approx(10.0, abs=0.1)
    # what the rounds cost the host, hidden or not, is kept beside it
    assert b._cost_ms[1] == pytest.approx(
        (gap_s + eng.build_s + eng.commit_s) * 1e3, rel=0.3)
    clock[0] += gap_s
    steps, scan_s, host_s = b._collect_round("idle")
    assert steps == 1 and not eng.scan_unread
    assert b.stats["chain_breaks_idle"] == 1


# --------------------------------------------------------------------- #
# (8) the engine's own callers see no change
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("steps", [1, 4])
def test_direct_decode_multi_returns_its_own_tokens(engines, steps):
    eng = engines["dense"]
    want = eng.generate([_req(PROMPTS[0], 12)])[0].token_ids
    slot = eng.submit(_req(PROMPTS[0], 12))
    got = list(eng.slots[slot].generated)
    while eng.slots[slot].finish_reason is None:
        out = eng.decode_multi(steps)
        assert not eng.scan_unread and 1 <= len(out[slot]) <= steps
        got += out[slot]
    assert got == want == eng.finish_slot(slot).token_ids


def test_generate_and_a_call_after_a_chain_see_current_mirrors(engines):
    eng = engines["dense"]
    want = eng.generate(_requests(0.0), use_multi_step=True)
    assert [len(r.token_ids) for r in want] == BUDGETS
    # a scan left unread by one caller is read by whoever comes next
    slot = eng.submit(_req(PROMPTS[0], 9))
    assert eng.decode_multi(4, ahead=True) == {} and eng.scan_unread
    assert eng.decode_budgets()[slot] == 4       # 8 left, less the scan
    own = eng.decode_multi(4)                    # reads both
    assert len(own[slot]) == 8 and not eng.scan_unread
    assert eng.finish_slot(slot).token_ids == want[0].token_ids


def test_lowered_graphs_hold_the_chained_schedule(engines):
    eng = engines["dense"]
    graphs = eng.lower_serving_graphs([1, 4], [])
    assert list(graphs) == ["decode_multi[T=1]", "decode_multi[T=4]",
                            "chain_sched"]
    # one program whatever the scan length, in memory once lowered
    assert eng._chain_sched_fn._cache_size() == 1


# --------------------------------------------------------------------- #
# the counters' route: batcher.stats -> heartbeat -> /metrics
# --------------------------------------------------------------------- #

def test_chain_counters_reach_the_planes_metrics():
    from distributed_gpu_inference_tpu.server.observability import (
        MetricsCollector,
    )
    from distributed_gpu_inference_tpu.worker.main import Worker

    class Eng:
        engine = None

        def serving_stats(self):
            return {"decode_rounds": 12, "scans_t1": 9, "scans_chained": 6,
                    "chain_breaks_admission": 2, "chain_breaks_idle": 1,
                    "chain_breaks_signal": 0, "round_host_exposed_s": 0.25,
                    "between_rounds_s": 0.5, "between_rounds": 11}

    worker = Worker.__new__(Worker)
    worker.engines = {"a": Eng(), "b": Eng()}
    worker.serving_capacity = lambda: 8
    sent = worker._batcher_stats()
    assert sent["scans_chained"] == 12 and sent["chain_breaks_admission"] == 4
    assert sent["round_host_exposed_s"] == 0.5
    mc = MetricsCollector()
    mc.record_batcher_engine("w1", sent)
    mc.record_batcher_engine("w1", dict(sent, scans_chained=20,
                                        chain_breaks_idle=5,
                                        round_host_exposed_s=0.75))
    text = mc.metrics.render().decode()
    if "batcher_scans_chained_total" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert 'batcher_scans_chained_total{worker="w1"} 20.0' in text
    assert ('batcher_chain_breaks_total{reason="admission",worker="w1"} 4.0'
            in text)
    assert 'batcher_chain_breaks_total{reason="idle",worker="w1"} 5.0' in text
    assert ('batcher_loop_seconds_total{part="round_host_exposed",'
            'worker="w1"} 0.75') in text
    # a chained scan is no "reason a scan got its length"
    assert 'reason="chained"' not in text


def test_under_a_mesh_a_chained_scan_compiles_nothing_anew(cpu_devices):
    """Rows and budgets taken on the device (``chain_sched``) come placed
    as the host's upload is (replicated): the scan graph and the uploads
    compile no more often than in an engine that chains nothing, the
    schedule's own program once, and the tokens are the same."""
    import numpy as np
    from jax.sharding import Mesh

    def make():
        return TPUEngine(
            get_model_config("llama3-tiny", dtype="float32"),
            EngineConfig(max_batch_size=2, max_seq_len=128, dtype="float32",
                         prefill_buckets=(16, 32), multi_step=4,
                         enable_prefix_cache=False),
            mesh=Mesh(np.array(cpu_devices[:2]), ("model",)), seed=0)

    def serve(eng, ahead):
        slot = eng.submit(_req(PROMPTS[0], 18))
        got = list(eng.slots[slot].generated)
        while eng.slots[slot].finish_reason is None or eng.scan_unread:
            out = eng.decode_multi(4, ahead=ahead) \
                if eng.decode_budgets().any() else eng.collect_scan()
            got += out.get(slot, [])
        return got

    plain, eng = make(), make()
    assert serve(eng, True) == serve(plain, False)
    assert eng._chain_sched_fn._cache_size() == 1
    assert plain._chain_sched_fn._cache_size() == 0
    for fn in ("_decode_multi_fn", "_unpack_sched_fn", "_unpack_core_fn"):
        assert getattr(eng, fn)._cache_size() == \
            getattr(plain, fn)._cache_size(), fn


@pytest.mark.parametrize("c0,c1,want", [
    ({"scans_t1": 10, "scans_t4": 2, "scans_chained": 4, "scans_amortise": 9},
     {"scans_t1": 40, "scans_t4": 12, "scans_chained": 34,
      "scans_amortise": 30}, 75.0),
    # the parent's program has no such counter: 0 %, which is the truth
    ({"scans_t4": 5}, {"scans_t4": 25}, 0.0),
    # no scan in the window: nothing to read
    ({"scans_t1": 7, "scans_chained": 3}, {"scans_t1": 7, "scans_chained": 3},
     None),
], ids=["window-delta", "no-counter", "no-scan"])
def test_the_benchmarks_reader_of_the_chained_share(c0, c1, want):
    import importlib.util
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    spec = importlib.util.spec_from_file_location(
        "batcher_chained_scan_share",
        bench / "layer_metrics" / "batcher_chained_scan_share.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({"win": {"c0": {"batcher": c0},
                                "c1": {"batcher": c1}}}) == want


# --------------------------------------------------------------------- #
# (9) admission ahead of the read (PR 40): where a request waits, a slot
#     is free and nothing else wants the engine, the admission pass runs
#     BEFORE the unread scan is read, while that scan runs on the device
# --------------------------------------------------------------------- #

def _serve_arriving(engine, first, later, steps=1, ahead=True,
                    before=None, **cfg):
    """``first`` at once, then ``later`` one at a time, each two chained
    scans after the one before it was admitted, so that it meets a scan
    in flight; returns (responses, stats, batcher). ``ahead=False`` is the
    parent's order: the pass before the read does not run, everything is
    admitted after it. ``before(b, i)`` runs in the loop step that sends
    arrival ``i``."""
    async def go():
        b = ContinuousBatcher(engine, BatcherConfig(
            max_wait_ms=1, adaptive=False, multi_step=steps,
            max_multi_step=max(steps, 4), **cfg))
        if not ahead:
            after_the_read = b._admission_pass

            async def only_after_the_read(ahead=False):
                if not ahead:
                    await after_the_read()
            b._admission_pass = only_after_the_read
        b.start()
        work = [asyncio.ensure_future(b.submit(r)) for r in first]
        await asyncio.sleep(0)          # they are in the queue now

        async def arrive():
            for i, r in enumerate(later):
                admitted = b.stats["ragged_admissions"]
                chained = b.stats["scans_chained"]
                while b.stats["scans_chained"] < chained + 2 \
                        and (b._slot_items or b._ragged or b._heap):
                    await asyncio.sleep(0.0005)
                if before is not None:
                    before(b, i)
                work.append(asyncio.ensure_future(b.submit(r)))
                while b.stats["ragged_admissions"] == admitted \
                        and not work[-1].done():
                    await asyncio.sleep(0.0005)
        await arrive()
        out = await asyncio.gather(*work, return_exceptions=True)
        stats = b.get_stats()
        await b.stop()
        return out, stats, b

    return asyncio.run(go())


def _long(temp=0.0):
    return [_req(PROMPTS[0], 100, temp, seed=3),
            _req(PROMPTS[1], 80, temp, seed=4)]


def _arrivals(temp=0.0):
    return [_req(PROMPTS[2], 7, temp, seed=11),
            _req(list(range(60, 95)), 5, temp, seed=12),
            _req(list(range(20, 31)), 9, temp, seed=13)]


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "seeded"])
@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("model", ["dense", "olmoe"])
def test_streams_with_admissions_ahead_are_the_same_bytes(
        engines, model, steps, temp):
    eng = engines[model]
    want, base, _ = _serve_arriving(eng, _long(temp), _arrivals(temp), steps,
                                    ahead=False)
    got, stats, _ = _serve_arriving(eng, _long(temp), _arrivals(temp), steps)
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    assert [r.finish_reason for r in got] == [r.finish_reason for r in want]
    assert [len(r.token_ids) for r in got] == [100, 80, 7, 5, 9]
    # the same admissions either way; only their place moved
    assert stats["ragged_admissions"] == base["ragged_admissions"] == 5
    assert base["admissions_ahead"] == 0
    assert 1 <= stats["admissions_ahead"] <= 3
    assert stats["chain_breaks_admission"] + stats["chain_breaks_round"] \
        >= stats["admissions_ahead"]
    assert _scans(stats) == stats["scans_chained"] + _breaks(stats)
    assert eng.manager.get_stats()["free_blocks"] == eng.num_blocks - 1
    assert eng.free_slots() == list(range(len(eng.slots)))


def test_an_admission_ahead_binds_no_slot_of_the_unread_scan(engines):
    eng = engines["dense"]
    real, seen = eng.submit_chunked_start, []

    def start(request, slot=None):
        prev = eng._unread
        adm = real(request, slot)
        # left unread: the start ran beside the scan
        if prev is not None and eng._unread is prev:
            seen.append((adm.slot, prev.active_mask.copy(),
                         eng._core_dirty, eng.slots[adm.slot].prefilling))
        return adm

    eng.submit_chunked_start = start
    try:
        got, stats, _ = _serve_arriving(eng, _long(), _arrivals())
    finally:
        del eng.submit_chunked_start
    assert all(r.ok for r in got)
    assert len(seen) == stats["admissions_ahead"] >= 1
    for slot, mask, dirty, prefilling in seen:
        assert not mask[slot] and mask.any()
        # no scan goes out on the stale core, and no scan runs the new row
        assert dirty and prefilling


def test_a_slot_that_comes_free_inside_the_unread_scan_is_admitted_after(
        engines):
    """Every slot is taken: the fifth request's slot comes free only when
    the scan that ends a row is read (``row_end_waiting`` / ``row_end``),
    so it is admitted after that read and ``admissions_ahead`` stands."""
    eng = engines["dense"]
    reqs = [_req(range(5 + 9 * i, 17 + 9 * i), 12 + 4 * i) for i in range(4)]
    got, stats = _serve(eng, reqs + [_req(PROMPTS[2], 6)], 1)
    assert [len(r.token_ids) for r in got] == [12, 16, 20, 24, 6]
    assert stats["ragged_admissions"] == 5 and stats["admissions_ahead"] == 0
    assert stats["chain_breaks_row_end_waiting"] \
        + stats["chain_breaks_row_end"] >= 1
    assert stats["chain_breaks_admission"] == 0


def _tight_pool():
    # 16 tokens a block, pad + 7: two rows of 14-token prompts hold two
    # blocks each once they decode, and a 60-token prompt needs four
    return _engine("llama3-tiny", max_batch_size=3, num_blocks=8,
                   max_seq_len=64, multi_step=1)


def test_out_of_blocks_ahead_defers_and_the_parents_victim_is_preempted():
    def reqs():
        return ([_req(range(3, 17), 40, priority=0),
                 _req(range(50, 64), 40, priority=1)],
                [_req(range(100, 160), 3, priority=5)])

    def run(ahead):
        eng = _tight_pool()
        real_start, real_preempt = eng.submit_chunked_start, eng.preempt_slot
        refused, victims = [], []

        def start(request, slot=None):
            prev = eng._unread
            try:
                return real_start(request, slot)
            except Exception as e:
                refused.append((type(e).__name__, prev is not None
                                and eng._unread is prev,
                                eng.pressure_pending))
                raise

        def preempt(slot):
            victims.append(list(eng.slots[slot].request.prompt_token_ids))
            return real_preempt(slot)

        eng.submit_chunked_start, eng.preempt_slot = start, preempt
        # (the policy resumes its victim ahead of the arrival it was
        # preempted for, round after round, until a row ends: on the
        # parent too; the cap is raised so that nobody is dropped for it)
        got, stats, b = _serve_arriving(eng, *reqs(), ahead=ahead,
                                        max_preemptions=100)
        assert all(r.ok for r in got), [r.error for r in got]
        # no block and no slot leaked, nothing left in the queue
        assert eng.manager.get_stats()["free_blocks"] == eng.num_blocks - 1
        assert eng.free_slots() == [0, 1, 2] and not b._heap
        return got, stats, refused, victims

    want, base, base_refused, base_victims = run(ahead=False)
    got, stats, refused, victims = run(ahead=True)
    # the pool could not hold the prompt beside the unread scan: no
    # pressure signalled there, the item back in the queue, and the pass
    # after the read met the pool the parent's met
    assert ("OutOfBlocksError", True, False) in refused
    assert ("OutOfBlocksError", False, True) in refused
    assert all(not beside for _, beside, _ in base_refused)
    assert stats["admissions_ahead"] == 0 == base["admissions_ahead"]
    # (how often the policy repeats itself hangs on the step at which the
    # arrival came, which no two runs share: the victim does not)
    assert victims[0] == base_victims[0] == list(range(3, 17))
    assert stats["preemptions"] >= 1 and base["preemptions"] >= 1
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    assert [len(r.token_ids) for r in got] == [40, 40, 3]


def test_a_request_cancelled_in_the_queue_is_not_admitted_ahead(engines):
    eng = engines["dense"]

    async def go():
        b = ContinuousBatcher(eng, BatcherConfig(
            max_wait_ms=1, adaptive=False, multi_step=1, max_multi_step=4))
        b.start()
        long = asyncio.ensure_future(b.submit(_req(PROMPTS[0], 60)))
        await _mid_chain(b)
        gone = asyncio.ensure_future(b.submit(_req(PROMPTS[2], 5)))
        await asyncio.sleep(0)          # it is in the queue now
        assert len(b._heap) == 1 and eng.scan_unread
        gone.cancel()
        kept = await b.submit(_req(PROMPTS[1], 4))
        resp = await long
        stats = b.get_stats()
        await b.stop()
        return gone, kept, resp, stats

    gone, kept, resp, stats = asyncio.run(go())
    assert gone.cancelled() and len(kept.token_ids) == 4
    assert len(resp.token_ids) == 60
    # the long one and the one that stayed: the cancelled one never bound
    assert stats["ragged_admissions"] == 2 and eng.stats["requests"] >= 2
    assert stats["admissions_ahead"] <= 1
    assert eng.free_slots() == list(range(len(eng.slots)))


@pytest.mark.parametrize("what", ["signal", "resume_hold", "foreign",
                                  "speculative_engine"])
def test_anything_else_pending_keeps_the_old_order(engines, what):
    """``_chain_break`` answers a cancel, a resume hold or a foreign call
    before it looks at the queue, and a speculative engine leaves no scan
    unread: the arrival is admitted after the read, as it always was."""
    eng = engines["dense"]
    cancel = threading.Event()
    first = [_req(PROMPTS[0], 60), _req(PROMPTS[1], 50)]
    tasks = []

    def before(b, i):
        if what == "signal":
            # the second row's cancel lands with the arrival
            next(it for it in b._slot_items.values()
                 if it.request is first[1]).cancel = cancel
            cancel.set()
        elif what == "resume_hold":
            # held over the round in flight, as after a preemption, until
            # the loop has answered it
            real = b._check_pressure

            async def held(after_round=False):
                await real(after_round)
                b._resume_hold = b.stats["chain_breaks_pressure"] == 0
            b._resume_hold, b._check_pressure = True, held
        elif what == "foreign":
            # a caller waits for the engine thread until the arrival is in
            async def release():
                while b.stats["ragged_admissions"] < 3:
                    await asyncio.sleep(0.0005)
                b._foreign -= 1
            b._foreign += 1
            tasks.append(asyncio.ensure_future(release()))

    if what == "speculative_engine":
        eng.supports_scan_ahead = False     # what cfg.speculative sets
    try:
        got, stats, _ = _serve_arriving(
            eng, first, [_req(PROMPTS[2], 6)], before=before)
    finally:
        eng.supports_scan_ahead = True
    assert len(got[0].token_ids) == 60 and len(got[2].token_ids) == 6
    assert (got[1].finish_reason == "abort") == (what == "signal")
    assert stats["ragged_admissions"] == 3 and stats["admissions_ahead"] == 0
    if what == "speculative_engine":
        assert stats["scans_chained"] == 0
    else:
        assert stats["scans_chained"] > 0
    assert eng.free_slots() == list(range(len(eng.slots)))


# the engine's side: the rule's one exception, decided from what it holds

def test_a_start_on_a_free_slot_leaves_the_scan_unread(engines):
    eng = engines["dense"]
    free = eng.manager.get_stats()["free_blocks"]
    want = eng.generate([_req(PROMPTS[0], 12), _req(PROMPTS[2], 5)])
    row = eng.submit(_req(PROMPTS[0], 12))
    assert eng.decode_multi(4, ahead=True) == {} and eng.scan_unread
    adm = eng.submit_chunked_start(_req(PROMPTS[2], 5))
    assert eng.scan_unread and adm.slot != row
    assert eng.slots[adm.slot].prefilling and eng._core_dirty
    # the new slot is no row of the scan, of its read or of the next one
    assert eng.decode_budgets()[adm.slot] == 0
    assert list(eng.collect_scan()) == [row]
    eng.ragged_round([adm])
    while eng.slots[row].finish_reason is None \
            or eng.slots[adm.slot].finish_reason is None:
        eng.decode_multi(4)
    assert eng.finish_slot(row).token_ids == want[0].token_ids
    assert eng.finish_slot(adm.slot).token_ids == want[1].token_ids
    assert eng.manager.get_stats()["free_blocks"] == free


def test_a_start_on_a_slot_of_the_unread_scan_reads_first(engines):
    eng = engines["dense"]
    row = eng.submit(_req(PROMPTS[0], 12))
    before = len(eng.slots[row].generated)
    assert eng.decode_multi(4, ahead=True) == {} and eng.scan_unread
    with pytest.raises(RuntimeError, match="busy"):
        eng.submit_chunked_start(_req(PROMPTS[2], 5), slot=row)
    # read before anything else was looked at: the mirrors are current
    assert not eng.scan_unread
    assert len(eng.slots[row].generated) == before + 4
    # ... and every other entry reads first, a free slot or not
    assert eng.decode_multi(4, ahead=True) == {} and eng.scan_unread
    other = eng.submit(_req(PROMPTS[2], 5))
    assert not eng.scan_unread and other != row
    eng.finish_slot(other)
    eng.finish_slot(row)


def test_out_of_blocks_beside_an_unread_scan_signals_no_pressure():
    eng = _tight_pool()
    free = eng.manager.get_stats()["free_blocks"]
    a = eng.submit(_req(range(3, 17), 40))
    b = eng.submit(_req(range(50, 64), 40))
    eng.decode_multi(4)                      # both rows in their second block
    assert eng.decode_multi(1, ahead=True) == {} and eng.scan_unread
    held = eng.manager.get_stats()["free_blocks"]
    with pytest.raises(batcher_mod.OutOfBlocksError):
        eng.submit_chunked_start(_req(range(100, 160), 3))
    # nothing signalled, nothing bound, nothing taken, the scan still out
    assert eng.scan_unread and not eng.pressure_pending
    assert eng.free_slots() == [2]
    assert eng.manager.get_stats()["free_blocks"] == held
    eng.collect_scan()
    # with the scan read the same refusal is the pool's word: pressure
    with pytest.raises(batcher_mod.OutOfBlocksError):
        eng.submit_chunked_start(_req(range(100, 160), 3))
    assert eng.take_pressure().source == "admission"
    eng.finish_slot(a)
    eng.finish_slot(b)
    assert eng.manager.get_stats()["free_blocks"] == free


def test_admissions_ahead_reach_get_stats_and_the_planes_metrics(engines):
    from distributed_gpu_inference_tpu.runtime.batcher import BatcherServing
    from distributed_gpu_inference_tpu.server.observability import (
        MetricsCollector,
    )
    from distributed_gpu_inference_tpu.worker.main import Worker

    serving = BatcherServing(engines["dense"], BatcherConfig())
    try:
        stats = serving.get_stats()
    finally:
        serving.stop()
    assert stats["admissions_ahead"] == 0 == stats["ragged_admissions"]

    class Eng:
        engine = None

        def serving_stats(self):
            return {"decode_rounds": 12, "ragged_admissions": 5,
                    "admissions_ahead": 3}

    worker = Worker.__new__(Worker)
    worker.engines = {"a": Eng(), "b": Eng()}
    worker.serving_capacity = lambda: 8
    sent = worker._batcher_stats()
    assert sent["ragged_admissions"] == 10 and sent["admissions_ahead"] == 6
    mc = MetricsCollector()
    mc.record_batcher_engine("w1", sent)
    mc.record_batcher_engine("w1", dict(sent, ragged_admissions=14,
                                        admissions_ahead=9))
    text = mc.metrics.render().decode()
    if "batcher_admissions_total" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert 'batcher_admissions_total{worker="w1"} 14.0' in text
    assert 'batcher_admissions_ahead_total{worker="w1"} 9.0' in text


# --------------------------------------------------------------------- #
# (10) a ragged round BEHIND the unread scan (PR 51): the round that
#      carries an arrival's piece goes out before the scan in flight is
#      read; the call brings the scan's tokens back and leaves the round's
#      on the device for the next read
# --------------------------------------------------------------------- #

# dense, routed, latent (MLA), hybrid (state rows), mixed window and full
# layers (pages per kind), indexed (learned sparse attention)
ROUND_MODELS = {
    "dense": ("llama3-tiny", {}, {}),
    "routed": ("olmoe-tiny", {}, {}),
    "latent": ("openpangu-ultra-moe-tiny", {"held_experts": (2, 4)},
               {"block_size": 16}),
    "hybrid": ("kimi-linear-tiny", {},
               {"block_size": 16, "quantization": "int8"}),
    "mixed-window": ("laguna-tiny", {},
                     {"block_size": 16, "quantization": "int8"}),
    "indexed": ("keye-vl-tiny", {},
                {"block_size": 16, "quantization": "int8"}),
}
_round_engines = {}


def _round_engine(kind, fresh=False, **kw):
    """One engine a model kind for the module (a fresh one where a test
    changes the pool)."""
    if fresh or kw or kind not in _round_engines:
        name, model_kw, engine_kw = ROUND_MODELS[kind]
        cfg = dict(max_batch_size=4, max_seq_len=256, dtype="float32",
                   prefill_buckets=(16, 32, 64), ragged_chunk=32,
                   multi_step=4, enable_prefix_cache=False)
        cfg.update(engine_kw)
        cfg.update(kw)
        eng = TPUEngine(
            get_model_config(name, **(model_kw or {"dtype": "float32"})),
            EngineConfig(**cfg), seed=0)
        if fresh or kw:
            return eng
        _round_engines[kind] = eng
    return _round_engines[kind]


def _drive(eng, first, later, steps, chained):
    """A schedule with no clock in it, on the engine itself: ``first`` are
    admitted at once; scans of ``steps`` go out one behind the other; after
    the ``k``-th, ``later[k]`` is bound beside it while it is unread and
    its pieces ride the rounds that follow. ``chained``: each round finds
    the scan unread (the new order); else the caller reads the scan first
    (the old one). Returns what there is to compare."""
    counted = ("ragged_positions_live", "prefill_tokens", "decode_calls",
               "generated_tokens", "mla_pairs_ragged",
               "mla_context_tokens_ragged", "attn_pairs_ragged_full",
               "attn_pairs_ragged_window", "index_pairs_ragged",
               "index_selected_pairs_ragged", "kda_tokens_ragged",
               "kda_chunks_ragged", "moe_assignments_ragged",
               "moe_assignments_scan", "mla_context_tokens_scan",
               "index_context_tokens_scan", "kda_row_steps_scan")
    before = {k: eng.stats[k] for k in counted if k in eng.stats}
    chained0 = eng.stats["ragged_rounds_chained"]
    free = eng.manager.get_stats()["free_blocks"]
    order, flying, rounds = [], [], []
    for r in first:
        flying.append(eng.submit_chunked_start(r))
        order.append(flying[-1].slot)
    scans = 0
    while True:
        if flying:
            was_unread = eng.scan_unread
            if not chained:
                eng.collect_scan()
            back = eng.ragged_round(flying)
            unread = eng.round_unread
            assert unread == (chained and was_unread)
            rounds.append((unread, back, eng.collect_scan()))
            flying = [a for a in flying if not a.done]
        elif eng.decode_budgets().any():
            eng.decode_multi(steps, ahead=True)
            scans += 1
            if scans in later:
                assert eng.scan_unread
                flying.append(eng.submit_chunked_start(later[scans]))
                order.append(flying[-1].slot)
                assert eng.scan_unread      # bound beside it
        else:
            eng.collect_scan()
            break
    seen = {
        "kv_lens": [int(eng._kv_lens[i]) for i in order],
        "managed": [list(eng.manager.seq_tokens[eng.slots[i].seq_id])
                    for i in order],
        "counted": {k: eng.stats[k] - v for k, v in before.items()},
        "chained": eng.stats["ragged_rounds_chained"] - chained0,
        "rounds": rounds,
    }
    done = [eng.finish_slot(i) for i in order]
    seen["tokens"] = [r.token_ids for r in done]
    seen["reasons"] = [r.finish_reason for r in done]
    assert eng.manager.get_stats()["free_blocks"] == free
    assert not eng.scan_unread
    return seen


def _schedule(temp, stop=(), budgets=(40, 7)):
    """Two rows decode; an arrival of one piece comes beside the second
    scan, one of three pieces beside the fourth. ``budgets``: the second
    row's ends inside the scan its first arrival meets."""
    first = [_req(PROMPTS[0], budgets[0], temp, seed=21, stop=stop),
             _req(PROMPTS[1], budgets[1], temp, seed=22, stop=stop)]
    later = {2: _req(PROMPTS[2], 9, temp, seed=23, stop=stop),
             4: _req(list(range(100, 170)), 6, temp, seed=24, stop=stop)}
    return first, later


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "seeded"])
@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("kind", list(ROUND_MODELS))
def test_a_round_behind_the_unread_scan_is_the_same_bytes(kind, steps, temp):
    eng = _round_engine(kind)
    want = _drive(eng, *_schedule(temp), steps, chained=False)
    got = _drive(eng, *_schedule(temp), steps, chained=True)
    assert got["tokens"] == want["tokens"]
    assert got["reasons"] == want["reasons"]
    assert [len(t) for t in got["tokens"]] == [40, 7, 9, 6]
    # the mirrors and the manager's lists end where the old order's do
    assert got["kv_lens"] == want["kv_lens"]
    assert got["managed"] == want["managed"]
    # what the rounds and scans held, as the benchmark's readers count it
    assert got["counted"] == want["counted"]
    # the first piece of each arrival went out behind a scan, the long
    # prompt's other two followed their own round's read
    assert want["chained"] == 0 and got["chained"] == 2
    assert [r[0] for r in got["rounds"]].count(True) == 2
    for unread, back, own in got["rounds"]:
        if unread:
            # the call brought the scan's tokens, the read the round's: a
            # step a decoding row, nothing for a piece that is not the last
            assert back and all(1 <= len(t) <= steps for t in back.values())
            assert all(len(t) == 1 for t in own.values())


@pytest.mark.parametrize("end", ["stop", "budget"])
@pytest.mark.parametrize("kind", ["dense", "routed", "hybrid"])
def test_a_row_that_ends_inside_the_unread_scan_is_not_advanced_by_the_round(
        kind, end):
    """The round is built on mirrors that still show the row decoding; the
    device finds it ended (its budget, or a stop id as its last token) and
    leaves it out: no token, no core advance, no KV written past its end."""
    eng = _round_engine(kind)
    stop, budgets = (), (40, 7)
    if end == "stop":
        ref = _drive(eng, *_schedule(0.0, budgets=(40, 30)), 4,
                     chained=False)
        # the second row's sixth token, which scan 2 emits at its first
        # step (token 0 came with the prompt, scan 1 brought 1-4)
        stop, budgets = (ref["tokens"][1][5],), (40, 30)
        assert stop[0] not in ref["tokens"][1][:5]
    want = _drive(eng, *_schedule(0.0, stop, budgets), 4, chained=False)
    got = _drive(eng, *_schedule(0.0, stop, budgets), 4, chained=True)
    assert got["tokens"] == want["tokens"]
    assert got["reasons"] == want["reasons"]
    assert got["reasons"][1] == ("stop" if end == "stop" else "length")
    assert got["kv_lens"] == want["kv_lens"]
    assert got["managed"] == want["managed"]
    assert got["counted"] == want["counted"]
    # the first chained round met the row ended inside scan 2: the scan's
    # tokens hold its last, the round's read nothing of it
    unread, back, own = next(r for r in got["rounds"] if r[0])
    assert 1 in back and 1 not in own and 0 in own


def test_a_round_behind_a_scan_runs_the_graph_the_warm_up_lowered():
    """The operands a chained round hands ``ragged_round`` come from
    ``chain_round``; the round graph is the one every other round runs
    (no second program a packed length), and the small programs are in
    memory once ``lower_serving_graphs`` has run."""
    eng = _round_engine("dense", fresh=True)
    graphs = eng.lower_serving_graphs([1, 4], [16, 32])
    rungs = [k for k in graphs if k.startswith("ragged_round[")]
    assert rungs and "merge_core" in graphs
    assert [k for k in graphs if k.startswith("chain_round[")] == [
        k.replace("ragged_round", "chain_round") for k in rungs]
    assert eng._chain_round_fn._cache_size() == len(rungs)
    assert eng._merge_core_fn._cache_size() == 1
    for name in graphs:
        if name.startswith(("chain_round", "merge_core", "chain_sched")):
            assert "kernel_name" not in graphs[name].as_text()
    from distributed_gpu_inference_tpu.utils.device import compile_log

    for lowered in graphs.values():
        lowered.compile()               # as the worker's warm-up does
    plain = _round_engine("dense", fresh=True)
    want = _drive(plain, *_schedule(0.0), 4, chained=False)
    log = compile_log()
    mark = len(log.rows)
    got = _drive(eng, *_schedule(0.0), 4, chained=True)
    assert got["tokens"] == want["tokens"] and got["chained"] == 2
    # nothing was traced or compiled for the chained rounds: not the small
    # programs, not a round graph a second time for their operands
    assert eng._chain_round_fn._cache_size() == len(rungs)
    assert eng._merge_core_fn._cache_size() == 1
    # (a first call still reports a trace stage, of a jaxpr found in memory)
    assert not [r["fn"] for r in log.rows[mark:] if r["stage"] == "backend"
                and ("round" in r["fn"] or "decode_multi" in r["fn"]
                     or "merge_core" in r["fn"])]
    # without a scan length nothing chains: the round graphs alone
    assert list(plain.lower_serving_graphs([], [16])) == [
        k for k in plain.lower_serving_graphs([4], [16])
        if k.startswith("ragged_round[")]


def test_out_of_blocks_behind_the_scan_reads_first_and_signals_as_before():
    # 16 tokens a block; two rows of 15-token prompts fill a block each
    # with their pending token, two 16-step scans take each to the end of
    # its third, and the pool (pad + 8) has two blocks left: the arrival
    # takes one, and behind the second scan the round's two decode rows
    # need a fourth block each
    def make():
        return _engine("llama3-tiny", max_batch_size=3, num_blocks=9,
                       max_seq_len=64, multi_step=16)

    def run(eng, chained):
        a = eng.submit(_req(range(3, 18), 40))
        b = eng.submit(_req(range(50, 65), 40))
        assert eng.decode_multi(16, ahead=True) == {}
        assert eng.decode_multi(16, ahead=True)     # reads scan 1
        adm = eng.submit_chunked_start(_req(PROMPTS[2], 3))
        assert eng.scan_unread and eng.take_pressure() is None
        if not chained:
            eng.collect_scan()
        out = eng.ragged_round([adm])
        # no room behind the scan: it was read first, the round read
        # itself, one decode row froze and the pressure is the pool's word
        assert not eng.scan_unread
        pressure = eng.take_pressure()
        assert pressure is not None and pressure.source == "decode"
        frozen = pressure.slots
        assert adm.done and len(out[adm.slot]) == 1
        for i in (a, b):
            assert (i in frozen) == (len(out.get(i, [])) in (0, 16))
        state = {i: (list(eng.slots[i].generated), int(eng._kv_lens[i]))
                 for i in (a, b, adm.slot)}
        for i in (a, b, adm.slot):
            eng.finish_slot(i)
        return state, len(frozen), eng.stats["ragged_rounds_chained"]

    free = make().manager.get_stats()["free_blocks"]
    eng = make()
    want, want_frozen, _ = run(make(), chained=False)
    got, got_frozen, n = run(eng, chained=True)
    assert got == want and got_frozen == want_frozen >= 1 and n == 0
    assert eng.manager.get_stats()["free_blocks"] == free


def test_under_a_mesh_a_round_behind_a_scan_compiles_nothing_anew(
        cpu_devices):
    """``chain_round`` and ``merge_core`` hand their values back placed as
    the round graph takes the host's (replicated): the round graphs and
    the uploads compile no more often than in an engine that reads every
    scan first, and the tokens are the same."""
    from jax.sharding import Mesh

    def make():
        return TPUEngine(
            get_model_config("llama3-tiny", dtype="float32"),
            EngineConfig(max_batch_size=4, max_seq_len=256, dtype="float32",
                         prefill_buckets=(16, 32, 64), ragged_chunk=32,
                         multi_step=4, enable_prefix_cache=False),
            mesh=Mesh(np.array(cpu_devices[:2]), ("model",)), seed=0)

    from distributed_gpu_inference_tpu.utils.device import compile_log

    # (no row ends inside a scan an arrival meets: a round built on the
    # lagging mirrors may take a longer rung than one built after the read)
    plan = lambda: _schedule(0.0, budgets=(40, 30))  # noqa: E731
    plain, eng = make(), make()
    want = _drive(plain, *plan(), 4, chained=False)
    # the engine's own rounds in the old order first, so that every round
    # graph the schedule reaches is compiled; then the same in the new one
    assert _drive(eng, *plan(), 4, chained=False)["tokens"] \
        == want["tokens"]
    sizes = {fn: getattr(eng, fn)._cache_size()
             for fn in ("_ragged_round_fn", "_decode_multi_fn",
                        "_unpack_sched_fn", "_unpack_core_fn")}
    log = compile_log()
    mark = len(log.rows)
    got = _drive(eng, *plan(), 4, chained=True)
    assert got["tokens"] == want["tokens"] and got["chained"] == 2
    assert sorted({r["fn"] for r in log.rows[mark:]
                   if r["stage"] == "backend"}) == [
        "jit(chain_round)", "jit(merge_core)"]
    for fn, size in sizes.items():
        assert getattr(eng, fn)._cache_size() == size, fn


# the loop's side: it asks for the round behind the scan and delivers the
# scan's tokens while the device runs the round

def _read_first(engine):
    """The old order on an engine instance: every ``ragged_round`` finds
    the scan read (the control of the loop's tests)."""
    real = engine.ragged_round

    def ragged_round(admissions=(), chunk_caps=None):
        engine.collect_scan()
        return real(admissions, chunk_caps)

    engine.ragged_round = ragged_round


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "seeded"])
@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("model", ["dense", "olmoe"])
def test_streams_with_rounds_behind_the_scan_are_the_same_bytes(
        engines, model, steps, temp):
    eng = engines[model]
    _read_first(eng)
    try:
        want, base, _ = _serve_arriving(eng, _long(temp), _arrivals(temp),
                                        steps)
    finally:
        del eng.ragged_round
    got, stats, _ = _serve_arriving(eng, _long(temp), _arrivals(temp), steps)
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    assert [r.finish_reason for r in got] == [r.finish_reason for r in want]
    assert [len(r.token_ids) for r in got] == [100, 80, 7, 5, 9]
    # the control's engine chained no round, whatever the loop asked for
    assert base["ragged_rounds_chained"] == 0 == base["chain_breaks_round"]
    # each arrival met a scan in flight: its first piece went out behind it
    # (the 35-token prompt's second piece followed its own round's read),
    # and every such round was read next, once
    assert 1 <= stats["ragged_rounds_chained"] <= 3
    assert stats["chain_breaks_round"] == stats["ragged_rounds_chained"]
    assert stats["ragged_rounds_chained"] <= stats["admissions_ahead"]
    assert stats["ragged_rounds"] == base["ragged_rounds"]
    assert _scans(stats) == stats["scans_chained"] + _breaks(stats)
    assert eng.manager.get_stats()["free_blocks"] == eng.num_blocks - 1
    assert eng.free_slots() == list(range(len(eng.slots)))


def test_the_scans_tokens_carry_the_scans_stamp_and_the_rounds_the_rounds(
        engines):
    """A stream that decodes across an arrival: the call that dispatches
    the round brings the scan's tokens (stamp ``scan``), the read that
    follows brings the round's (``ragged``, one piece, ``ragged_1``), both
    under the round's number; the arrival's first token comes with the
    round's read. ``batcher.longest_wait_piece_share`` reads these."""
    eng = engines["dense"]
    seen = {"long": [], "new": []}

    def watch(who):
        def observer(snap):
            seen[who].append((len(snap), snap.round))
        return observer

    async def go():
        b = ContinuousBatcher(eng, BatcherConfig(
            max_wait_ms=1, adaptive=False, multi_step=1, max_multi_step=4))
        b.start()
        long = asyncio.ensure_future(
            b.submit(_req(PROMPTS[0], 60), observer=watch("long")))
        await _mid_chain(b)
        new = await b.submit(_req(PROMPTS[2], 4), observer=watch("new"))
        resp = await long
        stats = b.get_stats()
        await b.stop()
        return resp, new, stats

    resp, new, stats = asyncio.run(go())
    assert len(resp.token_ids) == 60 and len(new.token_ids) == 4
    assert stats["ragged_rounds_chained"] == 1 == stats["chain_breaks_round"]
    stamps = [st for _, st in seen["long"]]
    at = next(i for i, st in enumerate(stamps) if st.kind == "ragged" and i)
    scan, ragged = stamps[at - 1], stamps[at]
    assert (scan.kind, scan.steps, scan.pieces, scan.cause) \
        == ("scan", 1, 0, "scan")
    assert (ragged.kind, ragged.steps, ragged.pieces, ragged.cause) \
        == ("ragged", 1, 1, "ragged_1")
    assert scan.round == ragged.round and scan.ready < ragged.ready
    # each brought the stream one token: the scan's, then the round's
    assert seen["long"][at][0] - seen["long"][at - 1][0] == 1
    assert seen["long"][at - 1][0] - seen["long"][at - 2][0] == 1
    # the arrival's first token came with the round's read
    assert seen["new"][0][0] == 1 and seen["new"][0][1] == ragged


def test_an_arrival_during_an_unread_round_is_bound_beside_it(engines):
    """``_collect_unless_free``'s rule holds for a round left unread as for
    a scan: a slot that is free and no row of the dispatch is bound without
    reading it; the round is still read next, and the arrival's own round
    follows that read."""
    eng = engines["dense"]
    late = {}

    async def go():
        b = ContinuousBatcher(eng, BatcherConfig(
            max_wait_ms=1, adaptive=False, multi_step=1, max_multi_step=4))
        deliver = b._deliver

        async def deliver_then_arrive(measured):
            await deliver(measured)
            if b._unread_round is not None and "work" not in late:
                # the round's call has returned, its read has not begun
                late["at"] = dict(b.stats)
                late["work"] = asyncio.ensure_future(
                    b.submit(_req(list(range(20, 31)), 5)))
                await asyncio.sleep(0)      # it is in the queue now
                assert eng.round_unread and len(b._heap) == 1
        b._deliver = deliver_then_arrive
        real_start = eng.submit_chunked_start

        def start(request, slot=None):
            beside = eng.round_unread
            adm = real_start(request, slot)
            late.setdefault("beside", []).append(beside and eng.round_unread)
            return adm
        eng.submit_chunked_start = start
        b.start()
        long = asyncio.ensure_future(b.submit(_req(PROMPTS[0], 60)))
        await _mid_chain(b)
        new = await b.submit(_req(PROMPTS[2], 4))
        out = [await long, new, await late["work"]]
        stats = b.get_stats()
        await b.stop()
        return out, stats

    try:
        out, stats = asyncio.run(go())
    finally:
        del eng.submit_chunked_start
    assert [len(r.token_ids) for r in out] == [60, 4, 5]
    # the third request was bound while the round was unread, and left it so
    assert late["beside"][-1] is True
    assert stats["admissions_ahead"] == late["at"]["admissions_ahead"] + 1
    # its own round followed the read: one round went out behind a scan
    assert stats["ragged_rounds_chained"] == 1 == stats["chain_breaks_round"]
    assert stats["ragged_admissions"] == 3
    assert eng.free_slots() == list(range(len(eng.slots)))


def test_a_caller_waiting_for_the_thread_keeps_the_round_read_first(engines):
    """``BatcherServing.run_exclusive`` counts on nothing being unread
    while a caller waits (``_foreign``): the round's call reads the scan
    first and then itself."""
    eng = engines["dense"]
    tasks = []

    def before(b, i):
        async def release():
            while b.stats["ragged_admissions"] < 3:
                await asyncio.sleep(0.0005)
            while b._ragged:
                await asyncio.sleep(0.0005)
            b._foreign -= 1
        b._foreign += 1
        tasks.append(asyncio.ensure_future(release()))

    got, stats, _ = _serve_arriving(
        eng, [_req(PROMPTS[0], 60), _req(PROMPTS[1], 50)],
        [_req(PROMPTS[2], 6)], before=before)
    assert [len(r.token_ids) for r in got] == [60, 50, 6]
    assert stats["ragged_rounds_chained"] == 0 == stats["chain_breaks_round"]
    assert not eng.scan_unread
