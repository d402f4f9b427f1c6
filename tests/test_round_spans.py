"""Round spans and counters (runtime/flight.py ``span``; docs/observability.md,
"Round spans and counters"): the helper alone, the counters' identities on a
tiny engine + batcher run, and the spans as a profiler session on the CPU
records them — ``dgi.engine.*`` with their attributes, nested inside the
``dgi.batcher.round`` of the same round."""

import asyncio
import glob
import os
import subprocess
import sys
import time

import pytest

from distributed_gpu_inference_tpu.runtime import flight
from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
)
from distributed_gpu_inference_tpu.runtime.engine import EngineConfig, TPUEngine
from distributed_gpu_inference_tpu.server.observability import MetricsCollector
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)
from distributed_gpu_inference_tpu.utils.device import compile_log

PHASES = ("build", "dispatch", "readback", "commit")


# --------------------------------------------------------------------- #
# (a) the helper
# --------------------------------------------------------------------- #

def test_span_adds_elapsed_seconds_to_its_counter():
    stats = {"busy_s": 1.0}
    with flight.span("dgi.test.outer", stats, "busy_s", round=7) as sp:
        time.sleep(0.01)
        sp.set(rows=3)
    assert 1.009 < stats["busy_s"] < 1.5
    with flight.span("dgi.test.outer", stats, "new_s"):
        pass
    assert 0.0 <= stats["new_s"] < 0.01      # a key is made where missing


def test_spans_nest_and_a_child_is_inside_its_parent():
    stats = {}
    with flight.span("dgi.test.parent", stats, "parent_s"):
        with flight.span("dgi.test.child", stats, "child_s"):
            time.sleep(0.005)
        with flight.span("dgi.test.child", stats, "child_s"):
            time.sleep(0.005)
    assert 0.009 < stats["child_s"] <= stats["parent_s"]


@pytest.mark.parametrize("given", ["no_counter", "stats_without_key"])
def test_span_without_a_counter_counts_nothing(given):
    stats = {}
    args = () if given == "no_counter" else (stats,)
    with flight.span("dgi.test.bare", *args, level=4):
        pass
    assert stats == {}


def test_span_never_raises_where_jax_is_absent(monkeypatch):
    monkeypatch.setattr(flight, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)   # ImportError
    stats = {}
    with flight.span("dgi.test.nojax", stats, "s", round=1) as sp:
        sp.set(rows=2)
    assert flight._annotation is False and stats["s"] >= 0.0
    with pytest.raises(KeyError):          # and it hides no error of the body
        with flight.span("dgi.test.nojax", stats, "s"):
            raise KeyError("from the body")


def test_span_costs_microseconds_with_no_profiler_session():
    stats = {}
    with flight.span("dgi.test.warm"):      # the lazy import, once
        pass
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        with flight.span("dgi.test.cost", stats, "s", round=i, steps=4):
            pass
    each = (time.perf_counter() - t0) / n
    # ~1.4 us here; nine spans a round against rounds of >= 10 ms. The
    # bound is loose enough for a loaded CI machine, tight enough to catch
    # a span that formats, allocates a timeline or takes a lock
    assert each < 50e-6, each


def test_the_control_plane_and_flight_import_without_jax():
    code = ("import sys; import distributed_gpu_inference_tpu.server.app; "
            "import distributed_gpu_inference_tpu.runtime.flight as f; "
            "assert 'jax' not in sys.modules, 'jax imported'; print('ok')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-2000:]


# --------------------------------------------------------------------- #
# (b) the counters on a tiny engine + batcher run, and (c) the same run
#     under a profiler session
# --------------------------------------------------------------------- #

def _req(prompt, max_new):
    return InferenceRequest(
        prompt_token_ids=prompt,
        sampling=SamplingParams(max_new_tokens=max_new),
    )


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One engine, one batcher, six traced requests under a profiler
    session: ragged rounds admit them, scans decode them."""
    import jax

    engine = TPUEngine(
        "llama3-tiny",
        EngineConfig(max_batch_size=4, max_seq_len=128,
                     prefill_buckets=(16, 32, 64), multi_step=4),
    )
    compiles_before = compile_log().count
    timelines = [flight.Timeline(f"t{i}", source="test") for i in range(6)]
    walls = {}

    def prompt(i):
        return list(range(40 * i + 5, 40 * i + 25 + i))     # 20 + i tokens

    async def go():
        b = ContinuousBatcher(
            engine, BatcherConfig(max_wait_ms=1, max_multi_step=4))
        b.start()
        t0 = time.perf_counter()

        def send(i, max_new):
            return asyncio.ensure_future(
                b.submit(_req(prompt(i), max_new), flight=timelines[i]))

        # three at once; the loop parks when they are done
        resps = await asyncio.gather(*[send(i, 9) for i in range(3)])
        # two more, and a sixth while those two decode: its chunk round
        # carries their decode rows
        late = [send(3, 40), send(4, 40)]
        while b.stats["admitted"] < 5:
            await asyncio.sleep(0.001)
        late.append(send(5, 9))
        resps += await asyncio.gather(*late)
        assert all(r.ok for r in resps), [r.error for r in resps]
        walls["s"] = time.perf_counter() - t0
        stats = b.get_stats()
        await b.stop()
        return stats

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        bstats = asyncio.run(go())
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "the profiler left no .xplane.pb"
    spans = []
    data = jax.profiler.ProfileData.from_file(files[-1])
    for plane in data.planes:
        # a host thread is a line of the plane; lines share names
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("dgi."):
                    spans.append({
                        "name": ev.name, "a": ev.start_ns,
                        "b": ev.start_ns + ev.duration_ns,
                        "thread": (plane.name, thread), **dict(ev.stats),
                    })
    return {"engine": engine.get_stats(), "batcher": bstats,
            "wall_s": walls["s"], "timelines": timelines, "spans": spans,
            "levels": (1, 4), "compiles_before": compiles_before}


def test_counters_ragged_rectangle(served):
    e = served["engine"]
    assert 0 < e["ragged_positions_live"] <= e["ragged_positions_dispatched"]
    # every ragged round dispatched its packed length Tp, a rung of the
    # ladder for four rows and pieces of at most 64 tokens: what the dense
    # work ran over, well under the four rows x 32 of the rectangle
    rungs = [s["bucket"] for s in served["spans"]
             if s["name"] == "dgi.engine.ragged_round"]
    assert set(rungs) <= {16, 32, 72, 144, 256}
    assert e["ragged_positions_dispatched"] == sum(rungs)
    assert e["ragged_positions_dispatched"] < 4 * 32 * e["ragged_rounds"]
    # a live position is a decode row's token or a prompt token: all six
    # (unshared) prompts went through ragged rounds, the sixth beside the
    # two rows that were decoding
    prompts = sum(20 + i for i in range(6))
    decode_rows = sum(s["decode_rows"] for s in served["spans"]
                      if s["name"] == "dgi.engine.ragged_round")
    assert e["prefill_tokens"] == prompts and decode_rows >= 2
    assert e["ragged_positions_live"] == prompts + decode_rows


def test_counters_scans_by_level(served):
    e, b = served["engine"], served["batcher"]
    scans = {t: b[f"scans_t{t}"] for t in served["levels"]}
    assert sum(scans.values()) > 0
    assert e["rounds"] == sum(scans.values()) + e["ragged_rounds"]
    assert e["rounds"] == b["decode_rounds"]
    for t, n in scans.items():
        assert (b[f"scan_s_t{t}"] > 0) == (n > 0)
    assert "scans_t16" not in b         # only configured levels are counted


def test_counters_and_spans_say_why_each_scan_got_its_length(served):
    b = served["batcher"]
    reasons = ("amortise", "raised_waiting", "capped_by_budget")
    rounds = [s for s in served["spans"] if s["name"] == "dgi.batcher.round"]
    for reason in reasons:
        assert b[f"scans_{reason}"] == sum(
            1 for s in rounds if s["reason"] == reason)
    assert sum(b[f"scans_{r}"] for r in reasons) == sum(
        b[f"scans_t{t}"] for t in served["levels"])
    assert all(s["reason"] == "ragged" for s in rounds
               if s["kind"] == "ragged")
    # a scan runs the level the rule is at, or the one above it
    assert all(s["steps"] in (s["level"], 4 * s["level"]) for s in rounds
               if s["kind"] == "scan")
    # the rule's two measured times were sampled from the engine's phases
    assert b["step_latency_ema_ms"] > 0 and b["round_host_ema_ms"] > 0
    # six requests of a few tokens each on scans of 1 or 4 steps: rows
    # ended inside scans, by less than a scan each
    scans = [s for s in served["spans"]
             if s["name"] == "dgi.engine.decode_multi"]
    assert 0 <= b["scan_row_steps_masked"] < sum(
        s["steps"] * s["decode_rows"] for s in scans)


def test_counters_time_adds_up(served):
    e, b = served["engine"], served["batcher"]
    phases = sum(e[f"round_{p}_s"] for p in PHASES)
    assert all(e[f"round_{p}_s"] > 0 for p in PHASES)
    # the phases are inside the rounds, the rounds and the gaps between
    # them inside the run
    assert phases <= served["wall_s"]
    assert sum(b[f"scan_s_t{t}"] for t in served["levels"]) <= served["wall_s"]
    assert 0 < b["between_rounds"] <= e["rounds"]
    # the first three finished and the loop parked before the others came:
    # that idle wait is no gap between rounds
    assert b["between_rounds"] <= e["rounds"] - 2
    assert phases + b["between_rounds_s"] <= served["wall_s"]
    assert 0 < b["admit_s"] and 0 < b["deliver_s"]
    assert b["admit_s"] + b["deliver_s"] <= served["wall_s"]


def test_counters_compiles(served):
    e = served["engine"]
    # the tiny engine's round graphs compiled (or loaded from the cache:
    # a request either way) after the engine was built
    assert e["compiles"] == compile_log().count > served["compiles_before"]
    assert e["compile_s"] == compile_log().seconds > 0.0


def test_flight_events_carry_the_round_that_served_them(served):
    rounds = {s["round"] for s in served["spans"]
              if s["name"] == "dgi.batcher.round"}
    ragged = {s["round"] for s in served["spans"]
              if s["name"] == "dgi.batcher.round" and s["kind"] == "ragged"}
    assert rounds
    for tl in served["timelines"]:
        by_name = {}
        for name, _ts, attrs in tl.events:
            by_name.setdefault(name, []).append(attrs or {})
        admitted = by_name["batcher.admitted"][0]
        # an admission names the round about to run: its first chunk round
        assert admitted["round"] in ragged
        first = by_name["batcher.first_token"][0]
        assert first["round"] in ragged and first["round"] >= admitted["round"]
        for attrs in by_name.get("batcher.chunk_round", []):
            assert attrs["round"] in rounds


@pytest.mark.parametrize("kind,engine_span", [
    ("ragged", "dgi.engine.ragged_round"),
    ("scan", "dgi.engine.decode_multi"),
])
def test_xplane_holds_engine_spans_inside_their_batcher_round(
        served, kind, engine_span):
    spans = served["spans"]
    outer = [s for s in spans
             if s["name"] == "dgi.batcher.round" and s["kind"] == kind]
    inner = [s for s in spans if s["name"] == engine_span]
    assert outer and len(inner) == len(outer)
    by_round = {s["round"]: s for s in outer}
    for s in inner:
        o = by_round[s["round"]]                 # the same round number
        assert o["a"] <= s["a"] and s["b"] <= o["b"]
        assert s["thread"] == o["thread"]        # both on the engine thread
        assert s["steps"] == o["steps"] and o["level"] in served["levels"]
        assert s["positions"] > 0 and "queue_depth" in o
        if kind == "ragged":
            assert s["positions"] == s["bucket"]        # the packed length
            assert 0 < s["live_prompt_tokens"] + s["decode_rows"] \
                <= s["positions"]
            assert s["admission_rows"] >= 1
        else:
            assert 1 <= s["decode_rows"] <= 4
            assert s["positions"] == 4 * s["steps"]
        # the four phases, in order, inside the engine span; a scan that
        # is left unread (PR 32) has its readback and commit in the call
        # that reads it: the next scan's, or the batcher's collect round;
        # so has a ragged round that went out behind an unread scan (PR
        # 51: ``chained`` 1 on both its spans), which is read by the
        # collect round that follows it
        kids = sorted((c for c in spans
                       if c["name"].startswith(engine_span + ".")
                       and s["a"] <= c["a"] and c["b"] <= s["b"]),
                      key=lambda c: c["a"])
        names = [c["name"].rsplit(".", 1)[1] for c in kids]
        if kind == "ragged":
            assert names in (list(PHASES), list(PHASES[:2]))
            assert s["chained"] == o["chained"] == (len(names) == 2)
            if s["chained"]:
                read = min((c for c in spans
                            if c["name"] == "dgi.batcher.round"
                            and c["a"] >= o["b"]), key=lambda c: c["a"])
                assert (read["kind"], read["reason"]) == ("collect", "round")
                assert [c["name"].rsplit(".", 1)[1] for c in sorted(
                    (c for c in spans
                     if c["name"].startswith(engine_span + ".")
                     and read["a"] <= c["a"] and c["b"] <= read["b"]),
                    key=lambda c: c["a"])] == list(PHASES[2:])
        else:
            assert names in (list(PHASES), list(PHASES[:2]))
            assert s["chained"] == o["chained"] == (len(names) == 4)
        assert all(x["b"] <= y["a"] for x, y in zip(kids, kids[1:]))


def test_xplane_holds_the_loop_spans_on_another_thread(served):
    spans = served["spans"]
    admit = [s for s in spans if s["name"] == "dgi.batcher.admit"]
    deliver = [s for s in spans if s["name"] == "dgi.batcher.deliver"]
    rounds = [s for s in spans if s["name"] == "dgi.batcher.round"]
    # a deliver follows every round that dispatched; a scan read back
    # because the next round is not a scan behind it (kind "collect") has
    # one of its own or is read inside the deliver that found a row ended
    dispatched = [s for s in rounds if s["kind"] != "collect"]
    assert len(dispatched) <= len(deliver) <= len(rounds)
    assert len(admit) >= len(dispatched)
    assert {s["thread"] for s in admit + deliver}.isdisjoint(
        {s["thread"] for s in rounds})
    assert all("queue_depth" in s for s in admit)
    # six requests finished, each in the deliver that followed its last round
    assert sum(s["finished"] for s in deliver) == 6


def test_xplane_admit_span_says_whether_it_ran_ahead_of_the_read(served):
    """PR 40: the pass that admits an arrival while the scan before its
    round is still unread carries ``ahead`` 1 and lies before that scan's
    read on the clock. PR 51: that read is the ragged round the pass
    admitted into, which went out behind the scan (``chained`` 1, then its
    own read: a ``collect`` round, reason ``round``), or, where the pass
    left something for the read to settle, the ``collect`` round with
    reason ``admission`` as before."""
    spans, b = served["spans"], served["batcher"]
    admit = sorted((s for s in spans if s["name"] == "dgi.batcher.admit"),
                   key=lambda s: s["a"])
    assert all(s["ahead"] in (0, 1) for s in admit)
    ahead = [s for s in admit if s["ahead"]]
    # the sixth request came while two rows decoded in chained scans
    assert 1 <= b["admissions_ahead"] <= len(ahead)
    assert b["admissions_ahead"] <= b["ragged_admissions"] == 6
    rounds = sorted((s for s in spans if s["name"] == "dgi.batcher.round"),
                    key=lambda s: s["a"])
    reads = [s for s in rounds if s["kind"] == "collect"
             and s["reason"] == "admission"]
    behind = [s for s in rounds if s["kind"] == "ragged" and s["chained"]]
    assert len(reads) == b["chain_breaks_admission"]
    assert len(behind) == b["ragged_rounds_chained"] \
        == b["chain_breaks_round"] >= 1
    for s in ahead:
        nxt = next(x for x in rounds if x["a"] >= s["b"])
        assert nxt in reads or nxt in behind or (
            nxt["kind"], nxt["reason"]) == ("collect", "round")
        if nxt in reads:
            # ... and the pass after the read is the next one, not ahead
            after = next(x for x in admit if x["a"] >= nxt["b"])
            assert not after["ahead"]
    for s in behind:
        # no pass between the one ahead and the round, and the round's own
        # read is what the loop does next
        before = max((x for x in admit if x["b"] <= s["a"]),
                     key=lambda x: x["a"])
        assert before["ahead"]
        nxt = next(x for x in rounds if x["a"] >= s["b"])
        assert (nxt["kind"], nxt["reason"]) == ("collect", "round")


# --------------------------------------------------------------------- #
# the operator's route: heartbeat payload -> /metrics
# --------------------------------------------------------------------- #

def test_worker_ships_round_counters_and_the_plane_counts_their_deltas():
    from distributed_gpu_inference_tpu.worker.main import Worker

    class Core:
        def get_stats(self):
            return {"round_build_s": 0.5, "round_dispatch_s": 0.125,
                    "round_readback_s": 2.0, "round_commit_s": 0.25,
                    "rounds": 10, "compiles": 7, "compile_s": 1.5,
                    "ragged_kv_path": "in_place"}

    class Eng:
        engine = Core()

        def __init__(self, stats):
            self.stats = stats

        def serving_stats(self):
            return self.stats

    one = {"decode_rounds": 10, "between_rounds_s": 0.5, "between_rounds": 9,
           "admit_s": 0.2, "deliver_s": 0.25, "scans_t1": 0, "scans_t4": 7,
           "scan_s_t4": 0.3, "horizon": 4.0, "step_latency_ema_ms": 11.25,
           "round_host_ema_ms": 8.0, "scans_amortise": 4,
           "scans_raised_waiting": 2, "scans_capped_by_budget": 1,
           "scan_row_steps_masked": 5}
    worker = Worker.__new__(Worker)
    worker.engines = {"a": Eng(one), "b": Eng(dict(one, scans_t4=1))}
    worker.serving_capacity = lambda: 8
    sent = worker._batcher_stats()
    assert sent["between_rounds"] == 18 and sent["scans_t4"] == 8
    assert sent["between_rounds_s"] == 1.0 and sent["admit_s"] == 0.4
    assert "scan_s_t4" not in sent          # seconds by level stay local
    # the horizon rule's counters are summed, its gauges are the last's
    assert sent["scans_raised_waiting"] == 4 and sent["scans_amortise"] == 8
    assert sent["scan_row_steps_masked"] == 10
    assert sent["step_latency_ema_ms"] == 11.25 and sent["horizon"] == 4.0
    assert sent["round_readback_s"] == 4.0 and sent["round_build_s"] == 1.0
    # compiles are the process's, not an engine's: not summed over engines
    assert sent["compiles"] == 7 and sent["compile_s"] == 1.5
    # which KV path the multi-token rounds were built with: a fact, as is
    assert sent["ragged_kv_path"] == "in_place"

    mc = MetricsCollector()
    mc.record_batcher_engine("w1", sent)
    mc.record_batcher_engine("w1", dict(sent, between_rounds=20,
                                        between_rounds_s=1.25, scans_t4=11,
                                        scans_t16=2, deliver_s="garbage",
                                        scans_capped_by_budget=3,
                                        scan_row_steps_masked=12,
                                        round_host_ema_ms=7.5,
                                        round_readback_s=5.5,
                                        compiles=10))
    text = mc.metrics.render().decode()
    if "batcher_between_rounds_total" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert 'batcher_between_rounds_total{worker="w1"} 20.0' in text
    assert ('batcher_loop_seconds_total{part="between_rounds",worker="w1"}'
            ' 1.25') in text
    assert 'batcher_loop_seconds_total{part="deliver",worker="w1"} 0.5' \
        in text
    assert 'batcher_scans_total{steps="4",worker="w1"} 11.0' in text
    assert 'batcher_scans_total{steps="16",worker="w1"} 2.0' in text
    assert ('batcher_scan_reasons_total{reason="capped_by_budget",'
            'worker="w1"} 3.0') in text
    assert ('batcher_scan_reasons_total{reason="raised_waiting",'
            'worker="w1"} 4.0') in text
    assert 'batcher_scan_row_steps_masked_total{worker="w1"} 12.0' in text
    assert 'batcher_scan_step_ms{worker="w1"} 11.25' in text
    assert 'batcher_round_host_ms{worker="w1"} 7.5' in text
    assert 'engine_round_seconds_total{phase="readback",worker="w1"} 5.5' \
        in text
    assert 'engine_round_seconds_total{phase="build",worker="w1"} 1.0' in text
    assert 'worker_compiles_total{worker="w1"} 10.0' in text
    assert ('worker_compile_seconds_total{stage="backend",worker="w1"} 1.5'
            in text)
    assert 'worker_ragged_kv_path{path="in_place",worker="w1"} 1.0' in text
    assert 'worker_ragged_kv_path{path="layer_copy",worker="w1"} 0.0' in text
    # an engine restart re-anchors: totals fall, nothing is subtracted
    mc.record_batcher_engine("w1", dict(sent, between_rounds=3))
    assert 'batcher_between_rounds_total{worker="w1"} 20.0' \
        in mc.metrics.render().decode()
