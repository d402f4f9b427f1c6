"""A token's way out of the worker and a request's way in (docs/observability.md,
"A token's way out"): the four stamps of every event, the direct server's
stage counters, the batcher's longest-wait counts, the three timeline events
and the clock anchor of a traced stream, nothing at all for an untraced one,
two faults driven through the instrument, and the bytes on the wire. Real
engine (tiny model, CPU), real batcher, real ``DirectServer`` on its own
thread and loop, real sockets."""

import json
import threading
import time

import httpx
import pytest

from distributed_gpu_inference_tpu.runtime import flight
from distributed_gpu_inference_tpu.server.observability import MetricsCollector
from distributed_gpu_inference_tpu.utils.data_structures import WorkerState
from distributed_gpu_inference_tpu.worker import direct_server as ds_mod
from distributed_gpu_inference_tpu.worker.direct_server import DirectServer
from distributed_gpu_inference_tpu.worker.engines import llm as llm_mod
from distributed_gpu_inference_tpu.worker.engines.llm import TPULLMEngine

STAGES = ("ready", "notified", "pumped", "written")


class _Worker:
    """The claim surface a ``Worker`` gives its direct server."""

    def __init__(self, eng):
        self.engines = {"llm": eng}
        self.state = WorkerState.IDLE
        self._serving = 0
        self._lock = threading.Lock()

    def try_begin_job(self):
        with self._lock:
            if self.state != WorkerState.IDLE:
                return False
            self.state = WorkerState.BUSY
            return True

    def end_job(self):
        with self._lock:
            self.state = WorkerState.IDLE

    def try_begin_serving(self):
        with self._lock:
            if self.state == WorkerState.BUSY and not self._serving:
                return False
            self.state = WorkerState.BUSY
            self._serving += 1
            return True

    def end_serving(self):
        with self._lock:
            self._serving -= 1
            if not self._serving:
                self.state = WorkerState.IDLE

    def get_status(self):
        return {"state": self.state.value}


class _Rig:
    def __init__(self):
        self.eng = TPULLMEngine({
            "model": "llama3-tiny", "max_batch_size": 4, "max_seq_len": 128,
            # one step a scan, so that a stream meets many rounds
            "multi_step": 1,
            "serving": {"max_wait_ms": 1.0, "multi_step": 1,
                        "max_horizon": 4},
        })
        self.eng.load_model()
        self.ds = DirectServer(_Worker(self.eng), host="127.0.0.1", port=0)
        self.ds.start()
        port = self.ds._runner.addresses[0][1]
        self.url = f"http://127.0.0.1:{port}/inference/stream"
        # every event's stamps as the direct server counted them
        self.wrote = []
        counted = self.ds._wrote

        def recording(egress, written, watch):
            self.wrote.append((egress, written))
            counted(egress, written, watch)

        self.ds._wrote = recording
        # and every watch it made (one a traced stream, none otherwise)
        self.watches = []
        self.plain_watch = ds_mod._StreamWatch
        rig = self

        class Watch(ds_mod._StreamWatch):
            def __init__(self):
                super().__init__()
                rig.watches.append(self)

        ds_mod._StreamWatch = Watch
        self.stream("warm the graphs", 6)

    @property
    def batcher(self):
        return self.eng.serving.batcher

    def stream(self, prompt, max_new, trace_id=None, stream_id=None):
        """One stream: its raw bytes and its events' JSON."""
        params = {"prompt": prompt, "max_new_tokens": max_new,
                  "temperature": 0.0, "ignore_eos": True}
        if trace_id:
            params["trace_id"] = trace_id
        body = {"type": "llm", "params": params}
        if stream_id:
            body["stream_id"] = stream_id
        with httpx.stream("POST", self.url, json=body, timeout=120.0) as r:
            assert r.status_code == 200
            raw = b"".join(r.iter_raw())
        events = [json.loads(line[5:]) for line in raw.split(b"\n")
                  if line.startswith(b"data:")]
        return raw, events

    def close(self):
        ds_mod._StreamWatch = self.plain_watch
        self.ds.stop()
        self.eng.unload()


def _timeline(events):
    tl = events[-1].get("timeline") or {}
    return tl, {name: (ts, attrs or {}) for name, ts, attrs
                in tl.get("events") or []}


@pytest.fixture(scope="module")
def rig():
    r = _Rig()
    yield r
    r.close()


@pytest.fixture(scope="module")
def served(rig):
    """Six concurrent streams over four slots, three of them traced."""
    rig.wrote.clear()
    rig.watches.clear()
    direct0 = dict(rig.ds.stats)
    batcher0 = dict(rig.batcher.stats)
    out = {}

    def one(i):
        out[i] = rig.stream("abcdefgh" * (i + 1), 10 + 3 * i,
                            trace_id=f"t{i}" if i % 2 else None)

    # a round with a prompt piece takes the device 60 ms here (its readback
    # waits that long), many times a tiny model's scan: which round ends a
    # stream's longest wait is then no matter of the machine's load
    core = rig.eng.engine
    read_round = core._collect_ragged

    def slow_round(rnd, sp):
        time.sleep(0.06 if rnd.ready else 0.0)
        return read_round(rnd, sp)

    core._collect_ragged = slow_round
    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        del core._collect_ragged
    assert sorted(out) == list(range(6))

    def moved(now, then):
        return {k: v - then.get(k, 0) for k, v in now.items()
                if isinstance(v, (int, float))}

    return {"streams": out, "wrote": list(rig.wrote),
            "watches": len(rig.watches),
            "ring": list(rig.eng._flight_recent),
            "direct": moved(rig.ds.stats, direct0),
            "batcher": moved(rig.batcher.stats, batcher0)}


def test_the_four_stamps_of_every_event_are_non_decreasing(served):
    assert served["wrote"]
    for (rstamp, notified, pumped, req), written in served["wrote"]:
        assert rstamp.ready <= notified <= pumped <= written, \
            (rstamp, notified, pumped, written)
        assert rstamp.kind in ("ragged", "scan", "collect") and req
        assert rstamp.cause in flight.WAIT_CAUSES
    # one stamp a round, shared by the rows it served
    by_round = {}
    for (rstamp, *_), _ in served["wrote"]:
        by_round.setdefault((rstamp.round, rstamp.kind), set()).add(rstamp)
    assert all(len(stamps) == 1 for stamps in by_round.values())


def test_every_event_but_the_closing_one_was_counted_once(served):
    """Every event a snapshot or the response brought (a token, or the
    text held back until the end) carries a stamp; ``done`` carries none."""
    sent = sum(1 for _, events in served["streams"].values()
               for e in events if not e.get("done"))
    assert served["direct"]["sse_events"] == len(served["wrote"]) == sent


def test_the_three_stages_add_up_to_egress_s(served):
    d = served["direct"]
    parts = d["egress_notify_s"] + d["egress_pump_s"] + d["egress_write_s"]
    assert d["egress_s"] > 0 and min(
        d["egress_notify_s"], d["egress_pump_s"], d["egress_write_s"]) > 0
    assert parts == pytest.approx(d["egress_s"], rel=1e-9)
    assert d["egress_s"] == pytest.approx(sum(
        written - e[0].ready for e, written in served["wrote"]), rel=1e-9)
    assert d["admit_s"] > 0
    # nothing on this machine takes 50 ms from a round to the socket
    assert d["egress_stalled"] >= 0 and d["egress_stall_s"] >= 0.0


def test_longest_wait_counts_add_up_to_the_streams_completed(served):
    b = served["batcher"]
    counts = {c: b[f"longest_wait_{c}"] for c in flight.WAIT_CAUSES}
    assert sum(counts.values()) == len(served["streams"]) == 6, counts
    for c in flight.WAIT_CAUSES:
        assert (b[f"longest_wait_s_{c}"] > 0) == (counts[c] > 0)
    # six streams over four slots: some stream sat through another's
    # prompt piece, and that round is the longest of a tiny model's
    assert counts["ragged_1"] + counts["ragged_2plus"] >= 1


@pytest.mark.parametrize("i", [1, 3, 5])
def test_a_traced_stream_gets_the_three_events_and_the_anchor(served, i):
    raw, events = served["streams"][i]
    tl, ev = _timeline(events)
    assert {"direct.accepted", "direct.first_write",
            "direct.longest_wait"} <= set(ev)
    # the anchor puts any event on the generator's clock
    on_mono = lambda name: ev[name][0] - tl["wall0"] + tl["mono0"]  # noqa: E731
    first = ev["direct.first_write"][1]
    assert on_mono("direct.first_write") == pytest.approx(
        first["written"], abs=1e-5)
    assert on_mono("direct.accepted") <= on_mono("batcher.enqueued") \
        <= first["ready"]
    assert abs(time.monotonic() - first["written"]) < 600
    assert [first[k] for k in STAGES] == sorted(first[k] for k in STAGES)
    # the longest wait between two writes: both events' stamps and rounds
    lw = ev["direct.longest_wait"][1]
    assert lw["wait_ms"] == pytest.approx(
        (lw["written"] - lw["prev_written"]) * 1e3, abs=2e-3)
    assert lw["wait_ms"] > 0 and lw["cause"] in flight.WAIT_CAUSES
    assert lw["round"] >= lw["prev_round"] >= first["round"]
    # and the same wait as the engine thread saw it, on batcher.completed
    done = ev["batcher.completed"][1]
    assert done["longest_wait_cause"] in flight.WAIT_CAUSES
    assert done["longest_wait_ms"] > 0 and done["longest_wait_round"] > 0
    if (lw["round"], lw["cause"]) == (done["longest_wait_round"],
                                      done["longest_wait_cause"]):
        # the judged wait adds up from the program's own stamps (a scan
        # read back on its own has its round's number and another cause)
        egress = (lw["written"] - lw["ready"]) \
            - (lw["prev_written"] - lw["prev_ready"])
        assert lw["wait_ms"] == pytest.approx(
            done["longest_wait_ms"] + egress * 1e3, abs=0.01)
    # the heartbeat ring's copy holds them too
    ring = [w for w in served["ring"] if w["trace_id"] == f"t{i}"]
    assert ring and "direct.longest_wait" in {e[0] for e in ring[-1]["events"]}


@pytest.mark.parametrize("i", [0, 2, 4])
def test_an_untraced_stream_gets_nothing_and_allocates_nothing(served, i):
    raw, events = served["streams"][i]
    assert "timeline" not in events[-1] and events[-1]["done"] is True
    assert flight.EGRESS_KEY.encode() not in raw
    # three traced streams, three watches: none for the other three
    assert served["watches"] == 3


def test_the_bytes_on_the_wire_are_the_parents(rig):
    """One stream replayed with and without a ``trace_id``: the same bytes
    but for the closing event's ``timeline``, and each event exactly what
    the parent's direct server wrote for its chunk."""
    plain, events = rig.stream("replay me", 12, stream_id="s-replay")
    traced, tevents = rig.stream("replay me", 12, trace_id="tr",
                                 stream_id="s-replay")
    assert len(events) == len(tevents) == 13
    assert tevents[-1].pop("timeline")["trace_id"] == "tr"
    assert events == tevents
    assert plain.split(b"\n\n")[:-2] == traced.split(b"\n\n")[:-2]
    assert plain == b"".join(
        f"id: {e['offset']}\ndata: {json.dumps(e)}\n\n".encode()
        for e in events)
    for e in events[:-1]:
        assert set(e) == {"text_delta", "token_ids", "stream_id", "offset"}
    assert set(events[-1]) == {"done", "finish_reason", "usage",
                               "stream_id", "offset"}


def test_inference_reply_is_unchanged_and_carries_no_private_key(rig):
    with httpx.Client(timeout=120.0) as c:
        url = rig.url.replace("/stream", "")
        body = {"type": "llm", "params": {"prompt": "reply", "max_tokens": 4}}
        r = c.post(url, json=body)
        body["params"]["trace_id"] = "tq"
        rt = c.post(url, json=body)
    assert r.status_code == rt.status_code == 200
    plain, traced = r.json()["result"], rt.json()["result"]
    names = {e[0] for e in traced.pop("timeline")["events"]}
    assert "direct.accepted" in names
    assert set(plain) == set(traced) and plain["text"] == traced["text"]
    assert "_flight" not in r.text and flight.EGRESS_KEY not in r.text


# --------------------------------------------------------------------- #
# two faults, driven through the instrument
# --------------------------------------------------------------------- #

# what the two planted faults sleep: many times anything a round or a
# delivery of the rig takes, also with the machine shared between six test
# workers (30 ms was not always the stream's longest wait there, nor 27 of
# it left of an egress measured against the event before: ROADMAP D16), so
# that the slept round, or event, dominates by construction
SLEPT_S = 0.4


def _one_traced(rig, trace_id):
    direct0 = dict(rig.ds.stats)
    rig.wrote.clear()
    _, events = rig.stream("a fault on the way", 16, trace_id=trace_id)
    _, ev = _timeline(events)
    lw, done = ev["direct.longest_wait"][1], ev["batcher.completed"][1]
    egress_ms = ((lw["written"] - lw["ready"])
                 - (lw["prev_written"] - lw["prev_ready"])) * 1e3
    # the returns of the rounds that brought the stream a token
    ready = sorted({e[0].ready for e, _ in rig.wrote})
    return {"lw": lw, "done": done, "egress_ms": egress_ms,
            "round_gap_ms": max(b - a for a, b in zip(ready, ready[1:]))
            * 1e3,
            "pump_s": rig.ds.stats["egress_pump_s"]
            - direct0["egress_pump_s"]}


def test_a_sleep_on_the_observers_path_moves_the_egress_and_not_the_wait(
        rig, monkeypatch):
    """0.4 s slept in the stream's pump thread before one token's chunk:
    the delivery's metrics take it, the engine thread's does not."""
    advance = llm_mod._StreamSplicer.advance

    def slow(self, gen, finished):
        if len(gen) == 9:
            time.sleep(SLEPT_S)
        return advance(self, gen, finished)

    monkeypatch.setattr(llm_mod._StreamSplicer, "advance", slow)
    got = _one_traced(rig, "fault-pump")
    assert got["egress_ms"] >= SLEPT_S * 1e3 * 0.9
    assert got["pump_s"] >= SLEPT_S * 0.9
    assert got["lw"]["wait_ms"] >= SLEPT_S * 1e3 * 0.9
    assert got["lw"]["pumped"] - got["lw"]["notified"] >= SLEPT_S * 0.9
    # the wait between two rounds' returns knows nothing of it: it is
    # the longest stretch between the rounds' own stamps, as it was
    assert got["done"]["longest_wait_ms"] == pytest.approx(
        got["round_gap_ms"], abs=2e-3)
    assert (got["lw"]["ready"] - got["lw"]["prev_ready"]) * 1e3 == \
        pytest.approx(got["lw"]["wait_ms"] - got["egress_ms"], abs=2e-3)


def test_a_sleep_inside_an_engine_round_moves_the_wait_and_names_the_round(
        rig, monkeypatch):
    """0.4 s slept inside one scan's call on the engine thread: the wait
    between two rounds' returns takes it and names that round and its
    cause; what delivery adds does not move."""
    core, b = rig.eng.engine, rig.batcher
    decode_multi, slept = core.decode_multi, {}

    def slow(steps, **kw):
        slept["calls"] = slept.get("calls", 0) + 1
        if slept["calls"] == 5:
            slept["round"] = b._round
            time.sleep(SLEPT_S)
        return decode_multi(steps, **kw)

    monkeypatch.setattr(core, "decode_multi", slow)
    got = _one_traced(rig, "fault-round")
    done = got["done"]
    assert done["longest_wait_ms"] >= SLEPT_S * 1e3
    assert done["longest_wait_round"] == slept["round"]
    assert done["longest_wait_cause"] in ("scan", "scan_raised")
    # the client's longest wait is that round too, and delivery added
    # nothing like the sleep to it
    assert got["lw"]["round"] == slept["round"]
    assert abs(got["egress_ms"]) < SLEPT_S * 1e3 / 2
    assert got["lw"]["wait_ms"] == pytest.approx(
        done["longest_wait_ms"] + got["egress_ms"], abs=0.01)


def test_a_stalled_event_is_counted_and_logged_once_with_its_stamps(
        rig, caplog):
    stamp = flight.RoundStamp(100.0, 7, "ragged", 1, 2, "ragged_2plus")
    before = dict(rig.ds.stats)
    with caplog.at_level("WARNING", logger=ds_mod.log.name):
        rig.ds._wrote((stamp, 100.001, 100.002, "req-9"), 100.010, None)
        rig.ds._wrote((stamp, 100.001, 100.060, "req-9"), 100.075, None)
    st = rig.ds.stats
    assert st["egress_stalled"] - before["egress_stalled"] == 1
    assert st["egress_stall_s"] - before["egress_stall_s"] == \
        pytest.approx(0.025, abs=1e-9)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1 and "req-9" in lines[0] and "round 7" in lines[0]
    assert "pumped +0.060" in lines[0] and "written +0.075" in lines[0]
    for k in before:        # leave the rig's counters as they were
        rig.ds.stats[k] = before[k]


# --------------------------------------------------------------------- #
# the operator's route: heartbeat payload -> /metrics
# --------------------------------------------------------------------- #

def test_worker_ships_egress_counters_and_the_plane_counts_their_deltas():
    from distributed_gpu_inference_tpu.worker.main import Worker

    class Core:
        def get_stats(self):
            return {}

    class Eng:
        engine = Core()

        def __init__(self, stats):
            self.stats = stats

        def serving_stats(self):
            return self.stats

    one = {"decode_rounds": 10, "longest_wait_ragged_1": 3,
           "longest_wait_s_ragged_1": 0.12, "longest_wait_scan_raised": 1,
           "longest_wait_s_scan_raised": 0.045, "longest_wait_other": 0,
           "longest_wait_s_other": 0.0}
    worker = Worker.__new__(Worker)
    worker.engines = {"a": Eng(one), "b": Eng(dict(one))}
    worker.serving_capacity = lambda: 8
    sent = worker._batcher_stats()
    assert sent["longest_wait_ragged_1"] == 6
    assert sent["longest_wait_s_ragged_1"] == 0.24
    assert sent["longest_wait_s_scan_raised"] == 0.09

    ds = DirectServer(object())
    ds.stats.update(sse_events=400, egress_s=0.5, egress_notify_s=0.25,
                    egress_pump_s=0.125, egress_write_s=0.125,
                    egress_stalled=2, egress_stall_s=0.03, admit_s=0.0625)
    wire = ds.wire_stats()
    assert wire["sse_events"] == 400 and wire["egress_pump_s"] == 0.125
    assert "egress_s" not in wire           # the stages add up to it

    mc = MetricsCollector()
    mc.record_batcher_engine("w1", sent)
    mc.record_batcher_engine("w1", dict(sent, longest_wait_ragged_1=9,
                                        longest_wait_s_ragged_1=0.375,
                                        longest_wait_s_other="garbage"))
    mc.record_direct_engine("w1", wire)
    mc.record_direct_engine("w1", dict(wire, sse_events=1000,
                                       egress_notify_s=0.75,
                                       egress_write_s="garbage",
                                       egress_stalled=3))
    text = mc.metrics.render().decode()
    if "direct_sse_events_total" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert ('batcher_stream_longest_wait_total{cause="ragged_1",'
            'worker="w1"} 9.0') in text
    assert ('batcher_stream_longest_wait_seconds_total{cause="ragged_1",'
            'worker="w1"} 0.375') in text
    assert ('batcher_stream_longest_wait_total{cause="scan_raised",'
            'worker="w1"} 2.0') in text
    assert 'direct_sse_events_total{worker="w1"} 1000.0' in text
    assert ('direct_token_egress_seconds_total{stage="notify",worker="w1"}'
            ' 0.75') in text
    assert ('direct_token_egress_seconds_total{stage="pump",worker="w1"}'
            ' 0.125') in text
    assert ('direct_token_egress_seconds_total{stage="write",worker="w1"}'
            ' 0.125') in text
    assert 'direct_egress_stalls_total{worker="w1"} 3.0' in text
    assert 'direct_admit_seconds_total{worker="w1"} 0.0625' in text
    # a worker restart re-anchors: totals fall, nothing is subtracted
    mc.record_direct_engine("w1", dict(wire, sse_events=5))
    assert 'direct_sse_events_total{worker="w1"} 1000.0' \
        in mc.metrics.render().decode()
