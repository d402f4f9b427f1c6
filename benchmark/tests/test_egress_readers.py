"""The readers of a token's way out of the worker and of a request's way in
(the direct server's egress counters, the batcher's longest-wait counts, the
timeline events ``direct.accepted`` / ``direct.first_write`` /
``direct.longest_wait`` and ``batcher.completed``'s wait), each on rows and
counters made by hand; and what each gives for a program that has no such
counter or event (the parent of the PR that added them): nothing."""

import pytest

from harness import layers

CELL = {"name": "c", "end_to_end": {"gap_p50_ms": {}}}


def reader(name):
    entry = {"name": name, "moves": "gap_p50_ms"}
    return layers.readers(dict(CELL, per_layer=[entry]))[0][1]


def window(direct0, direct1, batcher0=None, batcher1=None, seconds=50.0):
    ends = lambda d, b: {"engine": {}, "batcher": b or {}, "direct": d}  # noqa: E731
    return {"w0": 100.0, "w1": 100.0 + seconds,
            "c0": ends(direct0, batcher0), "c1": ends(direct1, batcher1)}


PARENT = window({"requests": 3, "rejected": 0, "hedge_cancels": 0},
                {"requests": 90, "rejected": 0, "hedge_cancels": 0},
                {"completed": 3}, {"completed": 90})


def row(t, events):
    return {"t": t, "timeline": {"trace_id": "b1", "mono0": 1000.0,
                                 "wall0": 5000.0, "events": events}}


def stamps(ready, written, pre=""):
    return {f"{pre}ready": ready, f"{pre}notified": ready + 0.0002,
            f"{pre}pumped": ready + 0.0005, f"{pre}written": written,
            f"{pre}round": 7, f"{pre}cause": "ragged_1"}


# the parent's rows: a timeline without the new events or attributes
OLD_ROWS = [row([1012.0, 1012.5], [
    ["worker.picked_up", 5011.0, None], ["batcher.enqueued", 5011.1, None],
    ["batcher.completed", 5013.0, {"finish_reason": "length", "tokens": 9}],
]), {"t": [1.0], "timeline": None}]


@pytest.mark.parametrize("name", [
    "direct.token_egress_ms", "direct.token_egress_ms.itl50",
    "direct.token_egress_ms.tpot"])
def test_token_egress_ms_is_egress_seconds_over_events(name):
    read = reader(name)
    run = {"win": window({"sse_events": 100, "egress_s": 0.5},
                         {"sse_events": 16100, "egress_s": 24.5})}
    assert read(run) == pytest.approx(1.5)
    assert read({"win": PARENT}) is None
    # a window in which no event was written
    assert read({"win": window({"sse_events": 5, "egress_s": 0.1},
                               {"sse_events": 5, "egress_s": 0.1})}) is None


@pytest.mark.parametrize("name", [
    "direct.egress_stall_share", "direct.egress_stall_share.itl50",
    "direct.egress_stall_share.tpot"])
def test_egress_stall_share_is_stalled_seconds_over_the_window(name):
    read = reader(name)
    run = {"win": window({"egress_stall_s": 0.25, "egress_stalled": 2},
                         {"egress_stall_s": 0.75, "egress_stalled": 9})}
    assert read(run) == pytest.approx(1.0)
    # nothing stalled: a reading of zero, not nothing
    assert read({"win": window({"egress_stall_s": 0.0},
                               {"egress_stall_s": 0.0})}) == 0.0
    assert read({"win": PARENT}) is None


def test_longest_wait_piece_share_counts_the_streams_a_piece_stalled():
    read = reader("batcher.longest_wait_piece_share")
    causes = ("ragged_1", "ragged_2plus", "scan_raised", "scan", "other")
    before = {f"longest_wait_{c}": 1 for c in causes}
    after = dict(zip((f"longest_wait_{c}" for c in causes),
                     (121, 31, 41, 6, 6)))
    assert read({"win": window({}, {}, before, after)}) == pytest.approx(75.0)
    # no stream completed in the window, and the parent's counters
    assert read({"win": window({}, {}, before, before)}) is None
    assert read({"win": PARENT}) is None


def test_longest_wait_p50_ms_is_the_median_of_the_batchers_waits():
    read = reader("batcher.longest_wait_p50_ms")
    rows = [row([], [["batcher.completed", 5013.0,
                      {"tokens": 9, "longest_wait_ms": ms,
                       "longest_wait_round": 4,
                       "longest_wait_cause": "ragged_1"}]])
            for ms in (31.0, 36.5, 58.0)]
    assert read({"sample": rows + OLD_ROWS}) == pytest.approx(36.5)
    assert read({"sample": OLD_ROWS}) is None
    assert read({"sample": []}) is None


def test_longest_wait_egress_ms_is_what_delivery_added_to_the_wait():
    read = reader("direct.longest_wait_egress_ms")
    rows = [row([], [["direct.longest_wait", 5012.0, {
        "wait_ms": 36.0, **stamps(1012.000, 1012.000 + late),
        **stamps(1011.965, 1011.966, "prev_")}]])
        for late in (0.0015, 0.003, 0.0005)]
    # (written - ready) of the event that ended the wait, less 1 ms before
    assert read({"sample": rows + OLD_ROWS}) == pytest.approx(0.5, abs=1e-6)
    assert read({"sample": OLD_ROWS}) is None


def test_ingress_p50_ms_is_accepted_to_enqueued():
    read = reader("direct.ingress_p50_ms.itl50")
    rows = [row([], [["worker.picked_up", 5011.001, None],
                     ["batcher.enqueued", 5011.0 + took, None],
                     ["direct.accepted", 5011.0, None]])
            for took in (0.002, 0.004, 0.009)]
    assert read({"sample": rows + OLD_ROWS}) == pytest.approx(4.0, abs=1e-6)
    assert read({"sample": OLD_ROWS}) is None


@pytest.mark.parametrize("name", [
    "client.receive_lag_p50_ms", "client.receive_lag_p50_ms.itl50"])
def test_receive_lag_is_the_rows_first_instant_after_the_programs_write(name):
    read = reader(name)

    def rows(lags):
        return [row([1012.0 + lag, 1012.5], [
            ["direct.first_write", 5012.0, stamps(1011.99, 1012.0)]])
            for lag in lags]

    notes = {}
    run = {"sample": rows((0.0004, 0.0006, 0.002)) + OLD_ROWS, "notes": notes}
    assert read(run) == pytest.approx(0.6, abs=1e-6) and notes == {}
    # a row stamped before the write's return was: the server's thread was
    # waiting for the interpreter's lock; the reading stands, the notes say
    run = {"sample": rows((0.0004, -0.0002, 0.002)), "notes": notes}
    assert read(run) == pytest.approx(0.4, abs=1e-6)
    assert "1 of 3 rows stamped" in notes["client.receive_lag_p50_ms"]
    # a row received its event before the pump yielded it: two clocks
    run = {"sample": rows((0.0004, -0.25, 0.002)), "notes": notes}
    assert read(run) is None
    assert "1 of 3 rows received" in notes["client.receive_lag_p50_ms"]
    assert "-240.500 ms" in notes["client.receive_lag_p50_ms"]
    assert read({"sample": OLD_ROWS, "notes": {}}) is None


def test_every_new_entry_has_its_reader_and_moves_what_its_cells_report():
    import json

    from harness.spec import CHECKOUT

    with open(CHECKOUT / "BENCHMARK.json") as f:
        manifest = json.load(f)
    judged = {w: e["name"] for e in manifest["end_to_end"]
              for w in e.get("workloads", [])}
    new = [e for e in manifest["per_layer"] if e["name"].startswith((
        "direct.token_egress_ms", "direct.egress_stall_share",
        "batcher.longest_wait_", "direct.longest_wait_egress_ms",
        "direct.ingress_p50_ms", "client.receive_lag_p50_ms"))]
    assert len(new) == 12
    for e in new:
        assert layers.reader_path(e["name"]) is not None, e["name"]
        assert {judged[w] for w in e["workloads"]} == {e["moves"]}, e["name"]
