"""The reduction from a trace to device numbers: interval arithmetic on
made-up events, then the recorded traces under ``testdata/``."""

import pytest

from harness import spec, trace_reduce as tr


def test_union_gaps_and_clip():
    busy = tr.union([(0, 2), (1, 3), (5, 6), (5.5, 5.8)])
    assert busy == [(0, 3), (5, 6)]
    assert tr.total(busy) == 4
    assert tr.gaps(busy, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert tr.gaps(busy, 1, 4) == [(3, 4)]
    assert tr.clip(busy, 2, 5.5) == [(2, 3), (5, 5.5)]


def test_self_time_leaves_children_out_of_their_parent():
    # a while of 10 s that holds two fusions of 3 s and 4 s, then a copy
    events = [("while", 0, 10), ("fusion", 1, 4), ("fusion", 5, 9),
              ("copy", 10, 11)]
    assert tr.self_times(events) == {"while": 3, "fusion": 7, "copy": 1}
    assert sum(tr.self_times(events).values()) == tr.total(
        tr.union([(a, b) for _, a, b in events]))


def synthetic():
    ops = [("fusion.1", 1.0, 2.0), ("all-reduce.3", 2.0, 2.5),
           ("fusion.1", 4.0, 5.0), ("fusion.2", 7.0, 7.5)]
    return {
        "devices": [{"name": "/device:TPU:0", "ops": ops, "modules": [
            ("jit_decode_multi(1)", 1.0, 2.5), ("jit_ragged_round(2)", 4.0, 5.0),
            ("jit_decode_multi(1)", 7.0, 7.5)]}],
        "notes": [
            ("bench.slice", 0.0, 10.0, {"mono": "100.0"}),
            ("bench.decode_multi", 0.8, 2.7, {"steps": "4", "decode_rows": "3"}),
            ("bench.ragged_round", 3.5, 5.2,
             {"live_prompt_tokens": "40", "widest_piece": "40",
              "admission_rows": "1", "decode_rows": "3"}),
            ("bench.decode_multi", 6.9, 7.6, {"steps": "1", "decode_rows": "2"}),
        ],
        "structure": [],
    }


def test_reduce_charges_every_idle_second_once():
    trace = synthetic()
    a, b, offset = tr.slice_of(trace)
    assert (a, b, offset) == (0.0, 10.0, -100.0)
    # a request in flight from 0.5 to 8 on the trace's clock
    red = tr.reduce(trace, in_flight=[(0.5, 8.0)])
    assert red["window_s"] == 10 and red["busy_s"] == 3.0
    assert abs(red["collective_s"] - 0.5) < 1e-12
    idle = red["idle_seconds"]
    assert abs(sum(idle.values()) - 7.0) < 1e-9
    inside_decode = [v for k, v in idle.items() if "decode_multi" in k][0]
    assert abs(inside_decode - (0.2 + 0.2 + 0.1 + 0.1)) < 1e-9
    inside_ragged = [v for k, v in idle.items() if "ragged_round" in k][0]
    assert abs(inside_ragged - (0.5 + 0.2)) < 1e-9
    assert abs([v for k, v in idle.items() if "no request" in k][0]
               - (0.5 + 2.0)) < 1e-9       # before 0.5 and after 8
    mods = red["modules"]
    assert [m["call"] for m in mods] == ["decode_multi", "ragged_round",
                                         "decode_multi"]
    assert mods[0]["steps"] == "4" and mods[1]["widest_piece"] == "40"
    top = tr.breakdown(red)
    assert top["device_ops"][0] == ["fusion.1", 2.0]
    assert len(top["idle_gaps"]) == 4


RECORDED = sorted(spec.TESTDATA.glob("trace_*.xplane.pb.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace(path):
    """What must hold of any trace of a served slice: some device, busy
    inside the window, every idle second charged once, the round programs
    found by name and tied to the engine call that launched them."""
    trace = tr.load(str(path))
    assert trace["devices"], trace["structure"]
    marked = tr.slice_of(trace)
    assert marked is not None
    red = tr.reduce(trace, in_flight=[(marked[0], marked[1])])
    assert 0 < red["busy_s"] <= red["window_s"] + 1e-9
    assert abs(red["window_s"] - (marked[1] - marked[0])) < 1e-9
    idle = sum(red["idle_seconds"].values())
    assert abs(idle - (red["window_s"] - red["busy_s"])) < 1e-6
    # operations of one chip run one after the other, so their own times
    # add up to the busy time; a CPU trace's run side by side on threads
    ops = sum(red["op_seconds"].values())
    assert red["busy_s"] - 1e-6 <= ops
    if "TPU" in trace["devices"][0]["name"]:
        assert ops < 1.05 * red["busy_s"]
    rounds = [m for m in red["modules"]
              if "decode_multi" in m["name"] or "ragged_round" in m["name"]]
    assert rounds, [m["name"] for m in red["modules"]][:10]
    assert any(m.get("call") for m in rounds)
    assert "no request in flight" not in red["idle_seconds"]


def test_recorded_tpu_trace_numbers():
    """0.2 s of `mistral-7b-int8.chat` on a TPU v5 lite (my chip run, PR
    22): four one-step decode scans and one of four steps, every one
    11.3 ms a step; the chip 88 % busy; the int8 matmul kernel first among
    the operations, under the short name the reduction gives it."""
    path = spec.TESTDATA / "trace_tpu.xplane.pb.gz"
    red = tr.reduce(tr.load(str(path)))
    assert red["devices"] == 1 and abs(red["window_s"] - 0.2) < 1e-9
    assert abs(red["busy_s"] - 0.17658) < 1e-4
    scans = [m for m in red["modules"] if "decode_multi" in m["name"]]
    assert [int(m["steps"]) for m in scans] == [1, 1, 1, 1, 4]
    for m in scans:
        assert abs(m["seconds"] / int(m["steps"]) - 0.01128) < 5e-5
        assert int(m["decode_rows"]) >= 1
    ops = tr.breakdown(red)["device_ops"]
    assert ops[0][0] == "qmm_stacked_pallas.90" and " = " not in ops[0][0]
    assert red["collective_s"] == 0.0
    assert tr.short_name("%fusion.1 = bf16[8]{0} fusion(...)") == "fusion.1"
