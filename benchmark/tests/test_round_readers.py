"""The readers of the round spans' counters and of the named decode kernel,
each on a hand-made ``run``; and what each gives for a program that has no
such counter or name (the parent of the PR that added them): nothing."""

import pytest

from harness import layers, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}, "itl_p99_ms": {}}}


def reader(name):
    entry = {"name": name, "moves": "out_tok_s"}
    return layers.readers(dict(CELL, per_layer=[entry]))[0][1]


def window(engine0, engine1, batcher0=None, batcher1=None, seconds=50.0):
    ends = lambda e, b: {"engine": e, "batcher": b or {}, "direct": {}}
    return {"w0": 100.0, "w1": 100.0 + seconds,
            "c0": ends(engine0, batcher0), "c1": ends(engine1, batcher1)}


def test_round_host_ms_is_build_dispatch_and_commit_over_rounds():
    read = reader("engine.round_host_ms")
    before = {"rounds": 10, "round_build_s": 1.0, "round_dispatch_s": 0.5,
              "round_readback_s": 9.0, "round_commit_s": 0.25}
    after = {"rounds": 110, "round_build_s": 1.2, "round_dispatch_s": 0.6,
             "round_readback_s": 13.0, "round_commit_s": 0.3}
    run = {"win": window(before, after)}
    # (0.2 + 0.1 + 0.05) s over 100 rounds; the readback's wait is left out
    assert read(run) == pytest.approx(3.5)
    assert read({"win": window({"requests": 1}, {"requests": 9})}) is None


def test_between_rounds_ms_is_seconds_over_gaps():
    read = reader("batcher.between_rounds_ms")
    run = {"win": window({}, {},
                         {"between_rounds_s": 2.0, "between_rounds": 100},
                         {"between_rounds_s": 2.6, "between_rounds": 500})}
    assert read(run) == pytest.approx(1.5)
    assert read({"win": window({}, {}, {"decode_rounds": 1},
                               {"decode_rounds": 9})}) is None


def test_prefill_padding_share_is_what_the_rectangle_did_not_hold():
    read = reader("engine.prefill_padding_share")
    run = {"win": window(
        {"ragged_positions_dispatched": 2048, "ragged_positions_live": 300},
        {"ragged_positions_dispatched": 2048 * 11,
         "ragged_positions_live": 300 + 2048})}
    assert read(run) == pytest.approx(90.0)
    assert read({"win": window({"ragged_rounds": 0},
                               {"ragged_rounds": 7})}) is None


def test_raised_scan_time_share_reads_the_levels_above_the_settled_one():
    read = reader("batcher.raised_scan_time_share")
    before = {"scans_t1": 300, "scans_t4": 8, "scans_t16": 0, "scans_t64": 0,
              "scan_s_t1": 3.0, "scan_s_t4": 0.5, "scan_s_t16": 0.0,
              "scan_s_t64": 0.0}
    after = {"scans_t1": 3800, "scans_t4": 70, "scans_t16": 2, "scans_t64": 0,
             "scan_s_t1": 38.0, "scan_s_t4": 3.0, "scan_s_t16": 0.5,
             "scan_s_t64": 0.0}
    # settled at T=1 (3,500 of the window's scans): 2.5 + 0.5 s of 50 above it
    assert read({"win": window({}, {}, before, after)}) == pytest.approx(6.0)
    # settled at T=4: the T=1 scans below it are not raised ones
    at4 = dict(after, scans_t1=400, scans_t4=4000)
    assert read({"win": window({}, {}, before, at4)}) == pytest.approx(1.0)
    # no scan was raised: a reading of zero, not nothing
    flat = dict(before, scans_t1=900, scan_s_t1=9.0)
    assert read({"win": window({}, {}, before, flat)}) == 0.0
    # a window without a scan, and a program that does not count its levels
    assert read({"win": window({}, {}, before, before)}) is None
    assert read({"win": window({}, {}, {"horizon": 4.0},
                               {"horizon": 4.0})}) is None


def test_decode_attention_step_ms_finds_the_kernel_by_its_name():
    read = reader("kernels.decode_attention_step_ms")
    modules = [
        {"name": "jit_decode_multi(123)", "seconds": 0.045, "steps": 4},
        {"name": "jit_decode_multi(123)", "seconds": 0.045, "steps": "4"},
        {"name": "jit_ragged_round(9)", "seconds": 0.2, "widest_piece": 200},
        {"name": "jit_decode_multi(123)", "seconds": 0.01},   # no annotation
    ]
    ops = {"dgi_paged_decode.12": 0.006, "dgi_paged_decode.31": 0.002,
           "dgi_paged_decode_other.1": 5.0, "dgi_qmm.88": 0.05,
           "fusion.156": 0.3}
    run = {"trace": {"op_seconds": ops, "modules": modules}}
    assert read(run) == pytest.approx(1.0)              # 8 ms over 8 steps
    old = {"closed_call.31": 0.008, "qmm_stacked_pallas.88": 0.05}
    assert read({"trace": {"op_seconds": old, "modules": modules}}) is None
    assert read({"trace": None}) is None


def test_every_reader_of_the_manifest_is_a_file_with_read():
    import json

    from harness import layers

    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    for entry in manifest["per_layer"]:
        assert layers.reader_path(entry["name"]) is not None, entry["name"]


def test_a_split_metric_is_read_by_the_file_of_its_name_without_the_last_part():
    from harness import layers

    own = layers.reader_path("client.gap_p90_ms")
    assert own is not None and own.name == "client_gap_p90_ms.py"
    assert layers.reader_path("client.gap_p90_ms.tpot") == own
    assert layers.reader_path("client.no_such_ms") is None
    assert layers.reader_path("client") is None
