"""The readers PR 57 added (the state-space mixer's step and chunk kernels by
their names, their roofline shares, the chunks' padding, the whole step's
share of a model with a mixer beside attention) and ``harness/shapes_ssd.py``
against hand counts at the published widths, each on a hand-made ``run``;
and what each gives for a program that has no such counter or name (every
other model, the parent of that PR): nothing."""

import json

import pytest

from harness import layers, shapes_ssd, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}
CONFIG = json.loads((spec.BENCH / "configs"
                     / "falcon-h1-34b-pp4-18l-int8.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    entry = {"name": name, "moves": "out_tok_s"}
    return layers.readers(dict(CELL, per_layer=[entry]))[0][1]


def window(engine0, engine1, scans=(0, 0)):
    """Counters at the window's two ends; ``scans``: T=4 scans at each."""
    def ends(e, n):
        return {"engine": e, "direct": {},
                "batcher": {"scans_t1": 0, "scans_t4": n, "scans_total": 99}}
    return {"w0": 100.0, "w1": 151.0, "c0": ends(engine0, scans[0]),
            "c1": ends(engine1, scans[1])}


MODULES = [
    {"name": "jit_decode_multi(1)", "seconds": 0.06, "steps": 4,
     "decode_rows": 8},
    {"name": "jit_decode_multi(1)", "seconds": 0.24, "steps": "16",
     "decode_rows": 8},
    {"name": "jit_ragged_round(9)", "seconds": 0.05, "widest_piece": 128,
     "live_prompt_tokens": 100, "decode_rows": 7, "admission_rows": 1},
    {"name": "jit_ragged_round(9)", "seconds": 0.05, "widest_piece": 64,
     "live_prompt_tokens": 60, "decode_rows": 7, "admission_rows": 1},
    {"name": "jit_decode_multi(1)", "seconds": 0.01},       # no annotation
]
OPS = {"dgi_ssd_step.23": 0.034, "dgi_ssd_step.7": 0.006,
       "dgi_ssd_chunk.11": 0.03, "dgi_ssd_chunk.2": 0.01,
       "dgi_paged_decode.1": 0.01, "dgi_qmm.2": 0.2, "fusion.1": 0.3}
ROWS = [{"prompt_tokens": 100, "n": [400, 1]}]


def test_shapes_follow_the_published_widths():
    s = shapes_ssd.dims(CONFIG)
    assert (s["L"], s["sh"], s["sp"], s["sn"], s["sg"]) == (18, 32, 128, 256,
                                                            2)
    assert (s["ds"], s["conv"], s["Q"], s["V"]) == (4096, 5120, 128, 65280)
    # a row's state in a layer: 32 heads of 128 x 256 float32 (4.19 MB);
    # its tail: 3 rows of 5,120 bf16 values
    assert shapes_ssd.state_row_bytes(CONFIG) == 32 * 128 * 256 * 4 == 4194304
    assert shapes_ssd.tail_row_bytes(CONFIG) == 3 * 5120 * 2
    # a live row's step in a layer: both, twice, and dt x, the output, B, C
    a_row = 2 * 4194304 + 2 * 30720 + (2 * 4096 + 2 * 512) * 4
    assert shapes_ssd.ssd_step_bytes(CONFIG, 1) == a_row == 8486912
    assert shapes_ssd.ssd_step_bytes(CONFIG, 144) == 144 * a_row
    assert shapes_ssd.ssd_step_flops(CONFIG, 1) == 6 * 32 * 128 * 256
    # a chunk in a layer: B and C a group (128 x 256 each), a head's scaled
    # input and output (128 x 128 each) and decay; a segment: its state in
    # and out
    a_chunk = 4 * (2 * 2 * 128 * 256 + 32 * (2 * 128 * 128 + 1))
    assert shapes_ssd.ssd_chunk_bytes(CONFIG, 9, 8) \
        == 9 * a_chunk + 8 * 2 * 4194304
    assert shapes_ssd.ssd_chunk_flops(CONFIG, 1) \
        == 32 * 4 * 128 * 128 * 256
    # the weights of a step, by part: ISSUE 57's reckoning in bytes
    w = shapes_ssd.step_weight_bytes(CONFIG)
    mixer = (5120 * 9216 + 4 * 9216) + (4096 * 5120 + 4 * 5120) \
        + 5120 * 32 * 2 + 5 * 5120 * 2 + 4096 * 2 + 3 * 32 * 4
    assert w["mixer"] == 18 * mixer and 68.3e6 < mixer < 68.9e6
    attn = (5120 * 2560 + 4 * 2560) + 2 * (5120 * 512 + 4 * 512) \
        + (2560 * 5120 + 4 * 5120)
    assert w["attention"] == 18 * attn and 31.4e6 < attn < 31.6e6
    assert w["mlp"] == 18 * (3 * 5120 * 21504 + 4 * (2 * 21504 + 5120))
    assert w["head"] == 65280 * 5120 * 2
    assert 7.73e9 < w["mixer"] + w["attention"] + w["mlp"] < 7.76e9
    # a step of 8 rows at 300 cached tokens a row: ~9.7 GB, the states an
    # eighth of it and the mixer (weights and states) a quarter
    parts = shapes_ssd.decode_step_bytes(CONFIG, 8, 8 * 300)
    assert parts["state"] == 8 * 18 * a_row
    assert parts["kv_read"] == 8 * 300 * 18 * 2 * 4 * 128 * 2
    assert parts["kv_write"] == 8 * 18 * 2048
    assert 9.5e9 < parts["total"] < 9.9e9
    assert 0.12 < parts["state"] / parts["total"] < 0.13
    assert 0.24 < (parts["state"] + parts["mixer"]) / parts["total"] < 0.27
    flops = shapes_ssd.decode_step_flops(CONFIG, 8, 8 * 300)
    assert flops / 197e12 < parts["total"] / 819e9      # the bytes bound it


def test_the_step_kernel_by_its_name_and_the_rows_its_scans_held():
    ms, share = (reader("kernels.ssd_step_ms"),
                 reader("kernels.ssd_step_roofline"))
    # 1,000 steps in the window's 250 scans of four, 7.5 live rows a step
    # through 18 layers
    after = {"ssd_row_steps_scan": 1000 * 135}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after, (5, 255)), "config": CONFIG,
           "peaks": PEAKS}
    assert ms(run) == pytest.approx(2.0)            # 40 ms over 20 steps
    least = shapes_ssd.ssd_step_bytes(CONFIG, 135) / 819e9
    assert share(run) == pytest.approx(100 * least / 0.002)
    assert 0 < share(run) < 100
    note = run["notes"]["kernels.ssd_step_roofline"]
    assert note["bound"] == "hbm" and note["row_layer_steps_a_step"] == 135
    # a program without the counter, and one without the kernel's name
    assert share(dict(run, win=window({}, {"decode_calls": 9},
                                      (5, 255)))) is None
    bare = {"trace": {"op_seconds": {"fusion.1": 1.0}, "modules": MODULES}}
    assert ms(bare) is None and share(dict(run, **bare)) is None
    assert ms({"trace": None}) is None


def test_the_chunk_kernel_by_its_name_and_the_chunks_its_rounds_held():
    ms, share, padding = (reader("kernels.ssd_chunk_round_ms"),
                          reader("kernels.ssd_chunk_roofline"),
                          reader("ssd.chunk_padding_share"))
    # 400 rounds: an 80-token piece beside 7 decode rows by and large
    after = {"ragged_rounds": 400, "ssd_tokens_ragged": 400 * 87,
             "ssd_segments_ragged": 400 * 8, "ssd_chunks_ragged": 400 * 8}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after), "config": CONFIG, "peaks": PEAKS}
    assert ms(run) == pytest.approx(20.0)       # 40 ms over two rounds
    assert padding(run) == pytest.approx(100 * (1 - 87 / 1024))
    least = max(18 * shapes_ssd.ssd_chunk_flops(CONFIG, 8) / 197e12,
                18 * shapes_ssd.ssd_chunk_bytes(CONFIG, 8, 8) / 819e9)
    assert share(run) == pytest.approx(100 * least / 0.02)
    assert 0 < share(run) < 100
    assert run["notes"]["kernels.ssd_chunk_roofline"]["bound"] == "hbm"
    old = dict(run, win=window({}, {"ragged_rounds": 400}))
    assert share(old) is None and padding(old) is None
    bare = {"trace": {"op_seconds": {"fusion.1": 1.0}, "modules": MODULES}}
    assert ms(bare) is None and share(dict(run, **bare)) is None


def test_the_whole_step():
    whole = reader("engine.decode_multi_roofline.ssd")
    after = {"ssd_row_steps_scan": 8000 * 18}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after, (0, 250)), "config": CONFIG,
           "peaks": PEAKS, "rows": ROWS}
    # the mean context over the request's 401 decode steps: 100 + 200
    parts = shapes_ssd.decode_step_bytes(CONFIG, 8, 8 * 300.0)
    # the slice's scans took 0.3 s over 20 steps
    assert whole(run) == pytest.approx(100 * parts["total"] / 819e9 / 0.015)
    assert 0 < whole(run) < 100
    note = run["notes"]["engine.decode_multi_roofline.ssd"]
    assert note["bound"] == "hbm" and note["rows_a_step"] == 8
    assert note["mean_context"] == pytest.approx(300.0)
    # a program without the counter (every other model): nothing to read
    assert whole(dict(run, win=window({}, {"decode_calls": 9},
                                      (0, 250)))) is None
    assert whole(dict(run, trace=None)) is None


def test_the_manifest_lists_the_cell_and_its_readers():
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    cell = "falcon-h1-34b-pp4-18l-int8.decode"
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine == ["kernels.ssd_step_ms", "kernels.ssd_step_roofline",
                    "kernels.ssd_chunk_round_ms", "kernels.ssd_chunk_roofline",
                    "ssd.chunk_padding_share",
                    "engine.decode_multi_roofline.ssd"]
    assert all(layers.reader_path(name) is not None for name in mine)
    assert layers.reader_path("engine.decode_multi_roofline.ssd").name \
        == "engine_decode_multi_roofline_ssd.py"
    listed = {m["name"] for m in manifest["per_layer"]
              if cell in m.get("workloads", [])}
    assert "engine.decode_multi_roofline" not in listed
    assert {"batcher.occupancy", "engine.decode_step_ms", "device.idle_share",
            "kernels.decode_attention_step_ms",
            "startup.engine_init_s"} <= listed


def test_the_comparisons_layers_runs_and_statistic():
    """`compare_logits_ssd.py` off the chip: which layers it probes, the
    relative statistic, and that every planted fault is one of its runs."""
    import numpy as np

    import compare_logits_ssd as script

    assert script.probed_layers(CONFIG) == [0, 9, 17]
    want = [np.array([[0.0, 0.03, -0.04]])]
    got = [np.array([[0.0, 0.03, -0.03]])]
    stats = script.logit_stats(want, got)
    assert stats["rms_reference_logit"] == pytest.approx(
        (0.0025 / 3) ** 0.5)
    assert stats["rel_rms_logit_diff"] == pytest.approx(0.2)
    assert stats["argmax_agreement"] == 1.0
    assert set(script.FAILS) == {
        "state_bf16", "tail_dropped", "no_key_multiplier", "no_b_multiplier",
        "no_attention", "gate_after_norm"}
