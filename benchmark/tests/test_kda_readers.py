"""The readers PR 42 added (the gated delta-rule step and chunk kernels by
their names, their roofline shares, the chunks' padding, the whole hybrid
step's share) and ``harness/shapes_kda.py`` against hand counts at the
published widths, each on a hand-made ``run``; and what each gives for a
program that has no such counter or name (every other model, the parent of
that PR): nothing."""

import json

import pytest

from harness import layers, shapes_kda, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}
CONFIG = json.loads((spec.BENCH / "configs"
                     / "kimi-linear-48b-a3b-ep8-int8.json").read_text())
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
    {"name": "jit_decode_multi_counted(1)", "seconds": 0.04, "steps": 4,
     "decode_rows": 8},
    {"name": "jit_decode_multi_counted(1)", "seconds": 0.16, "steps": "16",
     "decode_rows": 8},
    {"name": "jit_ragged_round_counted(9)", "seconds": 0.09,
     "widest_piece": 256, "live_prompt_tokens": 256, "decode_rows": 7,
     "admission_rows": 1},
    {"name": "jit_ragged_round_counted(9)", "seconds": 0.07,
     "widest_piece": 256, "live_prompt_tokens": 512, "decode_rows": 6,
     "admission_rows": 2},
    {"name": "jit_decode_multi_counted(1)", "seconds": 0.01},  # no annotation
]
OPS = {"dgi_kda_step.23": 0.024, "dgi_kda_step.7": 0.004,
       "dgi_kda_chunk.11": 0.03, "dgi_kda_chunk.2": 0.01,
       "dgi_mla_decode.1": 0.01, "dgi_moe_gmm_step.2": 0.03, "fusion.1": 0.3}


def test_shapes_follow_the_published_widths():
    s = shapes_kda.dims(CONFIG)
    assert (s["L"], s["Lk"], s["Lm"]) == (27, 20, 7)
    assert (s["kh"], s["kd"], s["taps"]) == (32, 128, 4)
    assert (s["held"], s["E"], s["V"]) == (32, 256, 20480)
    # a row's state in a layer: 32 heads of 128 x 128 float32; its tail:
    # 3 rows of 12,288 bf16 values
    assert shapes_kda.state_row_bytes(CONFIG) == 32 * 128 * 128 * 4 == 2097152
    assert shapes_kda.tail_row_bytes(CONFIG) == 3 * 12288 * 2
    # a live row's step in a layer: both, twice, and q, k, k beta, v beta,
    # g in float32, beta, the output
    a_row = 2 * 2097152 + 2 * 73728 + 5 * 4096 * 4 + 32 * 4
    assert shapes_kda.kda_step_bytes(CONFIG, 1) == a_row == 4423808
    assert shapes_kda.kda_step_bytes(CONFIG, 160) == 160 * a_row
    assert shapes_kda.kda_step_flops(CONFIG, 1) == 8 * 32 * 128 * 128
    # a chunk in a layer: 32 heads x (five 64 x 128 tiles, the 64 x 64
    # matrix, the decay) float32; a segment: its state in and out
    a_chunk = 32 * 4 * (5 * 64 * 128 + 64 * 64 + 128)
    assert shapes_kda.kda_chunk_bytes(CONFIG, 11, 8) \
        == 11 * a_chunk + 8 * 2 * 2097152
    assert shapes_kda.kda_chunk_flops(CONFIG, 1) \
        == 32 * 2 * (3 * 64 * 128 * 128 + 64 * 64 * 128)
    # the weights of a step, by part: ISSUE 42's reckoning in bytes
    w = shapes_kda.step_weight_bytes(CONFIG)
    kda = (2304 * 12288 + 4 * 12288) + (4096 * 2304 + 4 * 2304) \
        + 2 * ((2304 * 128 + 4 * 128) + (128 * 4096 + 4 * 4096)) \
        + 2304 * 32 * 2 + 4 * 12288 * 2 + (32 + 4096) * 4
    assert w["kda_attention"] == 20 * kda
    assert 39.4e6 < kda < 39.8e6
    mla = (2304 * 6144 + 4 * 6144) + (2304 * 576 + 4 * 576) \
        + (4096 * 2304 + 4 * 2304) + 32 * 512 * 256 * 2
    assert w["mla_attention"] == 7 * mla
    expert = 3 * 2304 * 1024 + 4 * (2 * 1024 + 2304)
    assert shapes_kda.expert_bytes(CONFIG) == expert
    assert w["shared_expert"] == 26 * expert
    assert w["dense_mlp"] == 3 * 2304 * 9216 + 4 * (2 * 9216 + 2304)
    assert w["router"] == 26 * (2304 * 256 * 2 + 256 * 4)
    assert w["head"] == 20480 * 2304 * 2
    # a step of 8 rows at 2,000 cached tokens a row, 8 pairs a layer on 7
    # experts: ~3.4 GB, a fifth of it the state
    parts = shapes_kda.decode_step_bytes(CONFIG, 8, 8 * 2000, 26 * 7, 26 * 8)
    assert parts["state"] == 8 * 20 * a_row
    assert parts["latent_rows"] == 7 * 8 * 2000 * 576 * 2
    assert parts["held_experts"] == 26 * 7 * expert + 26 * 8 * 2 * 2304 * 2
    assert 3.2e9 < parts["total"] < 3.6e9
    assert 0.19 < parts["state"] / parts["total"] < 0.23
    flops = shapes_kda.decode_step_flops(CONFIG, 8, 8 * 2000, 26 * 8)
    assert flops / 197e12 < parts["total"] / 819e9      # the bytes bound it


def test_the_step_kernel_by_its_name_and_the_rows_its_scans_held():
    ms, share = (reader("kernels.kda_step_ms"),
                 reader("kernels.kda_step_roofline"))
    # 1,000 steps in the window's 250 scans of four, 7.5 live rows a step
    # through 20 layers
    after = {"kda_row_steps_scan": 1000 * 150}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after, (5, 255)), "config": CONFIG,
           "peaks": PEAKS}
    assert ms(run) == pytest.approx(1.4)            # 28 ms over 20 steps
    least = shapes_kda.kda_step_bytes(CONFIG, 150) / 819e9
    assert share(run) == pytest.approx(100 * least / 0.0014)
    assert 0 < share(run) < 100
    note = run["notes"]["kernels.kda_step_roofline"]
    assert note["bound"] == "hbm" and note["row_layer_steps_a_step"] == 150
    # a program without the counter, and one without the kernel's name
    assert share(dict(run, win=window({}, {"decode_calls": 9},
                                      (5, 255)))) is None
    bare = {"trace": {"op_seconds": {"fusion.1": 1.0}, "modules": MODULES}}
    assert ms(bare) is None and share(dict(run, **bare)) is None
    assert ms({"trace": None}) is None


def test_the_chunk_kernel_by_its_name_and_the_chunks_its_rounds_held():
    ms, share, padding = (reader("kernels.kda_chunk_round_ms"),
                          reader("kernels.kda_chunk_roofline"),
                          reader("kda.chunk_padding_share"))
    # 400 rounds: a 256-token piece beside 7 decode rows by and large
    after = {"ragged_rounds": 400, "kda_tokens_ragged": 400 * 263,
             "kda_segments_ragged": 400 * 8, "kda_chunks_ragged": 400 * 11}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after), "config": CONFIG, "peaks": PEAKS}
    assert ms(run) == pytest.approx(20.0)       # 40 ms over two rounds
    assert padding(run) == pytest.approx(100 * (1 - 263 / 704))
    least = max(20 * shapes_kda.kda_chunk_flops(CONFIG, 11) / 197e12,
                20 * shapes_kda.kda_chunk_bytes(CONFIG, 11, 8) / 819e9)
    assert share(run) == pytest.approx(100 * least / 0.02)
    assert 0 < share(run) < 100
    assert run["notes"]["kernels.kda_chunk_roofline"]["bound"] == "hbm"
    old = dict(run, win=window({}, {"ragged_rounds": 400}))
    assert share(old) is None and padding(old) is None
    bare = {"trace": {"op_seconds": {"fusion.1": 1.0}, "modules": MODULES}}
    assert ms(bare) is None and share(dict(run, **bare)) is None


def test_the_whole_hybrid_step():
    whole = reader("engine.decode_multi_roofline.hybrid")
    after = {"moe_assignments_scan": 1000 * 26 * 8,
             "moe_active_experts_scan": 1000 * 26 * 7,
             "mla_row_steps_scan": 8000,
             "mla_context_tokens_scan": 8000 * 2000,
             "kda_row_steps_scan": 8000 * 20}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after, (0, 250)), "config": CONFIG,
           "peaks": PEAKS}
    parts = shapes_kda.decode_step_bytes(CONFIG, 8, 8 * 2000, 26 * 7, 26 * 8)
    # the slice's scans took 0.2 s over 20 steps
    assert whole(run) == pytest.approx(100 * parts["total"] / 819e9 / 0.01)
    assert 0 < whole(run) < 100
    note = run["notes"]["engine.decode_multi_roofline.hybrid"]
    assert note["bound"] == "hbm" and note["rows_a_step"] == 8
    # an all-latent model has no state rows: nothing to read
    latent = dict(after, kda_row_steps_scan=0)
    assert whole(dict(run, win=window({}, latent, (0, 250)))) is None


def test_the_latent_layers_and_the_held_experts_of_a_hybrid_model():
    """What `harness/shapes_mla.py` counts for every layer and under
    another key, here for 7 latent layers of 27 and 32 held experts."""
    # a cached token is one row of 512 + 64 bf16 values a latent layer
    assert shapes_kda.latent_rows_bytes(CONFIG, 1000) == 7 * 1000 * 576 * 2
    assert shapes_kda.latent_attention_flops(CONFIG, 1) \
        == 7 * 2 * 32 * (2 * 512 + 64)
    expert = 3 * 2304 * 1024 + 4 * (2 * 1024 + 2304)
    assert shapes_kda.held_experts_bytes(CONFIG, 5, 9) \
        == 5 * expert + 9 * 2 * 2304 * 2
    assert shapes_kda.held_experts_flops(CONFIG, 9) == 9 * 6 * 2304 * 1024

    decode, gmm, active = (reader("kernels.mla_decode_roofline.hybrid"),
                           reader("kernels.moe_held_gmm_roofline.hybrid"),
                           reader("moe.held_active_expert_share.hybrid"))
    # 1,000 steps of 8 rows at 2,000 cached tokens, 26 expert layers with
    # 8 pairs on 4 of the 32 held experts each
    after = {"mla_context_tokens_scan": 1000 * 8 * 2000,
             "mla_row_steps_scan": 8000, "kda_row_steps_scan": 8000 * 20,
             "moe_layer_calls_scan": 26000,
             "moe_active_experts_scan": 26000 * 4,
             "moe_assignments_scan": 26000 * 8}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after, (0, 250)), "config": CONFIG,
           "peaks": PEAKS}
    # the slice: 20 steps, 10 ms of dgi_mla_decode, 30 of dgi_moe_gmm_step
    least = shapes_kda.latent_rows_bytes(CONFIG, 16000) / 819e9
    assert decode(run) == pytest.approx(100 * least / 0.0005)
    note = run["notes"]["kernels.mla_decode_roofline.hybrid"]
    assert note["bound"] == "hbm" and note["rows_a_step"] == 8
    least = shapes_kda.held_experts_bytes(CONFIG, 104, 208) / 819e9
    assert gmm(run) == pytest.approx(100 * least / 0.0015)
    assert run["notes"]["kernels.moe_held_gmm_roofline.hybrid"][
        "active_experts_a_step"] == 104
    assert active(run) == pytest.approx(12.5)
    assert all(0 < r(run) < 100 for r in (decode, gmm, active))
    # an all-latent model (its own entries read it) and a program without
    # the counters: nothing
    latent = dict(run, win=window({}, dict(after, kda_row_steps_scan=0),
                                  (0, 250)))
    bare = dict(run, win=window({}, {"decode_calls": 9}, (0, 250)))
    for r in (decode, gmm, active):
        assert r(latent) is None and r(bare) is None
    assert decode(dict(run, trace={"op_seconds": {"fusion.1": 1.0},
                                   "modules": MODULES})) is None


def test_the_latent_kernel_of_a_hybrid_models_rounds():
    share = reader("kernels.mla_ragged_roofline.hybrid")
    # 400 rounds of 263 live positions, each over ~1,000 cached tokens of
    # its row: 190,000 pairs and 14,000 cached tokens a round
    after = {"ragged_rounds": 400, "ragged_positions_live": 400 * 263,
             "mla_pairs_ragged": 400 * 190000,
             "mla_context_tokens_ragged": 400 * 14000,
             "kda_chunks_ragged": 400 * 11}
    ops = dict(OPS, **{"dgi_mla_ragged.3": 0.006})
    run = {"trace": {"op_seconds": ops, "modules": MODULES}, "notes": {},
           "win": window({}, after), "config": CONFIG, "peaks": PEAKS}
    # the slice's two rounds held 263 + 518 live positions
    pairs = 190000 / 263 * (263 + 518)
    least = max(shapes_kda.latent_attention_flops(CONFIG, pairs) / 197e12,
                shapes_kda.latent_rows_bytes(CONFIG, 2 * 14000) / 819e9)
    assert share(run) == pytest.approx(100 * least / 0.006)
    assert 0 < share(run) < 100
    assert run["notes"]["kernels.mla_ragged_roofline.hybrid"]["bound"] \
        == "mxu"
    assert share(dict(run, win=window(
        {}, dict(after, kda_chunks_ragged=0)))) is None
    assert share(dict(run, trace={"op_seconds": OPS,
                                  "modules": MODULES})) is None


def test_split_entries_of_this_cell_find_a_reader():
    """`engine.ragged_round_ms.tok` reads through `engine_ragged_round_ms`
    (the name less its last part); the `.hybrid` entries have files of
    their own and never fall back to the all-latent model's arithmetic."""
    assert layers.reader_path("engine.ragged_round_ms.tok").name \
        == "engine_ragged_round_ms.py"
    for name in ("kernels.mla_decode_roofline", "kernels.mla_ragged_roofline",
                 "kernels.moe_held_gmm_roofline",
                 "moe.held_active_expert_share",
                 "engine.decode_multi_roofline"):
        assert layers.reader_path(name + ".hybrid").name \
            == name.replace(".", "_") + "_hybrid.py"


def test_the_comparisons_order_of_work_its_layers_and_its_deficits():
    """`compare_logits_kda.py` off the chip: the rounds and steps it feeds
    (the engine's order), what it packs, which layers it probes and the
    first-token deficit it reports."""
    import numpy as np

    import compare_logits_kda as script

    # layers 1, 14 and 26 (0-based 0, 13, 25) of the 20 linear ones
    assert script.probed_layers(CONFIG) == [0, 13, 25]
    # rows of 3 and 70 prompt tokens, two fed tokens each, pieces of 32
    work = list(script.schedule([3, 70], [5, 72], 32))
    assert work[0] == (True, [(0, 0, 3), (1, 0, 32)])
    assert work[1] == (True, [(0, 3, 1), (1, 32, 32)])   # a decode row beside
    assert work[2] == (True, [(0, 4, 1), (1, 64, 6)])
    assert work[3:] == [(False, [(1, 70, 1)]), (False, [(1, 71, 1)])]
    assert sum(m for _, segs in work for *_, m in segs) == 5 + 72
    row, col, pos, last, lens = script.pack(work[1][1], 40, 2)
    assert row[:34].tolist() == [0] + [1] * 32 + [2]
    assert col[:3].tolist() == [0, 0, 1] and pos[:3].tolist() == [3, 32, 33]
    assert (pos[33:] == -1).all() and (row[33:] == 2).all()
    assert last.tolist() == [0, 32] and lens.tolist() == [4, 64]
    # the reference prefers id 2 by 0.5 over the served argmax, then agrees
    want = [np.array([[0.0, 1.0, 1.5], [3.0, 0.0, 0.0]])]
    got = [np.array([[0.0, 2.0, 1.0], [9.0, 0.0, 0.0]])]
    assert script.first_token_deficits(want, got).tolist() == [0.5, 0.0]
