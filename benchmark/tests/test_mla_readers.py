"""The readers PR 31 added (the absorbed latent-attention kernel by its
names in a scan step and in a round, its roofline shares, the held experts'
grouped matmul and its share, the whole step's share, the cached tokens a
scan row read, the share of the routed pairs that fell on held experts)
and ``harness/shapes_mla.py``, each on a hand-made ``run``; and what each
gives for a program that has no such counter or name (a K/V model, the
parent of that PR): nothing."""

import json

import pytest

from harness import layers, shapes_mla, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}
CONFIG = json.loads((spec.BENCH / "configs"
                     / "openpangu-ultra-moe-718b-ep16-int8.json").read_text())
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
    {"name": "jit_ragged_round_counted(9)", "seconds": 0.09},  # not annotated
    {"name": "jit_decode_multi_counted(1)", "seconds": 0.01},  # no annotation
]
OPS = {"dgi_mla_decode.23": 0.012, "dgi_mla_decode.7": 0.002,
       "dgi_mla_ragged.11": 0.03, "dgi_mla_write.3": 0.01,
       "dgi_moe_gmm_step.2": 0.03, "dgi_moe_gmm_step.4": 0.01,
       "dgi_moe_gmm.1": 0.5,
       "dgi_paged_decode.1": 9.0, "fusion.1": 0.3}


def test_shapes_follow_the_equations_of_the_absorbed_form():
    """A cached token is 576 values a layer; every one of 128 heads scores
    over the whole row and takes the latent part: 242 operations a byte."""
    tokens = 8 * 2500
    b = shapes_mla.attention_bytes(CONFIG, tokens)
    f = shapes_mla.attention_flops(CONFIG, tokens)
    assert b == 9 * tokens * 576 * 2
    assert f == 9 * tokens * 2 * 128 * (576 + 512)
    assert f / b == pytest.approx(2 * 128 * 1088 / 1152)    # 241.8
    assert 240 < f / b < 243


def test_mla_decode_step_ms_finds_the_kernel_by_its_name():
    read = reader("kernels.mla_decode_step_ms")
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}}
    assert read(run) == pytest.approx(0.7)          # 14 ms over 20 steps
    old = {k: v for k, v in OPS.items() if not k.startswith("dgi_mla")}
    assert read({"trace": {"op_seconds": old, "modules": MODULES}}) is None
    assert read({"trace": None}) is None


def test_mla_decode_roofline_is_the_least_time_over_the_kernels():
    read = reader("kernels.mla_decode_roofline")
    before = {"mla_row_steps_scan": 100, "mla_context_tokens_scan": 200_000,
              "decode_calls": 50}
    after = {"mla_row_steps_scan": 100 + 7000,
             "mla_context_tokens_scan": 200_000 + 7000 * 2500,
             "decode_calls": 50 + 1400}     # rounds with a decode row too
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window(before, after, (5, 255)), "config": CONFIG,
           "peaks": PEAKS}
    # 1,000 steps in the window's 250 scans of four: 7 rows a step at 2,500
    # cached tokens a row; the operations set the least time
    tokens = 7 * 2500
    least = max(shapes_mla.attention_flops(CONFIG, tokens) / 197e12,
                shapes_mla.attention_bytes(CONFIG, tokens) / 819e9)
    assert read(run) == pytest.approx(100 * least / 0.0007)
    assert 0 < read(run) < 100
    note = run["notes"]["kernels.mla_decode_roofline"]
    assert note["bound"] == "mxu" and note["rows_a_step"] == 7
    assert note["context_tokens_a_step"] == tokens
    # a program without the counters, and one without the kernel's name
    assert read(dict(run, win=window({"decode_calls": 1},
                                     {"decode_calls": 9}, (5, 255)))) is None
    assert read(dict(run, trace={"op_seconds": {"fusion.1": 1.0},
                                 "modules": MODULES})) is None


def test_the_ragged_kernel_by_its_name_and_the_pairs_its_rounds_held():
    ms, share = (reader("kernels.mla_ragged_round_ms"),
                 reader("kernels.mla_ragged_roofline"))
    # 400 rounds: 120,000 live positions (a piece and seven decode rows a
    # round by and large) that held 2,000 pairs each; 20,000 cached tokens
    # of a round's rows
    after = {"mla_pairs_ragged": 240_000_000, "ragged_positions_live": 120_000,
             "mla_context_tokens_ragged": 8_000_000, "ragged_rounds": 400}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after), "config": CONFIG, "peaks": PEAKS}
    assert ms(run) == pytest.approx(15.0)       # 30 ms over two rounds
    live = 256 + 7 + 512 + 6                    # the slice's two rounds
    least = max(shapes_mla.attention_flops(CONFIG, 2000 * live) / 197e12,
                shapes_mla.attention_bytes(CONFIG, 2 * 20_000) / 819e9)
    assert share(run) == pytest.approx(100 * least / 0.03)
    assert 0 < share(run) < 100
    assert run["notes"]["kernels.mla_ragged_roofline"]["bound"] == "mxu"
    old = dict(run, win=window({}, {"ragged_positions_live": 120_000,
                                    "ragged_rounds": 400}))
    assert share(old) is None
    bare = {"trace": {"op_seconds": {"fusion.1": 1.0}, "modules": MODULES}}
    assert ms(bare) is None and share(dict(run, **bare)) is None


def test_the_held_experts_kernel_and_the_whole_step():
    step, gmm, active, padding, whole = (
        reader("kernels.moe_scan_step_ms.tok"),
        reader("kernels.moe_held_gmm_roofline"),
        reader("moe.held_active_expert_share"),
        reader("moe.dispatch_padding_share.tok"),
        reader("engine.decode_multi_roofline.latent"))
    # 1,000 steps of 8 rows over 8 expert layers: 4 pairs a layer call on
    # 2.5 of the 16 held experts, in tiles of 8 rows
    after = {"moe_layer_calls_scan": 8000, "moe_active_experts_scan": 20_000,
             "moe_assignments_scan": 32_000,
             "moe_rows_dispatched_scan": 160_000,
             "mla_row_steps_scan": 8000,
             "mla_context_tokens_scan": 8000 * 2500}
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}, "notes": {},
           "win": window({}, after, (0, 250)), "config": CONFIG,
           "peaks": PEAKS}
    assert step(run) == pytest.approx(2.0)      # 40 ms over 20 steps
    assert active(run) == pytest.approx(100 * 2.5 / 16)
    assert padding(run) == pytest.approx(80.0)
    expert = 3 * 7680 * 2048 + 4 * (2 * 2048 + 7680)
    assert shapes_mla.expert_bytes(CONFIG) == expert
    need = (20 * expert + 32 * 2 * 7680 * 2) / 819e9
    assert gmm(run) == pytest.approx(100 * need / 0.002)
    assert run["notes"]["kernels.moe_held_gmm_roofline"]["bound"] == "hbm"
    # the whole step: the slice's scans took 0.2 s over 20 steps
    parts = shapes_mla.decode_step_bytes(CONFIG, 8, 8 * 2500, 20, 32)
    assert parts["held_experts"] == pytest.approx(need * 819e9)
    assert parts["latent_rows"] == 9 * 8 * 2500 * 576 * 2
    assert 4.0e9 < parts["total"] < 4.5e9
    assert whole(run) == pytest.approx(100 * parts["total"] / 819e9 / 0.01)
    assert 0 < whole(run) < 100
    # OLMoE holds every expert and has no latent rows: nothing to read
    olmoe = dict(run, win=window({}, {"moe_layer_calls_scan": 8000},
                                 (0, 250)))
    assert gmm(olmoe) is None and whole(olmoe) is None


def test_context_tokens_per_row_and_held_pair_share_read_the_counters():
    ctx, share = (reader("mla.context_tokens_per_row"),
                  reader("moe.held_pair_share"))
    run = {"win": window(
        {"mla_row_steps_scan": 10, "mla_context_tokens_scan": 10_000,
         "moe_pairs_routed_scan": 640, "moe_assignments_scan": 64},
        {"mla_row_steps_scan": 110, "mla_context_tokens_scan": 260_000,
         "moe_pairs_routed_scan": 640 + 6400,
         "moe_assignments_scan": 64 + 400})}
    assert ctx(run) == pytest.approx(2500.0)
    assert share(run) == pytest.approx(6.25)
    # OLMoE counts its assignments and holds every expert: nothing to read
    olmoe = {"win": window({"moe_assignments_scan": 5},
                           {"moe_assignments_scan": 500})}
    assert ctx(olmoe) is None and share(olmoe) is None
