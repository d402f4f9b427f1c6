"""The readers PR 45 added (the selection's kernels by their names, their
roofline share, the shares of the sparse model's bytes the decode and
ragged attention kernels and the whole step reach, the prefix cache's hit
share, the share of the context the selection kept) and
``harness/shapes_sparse_attn.py`` against hand counts at the published
widths, each on a hand-made ``run``; and what each gives for a program that
has no such counter or name (every other model, the parent of that PR):
nothing."""

import json

import pytest

from harness import layers, shapes_moe, shapes_sparse_attn, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}
CONFIG = json.loads((spec.BENCH / "configs"
                     / "keye-vl-2.0-30b-a3b-8l-int8.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("kv.prefix_hit_token_share", "index.selected_share",
       "kernels.index_select_step_ms", "kernels.index_select_round_ms",
       "kernels.index_select_roofline",
       "kernels.decode_attention_roofline.sparse",
       "kernels.ragged_attention_roofline.sparse",
       "kernels.moe_gmm_roofline.sparse",
       "engine.decode_multi_roofline.sparse")


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
OPS = {"dgi_index_score_step.3": 0.006, "dgi_index_threshold_step.5": 0.004,
       "dgi_index_score.1": 0.008, "dgi_index_threshold.9": 0.012,
       "dgi_paged_decode.2": 0.06, "dgi_ragged_attention.4": 0.05,
       "dgi_moe_gmm_step.2": 0.05, "fusion.1": 0.3}
# 20 T=4 scans of 8 rows at 20,000 cached tokens: 640 row-steps
ENGINE1 = {
    "index_row_steps_scan": 640, "index_context_tokens_scan": 640 * 20000,
    "index_selected_tokens_scan": 640 * 2048, "index_dense_rows_scan": 0,
    "index_pairs_ragged": 40_000_000, "index_selected_pairs_ragged": 4_000_000,
    "ragged_positions_live": 2000, "ragged_rounds": 10,
    "moe_assignments_scan": 640 * 8 * 8, "moe_active_experts_scan": 80 * 8 * 50,
    "moe_layer_calls_scan": 80 * 8,
    "kv_cache": {"prefix_hit_tokens": 190_000, "prefix_total_tokens": 200_000},
}
ENGINE0 = {k: ({"prefix_hit_tokens": 0, "prefix_total_tokens": 0}
               if k == "kv_cache" else 0) for k in ENGINE1}


def run_of(engine1=ENGINE1, ops=OPS, config=CONFIG):
    return {"win": window(ENGINE0, engine1, scans=(0, 20)),
            "trace": {"modules": MODULES, "op_seconds": ops},
            "config": config, "peaks": PEAKS, "notes": {}}


def test_shapes_follow_the_published_widths():
    s = shapes_sparse_attn.dims(CONFIG)
    assert (s["L"], s["nh"], s["nkv"], s["d"]) == (8, 32, 4, 128)
    assert (s["E"], s["k"], s["i"]) == (128, 8, 768)
    assert (s["hi"], s["di"], s["topk"]) == (16, 64, 2048)
    # a cached token a layer: a 64-value bf16 key read, a float32 score out
    assert shapes_sparse_attn.index_select_bytes(CONFIG, 1) == 8 * (128 + 4)
    assert shapes_sparse_attn.index_select_flops(CONFIG, 1) == 8 * 2 * 16 * 64
    # a selected token a layer: K and V rows of 4 heads of 128 bf16
    assert shapes_sparse_attn.selected_kv_bytes(CONFIG, 1) == 8 * 2048
    assert shapes_sparse_attn.selected_attention_flops(CONFIG, 1) \
        == 8 * 4 * 32 * 128
    # a layer's fixed weights: ISSUE 45's 18.87 M + 2.26 M + 0.26 M
    fixed = shapes_sparse_attn.layer_fixed_bytes(CONFIG)
    int8 = 2048 * 4096 * 2 + 2 * 2048 * 512 + 2048 * 1024
    assert fixed == int8 + 4 * (4096 + 1024 + 2048 + 1024) \
        + 2 * 2048 * (64 + 16 + 128)
    # an expert as OLMoE's arithmetic has it, at 3 x 2048 x 768
    moe = shapes_sparse_attn.moe_config(CONFIG)
    assert shapes_moe.expert_bytes(moe) == 3 * 2048 * 768 + 4 * (2 * 768 + 2048)
    parts = shapes_sparse_attn.decode_step_bytes(
        CONFIG, 8, 8 * 2048, 8 * 20000, 8 * 50, 8 * 64)
    assert parts["selected_kv"] == 8 * 2048 * 8 * 2048
    assert parts["index"] == 8 * 20000 * 8 * 132
    assert parts["head"] == 2 * 151936 * 2048
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    # the sparse step reads a tenth of the dense step's K/V
    assert parts["selected_kv"] * 9 < 8 * 20000 * 8 * 2048


def test_counter_readers():
    run = run_of()
    assert reader("kv.prefix_hit_token_share")(run) == pytest.approx(95.0)
    assert reader("index.selected_share")(run) == pytest.approx(10.24)


def test_kernel_times_tell_steps_from_rounds():
    run = run_of()
    # 20 annotated steps in the slice, two annotated rounds
    assert reader("kernels.index_select_step_ms")(run) \
        == pytest.approx(1e3 * 0.010 / 20)
    assert reader("kernels.index_select_round_ms")(run) \
        == pytest.approx(1e3 * 0.020 / 2)


def test_rooflines_against_hand_counts():
    run = run_of()
    # 80 window steps; 160,000 cached tokens and 16,384 selected a step
    need = 8 * 160000 * 132 / 819e9
    assert reader("kernels.index_select_roofline")(run) \
        == pytest.approx(100 * need * 20 / 0.010)
    need = 8 * 16384 * 2048 / 819e9
    assert reader("kernels.decode_attention_roofline.sparse")(run) \
        == pytest.approx(100 * need * 20 / 0.06)
    # 2,000 selected pairs a live position, 781 live positions in the slice
    need = 8 * 4 * 32 * 128 * 2000 * 781 / 197e12
    assert reader("kernels.ragged_attention_roofline.sparse")(run) \
        == pytest.approx(100 * need / 0.05)
    whole = reader("engine.decode_multi_roofline.sparse")(run)
    parts = run["notes"]["engine.decode_multi_roofline.sparse"]["bytes_a_step"]
    assert parts["selected_kv"] == 8 * 16384 * 2048
    assert whole == pytest.approx(100 * parts["total"] / 819e9 * 20 / 0.20)
    moe = reader("kernels.moe_gmm_roofline.sparse")(run)
    note = run["notes"]["kernels.moe_gmm_roofline.sparse"]
    assert note["active_experts_a_call"] == pytest.approx(50.0)
    expert = 3 * 2048 * 768 + 4 * (2 * 768 + 2048)
    need = 8 * (50 * expert + 64 * 2 * 2048 * 2) / 819e9
    assert moe == pytest.approx(100 * need * 20 / 0.05)
    for name in NEW:
        if "roofline" in name:
            assert 0 < reader(name)(run) < 100, name


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_or_names_gives_nothing(name):
    """The parent of the PR, and every model without an indexer."""
    other = json.loads((spec.BENCH / "configs"
                        / "olmoe-1b-7b-int8.json").read_text())
    engine1 = {"moe_assignments_scan": 100, "moe_active_experts_scan": 100,
               "moe_layer_calls_scan": 10, "ragged_positions_live": 2000,
               "kv_cache": {"prefix_hit_tokens": 0, "prefix_total_tokens": 0}}
    run = run_of(engine1, {"dgi_moe_gmm_step.2": 0.05, "fusion.1": 0.3},
                 config=other)
    assert reader(name)(run) is None
    assert reader(name)({**run, "trace": None}) is None
