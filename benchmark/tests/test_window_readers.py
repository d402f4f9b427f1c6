"""The readers PR 49 added (what a live row holds in the window kind's pool,
the share of prefix lookups the window kind cut back, the cached tokens a
row-step attends in a full and in a sliding layer, and the shares of the
mixed model's bytes and operations the decode and ragged attention kernels,
the held experts' step kernel and the whole step reach) and
``harness/shapes_window.py`` against hand counts at the published widths,
each on a hand-made ``run``; and what each gives for a program that has no
such counter (every other model, the parent of that PR): nothing."""

import json

import pytest

from harness import layers, shapes_window, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}
CONFIG = json.loads((spec.BENCH / "configs"
                     / "laguna-s-2.1-ep4-12l-int8.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("kv.window_resident_share", "kv.window_hit_cut_share",
       "attn.full_context_tokens_per_row",
       "attn.window_context_tokens_per_row",
       "kernels.decode_attention_roofline.mixed",
       "kernels.ragged_attention_roofline.mixed",
       "kernels.moe_held_gmm_roofline.mixed",
       "engine.decode_multi_roofline.mixed")


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
]
OPS = {"dgi_paged_decode.2": 0.06, "dgi_ragged_attention.4": 0.02,
       "dgi_moe_gmm_step.2": 0.05, "fusion.1": 0.3}
# 20 T=4 scans of 8 rows at 20,000 cached tokens: 640 row-steps
ENGINE1 = {
    "attn_row_steps_scan": 640, "attn_full_context_tokens_scan": 640 * 20000,
    "attn_window_context_tokens_scan": 640 * 512,
    "kv_window_resident_tokens_scan": 640 * 800,
    "attn_pairs_ragged_full": 40_000_000, "attn_pairs_ragged_window": 900_000,
    "ragged_positions_live": 2000, "ragged_rounds": 10,
    "moe_assignments_scan": 80 * 11 * 20, "moe_active_experts_scan": 80 * 11 * 18,
    "kv_cache": {"prefix_lookups_matched": 40, "prefix_hits_cut_by_window": 1},
}
ENGINE0 = {k: ({"prefix_lookups_matched": 0, "prefix_hits_cut_by_window": 0}
               if k == "kv_cache" else 0) for k in ENGINE1}


def run_of(engine1=ENGINE1, ops=OPS, config=CONFIG):
    return {"win": window(ENGINE0, engine1, scans=(0, 20)),
            "trace": {"modules": MODULES, "op_seconds": ops},
            "config": config, "peaks": PEAKS, "notes": {}}


def test_shapes_follow_the_published_widths():
    s = shapes_window.dims(CONFIG)
    assert (s["full_layers"], s["sliding_layers"], s["sparse_layers"]) \
        == (3, 9, 11)
    assert (s["full_heads"], s["sliding_heads"]) == (3 * 48, 9 * 72)
    assert shapes_window.kv_row_bytes(CONFIG) == 4096
    assert shapes_window.expert_bytes(CONFIG) == pytest.approx(9.44e6, 0.005)
    w = shapes_window.step_weight_bytes(CONFIG)
    # ISSUE 49's count: attention 3 x 44.2 M + 9 x 63.1 M, the dense layer
    # 113.2 M, 11 shared experts and routers, a quarter of the head
    assert w["attention"] == pytest.approx(0.704e9, 0.005)
    assert w["dense_mlp"] == pytest.approx(113.3e6, 0.005)
    assert w["shared_expert"] == pytest.approx(11 * 9.44e6, 0.005)
    assert w["router"] == 11 * 3072 * 256 * 2
    assert w["head"] == 25088 * 3072 * 2
    step = shapes_window.decode_step_bytes(
        CONFIG, 8, 8 * 19500, 8 * 512, 11 * 18, 11 * 20)
    assert step["kv_rows"] == 4096 * (3 * 8 * 19500 + 9 * 8 * 512)
    assert step["total"] == pytest.approx(5.04e9, 0.01)
    assert shapes_window.attention_flops(CONFIG, 10, 4) \
        == 4 * 128 * (144 * 10 + 648 * 4)


def test_the_counters_shares():
    run = run_of()
    assert reader("kv.window_resident_share")(run) \
        == pytest.approx(100 * 800 / 20000)
    assert reader("kv.window_hit_cut_share")(run) == pytest.approx(2.5)
    assert reader("attn.full_context_tokens_per_row")(run) == 20000
    assert reader("attn.window_context_tokens_per_row")(run) == 512


def test_the_rooflines_divide_the_mixed_models_work_by_the_kernels_time():
    run = run_of()
    steps, win_steps = 20, 80
    full, windowed = 640 * 20000 / win_steps, 640 * 512 / win_steps
    need = shapes_window.attention_kv_bytes(CONFIG, full, windowed) / 819e9
    assert reader("kernels.decode_attention_roofline.mixed")(run) \
        == pytest.approx(100 * need * steps / 0.06)
    experts = shapes_window.held_experts_bytes(CONFIG, 11 * 18, 11 * 20)
    assert reader("kernels.moe_held_gmm_roofline.mixed")(run) \
        == pytest.approx(100 * experts / 819e9 * steps / 0.05)
    live_slice = 256 + 7
    flops = shapes_window.attention_flops(
        CONFIG, 40e6 / 2000 * live_slice, 0.9e6 / 2000 * live_slice)
    assert reader("kernels.ragged_attention_roofline.mixed")(run) \
        == pytest.approx(100 * flops / 197e12 / 0.02)
    whole = reader("engine.decode_multi_roofline.mixed")(run)
    parts = run["notes"]["engine.decode_multi_roofline.mixed"]
    assert parts["rows_a_step"] == 8
    assert whole == pytest.approx(
        100 * parts["bytes_a_step"]["total"] / 819e9 * steps / 0.2)
    assert 0 < whole < 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_gives_nothing(name):
    """The parent of the PR that added them, and every other model: the
    reader returns None and does not raise."""
    bare = {"ragged_positions_live": 2000, "moe_assignments_scan": 100,
            "kv_cache": {"prefix_hit_tokens": 5}}
    run = run_of(engine1=bare)
    run["win"]["c0"]["engine"] = {"kv_cache": {}}
    assert reader(name)(run) is None
    empty = run_of(ops={})
    if name.startswith(("kernels.", "engine.")):
        empty["trace"]["modules"] = []
        assert reader(name)(empty) is None


def test_the_manifest_lists_them_for_the_new_cell():
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    cell = "laguna-s-2.1-ep4-12l-int8.sessions"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert cell in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "out_tok_s"
        assert layers.reader_path(name) is not None
    loaded = spec.load_cell(cell)
    assert {m["name"] for m in loaded["per_layer"]} >= set(NEW)
    assert len(loaded["per_layer"]) >= 21      # later PRs may append
