"""The readers PR 59 added (the windowed latent kernel's share of its
roofline in scans, and the `.mixed_latent` shares: the full layers' selected
walk in scans, the held experts and the whole step of a latent-attention
model of two attention kinds) and
``harness/shapes_mla_window.py`` against hand counts at the published
widths, each on a hand-made ``run``; and what each gives for a program that
has no such counter or kernel name (every other model, the parent of that
PR): nothing."""

import json

import pytest

from harness import layers, shapes_mla, shapes_mla_window, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}
CONFIG = json.loads((spec.BENCH / "configs"
                     / "dots3-note-prev-ep8-9l-int8.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("kernels.mla_window_roofline",
       "kernels.mla_decode_roofline.mixed_latent",
       "kernels.moe_held_gmm_roofline.mixed_latent",
       "engine.decode_multi_roofline.mixed_latent")


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
OPS = {"dgi_index_score_step.3": 0.006, "dgi_index_threshold_step.5": 0.002,
       "dgi_mla_decode_selected.2": 0.02, "dgi_mla_decode_selected.7": 0.01,
       "dgi_mla_ragged_selected.4": 0.03,
       "dgi_mla_window_decode.1": 0.012, "dgi_mla_window_decode.9": 0.008,
       "dgi_mla_window_ragged.6": 0.05, "dgi_moe_gmm_step.2": 0.03,
       # the dense walk's names are another model's
       "dgi_mla_decode.1": 9.0, "dgi_mla_ragged.1": 9.0, "fusion.1": 0.3}
# 20 T=4 scans of 8 rows at 20,000 cached tokens: 640 row-steps that fetch
# the pages of 14,000 tokens each in a full layer and attend 513 in a
# sliding one
ENGINE1 = {
    "index_row_steps_scan": 640, "index_context_tokens_scan": 640 * 20000,
    "index_selected_tokens_scan": 640 * 2048, "index_dense_rows_scan": 0,
    "index_fetched_tokens_scan": 640 * 14000,
    "index_pairs_ragged": 40_000_000, "index_selected_pairs_ragged": 4_000_000,
    "index_layers_scored": 3 * (80 + 10), "index_layers_shared": 0,
    "attn_row_steps_scan": 640, "attn_full_context_tokens_scan": 640 * 20000,
    "attn_window_context_tokens_scan": 640 * 513,
    "attn_pairs_ragged_full": 40_000_000,
    "attn_pairs_ragged_window": 1_000_000,
    "mla_row_steps_scan": 640, "mla_context_tokens_scan": 640 * 20000,
    "mla_pairs_ragged": 40_000_000, "mla_context_tokens_ragged": 10 * 160000,
    "ragged_positions_live": 2000, "ragged_rounds": 10,
    "moe_assignments_scan": 80 * 8 * 8, "moe_active_experts_scan": 80 * 8 * 6,
    "moe_layer_calls_scan": 80 * 8,
}


def run_of(engine1=ENGINE1, ops=OPS, config=CONFIG):
    return {"win": window({k: 0 for k in engine1}, engine1, scans=(0, 20)),
            "trace": {"modules": MODULES, "op_seconds": ops},
            "config": config, "peaks": PEAKS, "notes": {}}


def test_shapes_follow_the_published_widths():
    s = shapes_mla_window.dims(CONFIG)
    assert (s["L"], s["n_full"], s["n_sliding"], s["window"]) == (9, 3, 6, 513)
    assert (s["full"]["nh"], s["full"]["latent"], s["full"]["rope"]) \
        == (128, 512, 64)
    assert (s["sliding"]["nh"], s["sliding"]["latent"], s["sliding"]["dn"]) \
        == (64, 1024, 192)
    assert (s["hi"], s["di"], s["topk"]) == (64, 128, 2048)
    assert (s["held"], s["E"], s["lead"]) == (32, 256, 1)
    # a windowed token: 1,088 bf16 values in each of the SIX sliding layers
    assert shapes_mla_window.window_attention_bytes(CONFIG, 1) == 6 * 2176
    # a windowed pair: 64 heads over 1,088 values and back over 1,024
    assert shapes_mla_window.window_attention_flops(CONFIG, 1) \
        == 6 * 2 * 64 * (2 * 1024 + 64)
    # a fetched token: 576 bf16 values in each of the THREE full layers
    assert shapes_mla_window.selected_attention_bytes(CONFIG, 1) == 3 * 1152
    assert shapes_mla_window.selected_attention_flops(CONFIG, 1) \
        == 3 * 2 * 128 * (2 * 512 + 64)
    assert shapes_mla_window.index_select_bytes(CONFIG, 1) == 3 * (256 + 4)
    assert shapes_mla_window.index_select_flops(CONFIG, 1) == 3 * 2 * 64 * 128
    # ISSUE 59: full attention 117.3 MB int8 + 33.6 MB bf16 + a 1.3 MB gate,
    # sliding 69.5 + 41.9 + 0.7; an indexer 8.39 MB int8 + 2.0 MB bf16
    assert shapes_mla_window.attention_weight_bytes(CONFIG, "full") \
        == pytest.approx(152.2e6, rel=0.01)
    assert shapes_mla_window.attention_weight_bytes(CONFIG, "sliding") \
        == pytest.approx(112.1e6, rel=0.01)
    assert shapes_mla_window.indexer_weight_bytes(CONFIG) == 3 * (
        1024 * 8192 + 4 * 8192 + 2 * 5120 * (128 + 64) + 2 * 2 * 128)
    parts = shapes_mla_window.decode_step_bytes(
        CONFIG, 8, 8 * 14000, 8 * 513, 8 * 20000, 8 * 6, 8 * 8)
    assert parts["selected_latents"] == 3 * 8 * 14000 * 1152
    assert parts["window_latents"] == 6 * 8 * 513 * 2176
    assert parts["index"] == 3 * 8 * 20000 * 260
    assert parts["head"] == 2 * 19008 * 5120
    assert parts["held_experts"] == shapes_mla.held_experts_bytes(
        CONFIG, 8 * 6, 8 * 8)
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    # the weights a step reads whatever the router chose: ISSUE 59's nine
    # layers less the 32 routed experts a layer, and the head
    fixed = sum(shapes_mla_window.step_weight_bytes(CONFIG).values())
    assert fixed == pytest.approx(
        3 * 152.2e6 + 6 * 112.1e6 + 3 * 10.4e6 + 212.3e6
        + 8 * (23.6e6 + 2.6e6) + 194.6e6, rel=0.01)
    assert shapes_mla_window.decode_step_flops(
        CONFIG, 8, 8 * 2048, 8 * 513, 8 * 20000, 8 * 8) > 2 * 8 * 1.5e9


def test_times_and_rooflines_against_hand_counts():
    run = run_of()
    # 20 steps in the slice, 80 in the window; a step's rows attend 8 x 513 tokens a sliding layer:
    # 2 x 64 x 2,112 operations a pair against 2,176 bytes, 124 a byte: at
    # 64 heads under the chip's ridge (240), the HBM's
    need = 6 * 8 * 513 * 2176 / 819e9
    assert reader("kernels.mla_window_roofline")(run) \
        == pytest.approx(100 * need * 20 / 0.02)
    note = run["notes"]["kernels.mla_window_roofline"]
    assert note["bound"] == "hbm"
    assert note["window_tokens_a_step"] == pytest.approx(8 * 513)
    # the full layers: a step fetches 8 x 14,000 tokens and attends 8 x 2,048
    need = 3 * 8 * 14000 * 1152 / 819e9
    assert reader("kernels.mla_decode_roofline.mixed_latent")(run) \
        == pytest.approx(100 * need * 20 / 0.03)
    assert run["notes"]["kernels.mla_decode_roofline.mixed_latent"][
        "bound"] == "hbm"
    held = reader("kernels.moe_held_gmm_roofline.mixed_latent")(run)
    note = run["notes"]["kernels.moe_held_gmm_roofline.mixed_latent"]
    assert "kernels.moe_held_gmm_roofline" not in run["notes"]
    assert note["active_experts_a_step"] == pytest.approx(48.0)
    expert = 3 * 5120 * 1536 + 4 * (2 * 1536 + 5120)
    need = (48 * expert + 64 * 2 * 5120 * 2) / 819e9
    assert held == pytest.approx(100 * need * 20 / 0.03)
    whole = reader("engine.decode_multi_roofline.mixed_latent")(run)
    parts = run["notes"]["engine.decode_multi_roofline.mixed_latent"][
        "bytes_a_step"]
    assert parts["window_latents"] == 6 * 8 * 513 * 2176
    assert whole == pytest.approx(100 * parts["total"] / 819e9 * 20 / 0.20)
    for name in NEW:
        assert 0 < reader(name)(run) < 100, name


def test_a_program_without_the_counters_or_names_gives_nothing():
    """GLM-5.2's run (the selected walk's names, no pages per layer kind),
    openPangu's, and the parent's on any cell."""
    other = {k: v for k, v in ENGINE1.items() if not k.startswith("attn_")}
    ops = {k: v for k, v in OPS.items() if "window" not in k}
    for name in NEW:
        assert reader(name)(run_of(other, ops)) is None, name
    # no traced slice at all: an untraced run
    bare = run_of()
    bare["trace"] = None
    for name in NEW:
        assert reader(name)(bare) is None, name


def test_the_manifest_lists_them_for_the_one_cell_and_is_full():
    """The builder's contract allows a manifest 128 per-layer entries: these
    four are its last (the rounds' shares, the selection's and the windowed
    kernel's times wait for a `benchmark` PR that merges the families)."""
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert len(entries) <= 128
    for name in NEW:
        entry = entries[name]
        assert entry["workloads"] == ["dots3-note-prev-ep8-9l-int8.docqa"]
        assert (entry["layer"], entry["moves"], entry["source"],
                entry["unit"]) == ("kernels", "out_tok_s", "device_trace", "%")
