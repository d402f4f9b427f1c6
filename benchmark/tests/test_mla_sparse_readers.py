"""The readers PR 52 added (the share of layer calls that borrowed their
selection, the shares of their rooflines the selected latent walk reaches
in scans and rounds, the full layers' selection, the held experts and the
whole step of a latent-attention model with an indexer) and
``harness/shapes_mla_sparse.py`` against hand counts at the published
widths, each on a hand-made ``run``; and what each gives for a program that
has no such counter or kernel name (every other model, the parent of that
PR): nothing."""

import json

import pytest

from harness import layers, shapes_mla, shapes_mla_sparse, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}
CONFIG = json.loads((spec.BENCH / "configs"
                     / "glm-5.2-ep16-9l-int8.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("index.shared_layer_share",
       "kernels.mla_decode_roofline.sparse_latent",
       "kernels.mla_ragged_roofline.sparse_latent",
       "kernels.index_select_roofline.latent",
       "kernels.moe_held_gmm_roofline.sparse_latent",
       "engine.decode_multi_roofline.sparse_latent")


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
OPS = {"dgi_index_score_step.3": 0.003, "dgi_index_threshold_step.5": 0.002,
       "dgi_mla_decode_selected.2": 0.05, "dgi_mla_decode_selected.7": 0.01,
       "dgi_mla_ragged_selected.4": 0.08, "dgi_moe_gmm_step.2": 0.03,
       # the dense walk's names are another model's
       "dgi_mla_decode.1": 9.0, "dgi_mla_ragged.1": 9.0, "fusion.1": 0.3}
# 20 T=4 scans of 8 rows at 20,000 cached tokens: 640 row-steps that fetch
# the pages of 14,000 tokens each
ENGINE1 = {
    "index_row_steps_scan": 640, "index_context_tokens_scan": 640 * 20000,
    "index_selected_tokens_scan": 640 * 2048, "index_dense_rows_scan": 0,
    "index_fetched_tokens_scan": 640 * 14000,
    "index_pairs_ragged": 40_000_000, "index_selected_pairs_ragged": 4_000_000,
    "index_layers_scored": 3 * (80 + 10), "index_layers_shared": 6 * (80 + 10),
    "mla_row_steps_scan": 640, "mla_context_tokens_scan": 640 * 20000,
    "mla_pairs_ragged": 40_000_000, "mla_context_tokens_ragged": 10 * 160000,
    "ragged_positions_live": 2000, "ragged_rounds": 10,
    "moe_assignments_scan": 80 * 8 * 4, "moe_active_experts_scan": 80 * 8 * 3,
    "moe_layer_calls_scan": 80 * 8,
}
ENGINE0 = {k: 0 for k in ENGINE1}


def run_of(engine1=ENGINE1, ops=OPS, config=CONFIG):
    return {"win": window({k: 0 for k in engine1}, engine1, scans=(0, 20)),
            "trace": {"modules": MODULES, "op_seconds": ops},
            "config": config, "peaks": PEAKS, "notes": {}}


def test_shapes_follow_the_published_widths():
    s = shapes_mla_sparse.dims(CONFIG)
    assert (s["L"], s["nh"], s["latent"], s["rope"]) == (9, 64, 512, 64)
    assert (s["hi"], s["di"], s["topk"]) == (32, 128, 2048)
    assert (s["full"], s["borrowing"]) == (3, 6)
    assert (s["held"], s["E"], s["lead"]) == (16, 256, 1)
    # a fetched token: 576 bf16 values in each of the NINE layers
    assert shapes_mla_sparse.selected_attention_bytes(CONFIG, 1) == 9 * 1152
    # a selected pair: 64 heads over 576 values and back over 512, a layer
    assert shapes_mla_sparse.selected_attention_flops(CONFIG, 1) \
        == 9 * 2 * 64 * (2 * 512 + 64)
    # a cached token: a 128-value bf16 key read and a float32 score written
    # in each of the THREE full layers
    assert shapes_mla_sparse.index_select_bytes(CONFIG, 1) == 3 * (256 + 4)
    assert shapes_mla_sparse.index_select_flops(CONFIG, 1) \
        == 3 * 2 * 32 * 128
    # ISSUE 52's 8.39 M int8 + (0.79 M + 0.20 M) bf16 an indexer
    assert shapes_mla_sparse.indexer_weight_bytes(CONFIG) == 3 * (
        2048 * 4096 + 4 * 4096 + 2 * 6144 * (128 + 32) + 2 * 2 * 128)
    parts = shapes_mla_sparse.decode_step_bytes(
        CONFIG, 8, 8 * 14000, 8 * 20000, 8 * 3, 8 * 4)
    assert parts["selected_latents"] == 9 * 8 * 14000 * 1152
    assert parts["index"] == 3 * 8 * 20000 * 260
    assert parts["head"] == 2 * 19360 * 6144
    # the dense parts are the latent model's, untouched
    dense = shapes_mla.step_weight_bytes(CONFIG)
    assert all(parts[k] == v for k, v in dense.items())
    # ISSUE 52: attention 150.3 MB int8 + 29.4 MB bf16 a layer
    assert dense["attention"] / 9 == pytest.approx(179.7e6, rel=0.01)
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    # the selected walk reads seven tenths of what the dense walk reads
    assert parts["selected_latents"] \
        == pytest.approx(0.7 * shapes_mla.attention_bytes(CONFIG, 8 * 20000))


def test_the_share_of_layer_calls_that_borrowed():
    assert reader("index.shared_layer_share")(run_of()) \
        == pytest.approx(100 * 6 / 9)
    # a shared layer that scored again shows
    engine = dict(ENGINE1, index_layers_scored=4 * 90,
                  index_layers_shared=5 * 90)
    assert reader("index.shared_layer_share")(run_of(engine)) \
        == pytest.approx(100 * 5 / 9)


def test_rooflines_against_hand_counts():
    run = run_of()
    # 80 window steps, 20 in the slice; a step fetches 8 x 14,000 tokens and
    # attends 8 x 2,048
    need = 9 * 8 * 14000 * 1152 / 819e9
    assert reader("kernels.mla_decode_roofline.sparse_latent")(run) \
        == pytest.approx(100 * need * 20 / 0.06)
    note = run["notes"]["kernels.mla_decode_roofline.sparse_latent"]
    assert note["bound"] == "hbm"
    assert note["fetched_tokens_a_step"] == pytest.approx(8 * 14000)
    # 2,000 selected pairs a live position, 781 live positions in the slice
    need = 9 * 2 * 64 * 1088 * 2000 * 781 / 197e12
    assert reader("kernels.mla_ragged_roofline.sparse_latent")(run) \
        == pytest.approx(100 * need / 0.08)
    need = 3 * 8 * 20000 * 260 / 819e9
    assert reader("kernels.index_select_roofline.latent")(run) \
        == pytest.approx(100 * need * 20 / 0.005)
    held = reader("kernels.moe_held_gmm_roofline.sparse_latent")(run)
    note = run["notes"]["kernels.moe_held_gmm_roofline.sparse_latent"]
    assert "kernels.moe_held_gmm_roofline" not in run["notes"]
    assert note["active_experts_a_step"] == pytest.approx(24.0)
    expert = 3 * 6144 * 2048 + 4 * (2 * 2048 + 6144)
    need = (24 * expert + 32 * 2 * 6144 * 2) / 819e9
    assert held == pytest.approx(100 * need * 20 / 0.03)
    whole = reader("engine.decode_multi_roofline.sparse_latent")(run)
    parts = run["notes"]["engine.decode_multi_roofline.sparse_latent"][
        "bytes_a_step"]
    assert parts["selected_latents"] == 9 * 8 * 14000 * 1152
    assert whole == pytest.approx(100 * parts["total"] / 819e9 * 20 / 0.20)
    for name in NEW:
        assert 0 < reader(name)(run) < 100, name


def test_a_program_without_the_counters_or_names_gives_nothing():
    """openPangu's or Keye's run (the dense walk's kernel names, no
    ``index_layers_*``), and the parent's on any cell."""
    other = {k: v for k, v in ENGINE1.items()
             if k not in ("index_layers_scored", "index_layers_shared",
                          "index_fetched_tokens_scan")}
    ops = {"dgi_mla_decode.1": 0.05, "dgi_mla_ragged.1": 0.05,
           "dgi_index_score_step.3": 0.003, "dgi_moe_gmm_step.2": 0.03}
    for name in NEW:
        assert reader(name)(run_of(other, ops)) is None, name
    # no traced slice at all: an untraced run
    bare = run_of()
    bare["trace"] = None
    for name in NEW[1:]:
        assert reader(name)(bare) is None, name
