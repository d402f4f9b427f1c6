"""FLOP and byte counts against hand counts for mistral-7b."""

import json

from harness import shapes, spec


def load(name):
    return spec.load_config(spec.BENCH / "configs" / f"{name}.json")


def test_mistral_7b_hand_counts():
    cfg = load("mistral-7b-int8")
    lp = shapes.layer_params(cfg)
    # attention: wq 4096x4096, wk and wv 4096x1024, wo 4096x4096
    assert lp["attn"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 == 41_943_040
    assert lp["mlp_active"] == lp["mlp_stored"] == 3 * 4096 * 14336 \
        == 176_160_768
    # 32 layers (with two norms each), embedding, head, final norm
    assert shapes.total_params(cfg) == 32 * (218_103_808 + 8192) \
        + 2 * 32000 * 4096 + 4096 == 7_241_732_096
    w = shapes.weight_bytes(cfg)
    scales = 4 * (4096 + 1024 + 1024 + 4096 + 14336 + 14336 + 4096)
    assert w["layers"] == 32 * (218_103_808 + scales + 2 * 4096 * 2)
    assert w["head"] == 32000 * 4096 * 2 + 4096 * 2
    assert shapes.kv_bytes_per_token(cfg) == 2 * 32 * 8 * 128 * 2 == 131_072
    b = shapes.decode_step_bytes(cfg, rows=8, mean_ctx=200)
    # 200 tokens of context are 13 pages of 16
    assert b["kv_read"] == 8 * 208 * 131_072
    assert 7.45e9 < b["total"] < 7.48e9
    # a 256-wide round of 8 rows: 2,048 positions through every layer, and
    # a head row per sequence: 28.6 TFLOP of dense work
    assert shapes.dispatched_positions_flops(cfg, 8, 256) \
        == 2048 * 32 * 2 * 218_103_808 + 8 * 2 * 32000 * 4096
    # one live 256-token piece: 2 x 6.98 G x 256 plus attention and a head row
    f = shapes.prefill_flops(cfg, 256, 0, 1)
    attn = 32 * 4 * 32 * 128 * (256 * 257 / 2)
    assert f == 32 * 256 * 2 * 218_103_808 + attn + 2 * 32000 * 4096


def test_window_bounds_the_context_read():
    cfg = dict(load("mistral-7b-int8"), sliding_window=64)
    b = shapes.decode_step_bytes(cfg, rows=1, mean_ctx=1000)
    assert b["kv_read"] == 64 * 131_072


def test_bias_gqa_and_experts():
    q = load("qwen2.5-7b-int8")
    lp = shapes.layer_params(q)
    assert lp["attn"] == 2 * 3584 * 3584 + 2 * 3584 * 512
    assert shapes.kv_bytes_per_token(q) == 2 * 28 * 4 * 128 * 2
    assert shapes.total_params(q) == 28 * (
        lp["attn"] + 3 * 3584 * 18944 + 2 * 3584 + 3584 + 2 * 512
    ) + 2 * 152064 * 3584 + 3584
    m = load("mixtral-8x7b-int8-tp4")
    lp = shapes.layer_params(m)
    assert lp["mlp_stored"] == 8 * 176_160_768
    assert lp["mlp_active"] == 2 * 176_160_768
    assert 46.6e9 < shapes.total_params(m) < 46.8e9
    per_chip = sum(shapes.weight_bytes(m, tp=4).values())
    assert 11.8e9 < per_chip < 12.0e9      # read per step; the chip also
    # holds the 0.26 GB embedding, of which a step reads eight rows
    # every stored expert is dispatched, two of eight are needed
    assert shapes.dispatched_positions_flops(m, 8, 256, tp=4) \
        > 3.5 * shapes.prefill_flops(m, 8 * 256, 0, 0, tp=4) * 0.9


def test_roofline_names_its_bound():
    peaks = json.load(open(spec.BENCH / "harness" / "peaks.json"))["TPU v5 lite"]
    r = shapes.roofline_s(1e12, 8.19e9, peaks)
    assert r["bound"] == "hbm" and abs(r["seconds"] - 0.01) < 1e-9
    assert shapes.roofline_s(197e12, 1e9, peaks)["bound"] == "mxu"
