"""What the many-expert configuration brought: its plain reference against
the program on the tiny configuration, the byte and operation counts of a
routed expert layer by hand, and the four readers on hand-made runs (and
on a program without the counters or the kernel: nothing)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import layers, reference_sparse, shapes_moe, spec

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.models.loader import (
    init_quantized_streamed,
)

PUBLISHED = dict(spec.PUBLISHED_KEYS, num_experts="num_experts",
                 norm_topk_prob="norm_topk_prob")


def published(mc):
    return {key: getattr(mc, attr) for key, attr in PUBLISHED.items()}


def test_reference_matches_forward_chunk_on_the_tiny_configuration():
    """float32 activations over the same int8 weights: float32 rounding
    over two layers (2e-6 measured), far under the 0.3 a renormalised or
    norm-less block is off by."""
    mc = get_model_config("olmoe-tiny")
    params = init_quantized_streamed(mc, "int8", seed=0)
    f32 = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)
    cfg = published(mc)
    ours = reference_sparse.SeedStream(cfg, 0)
    theirs = reference_sparse.FromTree(params)
    for layer in range(mc.num_layers):
        a, b = ours.layer(layer), theirs.layer(layer)
        assert set(a) == set(b)
        assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
                   for k in a)
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(4, 260, n)] for n in (5, 20)]
    want = reference_sparse.last_logits(cfg, ours, prompts)
    for p, w in zip(prompts, want):
        n = len(p)
        out = llama.forward_chunk(
            mc, f32, jnp.asarray([p]), jnp.arange(n)[None],
            llama.init_kv_pools(mc, 8, 16, jnp.float32),
            jnp.asarray([[1, 2, 3, 4]]), jnp.asarray([n]), block_size=16)
        assert np.abs(np.asarray(out.logits[0, 0]) - w).max() < 1e-4


def test_routed_layer_counts_by_hand():
    cfg = {"hidden_size": 8, "intermediate_size": 4, "num_hidden_layers": 2,
           "num_experts": 16, "num_experts_per_tok": 4}
    # gate and up 8x4, down 4x8: 96 int8 bytes; 4 + 4 + 8 scales of 4 bytes
    assert shapes_moe.expert_bytes(cfg) == 96 + 64
    # 3 experts' weights; 5 pairs, each a row of 8 bf16 in and one out
    assert shapes_moe.routed_layer_bytes(cfg, 3, 5) == 3 * 160 + 5 * 32
    assert shapes_moe.routed_layer_flops(cfg, 5) == 5 * 3 * 2 * 32
    # one row chooses 4 of 16; two rows 16 (1 - (3/4)^2) = 7
    assert shapes_moe.expected_active_experts(cfg, 1) == pytest.approx(4.0)
    assert shapes_moe.expected_active_experts(cfg, 2) == pytest.approx(7.0)


def test_published_sizes_of_the_real_configuration():
    cfg = spec.load_config(spec.BENCH / "configs" / "olmoe-1b-7b-int8.json")
    assert shapes_moe.dims(cfg) == {"h": 2048, "i": 1024, "L": 16, "E": 64,
                                    "k": 8}
    assert shapes_moe.expert_bytes(cfg) == 3 * 2048 * 1024 + 4 * 4096
    # 8 rows of a scan step reach about 42 of the 64 experts
    assert 41.5 < shapes_moe.expected_active_experts(cfg, 8) < 42.5


CELL = {"name": "c", "end_to_end": {"itl_p99_ms": {}}}


def reader(name):
    entry = {"name": name, "moves": "itl_p99_ms"}
    return layers.readers(dict(CELL, per_layer=[entry]))[0][1]


def window(engine0, engine1, batcher0=None, batcher1=None):
    ends = lambda e, b: {"engine": e, "batcher": b or {}, "direct": {}}
    return {"w0": 100.0, "w1": 150.0,
            "c0": ends(engine0, batcher0), "c1": ends(engine1, batcher1)}


CONFIG = {"hidden_size": 2048, "intermediate_size": 1024,
          "num_hidden_layers": 16, "num_experts": 64,
          "num_experts_per_tok": 8}
BEFORE = {"moe_layer_calls_scan": 160, "moe_active_experts_scan": 4000,
          "moe_assignments_scan": 5000, "moe_rows_dispatched_scan": 60000}
AFTER = {"moe_layer_calls_scan": 1760, "moe_active_experts_scan": 4000 + 51200,
         "moe_assignments_scan": 5000 + 64000,
         "moe_rows_dispatched_scan": 60000 + 640000}
NO_COUNTERS = window({"rounds": 1}, {"rounds": 9})


def test_active_expert_share_and_padding_share_read_the_scan_counters():
    run = {"win": window(BEFORE, AFTER), "config": CONFIG}
    # 51,200 active over 1,600 calls of 64 experts; 64,000 pairs in 640,000
    assert reader("moe.active_expert_share")(run) == pytest.approx(50.0)
    assert reader("moe.dispatch_padding_share")(run) == pytest.approx(90.0)
    for name in ("moe.active_expert_share", "moe.dispatch_padding_share"):
        assert reader(name)({"win": NO_COUNTERS, "config": CONFIG}) is None


MODULES = [
    {"name": "jit_decode_multi(1)", "seconds": 0.1, "steps": 16},
    {"name": "jit_decode_multi(1)", "seconds": 0.03, "steps": "4"},
    {"name": "jit_ragged_round(9)", "seconds": 0.05, "widest_piece": 200},
]
OPS = {"dgi_moe_gmm_step.3": 0.05, "dgi_moe_gmm_step.4": 0.03,
       "dgi_moe_gmm.7": 0.5, "dgi_qmm.1": 0.2}


def test_scan_step_ms_counts_the_step_kernel_and_not_the_round_kernel():
    run = {"trace": {"op_seconds": OPS, "modules": MODULES}}
    assert reader("kernels.moe_scan_step_ms")(run) == pytest.approx(4.0)
    old = {"trace": {"op_seconds": {"fusion.1": 1.0}, "modules": MODULES}}
    assert reader("kernels.moe_scan_step_ms")(old) is None
    assert reader("kernels.moe_scan_step_ms")({"trace": None}) is None


def test_gmm_roofline_is_needed_bytes_over_the_kernels_time():
    read = reader("kernels.moe_gmm_roofline")
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    modules = [dict(m, decode_rows=5) for m in MODULES]
    run = {"win": window(BEFORE, AFTER), "config": CONFIG, "peaks": peaks,
           "notes": {}, "trace": {"op_seconds": OPS, "modules": modules}}
    # the window: 32 experts a call at 64,000 / 8 / 1,600 = 5 rows a step;
    # the slice's 20 steps held 5 rows too, so 32 experts a call there
    need = 16 * (32 * (3 * 2048 * 1024 + 4 * 4096)
                 + 40 * 2 * 2048 * 2) / 819e9
    assert read(run) == pytest.approx(100.0 * need / 0.004)
    assert run["notes"]["kernels.moe_gmm_roofline"]["bound"] == "hbm"
    # a slice less busy than the window (2 rows a step) reads fewer experts a call
    run["trace"]["modules"] = [dict(m, decode_rows=2) for m in MODULES]
    fewer = shapes_moe.expected_active_experts(CONFIG, 2) \
        / shapes_moe.expected_active_experts(CONFIG, 5)
    need = 16 * (32 * fewer * (3 * 2048 * 1024 + 4 * 4096)
                 + 16 * 2 * 2048 * 2) / 819e9
    assert read(run) == pytest.approx(100.0 * need / 0.004)
    # a busier slice is not carried up: the window's count stands
    run["trace"]["modules"] = [dict(m, decode_rows=8) for m in MODULES]
    need = 16 * (32 * (3 * 2048 * 1024 + 4 * 4096)
                 + 40 * 2 * 2048 * 2) / 819e9
    assert read(run) == pytest.approx(100.0 * need / 0.004)
    assert read(dict(run, win=NO_COUNTERS)) is None
    assert read(dict(run, trace=None)) is None
