"""Compile the TPU kernels and the serving graph for a REAL v5e target from
the CPU sandbox.

Every other test here runs with ``JAX_PLATFORMS=cpu``, where kernel dispatch
turns the Pallas kernels off and the kernel tests run them in interpret
mode — which checks arithmetic, never whether Mosaic accepts the kernel.
``jax.experimental.topologies`` gives a compile-only v5e target with no
chip attached; lowering against it runs the real Pallas → Mosaic → XLA:TPU
pipeline, so block-shape rules, unsupported shape casts, VMEM limits and
"cannot be automatically partitioned" all surface here instead of on the
first request a TPU worker serves.

Skips only when ``libtpu`` is absent or the target cannot be made. A kernel
the compiler refuses is a failure.
"""

import functools
import importlib.util
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distributed_gpu_inference_tpu.models import llama, mla
from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.ops import attention, moe_gmm_pallas
from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
    page_write_plan,
    paged_decode_attention_fused,
    ragged_paged_attention,
    write_kv_pages_in_place,
)
from distributed_gpu_inference_tpu.ops.qmm_pallas import (
    pick_tiles,
    qmm_stacked_pallas,
)
from distributed_gpu_inference_tpu.ops.quantization import quantize_params
from distributed_gpu_inference_tpu.parallel import sharding as sh

MODELS = ("mistral-7b", "qwen2.5-7b")
# the window kind's pool of a model of mixed attention kinds, as the engine
# sizes it for 8 slots and a window of 512
WINDOW_BLOCKS = 1 + 8 * 8 * 32
# what the worker path produces: max_batch_size 8 rows, max_seq_len 2048
BATCH, CTX = 8, 2048


@pytest.fixture(scope="module")
def v5e():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — any failure to MAKE the target
        pytest.skip(f"compile-only v5e target unavailable: {exc}")
    return topo.devices


@pytest.fixture()
def tpu_dispatch(monkeypatch):
    """Kernel dispatch as it decides on a TPU backend (the one gate both
    attention and the int8 matmul read)."""
    monkeypatch.setattr(attention, "pallas_backend", lambda: True)


def _on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


_kernels = attention.pallas_kernels


def _pools(sds, cfg, block, quantized, layers=None):
    n = 1 + BATCH * (CTX // block)
    lead = () if layers is None else (layers,)
    dt = jnp.int8 if quantized else jnp.bfloat16
    pool = sds(lead + (n, cfg.num_kv_heads, block, cfg.head_dim), dt)
    scale = (
        sds(lead + (n, block, cfg.head_dim), jnp.bfloat16)
        if quantized else None
    )
    return pool, scale


# --------------------------------------------------------------------- #
# (a) each kernel alone, at the served geometry
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("block", [16, 32])
@pytest.mark.parametrize("model", MODELS)
def test_ragged_kernel_compiles(v5e, model, block, quantized):
    cfg = get_model_config(model)
    sds = _on(SingleDeviceSharding(v5e[0]))
    pool, scale = _pools(sds, cfg, block, quantized)
    fn = functools.partial(
        ragged_paged_attention, block_size=block, window=cfg.sliding_window
    )
    # a decode-heavy round (narrowest chunk bucket) and a full ragged_chunk
    for s in (16, 256):
        jax.jit(fn).lower(
            sds((BATCH, s, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
            pool, pool,
            sds((BATCH, CTX // block), jnp.int32),
            sds((BATCH, s), jnp.int32),
            sds((BATCH,), jnp.int32),
            k_scale=scale, v_scale=scale,
        ).compile()


def _fused_decode_lowered(v5e, model, block, quantized):
    """``dgi_paged_decode`` alone at a model's served shape."""
    cfg = get_model_config(model)
    sds = _on(SingleDeviceSharding(v5e[0]))
    layers = 2
    pool, scale = _pools(sds, cfg, block, quantized, layers)
    new = sds((BATCH, 1, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    fn = functools.partial(
        paged_decode_attention_fused, block_size=block,
        window=cfg.sliding_window,
    )
    return jax.jit(fn).lower(
        sds((BATCH, 1, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        new, new, pool, pool, sds((), jnp.int32),
        sds((BATCH, CTX // block), jnp.int32),
        sds((BATCH, 1), jnp.int32),
        sds((BATCH,), jnp.int32),
        k_scale=scale, v_scale=scale,
    )


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("block", [16, 32])
@pytest.mark.parametrize("model", MODELS)
def test_fused_decode_kernel_compiles(v5e, model, block, quantized):
    _fused_decode_lowered(v5e, model, block, quantized).compile()


def _mosaic_text(lowered):
    """The Mosaic module of the one Pallas kernel a lowered program calls,
    as text (the call carries it as MLIR bytecode)."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    (body,) = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                         lowered.as_text())
    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return str(ir.Module.parse(base64.b64decode(body)))


# the forms of the decode kernel's walk that share the one wait a group:
# name: (pool dtype, window, under a selection, tokens of a narrow and of
# the served group)
DECODE_WALKS = {
    "dense": (jnp.bfloat16, None, False, (256, 512)),
    "window": (jnp.bfloat16, 4096, False, (256, 512)),
    "selection": (jnp.bfloat16, None, True, (512, 2048)),
    "int8": (jnp.int8, None, False, (256, 512)),
    "fp8": (jnp.float8_e4m3fn, None, False, (256, 512)),
}


@pytest.mark.parametrize("walk", DECODE_WALKS)
def test_decode_kernel_waits_once_a_group_at_any_group_width(v5e, walk,
                                                             monkeypatch):
    """``dgi_paged_decode`` at the sparse model's served shape (8 rows of
    24,576 positions, 4 K/V heads) compiles in every form of its walk, and a
    wider group adds page starts but no wait: one a pool for a group's
    pages, beside the fused write's two a pool and row."""
    from distributed_gpu_inference_tpu.ops import paged_attention_pallas as pp

    dtype, window, selected, widths = DECODE_WALKS[walk]
    cfg = get_model_config(KEYE)
    sds = _on(SingleDeviceSharding(v5e[0]))
    block, layers, m = 16, 2, KEYE_CTX // 16
    quantized = dtype == jnp.int8
    pool = sds((layers, 1 + BATCH * m, cfg.num_kv_heads, block,
                cfg.head_dim), dtype)
    scale = sds((layers, 1 + BATCH * m, block, cfg.head_dim),
                jnp.bfloat16) if quantized else None
    new = sds((BATCH, 1, cfg.num_kv_heads, cfg.head_dim),
              jnp.bfloat16 if quantized else dtype)
    keep = sds((BATCH, 1, KEYE_CTX), jnp.float32) if selected else None
    waits, starts, ifs = [], [], []
    for tokens in widths:
        monkeypatch.setattr(
            pp, "_SELECTED_GROUP_TOKENS" if selected else "_GROUP_TOKENS",
            tokens)
        # a function of its own a width: jit would hand back the first trace
        fn = functools.partial(
            paged_decode_attention_fused, block_size=block, window=window)
        lowered = jax.jit(fn).lower(
            sds((BATCH, 1, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
            new, new, pool, pool, sds((), jnp.int32),
            sds((BATCH, m), jnp.int32), sds((BATCH, 1), jnp.int32),
            sds((BATCH,), jnp.int32), k_scale=scale, v_scale=scale,
            keep=keep)
        assert _kernels(lowered) == {"dgi_paged_decode"}
        lowered.compile()
        text = _mosaic_text(lowered)
        waits.append(text.count("tpu.wait_dma2"))
        starts.append(text.count("tpu.enqueue_dma"))
        ifs.append(text.count("scf.if"))
    pools = 4 if quantized else 2
    assert waits == [2 * pools * BATCH + pools] * 2, (waits, starts)
    # the kernel traces a start a site and a branch a phase (PR 56), and
    # Mosaic still gets them a page and a row: two sites of a group's page
    # starts (a selection's: a rolled loop of runs of eight) beside the
    # write's two a pool and row; the write's five phases a row beside the
    # eight branches of a cell
    assert starts == [
        pools * (2 * (pp._SELECTED_UNROLL if selected else tokens // block)
                 + 2 * BATCH) for tokens in widths], starts
    assert ifs == [5 * BATCH + 8] * 2, ifs


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_kernel_unrolls_its_rows_and_page_starts_at_lowering(
        v5e, quantized):
    """``dgi_paged_decode`` at Mistral's served shape (8 rows, 32 pages a
    group): its body's loops over rows and pages are ``fori_loop(...,
    unroll=True)``, traced once, and the module Mosaic gets holds every copy
    and branch with a constant index, as it did when Python unrolled them.
    A loop left rolled here would run the scalar core's descriptors behind
    a counter (PR 50 read 4 % of the kernel for that)."""
    text = _mosaic_text(
        _fused_decode_lowered(v5e, "mistral-7b", 16, quantized))
    pools = 2 if quantized else 1       # a scale pool beside K and beside V
    assert {op: text.count(op) for op in (
        "tpu.enqueue_dma", "tpu.wait_dma2", "scf.if", "scf.for")} == {
        # 128 of the walk's two ``group_dma`` sites, 32 of the write
        "tpu.enqueue_dma": 160 * pools,
        # 2 of the walk's one wait a pool, 32 of the write
        "tpu.wait_dma2": 34 * pools,
        "scf.if": 48,
        # ``next_chunk``'s search for the next row with a group, no other
        "scf.for": 1,
    }


@pytest.mark.parametrize("block", [16, 32])
@pytest.mark.parametrize("model", MODELS + ("olmoe-1b-7b",))
def test_page_write_kernel_compiles(v5e, model, block):
    """The in-place page write at the rectangles the ladder gives it (64,
    256) and at a whole-prompt chunk, whose span takes several tiles."""
    cfg = get_model_config(model)
    sds = _on(SingleDeviceSharding(v5e[0]))
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    pool, _ = _pools(sds, cfg, block, False, layers=2)

    def write(k, v, k_pool, v_pool, layer, tables, positions):
        plan = page_write_plan(tables, positions, block, hkv * block * d * 2)
        return write_kv_pages_in_place(
            k.reshape(-1, hkv, d), v.reshape(-1, hkv, d), k_pool, v_pool,
            layer, plan)

    for s in (64, 256, CTX):
        new = sds((BATCH, s, hkv, d), jnp.bfloat16)
        lowered = jax.jit(write, donate_argnums=(2, 3)).lower(
            new, new, pool, pool, sds((), jnp.int32),
            sds((BATCH, CTX // block), jnp.int32), sds((BATCH, s), jnp.int32),
        )
        assert _kernels(lowered) == {"dgi_paged_write"}
        lowered.compile()


# the four one-chip configurations of the benchmark's cells
ONE_CHIP = MODELS + ("olmoe-1b-7b", "openpangu-ultra-moe-718b-ep16")


def _qmm_shapes(cfg):
    """Every (K, N) a layer of ``cfg`` sends through ``dgi_qmm``: the int8
    projections whose widths tile (openPangu's 7680 x 576 ``wkv_a`` does
    not and stays on XLA's path; routed experts take ``dgi_moe_gmm``)."""
    if cfg.latent_kv:
        shapes = {
            shape
            for group, _ in mla.layer_groups(cfg)
            for shape, _, kind in mla.leaf_specs(cfg, group).values()
            if kind == "q" and len(shape) == 2
        }
    else:
        h, i = cfg.hidden_size, cfg.intermediate_size
        q_out, kv_out = (cfg.num_heads * cfg.head_dim,
                         cfg.num_kv_heads * cfg.head_dim)
        # wq, wk/wv, wo and, in a dense layer, w_gate/w_up, w_down
        shapes = {(h, q_out), (h, kv_out), (q_out, h)}
        if not cfg.num_experts:
            shapes |= {(h, i), (i, h)}
    return sorted(kn for kn in shapes if pick_tiles(*kn) is not None)


@pytest.mark.parametrize("m", [16, 144, 256])   # 256: the widest row count
@pytest.mark.parametrize("model", ONE_CHIP)
def test_qmm_kernel_compiles(v5e, model, m):
    """Mosaic accepts the block ``pick_tiles`` gives every projection of
    the configuration, and its VMEM, at a decode step's rows, a ragged
    round's and the row gate's bound."""
    sds = _on(SingleDeviceSharding(v5e[0]))
    shapes = _qmm_shapes(get_model_config(model))
    assert shapes

    def all_projections(xs, ws, idx):
        return [
            qmm_stacked_pallas(x, w["qw"], w["scale"], idx)
            for x, w in zip(xs, ws)
        ]

    xs = [sds((m, k), jnp.bfloat16) for k, _ in shapes]
    ws = [{"qw": sds((2, k, n), jnp.int8),
           "scale": sds((2, 1, n), jnp.float32)} for k, n in shapes]
    jax.jit(all_projections).lower(xs, ws, sds((), jnp.int32)).compile()


@pytest.mark.parametrize("k,n,want", [
    (2048, 1024, (2048, 1024)), (1024, 2048, (1024, 2048)),     # OLMoE
    (7680, 2048, (3840, 512)), (2048, 7680, (2048, 512)),       # openPangu
])
def test_expert_kernel_keeps_its_blocks(k, n, want):
    """``dgi_moe_gmm`` shares ``block_tiles`` with ``dgi_qmm`` under a
    budget of its own: the cells' expert shapes tile as they did before
    ``dgi_qmm``'s rule changed (``kernels.moe_*`` are read against them)."""
    assert moe_gmm_pallas.weight_tiles(k, n) == want


@pytest.mark.parametrize("rows", [BATCH, 128])
@pytest.mark.parametrize("model", [
    "olmoe-1b-7b", "openpangu-ultra-moe-718b-ep16",
    "kimi-linear-48b-a3b-ep8"])
def test_expert_step_kernel_compiles(v5e, model, rows):
    """``dgi_moe_gmm_step`` at the three sparse models' published widths
    (64 experts of 2048 x 1024, 32 held of 2304 x 1024, 16 held of 7680 x
    2048): the engine's 8 rows and the most the step form takes (128), the
    three weight blocks of a grid step with their bf16 copies inside the
    VMEM the call asks for, the grid's first bound read at run time."""
    cfg = get_model_config(model)
    e = cfg.num_held_experts or cfg.num_experts
    h = cfg.hidden_size
    i = cfg.moe_intermediate_size or cfg.intermediate_size
    k = cfg.num_experts_per_tok
    sds = _on(SingleDeviceSharding(v5e[0]))
    w = lambda kk, n: {"qw": sds((2, e, kk, n), jnp.int8),  # noqa: E731
                       "scale": sds((2, e, 1, n), jnp.float32)}

    def layer(x, stacked, idx, experts, topv, live):
        plan = moe_gmm_pallas.step_plan(
            experts, topv, live, e, moe_gmm_pallas.step_rows(rows, x.dtype))
        return moe_gmm_pallas.routed_step(
            x, {}, stacked, idx, plan, jax.nn.silu)

    assert moe_gmm_pallas.takes_step_form(rows, jnp.bfloat16,
                                          {"we_gate": w(h, i)})
    jax.jit(layer).lower(
        sds((rows, h), jnp.bfloat16),
        {"we_gate": w(h, i), "we_up": w(h, i), "we_down": w(i, h)},
        sds((), jnp.int32), sds((rows, k), jnp.int32),
        sds((rows, k), jnp.float32), sds((rows, k), jnp.bool_),
    ).compile()


# --------------------------------------------------------------------- #
# (b) the whole serving graph, one chip and a model=4 mesh
# --------------------------------------------------------------------- #

def _forward_chunk_lowered(cfg, s, mesh, devices, tp=None, ctx=CTX,
                           quantized_kv=False):
    """``forward_chunk`` for int8 ``cfg`` at [BATCH, s], lowered for one
    device (``mesh=None``) or sharded over ``mesh`` by the engine's own
    rules, with ``pallas`` and ``heads`` set the way
    ``TPUEngine._build_jit_fns`` sets them. ``tp``: the plain ragged
    round's form, ``tp`` live tokens packed on one axis with [BATCH, s] the
    rectangle attention sees. ``ctx``: tokens of context a row's block
    table and the pool hold. ``quantized_kv``: int8 pools."""
    params = jax.eval_shape(
        lambda: quantize_params(
            llama.init_params(cfg, jax.random.PRNGKey(0)), "int8"
        )
    )
    kinds = 2 if cfg.mixed_attention else 1
    kv = jax.eval_shape(
        lambda: llama.init_kv_pools(
            cfg, 1 + BATCH * (ctx // 16), 16,
            dtype=jnp.int8 if quantized_kv else None,
            state_rows=BATCH if cfg.num_state_layers else None,
            window_blocks=WINDOW_BLOCKS if kinds > 1 else None)
    )
    if mesh is None:
        one = SingleDeviceSharding(devices[0])
        p_sh = jax.tree.map(lambda _: one, params)
        kv_sh = jax.tree.map(lambda _: one, kv)
        rep = one
    else:
        p_sh = sh.prune_rules(sh.param_shardings(mesh), params)
        kv_sh = {name: sh.kv_scale_sharding(mesh) if name.endswith("_scale")
                 else sh.kv_sharding(mesh) for name in kv}
        rep = NamedSharding(mesh, P())
    place = lambda tree, shard: jax.tree.map(
        lambda a, s_: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s_),
        tree, shard,
    )
    sds = _on(rep)

    def step(params, kv, toks, pos, tables, lens, *where):
        out = llama.forward_chunk(
            cfg, params, toks, pos, kv, tables, lens, block_size=16,
            pallas=mesh is None, heads=sh.head_shards(mesh),
            packing=llama.Packing(*where, s) if where else None,
        )
        return out.logits, out.kv

    tokens = (BATCH, s) if tp is None else (tp,)
    where = () if tp is None else (
        sds((tp,), jnp.int32), sds((tp,), jnp.int32),
        sds((BATCH,), jnp.int32),
    )
    return jax.jit(step, donate_argnums=(1,)).lower(
        place(params, p_sh), place(kv, kv_sh),
        sds(tokens, jnp.int32), sds(tokens, jnp.int32),
        sds((BATCH, kinds * (ctx // 16)), jnp.int32),
        sds((BATCH,), jnp.int32), *where,
    )


@pytest.mark.parametrize("s", [1, 256])
def test_forward_chunk_compiles_one_chip(v5e, tpu_dispatch, s):
    lowered = _forward_chunk_lowered(
        get_model_config("mistral-7b"), s, None, v5e
    )
    found = _kernels(lowered)
    if s == 1:
        # a decode step: fused write+attend, projections through the int8
        # kernel
        assert found == {"dgi_paged_decode", "dgi_qmm"}, found
    else:
        # a ragged round at the full chunk: 8 x 256 rows is past the int8
        # kernel's bandwidth-bound regime, so projections take the XLA path
        assert found == {"dgi_paged_write", "dgi_ragged_attention"}, found
    lowered.compile()


@pytest.mark.parametrize("tp,s,qmm", [(128, 128, True), (264, 256, False)])
def test_packed_forward_chunk_compiles_one_chip(v5e, tpu_dispatch, tp, s,
                                                qmm):
    """The plain ragged round's graph: ``tp`` live tokens on one axis, the
    ragged kernel over the [8, s] rectangle. Up to 256 packed rows the
    projections run through the int8 kernel, past it on the XLA path."""
    lowered = _forward_chunk_lowered(
        get_model_config("mistral-7b"), s, None, v5e, tp=tp
    )
    assert _kernels(lowered) == {"dgi_paged_write", "dgi_ragged_attention"} \
        | ({"dgi_qmm"} if qmm else set())
    lowered.compile()


# TPUEngine._ragged_ladder at the worker's geometry: (Tp, the rectangle's S)
LADDER = ((64, 64), (128, 128), (264, 256), (528, 256), (2048, 256))


@pytest.mark.parametrize("tp,s", LADDER, ids=[f"Tp{t}" for t, _ in LADDER])
@pytest.mark.parametrize("model", MODELS + ("olmoe-1b-7b",))
def test_packed_round_moves_no_pool_layer(v5e, tpu_dispatch, model, tp, s):
    """Every rung of the ladder, every one-chip configuration: the round
    writes its pages into the stacked pools and reads them there. The
    compiled program holds no array of a pool layer's shape — no slice out
    of the stack, no copy between the scatter's layout and the kernel's,
    no write-back — and, up to the rungs whose own activations are
    smaller, less temporary memory than one layer's K."""
    cfg = get_model_config(model)
    lowered = _forward_chunk_lowered(cfg, s, None, v5e, tp=tp)
    assert {"dgi_paged_write", "dgi_ragged_attention"} <= _kernels(lowered)
    compiled = lowered.compile()
    blocks = 1 + BATCH * (CTX // 16)
    layer = f"[{blocks},{cfg.num_kv_heads},16,{cfg.head_dim}]"
    assert f"[{cfg.num_layers},{blocks}," in compiled.as_text()
    assert layer not in compiled.as_text()
    if tp <= 528:       # at 2048 the MLP's own [Tp, I] temporaries are more
        assert compiled.memory_analysis().temp_size_in_bytes \
            < blocks * cfg.num_kv_heads * 16 * cfg.head_dim * 2


@pytest.mark.parametrize("tp,s,qmm", [(None, 1, True), (128, 128, True),
                                      (264, 256, False)])
def test_olmoe_graphs_compile_one_chip(v5e, tpu_dispatch, tp, s, qmm):
    """OLMoE at its published widths (64 experts of 1024, top-8, 16 KV
    heads): a decode step and the packed round. The expert layer is the
    grouped-matmul kernel over the stacked int8 weights — named apart in a
    decode step, so that a trace tells a scan's expert time from a ragged
    round's — and the attention kernels take 16 KV heads inside VMEM."""
    lowered = _forward_chunk_lowered(
        get_model_config("olmoe-1b-7b"), s, None, v5e, tp=tp
    )
    want = {"dgi_paged_decode", "dgi_moe_gmm_step"} if tp is None \
        else {"dgi_paged_write", "dgi_ragged_attention", "dgi_moe_gmm"}
    assert _kernels(lowered) == want | ({"dgi_qmm"} if qmm else set())
    lowered.compile()


def test_olmoe_packed_round_holds_no_token_by_expert_tensor(v5e,
                                                            tpu_dispatch):
    """At ``Tp`` = 264 the dense einsum form needs a ``[264, 64, 2048]``
    float32 combine tensor (138 MB a layer) and bf16 copies of the expert
    weights (805 MB a layer); the routed form's temporaries are the tiled
    rows (at most 130 tiles of 32). At the served context of 2048."""
    compiled = _forward_chunk_lowered(
        get_model_config("olmoe-1b-7b"), 256, None, v5e, tp=264
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 264 * 64 * 2048 * 4


def _collectives(compiled):
    return sorted(re.findall(
        r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
        r"reduce-scatter)(?:-start)?\(", compiled.as_text()))


@pytest.mark.parametrize("model", ["mistral-7b", "mixtral-8x7b"])
def test_packed_forward_chunk_on_model4_mesh_adds_no_collective(
        v5e, tpu_dispatch, model):
    """Under the mesh the gathers between the packed axis and the
    rectangle run on replicated activations and head-sharded q/k/v, and the
    attention kernels a shard of heads a chip on pages that never cross
    chips: the compiled program holds the collectives of the rectangle
    form (the two all-reduces of a layer) and no other."""
    mesh = Mesh(np.array(v5e).reshape(4), ("model",))
    cfg = get_model_config(model)
    packed = _forward_chunk_lowered(cfg, 256, mesh, v5e, tp=264)
    assert _kernels(packed) == ROUND_KERNELS
    want = _collectives(_forward_chunk_lowered(cfg, 256, mesh, v5e).compile())
    assert want and _collectives(packed.compile()) == want
    # the two all-reduces of a layer (attention out, MLP or expert
    # combine), in the layer scan's body: what it held before the routed
    # expert layer existed, which a mesh does not take
    assert want == ["all-reduce", "all-reduce"]


# what a mesh that shards ``model`` alone takes of the kernels: attention,
# a shard of heads a chip under ``shard_map`` (a bare ``pallas_call`` has no
# partitioning rule: the projections and experts stay XLA's)
ROUND_KERNELS = {"dgi_paged_write", "dgi_ragged_attention"}


@pytest.mark.parametrize("model", ["mistral-7b", "mixtral-8x7b"])
@pytest.mark.parametrize("s", [1, 256])
def test_forward_chunk_compiles_on_model4_mesh(v5e, tpu_dispatch, s, model):
    """The attention kernels and no other, and the pools where they lie:
    no array of a chip's share of a pool layer in the compiled program (no
    slice out of the stack, no write-back), its temporaries smaller than
    that share of one layer's K."""
    mesh = Mesh(np.array(v5e).reshape(4), ("model",))
    cfg = get_model_config(model)
    lowered = _forward_chunk_lowered(cfg, s, mesh, v5e)
    assert _kernels(lowered) == (
        {"dgi_paged_decode"} if s == 1 else ROUND_KERNELS)
    compiled = lowered.compile()
    blocks, hkv = 1 + BATCH * (CTX // 16), cfg.num_kv_heads // 4
    assert f"[{cfg.num_layers},{blocks},{hkv},16," in compiled.as_text()
    assert f"[{blocks},{hkv},16,{cfg.head_dim}]" not in compiled.as_text()
    if s == 1:
        assert compiled.memory_analysis().temp_size_in_bytes \
            < blocks * hkv * 16 * cfg.head_dim * 2


@pytest.mark.parametrize("why,mesh_axes,quantized_kv", [
    ("int8 pools", (("model", 4),), True),
    ("a seq axis", (("seq", 2), ("model", 2)), False),
])
def test_forward_chunk_keeps_the_xla_paths_on_a_mesh_that(
        v5e, tpu_dispatch, why, mesh_axes, quantized_kv):
    """What ``attention_kernels`` refuses under a mesh traces no kernel at
    all, as every mesh did before."""
    names, shape = zip(*mesh_axes)
    mesh = Mesh(np.array(v5e).reshape(shape), names)
    cfg = get_model_config("mistral-7b")
    assert sh.head_shards(mesh) is None or quantized_kv
    for s in (1, 256):
        assert _kernels(_forward_chunk_lowered(
            cfg, s, mesh, v5e, quantized_kv=quantized_kv)) == set()


def test_mesh_engine_traces_no_pallas_kernel(tpu_dispatch, cpu_devices):
    """The engine — not the test — decides ``pallas=False`` under a mesh,
    and over int8 pools hands the attention kernels no heads either
    (``decode_attention`` says ``xla``). Geometry chosen so that dispatch
    WOULD pick every kernel (head_dim 128, 512-token tables, tileable int8
    projections): a mesh engine that let one through would fail to lower
    it for the CPU devices it runs on."""
    from distributed_gpu_inference_tpu.models.configs import ModelConfig
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )
    from distributed_gpu_inference_tpu.utils.data_structures import (
        InferenceRequest,
        SamplingParams,
    )

    cfg = ModelConfig(
        name="lowering-probe", vocab_size=256, hidden_size=256, num_layers=1,
        num_heads=2, num_kv_heads=2, intermediate_size=256, head_dim=128,
    )
    mesh = Mesh(np.array(cpu_devices[:2]), ("model",))
    eng = TPUEngine(
        cfg,
        EngineConfig(max_batch_size=2, max_seq_len=512, quantization="int8",
                     multi_step=4, kv_cache_dtype="int8"),
        mesh=mesh,
    )
    assert (eng.stats["decode_attention"], eng.stats["ragged_kv_path"]) \
        == ("xla", "layer_copy")
    # what chip_smoke.py reads the implementations from
    graphs = eng.lower_serving_graphs([4], [16])
    assert set(graphs) == {"decode_multi[T=4]", "ragged_round[Tp=64]",
                           "chain_sched", "chain_round[Tp=64]", "merge_core"}
    for lowered in graphs.values():
        assert _kernels(lowered) == set()
    out = eng.generate([
        InferenceRequest(
            prompt_token_ids=list(range(1, 20)),
            sampling=SamplingParams(max_new_tokens=10, temperature=0.0),
        )
    ])
    assert len(out[0].token_ids) == 10
    # three decode rounds, one program: slot state uploaded by the host and
    # slot state carried from the last round have the same (replicated)
    # sharding, so the round graph does not compile a second time
    assert eng._decode_multi_fn._cache_size() == 1


def test_a_round_behind_a_scan_takes_the_round_graphs_as_they_are():
    """PR 51: a ragged round may go out behind an unread scan. What that
    takes is BESIDE the nine round graphs (four scan lengths, five packed
    lengths at the chat cells' geometry), not in them: the small programs
    ``chain_round[Tp]`` (one a packed length) and ``merge_core``, which
    ``lower_serving_graphs`` lowers and runs and which hold no Pallas call.
    A round graph lowered from the operands a chained round hands it (the
    packed batch, the flags and the core as those programs return them) is
    the text the warm-up lowers from the host's, letter for letter, so the
    program a chained round runs is the one every other round runs. (The
    nine texts of this geometry were compared with the parent commit's when
    this was written, dense and routed: identical.)"""
    from distributed_gpu_inference_tpu.runtime.engine import (
        EngineConfig,
        TPUEngine,
    )

    eng = TPUEngine(
        get_model_config("llama3-tiny", dtype="float32"),
        EngineConfig(max_batch_size=8, max_seq_len=512, dtype="float32",
                     prefill_buckets=(16, 32, 64, 128, 256),
                     ragged_chunk=256, multi_step=4,
                     enable_prefix_cache=False), seed=0)
    graphs = eng.lower_serving_graphs([1, 4, 16, 64], [16, 32, 64, 128, 256])
    rounds = [k for k in graphs
              if k.startswith(("decode_multi[", "ragged_round["))]
    rungs = [int(k[len("ragged_round[Tp="):-1]) for k in rounds
             if k.startswith("ragged_round[")]
    assert len(rounds) == 9 and rungs == [64, 128, 264, 528, 2048]
    small = sorted(set(graphs) - set(rounds))
    assert small == sorted(["chain_sched", "merge_core"]
                           + [f"chain_round[Tp={tp}]" for tp in rungs])
    for name in small:
        assert _kernels(graphs[name]) == set(), name
        # a few hundred scalar-sized operations, nothing of the model
        assert len(graphs[name].as_text()) < 20_000, name
    # run once each, so that none compiles inside a request
    assert eng._chain_round_fn._cache_size() == len(rungs)
    assert eng._merge_core_fn._cache_size() == 1
    assert eng._chain_sched_fn._cache_size() == 1
    b = len(eng.slots)
    core = eng._sync_core()
    ci, cf = eng._pack_core()
    tables, _, flag = eng._sched_arrays(np.zeros((b,), bool),
                                        np.zeros((b,), np.int32))
    merged = eng._merge_core_fn(core, ci, cf, np.zeros((b,), bool))
    for tp in rungs:
        tok_at, lens_last, flag_d, _live = eng._chain_round_fn(
            merged, np.zeros((4, tp), np.int32), np.zeros((2, b), np.int32),
            flag, np.zeros((b, 2), np.int32))
        chained = eng._ragged_round_fn.lower(
            eng.params, eng.kv, tok_at, tables, lens_last, merged, flag_d,
            "greedy", eng._ragged_shape(tp)[1])
        assert chained.as_text() == graphs[f"ragged_round[Tp={tp}]"].as_text()


# --------------------------------------------------------------------- #
# (d) the latent-attention model: its kernels and its serving graphs
# --------------------------------------------------------------------- #

PANGU = "openpangu-ultra-moe-718b-ep16"
PANGU_CTX = 4096        # the cell serves 4096 positions


def _latent_kernel_lowered(v5e, model, ctx, s, selected):
    """The absorbed kernel alone at a configuration's served shape, ``s``
    queries a row, under a selection or not."""
    from distributed_gpu_inference_tpu.ops import mla_attention_pallas as mk

    cfg = get_model_config(model)
    sds = _on(SingleDeviceSharding(v5e[0]))
    w, m = mla.pool_width(cfg), ctx // 16
    # the function under ``jit``: the jitted entry would hand back a trace
    # made at another group width
    fn = functools.partial(
        mk.latent_paged_attention.__wrapped__, block_size=16,
        scale=cfg.qk_head_dim ** -0.5, latent=cfg.kv_lora_rank,
        decode=s == 1)
    return jax.jit(fn).lower(
        sds((BATCH, s, cfg.num_heads, w), jnp.bfloat16),
        sds((cfg.num_cache_layers, 1 + BATCH * m, 16, w), jnp.bfloat16),
        sds((), jnp.int32), sds((BATCH, m), jnp.int32),
        sds((BATCH, s), jnp.int32), sds((BATCH,), jnp.int32),
        keep=sds((BATCH, s, ctx), jnp.float32) if selected else None)


@pytest.mark.parametrize("s", [1, 16, 256])
def test_latent_kernels_compile(v5e, s):
    """The absorbed kernel (128 heads against 640-lane pool rows) and the
    one-pool page write, at the served geometry: a scan step, a narrow and
    a full rectangle."""
    from distributed_gpu_inference_tpu.ops import mla_attention_pallas as mk

    cfg = get_model_config(PANGU)
    sds = _on(SingleDeviceSharding(v5e[0]))
    w, blocks = mla.pool_width(cfg), 1 + BATCH * (PANGU_CTX // 16)
    pool = sds((cfg.num_layers, blocks, 16, w), jnp.bfloat16)
    tables = sds((BATCH, PANGU_CTX // 16), jnp.int32)

    def write(rows, pool, layer, tables, pos):
        plan = page_write_plan(tables, pos, 16, page_bytes=16 * w * 2)
        return mk.write_latent_pages_in_place(rows, pool, layer, plan)

    lowered = _latent_kernel_lowered(v5e, PANGU, PANGU_CTX, s, False)
    assert _kernels(lowered) == {
        "dgi_mla_decode" if s == 1 else "dgi_mla_ragged"}
    lowered.compile()
    jax.jit(write, donate_argnums=(1,)).lower(
        sds((BATCH * s, w), jnp.bfloat16), pool, sds((), jnp.int32), tables,
        sds((BATCH, s), jnp.int32)).compile()


@pytest.mark.parametrize("tp,s", [(None, 1), (264, 256), (2048, 256)],
                         ids=["scan-step", "Tp264", "Tp2048"])
def test_latent_model_graphs_compile_and_move_no_pool_layer(
        v5e, tpu_dispatch, tp, s):
    """openPangu's share at its published widths: a decode step and the
    packed round at a middle and the top rung. Latent pages are written
    (``dgi_mla_write``) and read by the absorbed kernel in the stacked pool;
    the held experts go through the grouped-matmul kernel; no array of a
    pool layer's shape exists, and the temporaries stay inside what the
    chip has left beside 9.3 GB of weights and the pool."""
    cfg = get_model_config(PANGU)
    lowered = _forward_chunk_lowered(cfg, s, None, v5e, tp=tp, ctx=PANGU_CTX)
    found = _kernels(lowered)
    want = {"dgi_mla_write", "dgi_mla_decode", "dgi_moe_gmm_step"} \
        if tp is None else {"dgi_mla_write", "dgi_mla_ragged", "dgi_moe_gmm"}
    assert want <= found and found <= want | {"dgi_qmm"}, found
    compiled = lowered.compile()
    blocks = 1 + BATCH * (PANGU_CTX // 16)
    text = compiled.as_text()
    assert f"[{cfg.num_layers},{blocks},16,640]" in text
    assert f"[{blocks},16,640]" not in text.replace(
        f"[{cfg.num_layers},{blocks},16,640]", "")
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 1024 ** 3


# --------------------------------------------------------------------- #
# (e) the hybrid model: the state pool's kernels and its serving graphs
# --------------------------------------------------------------------- #

KIMI = "kimi-linear-48b-a3b-ep8"


def test_state_pool_kernels_compile(v5e):
    """``dgi_kda_step`` (eight rows, 32 heads of 128 x 128 float32 in place
    in the stacked pool) and ``dgi_kda_chunk`` (the 12 chunks a one-piece
    round of 264 packed tokens is cut into), at the published widths."""
    from distributed_gpu_inference_tpu.models import kda
    from distributed_gpu_inference_tpu.ops import kda_pallas

    cfg = get_model_config(KIMI)
    sds = _on(SingleDeviceSharding(v5e[0]))
    h, d = cfg.kda_num_heads, cfg.kda_head_dim
    pool = sds((cfg.num_kda_layers, BATCH, h, d, d), jnp.float32)
    row = sds((BATCH, h, d), jnp.float32)
    lowered = jax.jit(kda_pallas.kda_step, donate_argnums=(5,)).lower(
        row, row, row, row, sds((BATCH, h), jnp.float32), pool,
        sds((), jnp.int32), sds((BATCH,), bool), sds((BATCH,), bool))
    assert _kernels(lowered) == {"dgi_kda_step"}
    lowered.compile()
    c = BATCH + 264 // kda.CHUNK
    tile = lambda *t: sds((c, h, *t), jnp.float32)        # noqa: E731
    ops = kda.ChunkOperands(
        w=tile(64, d), u=tile(64, d), qd=tile(64, d), kd=tile(64, d),
        b=tile(64, 64), dlast=tile(d))
    flags = sds((c,), bool)
    lowered = jax.jit(kda_pallas.kda_chunk_pass, donate_argnums=(1,)).lower(
        ops, pool, sds((), jnp.int32), sds((c,), jnp.int32), flags, flags,
        flags)
    assert _kernels(lowered) == {"dgi_kda_chunk"}
    lowered.compile()


@pytest.mark.parametrize("tp,s", [(None, 1), (264, 256), (2048, 256)],
                         ids=["scan-step", "Tp264", "Tp2048"])
def test_hybrid_model_graphs_compile_and_copy_no_pool(v5e, tpu_dispatch, tp,
                                                      s):
    """The hybrid share at its published widths, all 27 layers: a decode
    step and the packed round at a middle and the top rung. The state is
    read and written in place in the stacked pool by the two KDA kernels,
    the latent pages of the seven latent layers by theirs, the held experts
    go through the grouped-matmul kernel (no dequantised expert copy), no
    ``[T, 256, ...]`` temporary of the router's width exists, and no array
    of a pool layer's shape."""
    cfg = get_model_config(KIMI)
    lowered = _forward_chunk_lowered(cfg, s, None, v5e, tp=tp, ctx=PANGU_CTX)
    found = _kernels(lowered)
    want = {"dgi_mla_write", "dgi_mla_decode", "dgi_moe_gmm_step",
            "dgi_kda_step"} if tp is None else {
        "dgi_mla_write", "dgi_mla_ragged", "dgi_moe_gmm", "dgi_kda_chunk"}
    assert want <= found and found <= want | {"dgi_qmm"}, found
    compiled = lowered.compile()
    text = compiled.as_text()
    blocks = 1 + BATCH * (PANGU_CTX // 16)
    h, d = cfg.kda_num_heads, cfg.kda_head_dim
    for whole, layer in (
            (f"[{cfg.num_cache_layers},{blocks},16,640]",
             f"[{blocks},16,640]"),
            (f"[{cfg.num_kda_layers},{BATCH},{h},{d},{d}]",
             f"[{BATCH},{h},{d},{d}]")):
        assert whole in text
        assert layer not in text.replace(whole, "")
    tokens = BATCH if tp is None else tp
    mi, hid = cfg.moe_intermediate_size, cfg.hidden_size
    assert f"[{tokens},{cfg.num_experts}," not in text
    assert f"bf16[{cfg.num_held_experts},{hid},{mi}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 1024 ** 3


# --------------------------------------------------------------------- #
# (e) a state-space mixer beside attention: the state pool beside K/V pages
# --------------------------------------------------------------------- #

FALCON = "falcon-h1-34b-pp4-18l"


def test_ssd_kernels_compile(v5e):
    """``dgi_ssd_step`` (eight rows, 32 heads of 128 x 256 float32 in place
    in the stacked pool, B / C a group) and ``dgi_ssd_chunk`` (the 10
    chunks of 128 a one-piece round of 264 packed tokens is cut into), at
    the published widths."""
    from distributed_gpu_inference_tpu.models import ssd
    from distributed_gpu_inference_tpu.ops import ssd_pallas

    cfg = get_model_config(FALCON)
    sds = _on(SingleDeviceSharding(v5e[0]))
    h, p, n, g = (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
                  cfg.ssm_num_groups)
    pool = sds((cfg.num_layers, BATCH, h, p, n), jnp.float32)
    group = sds((BATCH, g, n), jnp.float32)
    lowered = jax.jit(ssd_pallas.ssd_step, donate_argnums=(5,)).lower(
        sds((BATCH, h, p), jnp.float32), group, group,
        sds((BATCH, h), jnp.float32), sds((h,), jnp.float32), pool,
        sds((), jnp.int32), sds((BATCH,), bool), sds((BATCH,), bool))
    assert _kernels(lowered) == {"dgi_ssd_step"}
    lowered.compile()
    q = cfg.ssm_chunk_size
    c = BATCH + 264 // q
    f32 = lambda *t: sds(t, jnp.float32)                      # noqa: E731
    ops = ssd.ChunkOperands(
        c=f32(c, g, q, n), b=f32(c, g, q, n), xdt=f32(c, h, p, q),
        dlast=f32(c, h), el=f32(c, h, q), intra=f32(c, h, q, p))
    flags = sds((c,), bool)
    lowered = jax.jit(ssd_pallas.ssd_chunk_pass, donate_argnums=(1,)).lower(
        ops, pool, sds((), jnp.int32), sds((c,), jnp.int32), flags, flags,
        flags)
    assert _kernels(lowered) == {"dgi_ssd_chunk"}
    lowered.compile()


@pytest.mark.parametrize("tp,s", [(None, 1), (264, 256)],
                         ids=["scan-step", "Tp264"])
def test_state_space_model_graphs_compile_and_copy_no_pool(v5e, tpu_dispatch,
                                                           tp, s):
    """The pipeline stage at its published widths, all 18 layers: a decode
    step and the packed round. The state is read and written in place in
    the stacked pool by the two SSD kernels, the K/V pages by theirs beside
    it, and no array of a pool layer's shape exists."""
    cfg = get_model_config(FALCON)
    lowered = _forward_chunk_lowered(cfg, s, None, v5e, tp=tp)
    found = _kernels(lowered)
    want = {"dgi_paged_decode", "dgi_ssd_step", "dgi_qmm"} if tp is None \
        else {"dgi_paged_write", "dgi_ragged_attention", "dgi_ssd_chunk"}
    assert found == want, found
    compiled = lowered.compile()
    text = compiled.as_text()
    h, p, n = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size
    whole = f"[{cfg.num_layers},{BATCH},{h},{p},{n}]"
    assert whole in text
    assert f"[{BATCH},{h},{p},{n}]" not in text.replace(whole, "")
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 1024 ** 3


# --------------------------------------------------------------------- #
# (e) a model with an indexer (learned sparse attention): Keye-VL-2.0's
# language model at its published widths and the served 24,576 positions
# --------------------------------------------------------------------- #

KEYE = "keye-vl-2.0-30b-a3b-8l"
KEYE_CTX = 24576


@pytest.mark.parametrize("s", [1, 256])
def test_selection_kernels_compile(v5e, s):
    """The selection's two kernels at the served context: 8 rows of a scan
    step under their ``_step`` names, a rectangle of 8 x 256 queries under
    the round's."""
    from distributed_gpu_inference_tpu.ops import index_select

    cfg = get_model_config(KEYE)
    sds = _on(SingleDeviceSharding(v5e[0]))
    hi, di = cfg.index_num_heads, cfg.index_head_dim

    def run(qi, wts, pool, tables, pos, lens):
        return index_select.select(qi, wts, pool, jnp.int32(3), tables, pos,
                                   lens, cfg.index_topk, kernels=True)

    lowered = jax.jit(run).lower(
        sds((BATCH, s, hi, di), jnp.bfloat16), sds((BATCH, s, hi), jnp.float32),
        sds((cfg.num_layers, 1 + BATCH * (KEYE_CTX // 16), 16,
             index_select.pool_lanes(di)), jnp.bfloat16),
        sds((BATCH, KEYE_CTX // 16), jnp.int32), sds((BATCH, s), jnp.int32),
        sds((BATCH,), jnp.int32))
    tail = "_step" if s == 1 else ""
    assert _kernels(lowered) == {"dgi_index_score" + tail,
                                 "dgi_index_threshold" + tail}
    lowered.compile()


@pytest.mark.parametrize("tp,s", [(None, 1), (128, 128), (264, 256),
                                  (2048, 256)],
                         ids=["scan-step", "Tp128", "Tp264", "Tp2048"])
def test_indexed_model_graphs_compile_and_copy_no_pool(v5e, tpu_dispatch, tp,
                                                       s):
    """A decode step and the packed round at three rungs, 24,576 positions a
    row. The selection runs in its own kernels and the attention kernels
    take it; K, V and the index keys are written and read in place in the
    stacked pools (no array of a pool layer's shape); nothing sorts a row
    of scores (the router's top-8 of 128 is the one sort a layer has); the
    128 experts go through the grouped-matmul kernel (no dequantised
    copy)."""
    cfg = get_model_config(KEYE)
    lowered = _forward_chunk_lowered(cfg, s, None, v5e, tp=tp, ctx=KEYE_CTX)
    found = _kernels(lowered)
    want = {"dgi_paged_decode", "dgi_moe_gmm_step", "dgi_index_score_step",
            "dgi_index_threshold_step"} if tp is None else {
        "dgi_paged_write", "dgi_ragged_attention", "dgi_moe_gmm",
        "dgi_index_score", "dgi_index_threshold"}
    assert want <= found and found <= want | {"dgi_qmm"}, found
    compiled = lowered.compile()
    text = compiled.as_text()
    blocks = 1 + BATCH * (KEYE_CTX // 16)
    L, nkv, d = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    for whole, layer in ((f"[{L},{blocks},{nkv},16,{d}]",
                          f"[{blocks},{nkv},16,{d}]"),
                         (f"[{L},{blocks},16,128]", f"[{blocks},16,128]")):
        assert whole in text
        assert layer not in text.replace(whole, "")
    for line in text.splitlines():
        if " sort(" in line:
            assert str(KEYE_CTX) not in line, line
    e, hid, mi = cfg.num_experts, cfg.hidden_size, cfg.mlp_width
    for dt in ("bf16", "f32"):
        assert f"{dt}[{e},{hid},{mi}]" not in text
        assert f"{dt}[{e},{mi},{hid}]" not in text
    # a round's largest temporaries are the rectangle's scores and mask
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (3 if tp == 2048 else 1) * 1024 ** 3


def _indexed_scan_lowered(cfg, steps, devices, ctx=KEYE_CTX):
    """``steps`` decode steps of int8 ``cfg`` over [BATCH, 1] in one
    ``lax.scan``, the way ``TPUEngine``'s ``decode_multi`` carries a model
    with an indexer: the storage of the scan's keys beside the pools (donated
    with them), ``llama.scan_index_keys`` ahead of the steps."""
    one = SingleDeviceSharding(devices[0])
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = jax.eval_shape(lambda: quantize_params(
        llama.init_params(cfg, jax.random.PRNGKey(0)), "int8"))
    from distributed_gpu_inference_tpu.ops import index_select

    kinds = 2 if cfg.mixed_attention else 1
    kv = jax.eval_shape(
        lambda: llama.init_kv_pools(
            cfg, 1 + BATCH * (ctx // 16), 16,
            window_blocks=WINDOW_BLOCKS if kinds > 1 else None))
    kv[llama.INDEX_SCAN_KEYS] = jax.ShapeDtypeStruct(
        index_select.scan_keys_shape(
            kv[llama.INDEX_KEYS].shape, BATCH, ctx // 16, cfg.index_topk),
        kv[llama.INDEX_KEYS].dtype)
    sds = _on(one)

    def scan(params, kv, last, lens, tables, active):
        kv = llama.scan_index_keys(cfg, kv, tables, lens, active, steps)

        def step(carry, _):
            kv, last, lens = carry
            pos = jnp.where(active, lens, -1)[:, None]
            out = llama.forward_chunk(
                cfg, params, last[:, None], pos, kv, tables,
                jnp.where(active, lens + 1, 0), block_size=16)
            toks = jnp.argmax(out.logits[:, 0], axis=-1).astype(jnp.int32)
            return (out.kv, toks, lens + 1), toks

        (kv, _, _), toks = jax.lax.scan(
            step, (kv, last, lens), None, length=steps)
        return kv, toks

    return jax.jit(scan, donate_argnums=(1,)).lower(
        place(params), place(kv), sds((BATCH,), jnp.int32),
        sds((BATCH,), jnp.int32),
        sds((BATCH, kinds * (ctx // 16)), jnp.int32),
        sds((BATCH,), jnp.bool_))


def _computations(text):
    """The compiled module's computations by name → their lines."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def test_indexed_scan_gathers_its_index_keys_once_outside_the_step_loop(
        v5e, tpu_dispatch):
    """The T=4 scan of the Keye configuration at 24,576 positions a row: the
    index-key pool is gathered in the branch of ONE conditional ahead of the
    step loop (every layer's keys at once, ``[L, B, J, 128]``) and by
    nothing inside the loop; the score kernel's operand is that array as
    the loop carries it (no slice of a layer, no copy, one layout from the
    scatter that appends to the kernel that reads); the array is the
    caller's storage, written in place (the program allocates none of its
    size)."""
    cfg = get_model_config(KEYE)
    lowered = _indexed_scan_lowered(cfg, 4, v5e)
    assert _kernels(lowered) == {
        "dgi_index_score_step", "dgi_index_threshold_step",
        "dgi_paged_decode", "dgi_moe_gmm_step", "dgi_qmm"}
    compiled = lowered.compile()
    comps = _computations(compiled.as_text())
    L, blocks = cfg.num_layers, 1 + BATCH * (KEYE_CTX // 16)
    keys = f"bf16[{L},{BATCH},{KEYE_CTX},128]"
    pool = f"bf16[{L},{blocks},16,128]"
    # (1) who gathers from the pool: one computation, a conditional's branch
    gathering = {
        name for name, lines in comps.items()
        if any("gather" in ln and "dgi_index_scan_keys" in ln
               for ln in lines)}
    branches = {
        b for lines in comps.values() for ln in lines
        if " conditional(" in ln and "dgi_index_scan_keys" in ln
        for b in re.findall(r"%([\w.\-]+)", ln.split("branch_computations")[1]
                            .split("}")[0])}
    assert gathering and gathering <= branches | {
        c for b in branches for ln in comps[b]
        for c in re.findall(r"calls=%([\w.\-]+)", ln)}, (gathering, branches)
    # the step's own selection gathers nothing: no gather under its scope
    for lines in comps.values():
        for ln in lines:
            if "dgi_index_select" in ln or "dgi_index/" in ln:
                assert " gather(" not in ln, ln
    # (2) loop bodies: the conditional is in none of them
    bodies = {m for lines in comps.values() for ln in lines
              if " while(" in ln
              for m in re.findall(r"body=%([\w.\-]+)", ln)}
    assert bodies
    for body in bodies:
        assert not any(" conditional(" in ln and "dgi_index_scan_keys" in ln
                       for ln in comps[body]), body
    # (3) the score kernel reads the carried array itself
    calls = [ln for lines in comps.values() for ln in lines
             if "dgi_index_score_step" in ln and "custom-call(" in ln]
    assert calls
    for ln in calls:
        assert keys in ln.split("operand_layout_constraints")[1], ln
    # (4) nothing copies or slices it, and it has one layout
    layouts = set()
    for lines in comps.values():
        for ln in lines:
            layouts |= set(re.findall(re.escape(keys) + r"\{([\d,]*)", ln))
            if keys in ln.split(" = ")[0] if " = " in ln else False:
                op = ln.split(" = ")[1]
                assert not re.match(r"\S+ (copy|dynamic-slice|transpose)\(",
                                    op), ln
    assert layouts == {"3,2,1,0"}, layouts
    # (5) the storage comes back with the pools, aliased to what went in:
    # the program holds no array of its size (403 MB) among its temporaries
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 64 * 1024 ** 2
    size = L * BATCH * KEYE_CTX * 128 * 2
    assert stats.alias_size_in_bytes >= size + 3 * L * blocks * 16 * 128 * 2


# --------------------------------------------------------------------- #
# (f) window and full attention mixed layer by layer, pages per kind:
# Laguna-S-2.1's first stage at its published widths and the served 24,576
# positions. GQA groups of 6 (full) and 9 (sliding) are no multiples of 8.
# --------------------------------------------------------------------- #

LAGUNA = "laguna-s-2.1-ep4-12l"
LAGUNA_CTX = 24576


@pytest.mark.parametrize("tp,s", [(None, 1), (264, 256), (2048, 256)],
                         ids=["scan-step", "Tp264", "Tp2048"])
def test_mixed_model_graphs_compile_and_copy_no_pool(v5e, tpu_dispatch, tp,
                                                     s):
    """A decode step and the packed round at two rungs, 24,576 positions a
    row, GQA groups 6 and 9, window 512. The attention kernels as they are,
    called per kind over that kind's pools and block table; both kinds'
    pages are written and read in place in their stacked pools (no array of
    a pool layer's shape); the 64 held experts go through the grouped-matmul
    kernel (no dequantised copy) and no ``[T, 256, ...]`` temporary of the
    router's width exists."""
    cfg = get_model_config(LAGUNA)
    lowered = _forward_chunk_lowered(cfg, s, None, v5e, tp=tp,
                                     ctx=LAGUNA_CTX)
    found = _kernels(lowered)
    want = {"dgi_paged_decode", "dgi_moe_gmm_step"} if tp is None else {
        "dgi_paged_write", "dgi_ragged_attention", "dgi_moe_gmm"}
    assert want <= found and found <= want | {"dgi_qmm"}, found
    compiled = lowered.compile()
    text = compiled.as_text()
    nkv, d = cfg.num_kv_heads, cfg.head_dim
    for (_, layers, _), blocks in zip(
            cfg.cache_kinds, (1 + BATCH * (LAGUNA_CTX // 16), WINDOW_BLOCKS)):
        whole = f"[{layers},{blocks},{nkv},16,{d}]"
        assert whole in text
        assert f"[{blocks},{nkv},16,{d}]" not in text.replace(whole, "")
    tokens = BATCH if tp is None else tp
    held, hid, mi = cfg.num_held_experts, cfg.hidden_size, cfg.mlp_width
    assert f"[{tokens},{cfg.num_experts}," not in text
    for dt in ("bf16", "f32"):
        assert f"{dt}[{held},{hid},{mi}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (3 if tp == 2048 else 1) * 1024 ** 3


# --------------------------------------------------------------------- #
# (g) the indexer on latent pages, its selection shared layer to layer:
# GLM-5.2's share at its published widths and the served 24,576 positions
# --------------------------------------------------------------------- #

GLM = "glm-5.2-ep16-9l"
GLM_CTX = 24576


def test_selected_latent_step_waits_once_a_slot_at_any_group_width(
        v5e, monkeypatch):
    """``dgi_mla_decode_selected`` at GLM-5.2's served shape (8 rows of
    24,576 positions, 64 heads, 640-lane rows) compiles at a narrow group
    and at the served one with the SAME Mosaic text but for the width: one
    wait a slot a site, and page starts that sit in a rolled loop, so a
    wider group is no more to trace and lower at every start of a worker."""
    from distributed_gpu_inference_tpu.ops import mla_attention_pallas as mk

    assert mk._WALK_GROUP_TOKENS == 2048
    waits, starts = [], []
    for tokens in (512, 2048):
        monkeypatch.setattr(mk, "_WALK_GROUP_TOKENS", tokens)
        lowered = _latent_kernel_lowered(v5e, GLM, GLM_CTX, 1, True)
        assert _kernels(lowered) == {"dgi_mla_decode_selected"}
        lowered.compile()
        text = _mosaic_text(lowered)
        assert f"memref<2x{tokens // 16}x16x640xbf16" in text
        waits.append(text.count("tpu.wait_dma2"))
        starts.append(text.count("tpu.enqueue_dma"))
    # the group in hand; the first group of all and the next group's
    assert waits == [1, 1], (waits, starts)
    assert starts == [2 * mk._SELECTED_UNROLL] * 2, (waits, starts)


@pytest.mark.parametrize("model,s,selected,name", [
    (PANGU, 1, False, "dgi_mla_decode"),
    (PANGU, 256, False, "dgi_mla_ragged"),
    (GLM, 16, True, "dgi_mla_ragged_selected"),
], ids=["step", "round", "round-selected"])
def test_the_other_latent_walks_keep_a_wait_and_a_start_a_page(
        v5e, model, s, selected, name):
    """The forms the scan step's selected walk shares its body with, at
    their served shapes: one wait and one start a page of a 32-page group at
    each site, per-page semaphores, as before that walk was given a form of
    its own. Three configurations trace this body in every graph at every
    start of a worker: a form for one of them is a static branch the others
    never trace (PERF.md section 6, PR 53)."""
    ctx = PANGU_CTX if model == PANGU else GLM_CTX
    lowered = _latent_kernel_lowered(v5e, model, ctx, s, selected)
    assert _kernels(lowered) == {name}
    text = _mosaic_text(lowered)
    assert "memref<2x32x16x640xbf16" in text
    assert "memref<2x32x!tpu.dma_semaphore" in text
    assert text.count("tpu.wait_dma2") == 32
    assert text.count("tpu.enqueue_dma") == 2 * 32


@pytest.mark.parametrize("tp,s", [(None, 1), (264, 256), (2048, 256)],
                         ids=["scan-step", "Tp264", "Tp2048"])
def test_sparse_latent_model_graphs_compile_and_read_no_pool_densely(
        v5e, tpu_dispatch, tp, s):
    """A decode step and the packed round at two rungs, 24,576 positions a
    row. The latent kernels under their ``_selected`` names (no dense walk
    is left in the graph), the selection in its own kernels (three full
    layers of nine: one traced period), latent pages and index keys written
    and read in place in their stacked pools; no ``[B, S, heads, J]`` score
    tensor of either the indexer's 32 heads or attention's 64; at decode no
    gather of a row's latent pages (the XLA form's dense read)."""
    cfg = get_model_config(GLM)
    lowered = _forward_chunk_lowered(cfg, s, None, v5e, tp=tp, ctx=GLM_CTX)
    found = _kernels(lowered)
    want = {"dgi_mla_write", "dgi_mla_decode_selected", "dgi_moe_gmm_step",
            "dgi_index_score_step", "dgi_index_threshold_step"} \
        if tp is None else {
        "dgi_mla_write", "dgi_mla_ragged_selected", "dgi_moe_gmm",
        "dgi_index_score", "dgi_index_threshold"}
    assert want <= found and found <= want | {"dgi_qmm"}, found
    compiled = lowered.compile()
    text = compiled.as_text()
    blocks = 1 + BATCH * (GLM_CTX // 16)
    for whole, layer in ((f"[9,{blocks},16,640]", f"[{blocks},16,640]"),
                         (f"[3,{blocks},16,128]", f"[{blocks},16,128]")):
        assert whole in text
        assert layer not in text.replace(whole, "")
    rows = s if tp is None else 256
    for heads in (cfg.index_num_heads, cfg.num_heads):
        assert f"[{BATCH},{rows},{heads},{GLM_CTX}]" not in text
        assert f"[{BATCH},{heads},{rows},{GLM_CTX}]" not in text
    # a row's cached latents in context order: what the XLA form gathers
    assert f"[{BATCH},{GLM_CTX},640]" not in text
    for line in text.splitlines():
        if " sort(" in line:
            assert str(GLM_CTX) not in line, line
    held, hid, mi = cfg.num_held_experts, cfg.hidden_size, \
        cfg.moe_intermediate_size
    for dt in ("bf16", "f32"):
        assert f"{dt}[{held},{hid},{mi}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (3 if tp == 2048 else 1) * 1024 ** 3


def test_sparse_latent_scan_carries_a_layer_of_keys_a_full_layer(
        v5e, tpu_dispatch):
    """The T=4 scan at 24,576 positions a row: the scan's keys are ``[3, B,
    J, 128]`` (a layer a FULL layer), gathered ahead of the step loop, the
    score kernel reads them as the loop carries them, and the storage comes
    back aliased with the two pools."""
    cfg = get_model_config(GLM)
    lowered = _indexed_scan_lowered(cfg, 4, v5e, ctx=GLM_CTX)
    assert _kernels(lowered) == {
        "dgi_index_score_step", "dgi_index_threshold_step", "dgi_mla_write",
        "dgi_mla_decode_selected", "dgi_moe_gmm_step", "dgi_qmm"}
    compiled = lowered.compile()
    text = compiled.as_text()
    keys = f"bf16[3,{BATCH},{GLM_CTX},128]"
    calls = [ln for ln in text.splitlines()
             if "dgi_index_score_step" in ln and "custom-call(" in ln]
    assert calls
    for ln in calls:
        assert keys in ln.split("operand_layout_constraints")[1], ln
    stats = compiled.memory_analysis()
    blocks = 1 + BATCH * (GLM_CTX // 16)
    assert stats.alias_size_in_bytes >= 3 * BATCH * GLM_CTX * 128 * 2 \
        + 9 * blocks * 16 * 640 * 2 + 3 * blocks * 16 * 128 * 2
    assert stats.temp_size_in_bytes < 256 * 1024 ** 2


# --------------------------------------------------------------------- #
# (h) latent pages of two kinds: dots3-note-prev's share at its published
# widths and the served 24,576 positions. Full layers (128 heads, 640-lane
# rows, the indexer's 64 heads) beside sliding layers (64 heads, latent
# 1,024 in 1,152-lane rows, a window of 513) with a pool a kind.
# --------------------------------------------------------------------- #

DOTS3 = "dots3-note-prev-ep8-9l"
DOTS3_CTX = 24576
# the window kind's pool as the engine sizes it: 8 slots, 8 windows of 33
DOTS3_WINDOW_BLOCKS = 1 + 8 * 8 * 33


@pytest.mark.parametrize("s,name", [
    (1, "dgi_mla_window_decode"), (16, "dgi_mla_window_ragged"),
    (256, "dgi_mla_window_ragged")], ids=["step", "tile", "round"])
def test_the_windowed_latent_kernel_compiles_at_the_published_widths(
        v5e, s, name):
    """The absorbed kernel at latent 1,024 / row 1,152 / 64 heads with the
    window as a rule of the walk (an eighth scalar operand, the tile's
    lowest query position), and the window pool's page write."""
    from distributed_gpu_inference_tpu.ops import mla_attention_pallas as mk

    cfg = get_model_config(DOTS3)
    kind = cfg.latent_kind("sliding")
    sds = _on(SingleDeviceSharding(v5e[0]))
    w, m = mla.pool_width(cfg, "sliding"), DOTS3_CTX // 16
    assert (w, kind.kv_rank, kind.heads, kind.window) == (1152, 1024, 64, 513)
    pool = sds((cfg.num_window_layers, DOTS3_WINDOW_BLOCKS, 16, w),
               jnp.bfloat16)
    fn = functools.partial(
        mk.latent_paged_attention.__wrapped__, block_size=16,
        scale=kind.qk ** -0.5, latent=kind.kv_rank, decode=s == 1,
        window=kind.window)
    lowered = jax.jit(fn).lower(
        sds((BATCH, s, kind.heads, w), jnp.bfloat16), pool,
        sds((), jnp.int32), sds((BATCH, m), jnp.int32),
        sds((BATCH, s), jnp.int32), sds((BATCH,), jnp.int32))
    assert _kernels(lowered) == {name}
    text = _mosaic_text(lowered)
    assert "memref<2x32x16x1152xbf16" in text
    lowered.compile()

    def write(rows, pool, layer, tables, pos):
        plan = page_write_plan(tables, pos, 16, page_bytes=16 * w * 2)
        return mk.write_latent_pages_in_place(rows, pool, layer, plan)

    jax.jit(write, donate_argnums=(1,)).lower(
        sds((BATCH * s, w), jnp.bfloat16), pool, sds((), jnp.int32),
        sds((BATCH, m), jnp.int32), sds((BATCH, s), jnp.int32)).compile()


@pytest.mark.parametrize("tp,s", [(None, 1), (264, 256), (2048, 256)],
                         ids=["scan-step", "Tp264", "Tp2048"])
def test_two_kind_latent_model_graphs_compile_and_copy_no_pool(
        v5e, tpu_dispatch, tp, s):
    """A decode step and the packed round at two rungs, 24,576 positions a
    row. The full layers' latent kernels under their ``_selected`` names,
    the sliding layers' under names of their own, the selection in its own
    kernels; each kind's pages and the index keys written and read in place
    in their stacked pools (no array of a pool layer's shape); no gather of
    a row's cached latents of either width."""
    cfg = get_model_config(DOTS3)
    lowered = _forward_chunk_lowered(cfg, s, None, v5e, tp=tp, ctx=DOTS3_CTX)
    found = _kernels(lowered)
    want = {"dgi_mla_write", "dgi_mla_decode_selected",
            "dgi_mla_window_decode", "dgi_moe_gmm_step",
            "dgi_index_score_step", "dgi_index_threshold_step"} \
        if tp is None else {
        "dgi_mla_write", "dgi_mla_ragged_selected", "dgi_mla_window_ragged",
        "dgi_moe_gmm", "dgi_index_score", "dgi_index_threshold"}
    assert want <= found and found <= want | {"dgi_qmm"}, found
    compiled = lowered.compile()
    text = compiled.as_text()
    blocks = 1 + BATCH * (DOTS3_CTX // 16)
    for whole, layer in (
            (f"[3,{blocks},16,640]", f"[{blocks},16,640]"),
            (f"[6,{WINDOW_BLOCKS},16,1152]", f"[{WINDOW_BLOCKS},16,1152]"),
            (f"[3,{blocks},16,128]", f"[{blocks},16,128]")):
        assert whole in text
        assert layer not in text.replace(whole, "")
    for width in (640, 1152):
        assert f"[{BATCH},{DOTS3_CTX},{width}]" not in text
    held, hid, mi = cfg.num_held_experts, cfg.hidden_size, \
        cfg.moe_intermediate_size
    for dt in ("bf16", "f32"):
        assert f"{dt}[{held},{hid},{mi}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (3 if tp == 2048 else 1) * 1024 ** 3


def test_two_kind_latent_scan_carries_its_keys_under_the_full_kinds_table(
        v5e, tpu_dispatch):
    """The T=4 scan at 24,576 positions a row: the scan's keys are ``[3, B,
    J, 128]`` (a layer a FULL layer), gathered by the full kind's half of a
    row's two tables, and the storage comes back aliased with the three
    pools."""
    cfg = get_model_config(DOTS3)
    lowered = _indexed_scan_lowered(cfg, 4, v5e, ctx=DOTS3_CTX)
    assert _kernels(lowered) == {
        "dgi_index_score_step", "dgi_index_threshold_step", "dgi_mla_write",
        "dgi_mla_decode_selected", "dgi_mla_window_decode",
        "dgi_moe_gmm_step", "dgi_qmm"}
    compiled = lowered.compile()
    stats = compiled.memory_analysis()
    blocks = 1 + BATCH * (DOTS3_CTX // 16)
    assert stats.alias_size_in_bytes >= 3 * BATCH * DOTS3_CTX * 128 * 2 \
        + 3 * blocks * 16 * 640 * 2 + 3 * blocks * 16 * 128 * 2 \
        + 6 * WINDOW_BLOCKS * 16 * 1152 * 2
    assert stats.temp_size_in_bytes < 256 * 1024 ** 2
