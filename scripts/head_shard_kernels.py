#!/usr/bin/env python3
"""The attention kernels a shard of heads a chip against the XLA path a mesh
took before them, alone, at a cell's geometry (PERF.md section 6, PR 58):

    chiprun --chips 4 -- python scripts/head_shard_kernels.py \\
        --out chiprun_out/head_shard_kernels.json

One layer call of each, inside a scan over the model's layers with the
pools donated, as the serving graphs hold them: a scan step (the fused
write + attention: ``dgi_paged_decode``) and a round (``dgi_paged_write``
and ``dgi_ragged_attention``), under ``jax.shard_map`` over ``model``; and
the path they replace (a layer of each pool sliced out of the stack,
scattered into, gathered by XLA's paged attention, written back). Outputs
compared: attention within the kernels' bf16 tolerance, the written pools
equal. ``--interpret --tiny`` rehearses the script on the CPU's virtual
devices; a time is a chip's alone."""
import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

sys.path.insert(0, ".")
from distributed_gpu_inference_tpu.models import llama  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    ModelConfig,
)
from distributed_gpu_inference_tpu.ops import (  # noqa: E402
    attention, paged_attention_pallas as pap,
)
from distributed_gpu_inference_tpu.parallel import sharding as sh  # noqa: E402
from distributed_gpu_inference_tpu.parallel.mesh import (  # noqa: E402
    MeshPlan, make_mesh,
)

ap = argparse.ArgumentParser()
ap.add_argument("--tp", type=int, default=4)
ap.add_argument("--out", default=None)
ap.add_argument("--interpret", action="store_true")
ap.add_argument("--tiny", action="store_true")
ap.add_argument("--reps", type=int, default=20)
args = ap.parse_args()

if args.interpret:
    attention.pallas_backend = lambda: True
    for name in ("paged_decode_attention_fused", "write_kv_pages_in_place",
                 "ragged_paged_attention"):
        setattr(pap, name, functools.partial(getattr(pap, name),
                                             interpret=True))

# the tp4 cell's: Mixtral's heads, 8 rows, 128 table columns of 16 tokens
layers, rows, cols, block = (2, 4, 32, 16) if args.tiny else (32, 8, 128, 16)
cfg = ModelConfig(
    name="head-shard-probe", vocab_size=256, hidden_size=256,
    num_layers=layers, num_heads=4 * args.tp if args.tiny else 32,
    num_kv_heads=args.tp if args.tiny else 8, intermediate_size=256,
    head_dim=128, dtype="bfloat16",
)
dtype = jnp.float32 if args.tiny else jnp.bfloat16
mesh = make_mesh(MeshPlan(model=args.tp), jax.devices()[:args.tp])
heads = sh.head_shards(mesh)
put = lambda a, spec: jax.device_put(  # noqa: E731
    a, jax.sharding.NamedSharding(mesh, spec))
rng = np.random.default_rng(0)
normal = lambda *shape: jnp.asarray(  # noqa: E731
    rng.standard_normal(shape), dtype)
nh, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
blocks = 1 + rows * cols
tables = put(jnp.asarray(
    1 + rng.permutation(rows * cols).reshape(rows, cols), jnp.int32),
    P())
# chat-short's live context: 100-400 tokens a row
ctx = rng.integers(100, 400, size=rows) if not args.tiny \
    else rng.integers(20, 60, size=rows)


@functools.partial(jax.jit, out_shardings=jax.sharding.NamedSharding(
    mesh, sh.POOL_HEADS))
def _pool(seed):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (layers, blocks, hkv, block, d), dtype)


def pools(seed):
    """Two pools of random values, made on the chips that hold them."""
    return _pool(2 * seed), _pool(2 * seed + 1)


def chunk(spans):
    """A rectangle whose row ``i`` holds ``spans[i]`` tokens at the tail of
    ``ctx[i] + spans[i]`` cached ones."""
    s = max(max(spans), 1)
    pos = np.full((rows, s), -1, np.int32)
    for i, span in enumerate(spans):
        pos[i, :span] = np.arange(ctx[i], ctx[i] + span)
    lens = jnp.asarray(ctx + np.asarray(spans), jnp.int32)
    return (put(normal(rows, s, nh, d), sh.CHUNK_HEADS),
            put(normal(rows, s, hkv, d), sh.CHUNK_HEADS),
            put(normal(rows, s, hkv, d), sh.CHUNK_HEADS),
            put(jnp.asarray(pos), P()), put(lens, P()))


def over_layers(layer_call):
    """``layer_call`` once a layer of the stack, the pools carried."""
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(k_pool, v_pool, q, k, v, pos, lens):
        def body(carry, layer):
            kp, vp, acc = carry
            attn, kp, vp = layer_call(q, k, v, kp, vp, layer, pos, lens)
            return (kp, vp, acc + attn.astype(jnp.float32)), None

        (k_pool, v_pool, acc), _ = lax.scan(
            body, (k_pool, v_pool, jnp.zeros(q.shape, jnp.float32)),
            jnp.arange(layers, dtype=jnp.int32))
        return k_pool, v_pool, acc

    return run


def xla_layer(q, k, v, k_pool, v_pool, layer, pos, lens):
    layer_k = lax.dynamic_index_in_dim(k_pool, layer, 0, keepdims=False)
    layer_v = lax.dynamic_index_in_dim(v_pool, layer, 0, keepdims=False)
    layer_k = llama._write_kv_pages(layer_k, k, tables, pos, block)
    layer_v = llama._write_kv_pages(layer_v, v, tables, pos, block)
    attn = attention.paged_attention(
        q, layer_k, layer_v, tables, pos, lens, block, impl="xla")
    return (attn, lax.dynamic_update_index_in_dim(k_pool, layer_k, layer, 0),
            lax.dynamic_update_index_in_dim(v_pool, layer_v, layer, 0))


def step_layer(q, k, v, k_pool, v_pool, layer, pos, lens):
    return llama._fused_decode(block, None, heads)(
        q, k, v, k_pool, v_pool, layer, tables, pos, lens)


def round_layer(q, k, v, k_pool, v_pool, layer, pos, lens):
    write, attn = llama._in_place_kv(
        cfg, {"k": k_pool, "v": v_pool}, tables, pos, lens, block,
        pallas=False, heads=heads)
    k_pool, v_pool = write(k.reshape(-1, hkv, d), v.reshape(-1, hkv, d),
                           k_pool, v_pool, layer)
    return attn(q, k_pool, v_pool, layer), k_pool, v_pool


def timed(run, operands):
    out = run(*pools(0), *operands)          # compiles
    jax.block_until_ready(out)
    kp, vp = jax.block_until_ready(pools(1))
    t0 = time.perf_counter()
    for _ in range(args.reps):
        kp, vp, acc = run(kp, vp, *operands)
    jax.block_until_ready((kp, vp, acc))
    ms = (time.perf_counter() - t0) / args.reps / layers * 1e3
    return out, ms


f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
report = {"device": {"platform": jax.devices()[0].platform,
                     "kind": jax.devices()[0].device_kind,
                     "count": len(mesh.devices.flat)},
          "geometry": {"layers": layers, "rows": rows, "table_cols": cols,
                       "block": block, "heads": nh, "kv_heads": hkv,
                       "head_dim": d, "tp": args.tp, "dtype": jnp.dtype(dtype).name},
          "cases": {}}
cases = {
    "scan_step": ([1] * rows, step_layer),
    # a round as chat-short has them: one piece beside rows that decode
    "round_piece_64": ([64] + [1] * (rows - 1), round_layer),
    "round_piece_256": ([256] + [1] * (rows - 1), round_layer),
}
if args.tiny:
    cases.pop("round_piece_256")
    cases["round_piece_64"] = ([19] + [1] * (rows - 1), round_layer)
for name, (spans, kernel_layer) in cases.items():
    operands = chunk(spans)
    (want_k, want_v, want), xla_ms = timed(over_layers(xla_layer), operands)
    (got_k, got_v, got), kernel_ms = timed(
        over_layers(kernel_layer), operands)
    live = f32(operands[3]) >= 0
    err = float(np.abs(f32(got) - f32(want))[live].max())
    report["cases"][name] = {
        "xla_ms_a_layer": xla_ms, "kernel_ms_a_layer": kernel_ms,
        "attn_max_abs_err_over_layers": err,
        "attn_scale": float(np.abs(f32(want))[live].max()),
        "pools_equal": bool(jnp.array_equal(got_k, want_k)
                            & jnp.array_equal(got_v, want_v)),
        "pool_sharding_kept": got_k.sharding.is_equivalent_to(
            sh.kv_sharding(mesh), got_k.ndim),
    }
    print(name, json.dumps(report["cases"][name]), flush=True)
print(json.dumps(report))
if args.out:
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
