#!/usr/bin/env python3
"""Rehearsal without the chip: compile a configuration's round graph at
full size for the compile-only ``v5e:2x2`` target.

    JAX_PLATFORMS=cpu python3 benchmark/compile_only.py --config <name>

It lowers the body of both round programs — ``forward_chunk`` over
``[max_batch_size, 1]`` (a decode step) and ``[max_batch_size,
ragged_chunk]`` (the widest ragged round) — with the configuration's
weights as shapes, int8, placed the way the engine places them (one device,
or the ``model`` mesh of ``tp_size`` devices with the program's sharding
rules and the XLA paths), and prints what the TPU compiler says each device
needs. What it refuses here it would refuse on the chip, at no chip time.
Nothing runs: this gives no time and no result, and is never reported as a
chip run. It counts one program, not what else the process keeps on the
device (the other compiled shapes' workspaces are reused, the weights and
the KV pool are arguments and are counted).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

from harness import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    args = ap.parse_args()
    cfg = spec.load_config(spec.BENCH / "configs" / f"{args.config}.json")
    geo = cfg["serving_geometry"]

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.models.configs import get_model_config
    from distributed_gpu_inference_tpu.ops import attention
    from distributed_gpu_inference_tpu.ops.quantization import quantize_params
    from distributed_gpu_inference_tpu.parallel import sharding as sh

    # kernel dispatch asks the backend, which is the CPU here: answer as a
    # TPU backend would, in this script and not through a program option
    attention.pallas_backend = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mc = get_model_config(cfg["registry_model"])
    tp, batch, block = geo["tp_size"], geo["max_batch_size"], geo["block_size"]
    blocks = int(batch * (geo["max_seq_len"] // block) * 1.5) + 1
    params = jax.eval_shape(lambda: quantize_params(
        llama.init_params(mc, jax.random.PRNGKey(0)), "int8"))
    kv = jax.eval_shape(lambda: llama.init_kv_pools(mc, blocks, block))
    if tp == 1:
        rep = SingleDeviceSharding(topo.devices[0])
        p_sh = jax.tree.map(lambda _: rep, params)
        kv_sh = jax.tree.map(lambda _: rep, kv)
    else:
        mesh = Mesh(np.array(topo.devices[:tp]).reshape(tp), ("model",))
        rep = NamedSharding(mesh, P())
        p_sh = sh.prune_rules(sh.param_shardings(mesh), params)
        kv_sh = jax.tree.map(lambda _: sh.kv_sharding(mesh), kv)

    def place(tree, shard):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shard)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    def step(params, kv, toks, pos, tables, lens):
        out = llama.forward_chunk(mc, params, toks, pos, kv, tables, lens,
                                  block_size=block, pallas=tp == 1)
        return out.logits, out.kv

    for width in (1, geo["ragged_chunk"]):
        t0 = time.monotonic()
        lowered = jax.jit(step, donate_argnums=(1,)).lower(
            place(params, p_sh), place(kv, kv_sh), sds((batch, width)),
            sds((batch, width)), sds((batch, geo["max_seq_len"] // block)),
            sds((batch,)),
        )
        kernels = sorted(attention.pallas_kernels(lowered))
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        collectives = {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                       for k in ("all-reduce", "all-gather", "reduce-scatter",
                                 "all-to-all", "collective-permute")}
        gib = 2.0 ** 30
        print(f"{args.config} [B={batch}, S={width}] tp={tp}: compiled for "
              f"v5e:2x2 in {time.monotonic() - t0:.0f}s; kernels {kernels}; "
              f"per device: arguments {mem.argument_size_in_bytes / gib:.2f} "
              f"GiB, outputs {mem.output_size_in_bytes / gib:.2f} GiB "
              f"(aliased {mem.alias_size_in_bytes / gib:.2f}), temporaries "
              f"{mem.temp_size_in_bytes / gib:.2f} GiB, program "
              f"{mem.generated_code_size_in_bytes / gib:.3f} GiB; "
              f"collectives in the text {collectives}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
