#!/usr/bin/env python3
"""Did a change touch a configuration's serving graphs? Lower them from ONE
tree for the compile-only v5e target (no chip) and write each graph's text.

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \\
        python scripts/lowered_texts.py <tree> <out-dir> [graph ...]

Run it on a copy of the parent (``git archive``) and on the change, then
``diff -rq`` the two directories or compare ``summary.json``'s digests: a
graph whose text is equal is the same program, so its cell can move by
nothing but its start-up's Python. Each Pallas kernel's Mosaic module is
written in its printed form: as a call carries it (MLIR bytecode) it holds
file paths and line numbers, which differ between two trees that lower the
same kernel. ``lower_s`` is this CPU's time to trace and lower, a hint for
a cell's ``timing.graphs_s`` and never a device metric. The graphs are the
ones ``tests/test_tpu_lowering.py`` compiles (``_forward_chunk_lowered``):
a scan step and the packed round at its middle rung, per one-chip
configuration (PERF.md section 6, PRs 53-54 and 58)."""
import base64
import hashlib
import json
import os
import re
import sys
import time

tree, out, only = os.path.abspath(sys.argv[1]), sys.argv[2], sys.argv[3:]
sys.path.insert(0, tree)
os.chdir(tree)
os.makedirs(out, exist_ok=True)

import jax  # noqa: E402
from jax._src.interpreters import mlir as jax_mlir  # noqa: E402
from jax._src.lib.mlir import ir  # noqa: E402
from jax.experimental import topologies  # noqa: E402

import tests.test_tpu_lowering as lowering  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    get_model_config,
)
from distributed_gpu_inference_tpu.ops import attention  # noqa: E402

assert lowering.__file__.startswith(tree), lowering.__file__
attention.pallas_backend = lambda: True      # dispatch as on a TPU backend
devices = topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices

# name: (model, packed tokens or None for a scan step, width, context)
GRAPHS = {
    "pangu.scan-step": (lowering.PANGU, None, 1, lowering.PANGU_CTX),
    "pangu.Tp264": (lowering.PANGU, 264, 256, lowering.PANGU_CTX),
    "kimi.scan-step": (lowering.KIMI, None, 1, lowering.PANGU_CTX),
    "kimi.Tp264": (lowering.KIMI, 264, 256, lowering.PANGU_CTX),
    "glm.scan-step": (lowering.GLM, None, 1, lowering.GLM_CTX),
    "glm.Tp264": (lowering.GLM, 264, 256, lowering.GLM_CTX),
    # the K/V recipe's one-chip configurations (PR 58: an edit to
    # ``models/llama.py``'s attention calls that a mesh alone may trace)
    "mistral.scan-step": ("mistral-7b", None, 1, lowering.CTX),
    "mistral.Tp264": ("mistral-7b", 264, 256, lowering.CTX),
    "qwen.scan-step": ("qwen2.5-7b", None, 1, lowering.CTX),
    "qwen.Tp264": ("qwen2.5-7b", 264, 256, lowering.CTX),
    "olmoe.scan-step": ("olmoe-1b-7b", None, 1, lowering.CTX),
    "olmoe.Tp264": ("olmoe-1b-7b", 264, 256, lowering.CTX),
    "falcon.scan-step": (lowering.FALCON, None, 1, lowering.CTX),
    "falcon.Tp264": (lowering.FALCON, 264, 256, lowering.CTX),
    "keye.scan-step": (lowering.KEYE, None, 1, lowering.KEYE_CTX),
    "keye.Tp264": (lowering.KEYE, 264, 256, lowering.KEYE_CTX),
    "laguna.scan-step": (lowering.LAGUNA, None, 1, lowering.LAGUNA_CTX),
    "laguna.Tp264": (lowering.LAGUNA, 264, 256, lowering.LAGUNA_CTX),
}


def without_locations(text: str) -> str:
    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def printed(match):
        with ctx:
            return str(ir.Module.parse(base64.b64decode(match.group(1))))

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', printed, text)


summary = {}
for name in only or GRAPHS:
    model, tp, s, ctx = GRAPHS[name]
    jax.clear_caches()
    t0 = time.perf_counter()
    lowered = lowering._forward_chunk_lowered(
        get_model_config(model), s, None, devices, tp=tp, ctx=ctx)
    lower_s = time.perf_counter() - t0
    text = without_locations(lowered.as_text())
    with open(os.path.join(out, name + ".txt"), "w") as f:
        f.write(text)
    summary[name] = {
        "sha256": hashlib.sha256(text.encode()).hexdigest(),
        "bytes": len(text), "lower_s": round(lower_s, 3),
        "kernels": sorted(lowering._kernels(lowered))}
    print(name, summary[name], flush=True)
with open(os.path.join(out, "summary.json"), "w") as f:
    json.dump(summary, f, indent=1)
