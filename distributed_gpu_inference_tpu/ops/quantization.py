"""TPU-native weight-only quantization: int8 / fp8 with per-channel scales.

The reference exposes quantization purely as engine passthrough flags —
AWQ/GPTQ/FP8/INT8 strings handed to vLLM (``worker/engines/llm_vllm.py:83-87``)
and SGLang; the actual kernels live in those CUDA deps. Here quantization is
first-party and TPU-shaped:

- **Storage**: matmul weights live in HBM as int8 (or float8_e4m3) with a
  float32 per-output-channel scale — half the bytes, so a chip fits ~2x
  the model (or correspondingly more KV pages). That capacity win is the
  primary benefit today.
- **Compute**: the MXU consumes bf16. On the decode path (small activation
  row counts) the contraction runs through the Pallas kernel in
  ``ops/qmm_pallas.py``: int8 tiles are DMA'd HBM→VMEM and converted
  in-kernel, so HBM sees half the bytes. Everywhere else (prefill,
  CPU/tests) the convert is expressed inline in the XLA matmul — XLA can
  materialize the converted operand there, but those paths are
  compute-bound, not weight-bandwidth-bound.
- **Pytree shape**: a quantized weight is a sub-dict ``{"qw", "scale"}`` whose
  leaves both carry the stacked leading L axis, so ``lax.scan`` over layers,
  GSPMD sharding, and pipeline stage slicing all keep working unchanged.

``matmul(x, w)`` is the single dispatch point: models call it for every
projection and it transparently handles plain or quantized leaves.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from distributed_gpu_inference_tpu.ops import attention as _attention

QUANT_MODES = ("int8", "fp8")

# weight leaves eligible for quantization (matmul weights only: norms, biases,
# and the embedding table stay high-precision)
QUANT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
     # MoE expert weights (stacked [L, E, in, out]) share the same scheme;
     # the router projection stays high-precision — quantizing it perturbs
     # top-k expert selection far more than it saves in bytes
     "we_gate", "we_up", "we_down",
     # latent attention's low-rank projections and the shared expert
     # (models/mla.py); W_UK / W_UV stay bf16 like the router
     "wq_a", "wq_b", "wkv_a", "ws_gate", "ws_up", "ws_down",
     # gated delta-rule layers (models/kda.py): q|k|v and the low-rank gates
     "wqkv", "w_fa", "w_fb", "w_ga", "w_gb",
     # a state-space mixer's in (z | x | B | C) and out projections
     # (models/ssd.py); its step sizes' columns are one a head, under a
     # tile, and stay in the activation dtype
     "w_in", "w_out",
     # an indexer's query projection (models/llama.py); its key and head-
     # weight projections are 64 and 16 columns wide, under the int8
     # kernel's 128-column tiles, and stay in the activation dtype
     "wqi"}
)

_FP8_MAX = 448.0  # float8_e4m3 largest finite value


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "qw" in w and "scale" in w


def quantize_weight(w: jax.Array, mode: str) -> Dict[str, jax.Array]:
    """Symmetric per-output-channel quantization of ``w [..., in, out]``.

    Scale reduces the contraction axis (-2) only: shape ``[..., 1, out]`` —
    per layer (leading axes) and per output channel, the granularity that
    keeps GQA/MLP projections accurate without zero points.
    """
    if mode not in QUANT_MODES:
        raise ValueError(f"unknown quantization mode {mode!r}; use {QUANT_MODES}")
    wf = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    if mode == "int8":
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        qw = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    else:  # fp8
        scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
        qw = (wf / scale).astype(jnp.float8_e4m3fn)
    return {"qw": qw, "scale": scale.astype(jnp.float32)}


def dequantize(w: Dict[str, jax.Array], dtype: Any = jnp.float32) -> jax.Array:
    return (w["qw"].astype(jnp.float32) * w["scale"]).astype(dtype)


def _pallas_qmm_ok(m: int, k_dim: int, n: int, qdtype) -> bool:
    """Trace-time gate for the in-kernel-dequant Pallas matmul: TPU backend,
    int8 storage, a bandwidth-bound row count, and tileable K/N."""
    if not _attention.pallas_backend():
        return False
    from distributed_gpu_inference_tpu.ops import qmm_pallas

    return (
        qdtype in (jnp.int8, jnp.float8_e4m3fn)
        and qmm_pallas.qmm_rows_ok(m)
        and qmm_pallas.pick_tiles(k_dim, n) is not None
    )


def matmul(x: jax.Array, w: Any, pallas: bool = True) -> jax.Array:
    """``x @ w`` where ``w`` is a plain array or a quantized sub-dict.

    Quantized decode-shaped calls go through the Pallas VMEM-dequant kernel
    (int8 on the HBM wire); otherwise convert-on-read matmul in x.dtype
    (bf16 on the MXU), then scale the output channels. The scale broadcast
    ``[..., 1, out]`` collapses against ``x @ qw``'s trailing [..., out].
    ``pallas=False`` keeps the call on the XLA path whatever the backend
    (mesh-sharded weights: see ``ops.attention.pallas_backend``).
    """
    if not is_quantized(w):
        return x @ w
    qw = w["qw"]
    if qw.ndim == 2:
        lead = x.shape[:-1]
        m = 1
        for d in lead:
            m *= d
        if pallas and _pallas_qmm_ok(m, qw.shape[0], qw.shape[1], qw.dtype):
            # single dispatch point: lift to a 1-layer stack
            return matmul_stacked(
                x, {"qw": qw[None], "scale": w["scale"][None]}, jnp.int32(0)
            )
    out = x @ qw.astype(x.dtype)
    # scale shape [..., 1, out] → drop the kept contraction axis for broadcast
    scale = jnp.squeeze(w["scale"], axis=-2).astype(jnp.float32)
    return (out.astype(jnp.float32) * scale).astype(x.dtype)


# weight keys large enough to be worth the stacked-scan treatment
STACKED_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
     "wq_a", "wq_b", "wkv_a", "ws_gate", "ws_up", "ws_down",
     "wqkv", "w_fa", "w_fb", "w_ga", "w_gb", "wqi", "w_in", "w_out"}
)
# the MoE expert weights: kept whole for the routed layer's grouped-matmul
# kernel (ops/moe_gmm_pallas.py), scanned where the layer runs in XLA
EXPERT_KEYS = frozenset({"we_gate", "we_up", "we_down"})


def split_stacked_quant(layers: Dict[str, Any], experts: bool = False):
    """Partition a stacked layer tree for the scan in ``models/llama.py``:
    quantized matmul weights are pulled OUT of the scan xs (so the Pallas
    kernel can take the whole stacked array + a layer index instead of a
    materialized per-layer slice) and everything else stays scanned.
    ``experts``: the expert weights ``[L, E, in, out]`` too.

    → (scanned_layers, stacked_or_None)
    """
    keys = STACKED_KEYS | EXPERT_KEYS if experts else STACKED_KEYS
    stacked = {
        k: v for k, v in layers.items()
        if k in keys and is_quantized(v)
    }
    if not stacked:
        return layers, None
    scanned = {k: v for k, v in layers.items() if k not in stacked}
    return scanned, stacked


def matmul_stacked(
    x: jax.Array, w: Dict[str, jax.Array], layer_idx, pallas: bool = True
) -> jax.Array:
    """``x @ dequant(w[layer_idx])`` for a stacked quantized weight
    ``{"qw": [L, K, N], "scale": [L, 1, N]}`` — the scan-body entry point.

    Decode-shaped calls hit the Pallas kernel with the STACKED operand (no
    per-layer slice ever materializes); other shapes slice the layer and
    take the XLA convert-on-read path (equivalent HLO to scanning the
    weight as an xs leaf, so nothing regresses).
    """
    qw = w["qw"]
    _, k_dim, n = qw.shape
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    if pallas and _pallas_qmm_ok(m, k_dim, n, qw.dtype):
        from distributed_gpu_inference_tpu.ops.qmm_pallas import (
            qmm_stacked_pallas,
        )

        out = qmm_stacked_pallas(
            x.reshape(m, k_dim), qw, w["scale"], layer_idx
        )
        return out.reshape(*lead, n)
    sliced = {
        "qw": lax.dynamic_index_in_dim(qw, layer_idx, 0, keepdims=False),
        "scale": lax.dynamic_index_in_dim(
            w["scale"], layer_idx, 0, keepdims=False
        ),
    }
    return matmul(x, sliced, pallas=False)


def quantize_params(
    params: Dict[str, Any], mode: Optional[str], consume: bool = False
) -> Dict[str, Any]:
    """Quantize every eligible matmul weight in a model params pytree.

    Structure-preserving everywhere else; returns a new pytree. ``mode=None``
    is the identity.

    ``consume=True`` drops each source leaf's reference as soon as its
    quantized replacement exists (the input ``params['layers']`` dict is
    emptied). Peak HBM is then full-precision + ONE quantized leaf instead
    of full-precision + the whole quantized tree — the difference between
    fitting and OOM when cold-starting an int8 model near chip capacity.
    """
    if mode is None:
        return params
    out = dict(params)
    # a model whose layers are of two kinds keeps a second stack
    # (models/mla.py: the leading dense layers)
    # and a hybrid one a pair more (the gated delta-rule layers)
    # and a K/V model of mixed attention kinds its full layers' (models/
    # llama.py group_of)
    # and a latent model with an indexer its full layers', which hold one
    # (models/mla.py group_of)
    for group in ("layers", "dense_layers", "kda_layers",
                  "kda_dense_layers", "full_layers", "full_dense_layers",
                  "ix_layers", "ix_dense_layers",
                  # and one of two attention kinds its sliding layers'
                  "sw_layers", "sw_dense_layers"):
        if group in params:
            out[group] = _quantize_group(params[group], mode, consume)
    return out


def _quantize_group(src: Dict[str, Any], mode: str, consume: bool
                    ) -> Dict[str, Any]:
    if consume:
        new_layers: Dict[str, Any] = {}
        for k in list(src.keys()):
            v = src.pop(k)
            if k in QUANT_KEYS and not is_quantized(v):
                new_layers[k] = quantize_weight(v, mode)
                # block so the source buffer is actually dead before the
                # next leaf allocates (dispatch runs ahead of the device)
                jax.block_until_ready(
                    jax.tree.leaves(new_layers[k])[0]
                )
                del v
            else:
                new_layers[k] = v
        return new_layers
    return {
        k: (quantize_weight(v, mode)
            if (k in QUANT_KEYS and not is_quantized(v)) else v)
        for k, v in src.items()
    }


def param_bytes(params: Dict[str, Any]) -> int:
    """Total HBM bytes of a params pytree (quantized or not)."""
    return sum(
        leaf.dtype.itemsize * leaf.size for leaf in jax.tree.leaves(params)
    )
