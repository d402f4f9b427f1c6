"""GSPMD sharding rules for the Llama params pytree, KV pools, and batch state.

Megatron-style tensor parallelism expressed as NamedShardings — XLA inserts
the all-reduces over the ``model`` ICI axis (no hand-written collectives in
the forward pass). This replaces the reference's TP-by-delegation
(``worker/engines/llm_vllm.py:56`` just forwards ``tensor_parallel_size`` to
vLLM's process groups; SURVEY §2.2 flags it as passthrough-only).

Layout (params from ``models/llama.py``; L = stacked layer axis):

==================  ===========================  ==========================
param               shape                        spec
==================  ===========================  ==========================
embedding           [V, H]                       replicated
layers.attn_norm    [L, H]                       replicated
layers.wq           [L, H, Nh*D]                 shard out dim on ``model``
layers.wk / wv      [L, H, Nkv*D]                shard out dim on ``model``
layers.q_norm/k_norm [L, Nh*D] / [L, Nkv*D]     shard with their projection
layers.wo           [L, Nh*D, H]                 shard in dim on ``model``
layers.w_gate/up    [L, H, I]                    shard out dim on ``model``
layers.w_down       [L, I, H]                    shard in dim on ``model``
final_norm          [H]                          replicated
lm_head             [V, H]                       replicated
kv pools            [L, N, Hkv, Bk, D]           shard Hkv on ``model``
tokens/tables/lens  [B, ...]                     shard B on ``data``
==================  ===========================  ==========================

Pipeline (``stage``) sharding slices the L axis instead — see
``parallel/pipeline.py``.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_gpu_inference_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_MODEL,
    AXIS_SEQ,
)


def _ns(mesh: Mesh, *spec) -> NamedSharding:
    # drop axis names the mesh doesn't carry (trivial axes removed)
    clean = tuple(s if (s is None or s in mesh.axis_names) else None for s in spec)
    return NamedSharding(mesh, P(*clean))


def param_shardings(mesh: Mesh) -> Dict[str, Any]:
    """NamedSharding pytree matching ``models.llama.init_params`` layout."""
    return {
        "embedding": _ns(mesh, None, None),
        "layers": {
            "attn_norm": _ns(mesh, None, None),
            "wq": _ns(mesh, None, None, AXIS_MODEL),
            "wk": _ns(mesh, None, None, AXIS_MODEL),
            "wv": _ns(mesh, None, None, AXIS_MODEL),
            "wo": _ns(mesh, None, AXIS_MODEL, None),
            # Qwen2-style attention biases follow their projection's out dim
            "bq": _ns(mesh, None, AXIS_MODEL),
            "bk": _ns(mesh, None, AXIS_MODEL),
            "bv": _ns(mesh, None, AXIS_MODEL),
            # OLMoE QK-norm vectors follow their projection's out dim too
            # (the norm's mean over the whole width is XLA's all-reduce)
            "q_norm": _ns(mesh, None, AXIS_MODEL),
            "k_norm": _ns(mesh, None, AXIS_MODEL),
            "mlp_norm": _ns(mesh, None, None),
            "w_gate": _ns(mesh, None, None, AXIS_MODEL),
            "w_up": _ns(mesh, None, None, AXIS_MODEL),
            "w_down": _ns(mesh, None, AXIS_MODEL, None),
            # MoE: expert parallelism = shard the E axis over ``model``;
            # each chip computes its local experts, XLA all-reduces the
            # combine (models/llama.py _moe_mlp). Router replicated — every
            # chip needs all routing weights.
            "w_router": _ns(mesh, None, None, None),
            "we_gate": _ns(mesh, None, AXIS_MODEL, None, None),
            "we_up": _ns(mesh, None, AXIS_MODEL, None, None),
            "we_down": _ns(mesh, None, AXIS_MODEL, None, None),
        },
        "final_norm": _ns(mesh, None),
        "lm_head": _ns(mesh, None, None),
    }


# what an attention kernel's operands are sharded by where it runs a shard of
# heads a chip (``jax.shard_map`` over ``model``): the pools' spec
# (:func:`kv_sharding`) and the column-sharded q / k / v's. Everything else
# (block tables, positions, lengths, the layer index, a write plan) is
# replicated, ``P()``
POOL_HEADS = P(None, None, AXIS_MODEL, None, None)   # [L, N, Hkv, Bk, D]
CHUNK_HEADS = P(None, None, AXIS_MODEL, None)        # [B, S, heads, D]
ROW_HEADS = P(None, AXIS_MODEL, None)                # [T, heads, D]


def kv_sharding(mesh: Mesh) -> NamedSharding:
    """KV pools [L, N, Hkv, Bk, D]: heads sharded over ``model`` so each TP
    shard attends with its own KV heads — pages never cross chips."""
    return NamedSharding(mesh, POOL_HEADS)


class HeadShards(NamedTuple):
    """The mesh an engine's heads are sharded over and nothing else: what
    ``models/llama.forward_chunk`` needs to run an attention kernel a shard
    (``jax.shard_map`` over ``model``, operands by the specs above), as a
    value it can read inside a trace, where the mesh itself cannot be
    seen. A shard's call is the one-chip kernel at ``Hkv / size`` KV
    heads."""

    mesh: Mesh

    @property
    def size(self) -> int:
        """Shards of the head axis."""
        return self.mesh.shape[AXIS_MODEL]


def head_shards(mesh: Optional[Mesh]) -> Optional[HeadShards]:
    """The head sharding of a mesh whose only sharded axis is ``model``;
    None on one chip and under any other axis (``seq``: the pools' block
    axis is sharded too and attention goes through
    ``parallel/ring_attention.py``'s partial-softmax ops)."""
    if mesh is None or AXIS_MODEL not in mesh.shape or any(
            n > 1 for axis, n in mesh.shape.items() if axis != AXIS_MODEL):
        return None
    return HeadShards(mesh)


def kv_sharding_seq(mesh: Mesh) -> NamedSharding:
    """KV pools with the BLOCK axis sharded over ``seq`` (heads still over
    ``model``): per-device pool memory scales 1/seq — the storage side of
    long-context serving (decode reads via
    ``ring_attention.seq_parallel_paged_decode_attention``; page writes are
    GSPMD-partitioned scatters, verified to keep this sharding without
    replication)."""
    return _ns(mesh, None, AXIS_SEQ, AXIS_MODEL, None, None)


def kv_scale_sharding(mesh: Mesh) -> NamedSharding:
    """int8-KV scale pools [L, N, Bk, D]: one scale per (page, token)
    shared across KV heads, so there is no head axis to shard — the scale
    pool rides replicated next to head-sharded data pools (it is Hkv x
    smaller, so replication costs less HBM than data-pool sharding saves).
    The quantize amax reduces over ALL heads (a cross-shard reduce XLA
    lowers to an all-reduce-max over ``model``), keeping scales — and
    therefore the stored int8 — bit-identical to a single-chip engine."""
    return _ns(mesh, None, None, None, None)


def kv_scale_sharding_seq(mesh: Mesh) -> NamedSharding:
    """int8-KV scale pools under seq-sharded data pools: the scale pool's
    BLOCK axis shards over ``seq`` exactly like its data pool, so a (page,
    token)'s scale lives on the same device as its int8 rows and the
    shard_map partial-softmax ops dequantize locally — no scale traffic."""
    return _ns(mesh, None, AXIS_SEQ, None, None)


def batch_shardings(mesh: Mesh) -> Dict[str, NamedSharding]:
    return {
        "tokens": _ns(mesh, AXIS_DATA, None),       # [B, S]
        "positions": _ns(mesh, AXIS_DATA, None),    # [B, S]
        "block_tables": _ns(mesh, AXIS_DATA, None), # [B, M]
        "kv_lens": _ns(mesh, AXIS_DATA),            # [B]
        "vec": _ns(mesh, AXIS_DATA),                # any per-seq vector
        "replicated": _ns(mesh),
    }


def quantized_leaf_rules(rule: NamedSharding, leaf: Dict[str, Any]) -> Dict[str, Any]:
    """Expand a weight's sharding rule over a quantized ``{"qw","scale"}``
    sub-dict: qw keeps the weight spec; the scale drops axis names wherever
    its (size-1, reduced) dims can't carry a shard."""
    spec = tuple(rule.spec) + (None,) * (leaf["qw"].ndim - len(tuple(rule.spec)))
    scale_spec = tuple(
        s if (i < leaf["scale"].ndim and leaf["scale"].shape[i] > 1) else None
        for i, s in enumerate(spec)
    )
    return {
        "qw": rule,
        "scale": NamedSharding(rule.mesh, P(*scale_spec)),
    }


def prune_rules(rules: Dict[str, Any], params: Dict[str, Any]) -> Dict[str, Any]:
    """Restrict a sharding-rule pytree to the keys this model actually has
    (lm_head absent when tied; bias keys absent for bias-free families), and
    expand rules over quantized weight sub-dicts so the rule tree's structure
    matches the params tree exactly. Shared by the TP and pipeline pruners so
    they cannot drift."""
    rules = dict(rules)
    rules["layers"] = {
        k: (quantized_leaf_rules(v, params["layers"][k])
            if isinstance(params["layers"][k], dict) else v)
        for k, v in rules["layers"].items() if k in params["layers"]
    }
    if "lm_head" not in params:
        rules.pop("lm_head", None)
    return rules


def shard_params(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """device_put the params pytree onto the mesh under the TP rules.

    (With single-host multi-device this is a local reshard; multi-host uses
    the same rules via jax.make_array_from_process_local_data in the loader.)
    """
    return jax.device_put(params, prune_rules(param_shardings(mesh), params))


def shard_kv(kv: Dict[str, jax.Array], mesh: Mesh) -> Dict[str, jax.Array]:
    s = kv_sharding(mesh)
    return {k: jax.device_put(v, s) for k, v in kv.items()}
