"""Operations and bytes of a GQA decoder with a learned indexer (sparse
attention) and a many-expert MLP, from the configuration file's published
sizes (Keye-VL-2.0-30B-A3B's language model).

The counts follow the model's equations, not what an implementation
executes: a query attends ``min(context, topk)`` cached tokens, so THAT
many K and V rows are what attention must read, whatever pages a kernel
chose to walk under a mask; the indexer must read one index key a cached
token and write one score for it. Reading every page of a row's block
table, a 128-lane row for a 64-value key, the ``keep`` array between the
selection and the attention kernel: the implementation's cost, which shows
as a low share. Stored widths: matmul weights int8 with one float32 scale
an output channel; the indexer's key and head-weight projections, router,
embedding, head, K/V and index keys bf16.
"""

from __future__ import annotations

from typing import Any, Dict

from . import shapes_moe

ACT_BYTES = 2       # bf16 activations, K/V rows and index keys
SCORE_BYTES = 4     # a float32 index score


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    sa = cfg["sa_config"]
    return {
        "h": int(cfg["hidden_size"]), "nh": int(cfg["num_attention_heads"]),
        "nkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "i": int(cfg["moe_intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
        "E": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "hi": int(sa["indexer_num_heads"]), "di": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"]),
    }


def moe_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as ``shapes_moe`` reads one: the width of ONE
    expert under ``intermediate_size`` (this config names it
    ``moe_intermediate_size``; its ``intermediate_size`` no layer reads)."""
    return {**cfg, "intermediate_size": cfg["moe_intermediate_size"]}


def index_select_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Bytes the selection must move, all layers, for queries that could
    attend ``context_tokens`` cached tokens in all (summed over the
    queries): each token's index key read and its score written."""
    s = dims(cfg)
    return s["L"] * context_tokens * (s["di"] * ACT_BYTES + SCORE_BYTES)


def index_select_flops(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Operations of those scores: ``hi`` dot products of ``di`` a pair."""
    s = dims(cfg)
    return s["L"] * context_tokens * 2 * s["hi"] * s["di"]


def selected_kv_bytes(cfg: Dict[str, Any], selected_tokens: float) -> float:
    """Bytes attention must read, all layers, for ``selected_tokens``
    (query, selected cached token) pairs of one-token queries: a K row and
    a V row of every KV head a pair."""
    s = dims(cfg)
    return s["L"] * selected_tokens * 2 * s["nkv"] * s["d"] * ACT_BYTES


def selected_attention_flops(cfg: Dict[str, Any], pairs: float) -> float:
    """Operations of attention over ``pairs`` (query, selected token)
    pairs, all layers: q.k and p.v for every query head."""
    s = dims(cfg)
    return s["L"] * pairs * 2 * 2 * s["nh"] * s["d"]


def layer_fixed_bytes(cfg: Dict[str, Any]) -> float:
    """Bytes of one layer's weights every step reads whatever the router
    chose: attention and the indexer's query projection int8 with their
    scales, the indexer's narrow projections and the router bf16."""
    s = dims(cfg)
    h, q, kv = s["h"], s["nh"] * s["d"], s["nkv"] * s["d"]
    int8 = h * q + 2 * h * kv + q * h + h * s["hi"] * s["di"]
    scales = 4 * (q + 2 * kv + h + s["hi"] * s["di"])
    bf16 = ACT_BYTES * h * (s["di"] + s["hi"] + s["E"])
    return int8 + scales + bf16


def decode_step_bytes(cfg: Dict[str, Any], rows: float,
                      selected_tokens: float, context_tokens: float,
                      active_experts: float, pairs: float
                      ) -> Dict[str, float]:
    """What one decode step must move. ``selected_tokens`` and
    ``context_tokens`` are the step's sums over its rows (one layer's);
    ``active_experts`` and ``pairs`` its sums over the layers' calls."""
    s = dims(cfg)
    out = {
        "fixed_weights": s["L"] * layer_fixed_bytes(cfg),
        "experts": shapes_moe.routed_layer_bytes(
            moe_config(cfg), active_experts, pairs),
        "head": ACT_BYTES * s["V"] * s["h"],
        "selected_kv": selected_kv_bytes(cfg, selected_tokens),
        "index": index_select_bytes(cfg, context_tokens),
        "rows": rows * s["L"] * 4 * s["h"] * ACT_BYTES,
    }
    out["total"] = sum(out.values())
    return out


def decode_step_flops(cfg: Dict[str, Any], rows: float,
                      selected_tokens: float, context_tokens: float,
                      pairs: float) -> float:
    s = dims(cfg)
    h, q, kv = s["h"], s["nh"] * s["d"], s["nkv"] * s["d"]
    dense = 2 * rows * (
        s["L"] * (h * q + 2 * h * kv + q * h
                  + h * (s["hi"] * s["di"] + s["di"] + s["hi"] + s["E"]))
        + s["V"] * h)
    return dense + shapes_moe.routed_layer_flops(moe_config(cfg), pairs) \
        + selected_attention_flops(cfg, selected_tokens) \
        + index_select_flops(cfg, context_tokens)
