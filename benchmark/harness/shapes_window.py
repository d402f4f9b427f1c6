"""Operations and bytes of a GQA decoder whose layers mix window and full
attention, with a head count per kind, a dense first layer and then one
chip's share of a many-expert layer beside a shared expert, from the
configuration file's published sizes (Laguna-S-2.1).

The counts follow the model's equations, not what an implementation
executes: a one-token query of a full layer attends its whole context and a
sliding layer's the last ``sliding_window`` tokens of it, so THAT many K and
V rows are what attention must read, whatever pages a kernel walked; a piece's
query attends the keys inside causal reach (and inside the window). Stored
widths: matmul weights int8 with one float32 scale an output channel; the
head-wise gate, router, embedding, head and K/V bf16.
"""

from __future__ import annotations

from typing import Any, Dict

ACT_BYTES = 2       # bf16 activations and K/V rows


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    L = int(cfg["num_hidden_layers"])
    kinds = [t == "full_attention" for t in cfg["layer_types"][:L]]
    heads = [int(n) for n in cfg["num_attention_heads_per_layer"][:L]]
    sparse = [t == "sparse" for t in cfg["mlp_layer_types"][:L]]
    return {
        "h": int(cfg["hidden_size"]), "nkv": int(cfg["num_key_value_heads"]),
        "d": int(cfg["head_dim"]), "L": L, "V": int(cfg["vocab_size"]),
        "i": int(cfg["intermediate_size"]),
        "mi": int(cfg["moe_intermediate_size"]),
        "ms": int(cfg.get("shared_expert_intermediate_size") or 0),
        "E": int((cfg.get("expert_share") or {}).get(
            "of", cfg["num_experts"])),
        "held": int(cfg["num_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "window": int(cfg["sliding_window"]),
        "full_layers": sum(kinds), "sliding_layers": L - sum(kinds),
        "full_heads": sum(n for n, f in zip(heads, kinds) if f),
        "sliding_heads": sum(n for n, f in zip(heads, kinds) if not f),
        "heads": heads, "sparse_layers": sum(sparse),
        "gate": cfg.get("gating") == "per-head",
    }


def _int8(rows: int, cols: int) -> float:
    """A stored int8 matrix with its float32 scale an output channel."""
    return rows * cols + 4 * cols


def kv_row_bytes(cfg: Dict[str, Any]) -> int:
    """A cached token's K and V rows in ONE layer: 4,096 B published."""
    s = dims(cfg)
    return 2 * s["nkv"] * s["d"] * ACT_BYTES


def attention_kv_bytes(cfg: Dict[str, Any], full_tokens: float,
                       window_tokens: float) -> float:
    """Bytes one-token queries must read, all layers: ``full_tokens``
    (query, cached token) pairs a full layer and ``window_tokens`` a
    sliding one (each a sum over the queries of ONE layer)."""
    s = dims(cfg)
    return kv_row_bytes(cfg) * (s["full_layers"] * full_tokens
                                + s["sliding_layers"] * window_tokens)


def attention_flops(cfg: Dict[str, Any], full_pairs: float,
                    window_pairs: float) -> float:
    """Operations of attention over those pairs, all layers: q.k and p.v
    for every query head of the layer's kind."""
    s = dims(cfg)
    return 2 * 2 * s["d"] * (s["full_heads"] * full_pairs
                             + s["sliding_heads"] * window_pairs)


def expert_bytes(cfg: Dict[str, Any]) -> float:
    """One routed (or shared) expert's weights: 9.44 MB published."""
    s = dims(cfg)
    return 2 * _int8(s["h"], s["mi"]) + _int8(s["mi"], s["h"])


def held_experts_bytes(cfg: Dict[str, Any], active_experts: float,
                       pairs: float) -> float:
    """Bytes expert-layer calls must move whose pairs on held experts
    (``pairs``) fell on ``active_experts`` distinct ones, summed over the
    calls: each such expert's weights once, each pair's row in and out."""
    return active_experts * expert_bytes(cfg) \
        + pairs * 2 * dims(cfg)["h"] * ACT_BYTES


def held_experts_flops(cfg: Dict[str, Any], pairs: float) -> float:
    s = dims(cfg)
    return pairs * 3 * 2 * s["h"] * s["mi"]


def step_weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Weights every decode step reads whatever the router chose, by part
    (all layers)."""
    s = dims(cfg)
    h, kv = s["h"], s["nkv"] * s["d"]
    attn = sum(_int8(h, n * s["d"]) + 2 * _int8(h, kv) + _int8(n * s["d"], h)
               + (h * n * ACT_BYTES if s["gate"] else 0)
               for n in s["heads"])
    dense = 2 * _int8(h, s["i"]) + _int8(s["i"], h)
    shared = 2 * _int8(h, s["ms"]) + _int8(s["ms"], h) if s["ms"] else 0
    return {
        "attention": attn,
        "dense_mlp": (s["L"] - s["sparse_layers"]) * dense,
        "shared_expert": s["sparse_layers"] * shared,
        "router": s["sparse_layers"] * h * s["E"] * ACT_BYTES,
        "head": s["V"] * h * ACT_BYTES,
    }


def decode_step_bytes(cfg: Dict[str, Any], rows: float, full_tokens: float,
                      window_tokens: float, active_experts: float,
                      pairs: float) -> Dict[str, float]:
    """What one decode step must move. ``full_tokens`` / ``window_tokens``
    are the step's sums over its rows (one layer's of each kind);
    ``active_experts`` and ``pairs`` its sums over the layers' calls."""
    out = step_weight_bytes(cfg)
    out["held_experts"] = held_experts_bytes(cfg, active_experts, pairs)
    out["kv_rows"] = attention_kv_bytes(cfg, full_tokens, window_tokens)
    out["embedding_rows"] = rows * dims(cfg)["h"] * ACT_BYTES
    out["total"] = sum(out.values())
    return out


def decode_step_flops(cfg: Dict[str, Any], rows: float, full_tokens: float,
                      window_tokens: float, pairs: float) -> float:
    """Operations of that step: a row through every weight it meets (the
    routed experts by their pairs), and its attention."""
    w = step_weight_bytes(cfg)
    # about a byte a weight, but for the bf16 parts
    params = w["attention"] + w["dense_mlp"] + w["shared_expert"] \
        + (w["router"] + w["head"]) / ACT_BYTES
    return rows * 2 * params + held_experts_flops(cfg, pairs) \
        + attention_flops(cfg, full_tokens, window_tokens)
