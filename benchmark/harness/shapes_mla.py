"""Operations and bytes of a latent-attention (MLA) model that holds a share
of its routed experts, from shapes and from what its rounds held.

**Absorbed attention.** A cached token is one row of ``kv_lora_rank +
qk_rope_head_dim`` values a layer (512 + 64 at the published widths, bf16).
Every head's query, folded into the latent space, meets that one row: a
score is a dot over the whole row, a value the row's latent part, so a
(query, cached token) pair costs ``2 x heads x (row + latent)`` operations a
layer against ``row x 2`` bytes read once for all heads: 242 operations a
byte at 128 heads and one query a row, beside the chip's ridge (197 TFLOP/s
over 819 GB/s = 240); a round's piece brings many queries to a row's cache
and is bound by the operations. The counts follow the equations: the pool
row's pad lanes (640 stored for 576 values), masked tail positions of a
page group, a cache re-read for every tile of eight queries and the softmax
are the implementation's cost and show as a low share.

**The held experts and the step.** A decode step must read every weight the
chip holds for the layers it runs but the routed experts, of which only
those that received a row: int8 matrices with a float32 scale an output
channel, ``W_UK`` / ``W_UV``, router, embedding and head bf16.
"""

from __future__ import annotations

from typing import Any, Dict

ACT_BYTES = 2       # bf16 cache rows and activations


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    share = cfg.get("expert_share") or {}
    held = int(cfg.get("n_routed_experts") or 0)
    return {
        "h": int(cfg["hidden_size"]),
        "nh": int(cfg["num_attention_heads"]),
        "latent": int(cfg["kv_lora_rank"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "rq": int(cfg["q_lora_rank"]),
        "dn": int(cfg["qk_nope_head_dim"]),
        "dv": int(cfg["v_head_dim"]),
        "i": int(cfg["intermediate_size"]),
        "mi": int(cfg.get("moe_intermediate_size") or 0),
        "L": int(cfg["num_hidden_layers"]),
        "lead": int(cfg.get("first_k_dense_replace") or 0),
        "V": int(cfg["vocab_size"]),
        "held": held,                       # routed experts stored here
        "E": int(share.get("of", held)),    # the router's width
        "shared": int(cfg.get("n_shared_experts") or 0),
    }


def attention_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Bytes the model's layers must read to attend ``context_tokens``
    cached tokens in all: each token's row once a layer."""
    s = dims(cfg)
    return s["L"] * context_tokens * (s["latent"] + s["rope"]) * ACT_BYTES


def attention_flops(cfg: Dict[str, Any], pairs: float) -> float:
    """Operations (2 per multiply-add) of ``pairs`` (query, cached token)
    pairs: every head's score over the whole row and its value over the
    latent part, a layer."""
    s = dims(cfg)
    return s["L"] * pairs * 2 * s["nh"] * (2 * s["latent"] + s["rope"])


def _int8(fan_in: int, out: int) -> int:
    """An int8 matrix as stored: a byte a weight, a float32 scale an output
    channel."""
    return fan_in * out + 4 * out


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """One routed (or shared) expert of one layer: gate and up ``[h, mi]``,
    down ``[mi, h]``."""
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
    h, nh = s["h"], s["nh"]
    attn = (_int8(h, s["rq"]) + _int8(s["rq"], nh * (s["dn"] + s["rope"]))
            + _int8(h, s["latent"] + s["rope"]) + _int8(nh * s["dv"], h)
            + nh * s["latent"] * (s["dn"] + s["dv"]) * ACT_BYTES)
    expert_layers = s["L"] - s["lead"] if s["E"] else 0
    dense = 2 * _int8(h, s["i"]) + _int8(s["i"], h)
    return {
        "attention": s["L"] * attn,
        "dense_mlp": (s["L"] - expert_layers) * dense,
        "shared_expert": expert_layers * s["shared"] * expert_bytes(cfg),
        "router": expert_layers * h * s["E"] * ACT_BYTES,
        "head": s["V"] * h * ACT_BYTES,
    }


def decode_step_bytes(cfg: Dict[str, Any], rows: float,
                      context_tokens: float, active_experts: float,
                      pairs: float) -> Dict[str, float]:
    """Bytes one decode step must read: ``rows`` sequences that attended
    ``context_tokens`` cached tokens in all, ``pairs`` of their (token,
    expert) pairs on ``active_experts`` held experts (summed over the
    step's expert layers)."""
    out = dict(step_weight_bytes(cfg))
    out["held_experts"] = held_experts_bytes(cfg, active_experts, pairs)
    out["latent_rows"] = attention_bytes(cfg, context_tokens)
    out["embedding_rows"] = rows * dims(cfg)["h"] * ACT_BYTES
    out["total"] = sum(out.values())
    return out


def decode_step_flops(cfg: Dict[str, Any], rows: float,
                      context_tokens: float, pairs: float) -> float:
    """Operations of that step: a row through every weight it meets (the
    int8 and bf16 matrices above but the routed experts, whose pairs are
    counted), and its attention."""
    w = step_weight_bytes(cfg)
    s = dims(cfg)
    # a byte a weight, but for the bf16 parts; scales are not multiplied
    params = (w["attention"] - s["L"] * s["nh"] * s["latent"]
              * (s["dn"] + s["dv"])) + w["dense_mlp"] + w["shared_expert"] \
        + (w["router"] + w["head"]) / ACT_BYTES
    return rows * 2 * params + held_experts_flops(cfg, pairs) \
        + attention_flops(cfg, context_tokens)
