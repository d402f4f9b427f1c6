"""Operations and bytes of a latent-attention (MLA) model whose layers are of
two kinds (dots3-note-prev: ``full`` layers under an indexer beside
``sliding`` layers with a head count, latent ranks and head sizes of their
own that attend a window), from the configuration file's published sizes and
from what its rounds held. The held experts are ``shapes_mla``'s.

The counts follow the model's equations and the cache's granularity, not
what an implementation executes:

- **The windowed walk.** A query of a sliding layer attends ``min(context,
  sliding_window_size)`` cached tokens: that many (query, token) pairs are
  its operations, ``2 x swa heads x (row + latent)`` each (every head's score
  over the whole ``swa_kv_lora_rank + swa_qk_rope_head_dim`` row and its
  value over the latent part), and each attended token's row is what must be
  read, once a layer. Pad lanes (1,152 stored for 1,088), the page group's
  tokens before the window and the masked tail are the implementation's.
- **The selected walk.** A query of a full layer attends ``min(context,
  index_topk)`` cached tokens; the bytes are the pages that hold a selected
  token, whole (``fetched_tokens``: counted on the device, a full layer's).
- **The selection.** A full layer scores: one index key read and one score
  written a cached token a row, ``index_n_heads`` dot products of
  ``index_head_dim``.
"""

from __future__ import annotations

from typing import Any, Dict

from . import shapes_mla

ACT_BYTES = 2       # bf16 cache rows, index keys and activations
SCORE_BYTES = 4     # a float32 index score
_int8 = shapes_mla._int8


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    layers = int(cfg["num_hidden_layers"])
    kinds = ["full" if t.startswith("full") else "sliding"
             for t in cfg["layer_types"]][:layers]

    def kind(prefix: str) -> Dict[str, int]:
        return {
            "nh": int(cfg[prefix + "num_attention_heads"]),
            "rq": int(cfg[prefix + "q_lora_rank"]),
            "latent": int(cfg[prefix + "kv_lora_rank"]),
            "dn": int(cfg[prefix + "qk_nope_head_dim"]),
            "rope": int(cfg[prefix + "qk_rope_head_dim"]),
            "dv": int(cfg[prefix + "v_head_dim"]),
        }

    s = dict(shapes_mla.dims(cfg))
    s.update({
        "full": kind(""), "sliding": kind("swa_"),
        "n_full": kinds.count("full"), "n_sliding": kinds.count("sliding"),
        "window": int(cfg["sliding_window_size"]),
        "gate": cfg.get("attention_gate_type") == "headwise",
        "hi": int(cfg["index_n_heads"]), "di": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
    })
    return s


def _pair_flops(k: Dict[str, int]) -> int:
    return 2 * k["nh"] * (2 * k["latent"] + k["rope"])


def _row_bytes(k: Dict[str, int]) -> int:
    return (k["latent"] + k["rope"]) * ACT_BYTES


def window_attention_bytes(cfg: Dict[str, Any], window_tokens: float
                           ) -> float:
    """Bytes the sliding layers must read to attend ``window_tokens`` cached
    tokens in all (one layer's count): each token's row once a layer."""
    s = dims(cfg)
    return s["n_sliding"] * window_tokens * _row_bytes(s["sliding"])


def window_attention_flops(cfg: Dict[str, Any], pairs: float) -> float:
    """Operations of ``pairs`` (query, token inside its window) pairs (one
    layer's count), every sliding layer."""
    s = dims(cfg)
    return s["n_sliding"] * pairs * _pair_flops(s["sliding"])


def selected_attention_bytes(cfg: Dict[str, Any], fetched_tokens: float
                             ) -> float:
    """Bytes the full layers must read for selections whose pages hold
    ``fetched_tokens`` cached tokens in all (one layer's count)."""
    s = dims(cfg)
    return s["n_full"] * fetched_tokens * _row_bytes(s["full"])


def selected_attention_flops(cfg: Dict[str, Any], pairs: float) -> float:
    s = dims(cfg)
    return s["n_full"] * pairs * _pair_flops(s["full"])


def index_select_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    s = dims(cfg)
    return s["n_full"] * context_tokens * (s["di"] * ACT_BYTES + SCORE_BYTES)


def index_select_flops(cfg: Dict[str, Any], context_tokens: float) -> float:
    s = dims(cfg)
    return s["n_full"] * context_tokens * 2 * s["hi"] * s["di"]


def attention_weight_bytes(cfg: Dict[str, Any], kind: str) -> int:
    """One layer's attention of ``kind``: the int8 projections with their
    scales, ``W_UK`` / ``W_UV`` and the head-wise gate bf16."""
    s = dims(cfg)
    k, h = s[kind], s["h"]
    return (_int8(h, k["rq"]) + _int8(k["rq"], k["nh"] * (k["dn"] + k["rope"]))
            + _int8(h, k["latent"] + k["rope"]) + _int8(k["nh"] * k["dv"], h)
            + k["nh"] * k["latent"] * (k["dn"] + k["dv"]) * ACT_BYTES
            + (h * k["nh"] * ACT_BYTES if s["gate"] else 0))


def indexer_weight_bytes(cfg: Dict[str, Any]) -> float:
    """The full layers' indexers: the query projection from the query
    latent int8 with its scales, the key and head-weight projections bf16,
    the key's LayerNorm."""
    s = dims(cfg)
    return s["n_full"] * (
        _int8(s["full"]["rq"], s["hi"] * s["di"])
        + s["h"] * (s["di"] + s["hi"]) * ACT_BYTES + 2 * s["di"] * ACT_BYTES)


def step_weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Weights every decode step reads whatever the router chose, by part
    (all layers)."""
    s = dims(cfg)
    h = s["h"]
    expert_layers = s["L"] - s["lead"]
    dense = 2 * _int8(h, s["i"]) + _int8(s["i"], h)
    return {
        "attention_full": s["n_full"] * attention_weight_bytes(cfg, "full"),
        "attention_sliding":
            s["n_sliding"] * attention_weight_bytes(cfg, "sliding"),
        "indexer": indexer_weight_bytes(cfg),
        "dense_mlp": s["lead"] * dense,
        "shared_expert":
            expert_layers * s["shared"] * shapes_mla.expert_bytes(cfg),
        "router": expert_layers * h * s["E"] * ACT_BYTES,
        "head": s["V"] * h * ACT_BYTES,
    }


def decode_step_bytes(cfg: Dict[str, Any], rows: float,
                      fetched_tokens: float, window_tokens: float,
                      context_tokens: float, active_experts: float,
                      pairs: float) -> Dict[str, float]:
    """Bytes one decode step must read: every weight but the routed experts
    once, the held experts that received a row, the pages that hold a
    selected latent in the full layers, the window's rows in the sliding
    ones, one index key and one score a cached token a full layer."""
    out = dict(step_weight_bytes(cfg))
    out["held_experts"] = shapes_mla.held_experts_bytes(
        cfg, active_experts, pairs)
    out["selected_latents"] = selected_attention_bytes(cfg, fetched_tokens)
    out["window_latents"] = window_attention_bytes(cfg, window_tokens)
    out["index"] = index_select_bytes(cfg, context_tokens)
    out["embedding_rows"] = rows * dims(cfg)["h"] * ACT_BYTES
    out["total"] = sum(out.values())
    return out


def decode_step_flops(cfg: Dict[str, Any], rows: float,
                      selected_tokens: float, window_tokens: float,
                      context_tokens: float, pairs: float) -> float:
    """Operations of that step: a row through every weight it meets (a
    multiply-add a weight; the routed experts by their pairs), the two
    kinds' attention and the full layers' index scores."""
    w = step_weight_bytes(cfg)
    s = dims(cfg)
    # bf16 parts: two bytes a weight; int8 parts: about one (scales apart)
    bf16 = sum(s[n] * s[k]["nh"] * s[k]["latent"] * (s[k]["dn"] + s[k]["dv"])
               for n, k in (("n_full", "full"), ("n_sliding", "sliding")))
    params = (w["attention_full"] + w["attention_sliding"] - bf16
              + w["indexer"] + w["dense_mlp"] + w["shared_expert"]
              + (w["router"] + w["head"]) / ACT_BYTES)
    return rows * 2 * params + shapes_mla.held_experts_flops(cfg, pairs) \
        + selected_attention_flops(cfg, selected_tokens) \
        + window_attention_flops(cfg, window_tokens) \
        + index_select_flops(cfg, context_tokens)
