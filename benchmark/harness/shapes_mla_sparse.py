"""Operations and bytes of a latent-attention (MLA) model under a learned
indexer whose selection most layers borrow (GLM-5.2's IndexShare), from the
configuration file's published sizes and from what its rounds held. The
dense parts (weights a step reads, the held experts) are ``shapes_mla``'s.

The counts follow the model's equations and the cache's granularity, not
what an implementation executes:

- **The selected walk.** A query attends ``min(context, index_topk)``
  cached tokens a layer, in EVERY layer (a shared layer attends the
  selection of the full layer before it): that many (query, token) pairs
  are attention's operations. A cached token lies in a page of
  ``block_size`` tokens and a page is what a copy can address, so the bytes
  attention must read are the pages that hold a selected token, whole
  (``fetched_tokens``: the engine counts them on the device from the
  selection itself), ``kv_lora_rank + qk_rope_head_dim`` values a token.
  Pad lanes (640 stored for 576), a page's unselected tokens' operations
  and the walk's last partial group are the implementation's.
- **The selection.** Only a ``full`` layer scores: one index key read and
  one score written a cached token a row, ``index_n_heads`` dot products
  of ``index_head_dim``. The threshold's 32 counting passes are the
  implementation's.
"""

from __future__ import annotations

from typing import Any, Dict

from . import shapes_mla

ACT_BYTES = 2       # bf16 cache rows, index keys and activations
SCORE_BYTES = 4     # a float32 index score


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    s = dict(shapes_mla.dims(cfg))
    kinds = list(cfg["indexer_types"])[:s["L"]]
    s.update({
        "hi": int(cfg["index_n_heads"]), "di": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
        "full": kinds.count("full"), "borrowing": kinds.count("shared"),
    })
    return s


def selected_attention_bytes(cfg: Dict[str, Any], fetched_tokens: float
                             ) -> float:
    """Bytes the model's layers must read for selections whose pages hold
    ``fetched_tokens`` cached tokens in all (one layer's count; every layer
    walks a selection): each such token's row once a layer."""
    s = dims(cfg)
    return s["L"] * fetched_tokens * (s["latent"] + s["rope"]) * ACT_BYTES


def selected_attention_flops(cfg: Dict[str, Any], pairs: float) -> float:
    """Operations of ``pairs`` (query, selected token) pairs (one layer's
    count), every layer: ``shapes_mla.attention_flops``."""
    return shapes_mla.attention_flops(cfg, pairs)


def index_select_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Bytes the FULL layers' selections must move for queries that could
    attend ``context_tokens`` cached tokens in all: each token's index key
    read and its score written."""
    s = dims(cfg)
    return s["full"] * context_tokens * (s["di"] * ACT_BYTES + SCORE_BYTES)


def index_select_flops(cfg: Dict[str, Any], context_tokens: float) -> float:
    s = dims(cfg)
    return s["full"] * context_tokens * 2 * s["hi"] * s["di"]


def indexer_weight_bytes(cfg: Dict[str, Any]) -> float:
    """The full layers' indexers: the query projection from the query
    latent int8 with its scales, the key and head-weight projections bf16,
    the key's LayerNorm."""
    s = dims(cfg)
    return s["full"] * (
        shapes_mla._int8(s["rq"], s["hi"] * s["di"])
        + s["h"] * (s["di"] + s["hi"]) * ACT_BYTES + 2 * s["di"] * ACT_BYTES)


def decode_step_bytes(cfg: Dict[str, Any], rows: float,
                      fetched_tokens: float, context_tokens: float,
                      active_experts: float, pairs: float
                      ) -> Dict[str, float]:
    """Bytes one decode step must read: ``shapes_mla.decode_step_bytes``
    with the selected pages in place of every cached row, and beside them
    the indexers' weights and the full layers' index keys and scores."""
    out = dict(shapes_mla.step_weight_bytes(cfg))
    out["indexer"] = indexer_weight_bytes(cfg)
    out["held_experts"] = shapes_mla.held_experts_bytes(
        cfg, active_experts, pairs)
    out["selected_latents"] = selected_attention_bytes(cfg, fetched_tokens)
    out["index"] = index_select_bytes(cfg, context_tokens)
    out["embedding_rows"] = rows * dims(cfg)["h"] * ACT_BYTES
    out["total"] = sum(out.values())
    return out


def decode_step_flops(cfg: Dict[str, Any], rows: float,
                      selected_tokens: float, context_tokens: float,
                      pairs: float) -> float:
    """Operations of that step: ``shapes_mla.decode_step_flops`` over the
    selected pairs, the indexers' projections and their scores."""
    s = dims(cfg)
    indexer = s["full"] * (s["rq"] * s["hi"] * s["di"]
                           + s["h"] * (s["di"] + s["hi"]))
    return shapes_mla.decode_step_flops(cfg, rows, selected_tokens, pairs) \
        + rows * 2 * indexer + index_select_flops(cfg, context_tokens)
