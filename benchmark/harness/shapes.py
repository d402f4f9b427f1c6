"""Operations and bytes a round needs, from shapes alone.

The counts follow the layer equations of the Llama recipe (GQA attention
with optional QKV biases, SwiGLU MLP or top-k-of-E expert MLP), not what
any implementation happens to execute: padding, experts computed and then
masked, and re-reads are the implementation's cost and show as a low share.
Sizes are the published ``config.json`` keys of a configuration file.

Stored widths: matmul weights int8 (1 byte) with one float32 scale per
output channel, embedding/head/router/norms/biases bf16, KV pools bf16
unless ``kv_bytes`` says otherwise. ``tp`` divides what the program
shards over the ``model`` axis (heads, MLP columns, experts, KV heads);
embedding, head, router and norms are replicated, so they are not divided.
"""

from __future__ import annotations

from typing import Any, Dict


def _dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    h = int(cfg["hidden_size"])
    nh = int(cfg["num_attention_heads"])
    return {
        "h": h, "nh": nh,
        "nkv": int(cfg["num_key_value_heads"]),
        "d": int(cfg.get("head_dim") or h // nh),
        "i": int(cfg["intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]),
        "V": int(cfg["vocab_size"]),
        "E": int(cfg.get("num_local_experts") or 0),
        "k": int(cfg.get("num_experts_per_tok") or 0),
        "bias": 1 if cfg.get("attention_bias") else 0,
        "tied": 1 if cfg.get("tie_word_embeddings") else 0,
    }


def layer_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Matmul parameters of one layer: attention, the MLP a token passes
    through (``mlp_active``) and the MLP that is stored (``mlp_stored``)."""
    s = _dims(cfg)
    attn = 2 * s["h"] * s["nh"] * s["d"] + 2 * s["h"] * s["nkv"] * s["d"]
    one_mlp = 3 * s["h"] * s["i"]
    if s["E"]:
        router = s["h"] * s["E"]
        return {"attn": attn, "router": router,
                "mlp_active": s["k"] * one_mlp, "mlp_stored": s["E"] * one_mlp}
    return {"attn": attn, "router": 0,
            "mlp_active": one_mlp, "mlp_stored": one_mlp}


def total_params(cfg: Dict[str, Any]) -> int:
    s, lp = _dims(cfg), layer_params(cfg)
    per_layer = lp["attn"] + lp["router"] + lp["mlp_stored"] + 2 * s["h"] \
        + s["bias"] * (s["nh"] + 2 * s["nkv"]) * s["d"]
    head = 0 if s["tied"] else s["V"] * s["h"]
    return s["V"] * s["h"] + s["L"] * per_layer + head + s["h"]


def weight_bytes(cfg: Dict[str, Any], tp: int = 1) -> Dict[str, float]:
    """Bytes of weights one chip holds and reads once per round."""
    s, lp = _dims(cfg), layer_params(cfg)
    out_channels = (s["nh"] + 2 * s["nkv"]) * s["d"] + s["h"]
    if s["E"]:
        out_channels += s["E"] * (2 * s["i"] + s["h"])
    else:
        out_channels += 2 * s["i"] + s["h"]
    # one byte a matmul weight, a float32 scale a channel, bf16 biases
    sharded = lp["attn"] + lp["mlp_stored"] + out_channels * 4 \
        + s["bias"] * (s["nh"] + 2 * s["nkv"]) * s["d"] * 2
    replicated = lp["router"] * 2 + 2 * s["h"] * 2
    return {
        "layers": s["L"] * (sharded / tp + replicated),
        "head": s["V"] * s["h"] * 2 + s["h"] * 2,
    }


def kv_bytes_per_token(cfg: Dict[str, Any], tp: int = 1,
                       kv_bytes: int = 2) -> float:
    s = _dims(cfg)
    return 2 * s["L"] * (s["nkv"] / tp) * s["d"] * kv_bytes


def _ctx_read(cfg: Dict[str, Any], ctx: float, block: int) -> float:
    """Positions of context one query row reads: the window bounds it, and
    pages are read whole."""
    window = cfg.get("sliding_window")
    if window:
        ctx = min(ctx, float(window))
    return -(-ctx // block) * block


def decode_step_bytes(cfg: Dict[str, Any], rows: float, mean_ctx: float,
                      tp: int = 1, block: int = 16,
                      kv_bytes: int = 2) -> Dict[str, float]:
    """Bytes one chip must read for one decode step of ``rows`` sequences
    at ``mean_ctx`` tokens of context each: every weight once, the head
    once, each row's context from the KV pool, one new KV row written."""
    s = _dims(cfg)
    w = weight_bytes(cfg, tp)
    per_tok = kv_bytes_per_token(cfg, tp, kv_bytes)
    out = {
        "weights": w["layers"],
        "head": w["head"],
        "embedding_rows": rows * s["h"] * 2,
        "kv_read": rows * _ctx_read(cfg, mean_ctx, block) * per_tok,
        "kv_write": rows * per_tok,
    }
    out["total"] = sum(out.values())
    return out


def decode_step_flops(cfg: Dict[str, Any], rows: float, mean_ctx: float,
                      tp: int = 1) -> float:
    """Operations of one decode step on one chip (2 per multiply-add)."""
    s, lp = _dims(cfg), layer_params(cfg)
    ctx = min(mean_ctx, float(cfg["sliding_window"])) \
        if cfg.get("sliding_window") else mean_ctx
    per_row = s["L"] * (
        2 * (lp["attn"] + lp["mlp_active"]) / tp + 2 * lp["router"]
        + 4 * (s["nh"] / tp) * s["d"] * ctx
    ) + 2 * s["V"] * s["h"]
    return rows * per_row


def prefill_flops(cfg: Dict[str, Any], tokens: float, ctx_before: float = 0.0,
                  sampled_rows: float = 0.0, tp: int = 1) -> float:
    """Operations one chip needs for ``tokens`` live prompt tokens that
    follow ``ctx_before`` tokens of context already in the cache: the
    projections and the MLP the tokens pass through (top-k experts, not
    all), causal attention over what each token can see, and the head for
    the rows that sample."""
    s, lp = _dims(cfg), layer_params(cfg)
    seen = tokens * ctx_before + tokens * (tokens + 1) / 2.0
    window = cfg.get("sliding_window")
    if window and ctx_before + tokens > window:
        seen = min(seen, tokens * float(window))
    return s["L"] * (
        tokens * (2 * (lp["attn"] + lp["mlp_active"]) / tp
                  + 2 * lp["router"])
        + 4 * (s["nh"] / tp) * s["d"] * seen
    ) + sampled_rows * 2 * s["V"] * s["h"]


def dispatched_positions_flops(cfg: Dict[str, Any], batch: int, bucket: int,
                               tp: int = 1) -> float:
    """Operations of the dense work a ``[batch, bucket]`` round graph runs
    whatever is live in it: every position through the projections and
    through every stored expert."""
    s, lp = _dims(cfg), layer_params(cfg)
    return batch * bucket * s["L"] * (
        2 * (lp["attn"] + lp["mlp_stored"]) / tp + 2 * lp["router"]
    ) + batch * 2 * s["V"] * s["h"]


def roofline_s(flops: float, bytes_: float, peaks: Dict[str, Any]
               ) -> Dict[str, Any]:
    """The least time one chip could take, and which peak sets it."""
    t_mxu = flops / float(peaks["bf16_flops"])
    t_hbm = bytes_ / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_mxu, t_hbm), "mxu_s": t_mxu, "hbm_s": t_hbm,
            "bound": "mxu" if t_mxu >= t_hbm else "hbm"}
