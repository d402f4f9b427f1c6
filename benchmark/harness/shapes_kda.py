"""Operations and bytes of a hybrid model whose layers are gated delta-rule
linear attention (a fixed-size state a sequence) or latent attention (pages),
with a share of its routed experts held here: from shapes and from what its
rounds held.

**The step kernel.** A live row's step through one linear-attention layer
must read and write the row's state (``heads x d x d`` float32, twice),
read and write the convolution's tail (``taps - 1`` rows of ``3 x heads x
d`` values), read the row's q, k, v, g (float32 as the kernel takes them)
and its write strength, and write its output: 4.27 MB at the published
widths, 98 % of it the state. The operations (a few passes over the state)
are far under the bytes' time: the bytes are the roofline.

**The chunk kernel.** A 64-token chunk of a head takes four ``64 x d``
operands, the ``64 x 64`` in-chunk matrix and the chunk's decay, and gives a
``64 x d`` output: five matmuls against the ``d x d`` state that stays on
the chip between a segment's chunks; a segment's first chunk loads the state
and its last stores it. The in-chunk solve that prepares the operands runs
before the kernel, batched over chunks, and is not the kernel's.

**The latent layers and the held experts** count as `harness/shapes_mla.py`
counts them, for the latent layers the configuration lists (7 of 27, not
every layer) and the held experts under this family's key (``num_experts``
beside ``expert_share``).

**The step.** A decode step must read every weight the chip holds but the
routed experts, of which only those that received a row; the latent rows its
rows attended, in the latent layers only; and the state of its live rows,
read and written, in the others. Int8 matrices carry a float32 scale an
output channel; embedding, head, router, ``W_UK`` / ``W_UV``, the write
strength's matrix and the convolution are bf16.
"""

from __future__ import annotations

from typing import Any, Dict

ACT_BYTES = 2       # bf16 cache rows, tails and activations
F32 = 4
CHUNK = 64


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    share = cfg.get("expert_share") or {}
    held = int(cfg.get("num_experts") or 0)
    lin = cfg["linear_attn_config"]
    full = len(lin["full_attn_layers"])
    layers = int(cfg["num_hidden_layers"])
    return {
        "h": int(cfg["hidden_size"]),
        "nh": int(cfg["num_attention_heads"]),
        "latent": int(cfg["kv_lora_rank"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "dn": int(cfg["qk_nope_head_dim"]),
        "dv": int(cfg["v_head_dim"]),
        "kh": int(lin["num_heads"]), "kd": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "L": layers, "Lm": full, "Lk": layers - full,
        "i": int(cfg["intermediate_size"]),
        "mi": int(cfg.get("moe_intermediate_size") or 0),
        "lead": int(cfg.get("first_k_dense_replace") or 0),
        "V": int(cfg["vocab_size"]),
        "held": held,                       # routed experts stored here
        "E": int(share.get("of", held)),    # the router's width
        "shared": int(cfg.get("num_shared_experts") or 0),
    }


# --------------------------------------------------------------------- #
# the state pool's two kernels
# --------------------------------------------------------------------- #

def state_row_bytes(cfg: Dict[str, Any]) -> int:
    """One sequence's state in one linear-attention layer."""
    s = dims(cfg)
    return s["kh"] * s["kd"] * s["kd"] * F32


def tail_row_bytes(cfg: Dict[str, Any]) -> int:
    s = dims(cfg)
    return (s["taps"] - 1) * 3 * s["kh"] * s["kd"] * ACT_BYTES


def kda_step_bytes(cfg: Dict[str, Any], row_layer_steps: float) -> float:
    """Bytes ``row_layer_steps`` (live row x step x linear-attention layer)
    must move: the state and the tail read and written, q / k / v / g in
    and the output out in float32, the write strength."""
    s = dims(cfg)
    p = s["kh"] * s["kd"]
    a_row = 2 * state_row_bytes(cfg) + 2 * tail_row_bytes(cfg) \
        + 5 * p * F32 + s["kh"] * F32
    return row_layer_steps * a_row


def kda_step_flops(cfg: Dict[str, Any], row_layer_steps: float) -> float:
    """Decay, ``k^T S``, the rank-one write and ``q^T S``: four passes of a
    multiply and an add over the state."""
    s = dims(cfg)
    return row_layer_steps * 4 * 2 * s["kh"] * s["kd"] * s["kd"]


def kda_chunk_bytes(cfg: Dict[str, Any], chunks: float, segments: float
                    ) -> float:
    """Bytes the chunk kernel must move in ONE layer for ``chunks`` chunks
    of ``segments`` segments: a chunk's operands in and its output out, a
    segment's state in and out."""
    s = dims(cfg)
    a_chunk = s["kh"] * F32 * (5 * CHUNK * s["kd"] + CHUNK * CHUNK + s["kd"])
    return chunks * a_chunk + segments * 2 * state_row_bytes(cfg)


def kda_chunk_flops(cfg: Dict[str, Any], chunks: float) -> float:
    """Operations of the pass in ONE layer: ``w S``, ``qd S`` and ``kd^T U``
    (``64 x d x d`` each) and ``b U`` (``64 x 64 x d``), a head."""
    s = dims(cfg)
    d = s["kd"]
    return chunks * s["kh"] * 2 * (3 * CHUNK * d * d + CHUNK * CHUNK * d)


# --------------------------------------------------------------------- #
# the whole decode step
# --------------------------------------------------------------------- #

def _int8(fan_in: int, out: int) -> int:
    """An int8 matrix as stored: a byte a weight, a float32 scale an output
    channel."""
    return fan_in * out + F32 * out


def expert_bytes(cfg: Dict[str, Any]) -> int:
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


def latent_rows_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Bytes the latent layers (``Lm`` of the model's) must read to attend
    ``context_tokens`` cached tokens in all: each token's row once a
    latent layer."""
    s = dims(cfg)
    return s["Lm"] * context_tokens * (s["latent"] + s["rope"]) * ACT_BYTES


def latent_attention_flops(cfg: Dict[str, Any], pairs: float) -> float:
    """Operations of ``pairs`` (query, cached token) pairs in the latent
    layers: every head's score over the whole row and its value over the
    latent part."""
    s = dims(cfg)
    return s["Lm"] * pairs * 2 * s["nh"] * (2 * s["latent"] + s["rope"])


def kda_attention_bytes(cfg: Dict[str, Any]) -> int:
    """One linear-attention layer's attention weights as stored."""
    s = dims(cfg)
    h, p, d = s["h"], s["kh"] * s["kd"], s["kd"]
    return (_int8(h, 3 * p) + _int8(p, h)              # q | k | v, o
            + 2 * (_int8(h, d) + _int8(d, p))          # the low-rank gates
            + h * s["kh"] * ACT_BYTES                  # write strength
            + s["taps"] * 3 * p * ACT_BYTES            # convolution
            + (s["kh"] + p) * F32)                     # A_log, dt_bias


def mla_attention_bytes(cfg: Dict[str, Any]) -> int:
    """One latent layer's attention weights as stored (no query low-rank)."""
    s = dims(cfg)
    h, nh = s["h"], s["nh"]
    return (_int8(h, nh * (s["dn"] + s["rope"]))
            + _int8(h, s["latent"] + s["rope"]) + _int8(nh * s["dv"], h)
            + nh * s["latent"] * (s["dn"] + s["dv"]) * ACT_BYTES)


def step_weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Weights every decode step reads whatever the router chose, by part
    (all layers)."""
    s = dims(cfg)
    expert_layers = s["L"] - s["lead"] if s["E"] else 0
    dense = 2 * _int8(s["h"], s["i"]) + _int8(s["i"], s["h"])
    return {
        "kda_attention": s["Lk"] * kda_attention_bytes(cfg),
        "mla_attention": s["Lm"] * mla_attention_bytes(cfg),
        "dense_mlp": (s["L"] - expert_layers) * dense,
        "shared_expert": expert_layers * s["shared"] * expert_bytes(cfg),
        "router": expert_layers * (s["h"] * s["E"] * ACT_BYTES
                                   + s["E"] * F32),
        "head": s["V"] * s["h"] * ACT_BYTES,
    }


def decode_step_bytes(cfg: Dict[str, Any], rows: float,
                      context_tokens: float, active_experts: float,
                      pairs: float) -> Dict[str, float]:
    """Bytes one decode step must move: ``rows`` live sequences that
    attended ``context_tokens`` cached tokens in all (in each latent
    layer), ``pairs`` of their (token, expert) pairs on ``active_experts``
    held experts (summed over the step's expert layers)."""
    s = dims(cfg)
    out = dict(step_weight_bytes(cfg))
    out["held_experts"] = held_experts_bytes(cfg, active_experts, pairs)
    out["latent_rows"] = latent_rows_bytes(cfg, context_tokens)
    out["state"] = kda_step_bytes(cfg, rows * s["Lk"])
    out["embedding_rows"] = rows * s["h"] * ACT_BYTES
    out["total"] = sum(out.values())
    return out


def decode_step_flops(cfg: Dict[str, Any], rows: float,
                      context_tokens: float, pairs: float) -> float:
    """Operations of that step: a row through every matrix it meets (a
    multiply-add a stored weight, the routed experts by their pairs), its
    latent attention (every head's score over the whole row, its value over
    the latent part) and its state updates."""
    s = dims(cfg)
    w = step_weight_bytes(cfg)
    bf16 = s["Lm"] * s["nh"] * s["latent"] * (s["dn"] + s["dv"])
    params = (w["kda_attention"] + w["mla_attention"] - bf16 * ACT_BYTES
              + w["dense_mlp"] + w["shared_expert"]) \
        + bf16 + (w["router"] + w["head"]) / ACT_BYTES
    return rows * 2 * params + held_experts_flops(cfg, pairs) \
        + latent_attention_flops(cfg, context_tokens) \
        + kda_step_flops(cfg, rows * s["Lk"])
