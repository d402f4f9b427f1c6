"""Operations and bytes of a dense model whose every layer holds a
state-space (Mamba-2, SSD) mixer beside GQA attention: from shapes and from
what its rounds held.

**The step kernel.** A live row's step through one layer's mixer must read
and write the row's state (``heads x P x N`` float32, twice), read and write
the convolution's tail (``taps - 1`` rows of ``x | B | C`` values), read the
row's ``dt x`` (a head's ``P`` values) and ``B``, ``C`` (a group's ``N``
each) in float32 as the kernel takes them, and write its output: 8.47 MB at
the published widths, 99 % of it the state. The operations (three passes
over the state) are far under the bytes' time: the bytes are the roofline.

**The chunk kernel.** A chunk of ``Q`` tokens of a head takes the group's
``Q x N`` ``B`` and ``C``, the head's ``P x Q`` scaled input and its decay,
and gives a ``Q x P`` output: two matmuls against the ``P x N`` state that
stays on the chip between a segment's chunks; a segment's first chunk loads
the state and its last stores it. The in-chunk term runs before the kernel,
batched over chunks, and is not the kernel's.

**The step.** A decode step must read every weight the chip holds once (the
mixer's two projections, attention's four and the MLP's three as int8 with
a float32 scale an output channel; the step sizes' columns, the convolution,
norms and the head's slice in bf16), the K/V rows its rows attended and the
new row written, and the state of its live rows, read and written, in every
layer.
"""

from __future__ import annotations

from typing import Any, Dict

ACT_BYTES = 2       # bf16 cache rows, tails and activations
F32 = 4


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    g, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    ds = int(cfg["mamba_d_ssm"])
    return {
        "h": int(cfg["hidden_size"]), "nh": int(cfg["num_attention_heads"]),
        "nkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "i": int(cfg["intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
        "sh": int(cfg["mamba_n_heads"]), "sp": int(cfg["mamba_d_head"]),
        "sn": n, "sg": g, "taps": int(cfg["mamba_d_conv"]), "ds": ds,
        "conv": ds + 2 * g * n, "Q": int(cfg["mamba_chunk_size"]),
    }


# --------------------------------------------------------------------- #
# the state pool's two kernels
# --------------------------------------------------------------------- #

def state_row_bytes(cfg: Dict[str, Any]) -> int:
    """One sequence's state in one layer."""
    s = dims(cfg)
    return s["sh"] * s["sp"] * s["sn"] * F32


def tail_row_bytes(cfg: Dict[str, Any]) -> int:
    s = dims(cfg)
    return (s["taps"] - 1) * s["conv"] * ACT_BYTES


def ssd_step_bytes(cfg: Dict[str, Any], row_layer_steps: float) -> float:
    """Bytes ``row_layer_steps`` (live row x step x layer) must move: the
    state and the tail read and written, ``dt x`` in and the output out a
    head, ``B`` and ``C`` a group, in float32."""
    s = dims(cfg)
    a_row = 2 * state_row_bytes(cfg) + 2 * tail_row_bytes(cfg) \
        + (2 * s["ds"] + 2 * s["sg"] * s["sn"]) * F32
    return row_layer_steps * a_row


def ssd_step_flops(cfg: Dict[str, Any], row_layer_steps: float) -> float:
    """The decay, the rank-one write and ``S C``: three passes of a
    multiply and an add over the state."""
    s = dims(cfg)
    return row_layer_steps * 3 * 2 * s["sh"] * s["sp"] * s["sn"]


def ssd_chunk_bytes(cfg: Dict[str, Any], chunks: float, segments: float
                    ) -> float:
    """Bytes the chunk kernel must move in ONE layer for ``chunks`` chunks
    of ``segments`` segments: a chunk's ``B`` and ``C`` a group, its scaled
    input, decay and output a head, a segment's state in and out."""
    s = dims(cfg)
    q = s["Q"]
    a_chunk = F32 * (2 * s["sg"] * q * s["sn"]
                     + s["sh"] * (2 * q * s["sp"] + 1))
    return chunks * a_chunk + segments * 2 * state_row_bytes(cfg)


def ssd_chunk_flops(cfg: Dict[str, Any], chunks: float) -> float:
    """Operations of the pass in ONE layer: ``C S^T`` and ``(x dt)^T B``
    (``Q x P x N`` each), a head."""
    s = dims(cfg)
    return chunks * s["sh"] * 2 * 2 * s["Q"] * s["sp"] * s["sn"]


# --------------------------------------------------------------------- #
# the whole decode step
# --------------------------------------------------------------------- #

def _int8(fan_in: int, out: int) -> int:
    """An int8 matrix as stored: a byte a weight, a float32 scale an output
    channel."""
    return fan_in * out + F32 * out


def mixer_bytes(cfg: Dict[str, Any]) -> int:
    """One layer's mixer weights as stored."""
    s = dims(cfg)
    h, ds, conv, sh = s["h"], s["ds"], s["conv"], s["sh"]
    return (_int8(h, ds + conv) + _int8(ds, h)         # z | x | B | C, out
            + h * sh * ACT_BYTES                       # the step sizes
            + (s["taps"] + 1) * conv * ACT_BYTES       # convolution, bias
            + ds * ACT_BYTES                           # the gated norm
            + 3 * sh * F32)                            # A_log, D, dt_bias


def attention_bytes(cfg: Dict[str, Any]) -> int:
    s = dims(cfg)
    h, q, kv = s["h"], s["nh"] * s["d"], s["nkv"] * s["d"]
    return _int8(h, q) + 2 * _int8(h, kv) + _int8(q, h)


def step_weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Weights every decode step reads, by part (all layers)."""
    s = dims(cfg)
    return {
        "mixer": s["L"] * mixer_bytes(cfg),
        "attention": s["L"] * attention_bytes(cfg),
        "mlp": s["L"] * (2 * _int8(s["h"], s["i"]) + _int8(s["i"], s["h"])),
        "norms": (2 * s["L"] + 1) * s["h"] * ACT_BYTES,
        "head": s["V"] * s["h"] * ACT_BYTES,
    }


def kv_row_bytes(cfg: Dict[str, Any]) -> int:
    """A cached token's K and V over all layers."""
    s = dims(cfg)
    return s["L"] * 2 * s["nkv"] * s["d"] * ACT_BYTES


def decode_step_bytes(cfg: Dict[str, Any], rows: float,
                      context_tokens: float) -> Dict[str, float]:
    """Bytes one decode step must move: ``rows`` live sequences that
    attended ``context_tokens`` cached tokens in all (in each layer)."""
    s = dims(cfg)
    out = dict(step_weight_bytes(cfg))
    out["kv_read"] = context_tokens * kv_row_bytes(cfg)
    out["kv_write"] = rows * kv_row_bytes(cfg)
    out["state"] = ssd_step_bytes(cfg, rows * s["L"])
    out["embedding_rows"] = rows * s["h"] * ACT_BYTES
    out["total"] = sum(out.values())
    return out


def decode_step_flops(cfg: Dict[str, Any], rows: float,
                      context_tokens: float) -> float:
    """Operations of that step: a row through every matrix (a multiply-add
    a stored weight), its attention over its cached tokens (score and
    value, every query head) and its state updates."""
    s = dims(cfg)
    params = s["L"] * (
        s["h"] * (2 * s["ds"] + 2 * s["sg"] * s["sn"] + s["sh"])
        + s["ds"] * s["h"] + 2 * s["h"] * s["nh"] * s["d"]
        + 2 * s["h"] * s["nkv"] * s["d"] + 3 * s["h"] * s["i"]
    ) + s["V"] * s["h"]
    return rows * 2 * params \
        + s["L"] * context_tokens * 4 * s["nh"] * s["d"] \
        + ssd_step_flops(cfg, rows * s["L"])
