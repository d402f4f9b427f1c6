"""Operations and bytes of a routed expert layer, from shapes and from what
the router chose.

``shapes.py`` counts every stored expert as read in a decode step. A routed
layer (top-k of E experts, computed as grouped matmuls over the rows each
expert received) needs less: the weights of the experts that received at
least one row, and the rows in and out. The counts follow the layer's
equations — ``sum_e p_e * down_e(silu(gate_e(x)) * up_e(x))`` over a
token's k experts — not what an implementation executes: tile padding,
the intermediate ``[rows, expert width]`` tensor written and read again,
and weight blocks fetched once per column tile are the implementation's
cost and show as a low share. Stored widths: expert matrices int8 with one
float32 scale per output channel, activations bf16.
"""

from __future__ import annotations

from typing import Any, Dict

ACT_BYTES = 2       # bf16 rows in and out


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "h": int(cfg["hidden_size"]),
        "i": int(cfg["intermediate_size"]),     # the width of one expert
        "L": int(cfg["num_hidden_layers"]),
        "E": int(cfg.get("num_experts") or cfg.get("num_local_experts")
                 or 0),
        "k": int(cfg.get("num_experts_per_tok") or 0),
    }


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of one expert of one layer as stored: gate and up ``[h, i]``,
    down ``[i, h]`` int8, and a float32 scale per output channel."""
    s = dims(cfg)
    return 3 * s["h"] * s["i"] + 4 * (2 * s["i"] + s["h"])


def routed_layer_bytes(cfg: Dict[str, Any], active_experts: float,
                       assignments: float) -> float:
    """Bytes expert-layer calls must move whose router chose
    ``active_experts`` distinct experts (summed over the calls) for
    ``assignments`` (token, expert) pairs: each chosen expert's weights
    once, each pair's row in and its row out."""
    s = dims(cfg)
    return active_experts * expert_bytes(cfg) \
        + assignments * 2 * s["h"] * ACT_BYTES


def routed_layer_flops(cfg: Dict[str, Any], assignments: float) -> float:
    """Operations of those calls (2 per multiply-add): three ``h x i``
    matrices a pair."""
    s = dims(cfg)
    return assignments * 3 * 2 * s["h"] * s["i"]


def expected_active_experts(cfg: Dict[str, Any], rows: float) -> float:
    """Experts that receive a row when ``rows`` tokens each choose k of E
    uniformly: ``E (1 - (1 - k/E) ** rows)``."""
    s = dims(cfg)
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** rows)
