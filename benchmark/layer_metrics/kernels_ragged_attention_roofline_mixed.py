"""Share of the bf16 peak the ragged attention kernel reaches in the rounds
of a model of mixed attention kinds: the operations of the (query, key)
pairs inside causal reach in the full layers and inside the window too in
the sliding ones (`shapes_window.attention_flops`: 4 x heads of the kind x
128 a pair; the engine's `attn_pairs_ragged_full` / `_window`) over the
seconds of the operations named `dgi_ragged_attention.<n>`.

The kernel's seconds come from the traced slice; the pairs a live position
from the window's counters, scaled to the slice's live positions
(`harness/scans.py`)."""

from harness import scans, shapes, shapes_window
from harness.window import delta

KERNEL = "dgi_ragged_attention"


def read(run):
    seconds, rounds = scans.op_seconds(run, KERNEL), scans.slice_rounds(run)
    win = run["win"]
    full = delta(win, "engine", "attn_pairs_ragged_full")
    live = delta(win, "engine", "ragged_positions_live")
    if not (seconds and rounds and full and live and run["peaks"]):
        return None
    windowed = delta(win, "engine", "attn_pairs_ragged_window")
    live_slice = sum(int(m["live_prompt_tokens"]) + int(m["decode_rows"])
                     for m in rounds)
    need = shapes.roofline_s(
        shapes_window.attention_flops(
            run["config"], full / live * live_slice,
            windowed / live * live_slice),
        0.0, run["peaks"])
    run["notes"]["kernels.ragged_attention_roofline.mixed"] = {
        "least_round_ms": 1e3 * need["seconds"] / len(rounds),
        "full_pairs_a_live_position": full / live,
        "window_pairs_a_live_position": windowed / live,
        "live_positions_a_round_slice": live_slice / len(rounds),
    }
    return 100.0 * need["seconds"] / seconds
