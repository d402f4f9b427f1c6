"""Share of its roofline the ragged attention kernel reaches in the rounds
of a model with an indexer: the least time the chip needs for the (query,
SELECTED cached token) pairs the rounds held
(`shapes_sparse_attn.selected_attention_flops` over the bf16 peak) over the
seconds of the operations named `dgi_ragged_attention.<n>`. The pairs the
kernel computes under the selection's mask and throws away are the
implementation's cost.

The kernel's seconds come from the traced slice; the selected pairs are
counted by the engine at each round's build over the window
(`index_selected_pairs_ragged`). The window's pairs a live position
(`ragged_positions_live`) are carried to the live positions of the slice's
annotated rounds, as `kernels.mla_ragged_roofline` does it."""

from harness import scans, shapes, shapes_sparse_attn
from harness.window import delta

KERNEL = "dgi_ragged_attention"


def read(run):
    seconds, rounds = scans.op_seconds(run, KERNEL), scans.slice_rounds(run)
    win = run["win"]
    pairs = delta(win, "engine", "index_selected_pairs_ragged")
    live = delta(win, "engine", "ragged_positions_live")
    if not (seconds and rounds and pairs and live and run["peaks"]):
        return None
    live_slice = sum(int(m["live_prompt_tokens"]) + int(m["decode_rows"])
                     for m in rounds)
    need = shapes.roofline_s(
        shapes_sparse_attn.selected_attention_flops(
            run["config"], pairs / live * live_slice),
        0.0, run["peaks"])
    run["notes"]["kernels.ragged_attention_roofline.sparse"] = {
        "least_round_ms": 1e3 * need["seconds"] / len(rounds),
        "selected_pairs_a_live_position": pairs / live,
        "live_positions_a_round_slice": live_slice / len(rounds),
    }
    return 100.0 * need["seconds"] / seconds
