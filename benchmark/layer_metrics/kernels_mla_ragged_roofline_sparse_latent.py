"""Share of its roofline the absorbed latent-attention kernel reaches in
ragged rounds under a selection: the least time the chip needs for the
(query, selected token) pairs the rounds held
(`shapes_mla_sparse.selected_attention_flops` over the bf16 peak; the rows'
cached tokens read once a round over the HBM peak if that were more: a
piece's 256 queries select among them all) over the seconds of the
operations named `dgi_mla_ragged_selected.<n>`.

The kernel's seconds come from the traced slice; the pairs are counted by
the engine at each round's build over the window
(`index_selected_pairs_ragged`, `mla_context_tokens_ragged`). The window's
pairs a live position (`ragged_positions_live`) are carried to the live
positions of the slice's annotated rounds, its cached tokens a round to
their number. A program whose kernel has no such name gives nothing to
read."""

from harness import scans, shapes, shapes_mla, shapes_mla_sparse
from harness.window import delta

KERNEL = "dgi_mla_ragged_selected"


def read(run):
    seconds, rounds = scans.op_seconds(run, KERNEL), scans.slice_rounds(run)
    win = run["win"]
    pairs = delta(win, "engine", "index_selected_pairs_ragged")
    live = delta(win, "engine", "ragged_positions_live")
    win_rounds = delta(win, "engine", "ragged_rounds")
    if not (seconds and rounds and pairs and live and win_rounds
            and run["peaks"]):
        return None
    live_slice = sum(int(m["live_prompt_tokens"]) + int(m["decode_rows"])
                     for m in rounds)
    cached = delta(win, "engine", "mla_context_tokens_ragged") / win_rounds
    need = shapes.roofline_s(
        shapes_mla_sparse.selected_attention_flops(
            run["config"], pairs / live * live_slice),
        shapes_mla.attention_bytes(run["config"], cached * len(rounds)),
        run["peaks"],
    )
    run["notes"]["kernels.mla_ragged_roofline.sparse_latent"] = {
        "bound": need["bound"],
        "least_round_ms": 1e3 * need["seconds"] / len(rounds),
        "kernel_round_ms": 1e3 * seconds / len(rounds),
        "selected_pairs_a_live_position": pairs / live,
        "live_positions_a_round_slice": live_slice / len(rounds),
    }
    return 100.0 * need["seconds"] / seconds
