"""`kernels.mla_ragged_roofline` for a hybrid model: the absorbed
latent-attention kernel's share of its roofline in ragged rounds, where
only the layers the configuration lists are latent (`harness/shapes_kda.py`
counts those; `harness/shapes_mla.py` every layer). The least time the chip
needs for the (query, cached token) pairs the rounds held (operations over
the bf16 peak; the rows' cached tokens read once a round over the HBM peak
if that were more) over the seconds of the operations named
`dgi_mla_ragged.<n>`.

The kernel's seconds come from the traced slice; the pairs are counted by
the engine at each round's build over the window (`mla_pairs_ragged`,
`mla_context_tokens_ragged`), carried to the slice's rounds as
`kernels.mla_ragged_roofline` carries them. A model with no
linear-attention layer (no `kda_chunks_ragged`) gives nothing to read."""

from harness import scans, shapes, shapes_kda
from harness.window import delta

KERNEL = "dgi_mla_ragged"
NAME = "kernels.mla_ragged_roofline.hybrid"


def read(run):
    seconds, rounds = scans.op_seconds(run, KERNEL), scans.slice_rounds(run)
    win = run["win"]
    pairs = delta(win, "engine", "mla_pairs_ragged")
    live = delta(win, "engine", "ragged_positions_live")
    win_rounds = delta(win, "engine", "ragged_rounds")
    if not (seconds and rounds and pairs and live and win_rounds
            and run["peaks"] and delta(win, "engine", "kda_chunks_ragged")):
        return None
    live_slice = sum(int(m["live_prompt_tokens"]) + int(m["decode_rows"])
                     for m in rounds)
    cached = delta(win, "engine", "mla_context_tokens_ragged") / win_rounds
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_kda.latent_attention_flops(cfg, pairs / live * live_slice),
        shapes_kda.latent_rows_bytes(cfg, cached * len(rounds)),
        run["peaks"],
    )
    run["notes"][NAME] = {
        "bound": need["bound"],
        "least_round_ms": 1e3 * need["seconds"] / len(rounds),
        "pairs_a_live_position": pairs / live,
        "live_positions_a_round_slice": live_slice / len(rounds),
        "live_positions_a_round_window": live / win_rounds,
    }
    return 100.0 * need["seconds"] / seconds
