"""Share of the engine rounds that were ragged rounds (some prompt chunk
rode with the decode rows, every decode row got one token) and not
multi-step decode scans: `ragged_rounds` over `decode_rounds`, window
delta."""

from harness.window import delta


def read(run):
    rounds = delta(run["win"], "batcher", "decode_rounds")
    return 100.0 * delta(run["win"], "batcher", "ragged_rounds") / rounds \
        if rounds else None
