"""Share of the positions ragged rounds dispatched (rows x bucket) that held
no token: 1 - `ragged_positions_live` / `ragged_positions_dispatched`, the
engine's counters, window delta. A live position is a decode row's one
token or a token of an admission's piece; the rest of the rectangle is
computed and thrown away."""

from harness.window import delta


def read(run):
    sent = delta(run["win"], "engine", "ragged_positions_dispatched")
    if not sent:
        return None
    return 100.0 * (1.0 - delta(run["win"], "engine",
                                "ragged_positions_live") / sent)
