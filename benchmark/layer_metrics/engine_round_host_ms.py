"""The host's share of an engine round: seconds the engine thread spent
building a round (block reservation, the row batch, pending pool ops,
uploads), dispatching it (the jitted call) and committing its tokens, over
the rounds of the window. The wait for the device (`round_readback_s`) is
left out: this is the time the chip can only cover by running ahead. The
engine's own time counters (`runtime/flight.py` `span`), window delta; a
program without them gives nothing to read."""

from harness.window import delta

PHASES = ("round_build_s", "round_dispatch_s", "round_commit_s")


def read(run):
    rounds = delta(run["win"], "engine", "rounds")
    if not rounds:
        return None
    return 1e3 * sum(delta(run["win"], "engine", k) for k in PHASES) / rounds
