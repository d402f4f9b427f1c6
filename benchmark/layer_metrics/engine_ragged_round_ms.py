"""Device time of one ragged round at the widest bucket the cell's traffic
reaches: median over the executions of the `ragged_round` program whose
widest prompt piece fell into that bucket. It is what a co-scheduled stream waits
for its next token, and what a prompt pays per chunk."""

from harness.layers import widest_ragged
from harness.metrics import percentile


def read(run):
    mods = widest_ragged(run)
    return 1e3 * percentile([m["seconds"] for m in mods], 50) if mods else None
