"""Device time of the selection's kernels (all layers) in one ragged round:
the seconds of the operations named `dgi_index_score.<n>` and
`dgi_index_threshold.<n>` in the traced slice over the `ragged_round`
programs that ran in it: a piece's 256 queries (and the decode rows beside
it) scored against their rows' cached index keys, and the 2,048th largest
score of each found by bisection. A program with no such kernels gives
nothing to read."""

from harness import scans

KERNELS = ("dgi_index_score", "dgi_index_threshold")


def read(run):
    secs = sum(scans.op_seconds(run, k) for k in KERNELS)
    rounds = scans.slice_rounds(run)
    return 1e3 * secs / len(rounds) if secs and rounds else None
