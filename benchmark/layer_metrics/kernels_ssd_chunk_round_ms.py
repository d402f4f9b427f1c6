"""Device time of the state-space mixer's chunk kernel (all layers) in one
ragged round: the seconds of the operations named `dgi_ssd_chunk.<n>` in the
traced slice over the `ragged_round` programs that ran in it, as
`kernels.kda_chunk_round_ms` is built. The kernel is the pass over the
state (a segment's chunks in order, each from its row's stored state); the
in-chunk term that needs no state runs before it, in the round's program
(scope `dgi_ssd_prepare`), and is not in this time. A program whose kernel
has no such name gives nothing to read."""

from harness import scans

KERNEL = "dgi_ssd_chunk"


def read(run):
    seconds, rounds = scans.op_seconds(run, KERNEL), scans.slice_rounds(run)
    return 1e3 * seconds / len(rounds) if seconds and rounds else None
