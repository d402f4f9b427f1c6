"""Device time of the gated delta-rule chunk kernel (all linear-attention
layers) in one ragged round: the seconds of the operations named
`dgi_kda_chunk.<n>` in the traced slice over the `ragged_round` programs
that ran in it, as `kernels.mla_ragged_round_ms` is built. The kernel is
the pass over the state (a segment's chunks in order, each from its row's
stored state); the in-chunk solve that prepares its operands runs before
it, in the round's program, and is not in this time. A program whose kernel
has no such name gives nothing to read."""

from harness import scans

KERNEL = "dgi_kda_chunk"


def read(run):
    seconds, rounds = scans.op_seconds(run, KERNEL), scans.slice_rounds(run)
    return 1e3 * seconds / len(rounds) if seconds and rounds else None
