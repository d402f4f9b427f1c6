"""Device time of the absorbed latent-attention kernel (all layers) in one
ragged round: the seconds of the operations named `dgi_mla_ragged.<n>` in
the traced slice over the `ragged_round` programs that ran in it. The
kernel carries that name in a round (a piece's queries gathered into tiles
of eight beside the decode rows) and `dgi_mla_decode.<n>` in a scan step.
A program whose kernel has no such name gives nothing to read."""

from harness import scans

KERNEL = "dgi_mla_ragged"


def read(run):
    seconds, rounds = scans.op_seconds(run, KERNEL), scans.slice_rounds(run)
    return 1e3 * seconds / len(rounds) if seconds and rounds else None
