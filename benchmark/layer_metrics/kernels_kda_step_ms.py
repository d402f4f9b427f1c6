"""Device time of the gated delta-rule step kernel (all linear-attention
layers) in one decode step: the seconds of the operations named
`dgi_kda_step.<n>` in the traced slice over the steps of the
`decode_multi` programs that ran in it, as `kernels.mla_decode_step_ms` is
built. The chunk form of a ragged round is `dgi_kda_chunk.<n>`. A program
whose kernel has no such name (every model without a state pool, the parent
of the PR that added it) gives nothing to read."""

from harness import scans

KERNEL = "dgi_kda_step"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    return 1e3 * seconds / steps if seconds and steps else None
