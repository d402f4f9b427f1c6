"""Device time of the state-space mixer's step kernel (all layers) in one
decode step: the seconds of the operations named `dgi_ssd_step.<n>` in the
traced slice over the steps of the `decode_multi` programs that ran in it,
as `kernels.kda_step_ms` is built. The chunk form of a ragged round is
`dgi_ssd_chunk.<n>`. A program whose kernel has no such name (every model
without a mixer, the parent of the PR that added it) gives nothing to
read."""

from harness import scans

KERNEL = "dgi_ssd_step"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    return 1e3 * seconds / steps if seconds and steps else None
