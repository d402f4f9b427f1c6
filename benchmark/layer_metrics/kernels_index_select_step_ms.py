"""Device time of the selection's kernels (all layers) in one decode step:
the seconds of the operations named `dgi_index_score_step.<n>` and
`dgi_index_threshold_step.<n>` in the traced slice over the steps of the
`decode_multi` programs that ran in it. The gather that lays a row's index
keys out in context order before them is an XLA fusion the trace does not
name and is not in it. A ragged round's calls carry the names without
`_step` (`kernels.index_select_round_ms`). A program with no such kernels
gives nothing to read."""

from harness import scans

KERNELS = ("dgi_index_score_step", "dgi_index_threshold_step")


def seconds(run):
    return sum(scans.op_seconds(run, k) for k in KERNELS)


def read(run):
    secs, steps = seconds(run), scans.slice_steps(run)
    return 1e3 * secs / steps if secs and steps else None
