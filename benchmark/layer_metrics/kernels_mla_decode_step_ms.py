"""Device time of the absorbed latent-attention kernel (all layers) in one
decode step: the seconds of the operations named `dgi_mla_decode.<n>` in
the traced slice over the steps of the `decode_multi` programs that ran in
it, as `kernels.decode_attention_step_ms` is built. The kernel carries that
name in a scan step and `dgi_mla_ragged.<n>` in a ragged round, so a
round's attention is not in it; the page write is `dgi_mla_write.<n>`. A
program whose kernel has no such name (a K/V model, the parent of the PR
that added it) gives nothing to read."""

from harness import scans

KERNEL = "dgi_mla_decode"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    return 1e3 * seconds / steps if seconds and steps else None
