"""Device time of the grouped expert matmul (gate, up and down of every
layer) in one decode step: the seconds of the operations named
`dgi_moe_gmm_step.<n>` in the traced slice over the steps of the
`decode_multi` programs that ran in it, as
`kernels.decode_attention_step_ms` is built. The kernel carries that name
in a decode step and `dgi_moe_gmm.<n>` in a ragged round, so a round's
expert time is not in it. A program whose kernel has no such name gives
nothing to read."""

from harness.layers import modules_named

KERNEL = "dgi_moe_gmm_step"


def seconds_and_steps(run):
    ops = (run.get("trace") or {}).get("op_seconds") or {}
    seconds = sum(s for name, s in ops.items()
                  if name.split(".")[0] == KERNEL)
    steps = sum(int(m["steps"]) for m in modules_named(run, "decode_multi")
                if m.get("steps"))
    return seconds, steps


def read(run):
    seconds, steps = seconds_and_steps(run)
    return 1e3 * seconds / steps if seconds and steps else None
