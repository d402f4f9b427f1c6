"""Device time of one decode step: the executions of the `decode_multi`
program in the traced slice (XLA module events of the first chip), their
seconds over the steps they ran. The step count of an execution is the
scan length the engine call asked for, which the benchmark's annotation
around `TPUEngine.decode_multi` carries."""

from harness.layers import modules_named


def read(run):
    mods = [m for m in modules_named(run, "decode_multi") if m.get("steps")]
    steps = sum(int(m["steps"]) for m in mods)
    return 1e3 * sum(m["seconds"] for m in mods) / steps if steps else None
