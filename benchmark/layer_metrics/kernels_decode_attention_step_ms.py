"""Device time of the fused decode-attention kernel (write the step's K/V
rows, attend over the paged context; all layers) in one decode step: the
seconds of the operations named `dgi_paged_decode.<n>` in the traced
slice over the steps of the `decode_multi` programs that ran in it. The
operations of a scan cut by the slice's edge are counted up to the edge
and its steps are not (under 2 % at a 5 s slice of 45 ms scans). A program
whose kernel has no such name gives nothing to read."""

from harness.layers import modules_named

KERNEL = "dgi_paged_decode"


def read(run):
    ops = (run.get("trace") or {}).get("op_seconds") or {}
    seconds = sum(s for name, s in ops.items()
                  if name.split(".")[0] == KERNEL)
    steps = sum(int(m["steps"]) for m in modules_named(run, "decode_multi")
                if m.get("steps"))
    return 1e3 * seconds / steps if seconds and steps else None
