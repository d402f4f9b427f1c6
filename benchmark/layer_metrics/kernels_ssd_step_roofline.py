"""Share of its roofline the state-space mixer's step kernel reaches in
scans: the least time the chip needs for what a scan step's live rows must
move through the layers' mixers (`shapes_ssd.ssd_step_bytes`: the state read
and written, the convolution's tail, the row's `dt x`, `B`, `C` and output,
over the HBM peak; the operations over the bf16 peak if that were more) over
the kernel's device time in a step (`kernels.ssd_step_ms`).

The kernel's time a step comes from the traced slice; the live row x step x
layer count from the window (`harness/scans.py` says why):
`ssd_row_steps_scan`, which the engine counts from the tokens each row
really emitted, over the steps of the window's scans (`scans_t<T>`). A row
that a scan masks is copied through by the kernel and is not in the count:
that copy is the implementation's cost and shows as a lower share."""

from harness import scans, shapes, shapes_ssd
from harness.window import delta

KERNEL = "dgi_ssd_step"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    row_steps = delta(run["win"], "engine", "ssd_row_steps_scan")
    if not (seconds and steps and win_steps and row_steps and run["peaks"]):
        return None
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_ssd.ssd_step_flops(cfg, row_steps / win_steps),
        shapes_ssd.ssd_step_bytes(cfg, row_steps / win_steps), run["peaks"])
    run["notes"]["kernels.ssd_step_roofline"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "row_layer_steps_a_step": row_steps / win_steps,
    }
    return 100.0 * need["seconds"] * steps / seconds
