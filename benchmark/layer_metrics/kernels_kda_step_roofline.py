"""Share of its roofline the gated delta-rule step kernel reaches in scans:
the least time the chip needs for what a scan step's live rows must move
through the linear-attention layers (`shapes_kda.kda_step_bytes`: the state
read and written, the convolution's tail, the row's q / k / v / g and write
strength, over the HBM peak; the operations over the bf16 peak if that were
more) over the kernel's device time in a step (`kernels.kda_step_ms`).

The kernel's time a step comes from the traced slice; the live row x step x
layer count from the window (`harness/scans.py` says why):
`kda_row_steps_scan`, which the engine counts from the tokens each row
really emitted, over the steps of the window's scans (`scans_t<T>`). A row
that a scan masks is copied through by the kernel and is not in the count:
that copy is the implementation's cost and shows as a lower share."""

from harness import scans, shapes, shapes_kda
from harness.window import delta

KERNEL = "dgi_kda_step"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    row_steps = delta(run["win"], "engine", "kda_row_steps_scan")
    if not (seconds and steps and win_steps and row_steps and run["peaks"]):
        return None
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_kda.kda_step_flops(cfg, row_steps / win_steps),
        shapes_kda.kda_step_bytes(cfg, row_steps / win_steps), run["peaks"])
    run["notes"]["kernels.kda_step_roofline"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "row_layer_steps_a_step": row_steps / win_steps,
    }
    return 100.0 * need["seconds"] * steps / seconds
