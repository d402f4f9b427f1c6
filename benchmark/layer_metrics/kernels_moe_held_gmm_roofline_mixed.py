"""Share of its roofline the grouped expert matmul reaches in the scans of
a K/V model that holds a share of its experts: the least time the HBM needs
for what a scan step's expert layers must move
(`shapes_window.held_experts_bytes`: the 9.44 MB of every held expert that
received a row, each pair's row in and out; operations over the bf16 peak
if that were more) over the seconds a step of the operations named
`dgi_moe_gmm_step.<n>` (`kernels.moe_scan_step_ms.tok`).
`kernels.moe_held_gmm_roofline` reads the latent recipe's configuration
keys (`harness/shapes_mla.py`).

The kernel's time a step comes from the traced slice; the experts read and
the pairs a step from the engine's scan counters over the window
(`moe_active_experts_scan`, `moe_assignments_scan`) over the steps of its
scans (`harness/scans.py`)."""

from harness import scans, shapes, shapes_window
from harness.window import delta

KERNEL = "dgi_moe_gmm_step"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    active = delta(run["win"], "engine", "moe_active_experts_scan")
    if not (seconds and steps and win_steps and active and run["peaks"]):
        return None
    pairs = delta(run["win"], "engine", "moe_assignments_scan")
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_window.held_experts_flops(cfg, pairs / win_steps),
        shapes_window.held_experts_bytes(cfg, active / win_steps,
                                         pairs / win_steps),
        run["peaks"],
    )
    run["notes"]["kernels.moe_held_gmm_roofline.mixed"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "active_experts_a_step": active / win_steps,
        "pairs_a_step": pairs / win_steps,
    }
    return 100.0 * need["seconds"] * steps / seconds
