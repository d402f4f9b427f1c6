"""Share of its roofline the absorbed latent-attention kernel reaches in
scans under a selection: the least time the chip needs for what a scan
step's attention must do (`shapes_mla_sparse`: the larger of the bytes of
the pages that hold a selected token over the HBM peak and the selected
pairs' absorbed operations over the bf16 peak, all nine layers: a shared
layer walks the selection it borrowed) over the device time in a step of
the operations named `dgi_mla_decode_selected.<n>`.

The kernel's time a step comes from the traced slice. What a step had to
fetch and attend comes from the window (`harness/scans.py` says why):
`index_fetched_tokens_scan` (counted on the device from the selections) and
`index_selected_tokens_scan`, over the steps of the window's scans. A
program whose kernel has no such name gives nothing to read."""

from harness import scans, shapes, shapes_mla_sparse
from harness.window import delta

KERNEL = "dgi_mla_decode_selected"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    fetched = delta(run["win"], "engine", "index_fetched_tokens_scan")
    if not (seconds and steps and win_steps and fetched and run["peaks"]):
        return None
    selected = delta(run["win"], "engine", "index_selected_tokens_scan")
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_mla_sparse.selected_attention_flops(cfg, selected / win_steps),
        shapes_mla_sparse.selected_attention_bytes(cfg, fetched / win_steps),
        run["peaks"],
    )
    run["notes"]["kernels.mla_decode_roofline.sparse_latent"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "kernel_step_ms": 1e3 * seconds / steps,
        "fetched_tokens_a_step": fetched / win_steps,
        "selected_tokens_a_step": selected / win_steps,
    }
    return 100.0 * need["seconds"] * steps / seconds
