"""`kernels.mla_decode_roofline.sparse_latent` for a model whose latent
layers are of two kinds: the selected walk of the FULL layers alone (three
of nine at the cut), `shapes_mla_window.selected_attention_*` over the
device time in a step of the operations named `dgi_mla_decode_selected.<n>`
(the sliding layers' calls carry another name and are
`kernels.mla_window_roofline`'s).

The kernel's time a step comes from the traced slice; what a step had to
fetch and attend from the window (`index_fetched_tokens_scan`, the mean
over the layers that walk a selection, and `index_selected_tokens_scan`)
over the steps of its scans. A program without pages per layer kind
(`attn_row_steps_scan`) or without the counters gives nothing to read."""

from harness import scans, shapes, shapes_mla_window
from harness.window import delta

KERNEL = "dgi_mla_decode_selected"


def read(run):
    if "attn_row_steps_scan" not in run["win"]["c1"]["engine"]:
        return None
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    fetched = delta(run["win"], "engine", "index_fetched_tokens_scan")
    if not (seconds and steps and win_steps and fetched and run["peaks"]):
        return None
    selected = delta(run["win"], "engine", "index_selected_tokens_scan")
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_mla_window.selected_attention_flops(cfg, selected / win_steps),
        shapes_mla_window.selected_attention_bytes(cfg, fetched / win_steps),
        run["peaks"])
    run["notes"]["kernels.mla_decode_roofline.mixed_latent"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "kernel_step_ms": 1e3 * seconds / steps,
        "fetched_tokens_a_step": fetched / win_steps,
        "selected_tokens_a_step": selected / win_steps,
    }
    return 100.0 * need["seconds"] * steps / seconds
