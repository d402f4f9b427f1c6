"""Share of its roofline the paged decode kernel reaches in the scans of a
model with an indexer: the least time the HBM needs for the K and V rows
the SPARSE model must read (`shapes_sparse_attn.selected_kv_bytes`:
`min(context, topk)` rows of every KV head a row-step a layer, the engine's
`index_selected_tokens_scan`) over the seconds of the operations named
`dgi_paged_decode.<n>` in a step. The pages the kernel chose to read (every
page of the row's table, under the selection's mask) are not the yardstick:
reading them is what a low share shows.

The kernel's time a step comes from the traced slice, the selected tokens a
step from the window's counters over the steps of its scans
(`harness/scans.py`)."""

from harness import scans, shapes, shapes_sparse_attn
from harness.window import delta

KERNEL = "dgi_paged_decode"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    selected = delta(run["win"], "engine", "index_selected_tokens_scan")
    if not (seconds and steps and win_steps and selected and run["peaks"]):
        return None
    cfg, tokens = run["config"], selected / win_steps
    need = shapes.roofline_s(
        shapes_sparse_attn.selected_attention_flops(cfg, tokens),
        shapes_sparse_attn.selected_kv_bytes(cfg, tokens), run["peaks"])
    run["notes"]["kernels.decode_attention_roofline.sparse"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "selected_tokens_a_step": tokens,
    }
    return 100.0 * need["seconds"] * steps / seconds
