"""Share of its roofline the selection reaches in the scans of a model
whose full layers alone score: the least time the chip needs for what a
scan step's selections must move (`shapes_mla_sparse.index_select_bytes`:
one index key read and one score written a cached token a row a FULL layer,
over the HBM peak; the scores' operations over the bf16 peak if that were
more) over the device time in a step of the operations named
`dgi_index_score_step.<n>` and `dgi_index_threshold_step.<n>`. The 32
counting passes of the threshold are the implementation's cost and show as
a low share.

The kernels' time a step comes from the traced slice; the cached tokens a
step from the window (`index_context_tokens_scan`; the row-steps that
select nothing are left in: an upper bound of the work), over the steps of
the window's scans. A program without `index_layers_scored` (one whose
every layer scores: `kernels.index_select_roofline` is its metric) gives
nothing to read."""

from harness import scans, shapes, shapes_mla_sparse
from harness.window import delta

KERNELS = ("dgi_index_score_step", "dgi_index_threshold_step")


def read(run):
    if "index_layers_scored" not in run["win"]["c1"]["engine"]:
        return None
    seconds = sum(scans.op_seconds(run, k) for k in KERNELS)
    steps, win_steps = scans.slice_steps(run), scans.window_steps(run)
    context = delta(run["win"], "engine", "index_context_tokens_scan")
    if not (seconds and steps and win_steps and context and run["peaks"]):
        return None
    cfg, tokens = run["config"], context / win_steps
    need = shapes.roofline_s(
        shapes_mla_sparse.index_select_flops(cfg, tokens),
        shapes_mla_sparse.index_select_bytes(cfg, tokens), run["peaks"])
    run["notes"]["kernels.index_select_roofline.latent"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "kernels_step_ms": 1e3 * seconds / steps,
        "context_tokens_a_step": tokens,
    }
    return 100.0 * need["seconds"] * steps / seconds
