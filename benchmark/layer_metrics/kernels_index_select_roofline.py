"""Share of its roofline the selection reaches in scans: the least time the
chip needs for what a scan step's selection must move
(`shapes_sparse_attn.index_select_bytes`: one index key read and one score
written a cached token a row a layer, over the HBM peak; the scores'
operations over the bf16 peak if that were more) over the kernels' device
time in a step (`kernels.index_select_step_ms`). The 32 counting passes of
the threshold and the 128-lane row a 64-value key is stored in are the
implementation's cost and show as a low share.

The kernels' time a step comes from the traced slice; the cached tokens a
step from the window (`harness/scans.py` says why):
`index_context_tokens_scan` less the row-steps that select nothing
(`index_dense_rows_scan` is counted, their tokens are below `topk` a row
and left in: an upper bound of the work, so a lower bound of no share),
over the steps of the window's scans (`scans_t<T>`)."""

import importlib.util
from pathlib import Path

from harness import scans, shapes, shapes_sparse_attn
from harness.window import delta

_spec = importlib.util.spec_from_file_location(
    "kernels_index_select_step_ms",
    Path(__file__).with_name("kernels_index_select_step_ms.py"))
_step = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_step)


def read(run):
    seconds, steps = _step.seconds(run), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    context = delta(run["win"], "engine", "index_context_tokens_scan")
    if not (seconds and steps and win_steps and context and run["peaks"]):
        return None
    cfg, tokens = run["config"], context / win_steps
    need = shapes.roofline_s(
        shapes_sparse_attn.index_select_flops(cfg, tokens),
        shapes_sparse_attn.index_select_bytes(cfg, tokens), run["peaks"])
    run["notes"]["kernels.index_select_roofline"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "context_tokens_a_step": tokens,
    }
    return 100.0 * need["seconds"] * steps / seconds
