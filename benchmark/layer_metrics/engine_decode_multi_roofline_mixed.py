"""Share of its roofline the `decode_multi` program of a model of mixed
attention kinds reaches: the least time one chip could take for a decode
step (`shapes_window.decode_step_bytes`: every weight but the routed
experts once, the held experts that received a pair, the head, a row's
context of K and V in the full layers and its window of them in the sliding
ones; operations over the bf16 peak if that were more) over the measured
`engine.decode_step_ms`: the share of the whole step.
`engine.decode_multi_roofline` counts every cached row in every layer of a
dense model (`harness/shapes.py`).

The step's time comes from the traced slice; rows, cached tokens of either
kind, experts read and pairs a step from the engine's scan counters over
the window over the steps of its scans (`harness/scans.py`)."""

from harness import scans, shapes, shapes_window
from harness.layers import modules_named
from harness.window import delta


def read(run):
    mods = [m for m in modules_named(run, "decode_multi") if m.get("steps")]
    steps, win_steps = scans.slice_steps(run), scans.window_steps(run)
    win = run["win"]
    row_steps = delta(win, "engine", "attn_row_steps_scan")
    if not (steps and win_steps and row_steps and run["peaks"]):
        return None
    full = delta(win, "engine", "attn_full_context_tokens_scan") / win_steps
    windowed = delta(win, "engine",
                     "attn_window_context_tokens_scan") / win_steps
    pairs = delta(win, "engine", "moe_assignments_scan") / win_steps
    active = delta(win, "engine", "moe_active_experts_scan") / win_steps
    rows, cfg = row_steps / win_steps, run["config"]
    parts = shapes_window.decode_step_bytes(
        cfg, rows, full, windowed, active, pairs)
    need = shapes.roofline_s(
        shapes_window.decode_step_flops(cfg, rows, full, windowed, pairs),
        parts["total"], run["peaks"])
    run["notes"]["engine.decode_multi_roofline.mixed"] = {
        "bound": need["bound"], "rows_a_step": rows,
        "least_step_ms": 1e3 * need["seconds"], "bytes_a_step": parts,
    }
    return 100.0 * need["seconds"] * steps / sum(m["seconds"] for m in mods)
