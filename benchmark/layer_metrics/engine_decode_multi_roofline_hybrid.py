"""Share of its roofline the `decode_multi` program of a hybrid model
reaches: the least time one chip could take for a decode step
(`shapes_kda.decode_step_bytes`: every weight but the routed experts once,
the held experts that received a row, the head, the cached latent rows the
step's rows attended in the latent layers, the state of its live rows read
and written in the linear-attention layers; operations over the bf16 peak
if that were more) over the measured `engine.decode_step_ms`: the share of
the whole step. `engine.decode_multi_roofline.latent` counts latent rows in
every layer and a query low-rank (`harness/shapes_mla.py`), which is the
all-latent model's.

The step's time comes from the traced slice; rows, cached tokens, experts
read and pairs a step from the engine's scan counters over the window over
the steps of its scans (`harness/scans.py`)."""

from harness import scans, shapes, shapes_kda
from harness.layers import modules_named
from harness.window import delta


def read(run):
    mods = [m for m in modules_named(run, "decode_multi") if m.get("steps")]
    steps, win_steps = scans.slice_steps(run), scans.window_steps(run)
    win = run["win"]
    if not (steps and win_steps and run["peaks"]):
        return None
    row_steps = delta(win, "engine", "mla_row_steps_scan")
    if not (row_steps and delta(win, "engine", "kda_row_steps_scan")):
        return None
    tokens = delta(win, "engine", "mla_context_tokens_scan") / win_steps
    pairs = delta(win, "engine", "moe_assignments_scan") / win_steps
    active = delta(win, "engine", "moe_active_experts_scan") / win_steps
    rows, cfg = row_steps / win_steps, run["config"]
    parts = shapes_kda.decode_step_bytes(cfg, rows, tokens, active, pairs)
    need = shapes.roofline_s(
        shapes_kda.decode_step_flops(cfg, rows, tokens, pairs),
        parts["total"], run["peaks"])
    run["notes"]["engine.decode_multi_roofline.hybrid"] = {
        "bound": need["bound"], "rows_a_step": rows,
        "least_step_ms": 1e3 * need["seconds"],
        "bytes_a_step": parts,
    }
    return 100.0 * need["seconds"] * steps / sum(m["seconds"] for m in mods)
