"""Share of its roofline the `decode_multi` program of a latent-attention
model of two attention kinds reaches: the least time one chip could take
for a decode step (`shapes_mla_window.decode_step_bytes`: every weight but
the routed experts once -- both kinds' attention, the gates, the indexers
--, the held experts that received a row, the head, the pages that hold a
selected latent in the full layers, the window's rows in the sliding ones,
one index key and one score a cached token a full layer; operations over
the bf16 peak if that were more) over the measured `engine.decode_step_ms`:
the share of the whole step.

The step's time comes from the traced slice; rows, fetched, selected,
windowed and cached tokens, experts read and pairs a step from the engine's
scan counters over the window over the steps of its scans
(`harness/scans.py`). A program without pages per layer kind on a latent
model with an indexer gives nothing to read."""

from harness import scans, shapes, shapes_mla_window
from harness.layers import modules_named
from harness.window import delta


def read(run):
    mods = [m for m in modules_named(run, "decode_multi") if m.get("steps")]
    steps, win_steps = scans.slice_steps(run), scans.window_steps(run)
    win = run["win"]
    if "attn_row_steps_scan" not in win["c1"]["engine"]:
        return None
    row_steps = delta(win, "engine", "mla_row_steps_scan")
    fetched = delta(win, "engine", "index_fetched_tokens_scan")
    if not (mods and steps and win_steps and row_steps and fetched
            and run["peaks"]):
        return None
    fetched /= win_steps
    selected = delta(win, "engine", "index_selected_tokens_scan") / win_steps
    context = delta(win, "engine", "index_context_tokens_scan") / win_steps
    windowed = delta(win, "engine", "attn_window_context_tokens_scan") \
        / win_steps
    pairs = delta(win, "engine", "moe_assignments_scan") / win_steps
    active = delta(win, "engine", "moe_active_experts_scan") / win_steps
    rows, cfg = row_steps / win_steps, run["config"]
    parts = shapes_mla_window.decode_step_bytes(
        cfg, rows, fetched, windowed, context, active, pairs)
    need = shapes.roofline_s(
        shapes_mla_window.decode_step_flops(cfg, rows, selected, windowed,
                                            context, pairs),
        parts["total"], run["peaks"])
    run["notes"]["engine.decode_multi_roofline.mixed_latent"] = {
        "bound": need["bound"], "rows_a_step": rows,
        "least_step_ms": 1e3 * need["seconds"], "bytes_a_step": parts,
    }
    return 100.0 * need["seconds"] * steps / sum(m["seconds"] for m in mods)
