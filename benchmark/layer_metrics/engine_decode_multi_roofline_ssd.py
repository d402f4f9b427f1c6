"""Share of its roofline the `decode_multi` program of a model with a
state-space mixer beside attention reaches: the least time one chip could
take for a decode step (`shapes_ssd.decode_step_bytes`: every held weight
once, the head's slice, the K/V rows the step's rows attended and the row
written, the state of its live rows read and written in every layer;
operations over the bf16 peak if that were more) over the measured
`engine.decode_step_ms`: the share of the whole step.
`engine.decode_multi_roofline` knows no mixer and no state
(`harness/shapes.py`).

The step's time comes from the traced slice; the live rows a step from the
engine's `ssd_row_steps_scan` over the window over the steps of its scans
(`harness/scans.py`); the cached tokens a row attended are the mean context
over the decode steps of the run's requests, as
`engine.decode_multi_roofline` takes them."""

from harness import scans, shapes, shapes_ssd
from harness.layers import modules_named
from harness.metrics import mean_decode_context
from harness.window import delta


def read(run):
    mods = [m for m in modules_named(run, "decode_multi") if m.get("steps")]
    steps, win_steps = scans.slice_steps(run), scans.window_steps(run)
    ctx = mean_decode_context(run["rows"])
    if not (steps and win_steps and run["peaks"]) or ctx is None:
        return None
    row_steps = delta(run["win"], "engine", "ssd_row_steps_scan")
    if not row_steps:
        return None
    cfg = run["config"]
    rows = row_steps / win_steps / shapes_ssd.dims(cfg)["L"]
    parts = shapes_ssd.decode_step_bytes(cfg, rows, rows * ctx)
    need = shapes.roofline_s(
        shapes_ssd.decode_step_flops(cfg, rows, rows * ctx),
        parts["total"], run["peaks"])
    run["notes"]["engine.decode_multi_roofline.ssd"] = {
        "bound": need["bound"], "rows_a_step": rows, "mean_context": ctx,
        "least_step_ms": 1e3 * need["seconds"], "bytes_a_step": parts,
    }
    return 100.0 * need["seconds"] * steps / sum(m["seconds"] for m in mods)
