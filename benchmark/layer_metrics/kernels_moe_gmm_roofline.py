"""Share of its roofline the grouped expert matmul reaches in scans: the
least time the HBM needs for what a scan step's expert layers must move
(`shapes_moe.routed_layer_bytes`: the weights of the experts that received
a row, each (token, expert) pair's row in and out; operations over the
bf16 peak if that were more) over the kernel's device time in a step
(`kernels.moe_scan_step_ms`).

The kernel's time comes from the traced slice, the experts a layer call
reads from the engine's scan counters over the whole window
(`moe_active_experts_scan` / `moe_layer_calls_scan`), and the slice may be
busier or emptier than the window. So the window's count is carried to the
slice's occupancy: the slice's rows a step (the annotated `decode_rows` of
its scans, weighted by their steps) against the window's
(`moe_assignments_scan` / k / `moe_layer_calls_scan`), through the count a
uniform router would give at either (`shapes_moe.expected_active_experts`).
`decode_rows` is read at a scan's start: rows that finish inside it make
the slice look busier than it was, so the count is only ever carried DOWN
(an emptier slice reads fewer experts a call); for a busier slice the
window's count stands and the share read is a lower bound."""

import importlib.util
from pathlib import Path

from harness import shapes, shapes_moe
from harness.layers import modules_named
from harness.window import delta

_spec = importlib.util.spec_from_file_location(
    "kernels_moe_scan_step_ms",
    Path(__file__).with_name("kernels_moe_scan_step_ms.py"))
_step = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_step)


def read(run):
    seconds, steps = _step.seconds_and_steps(run)
    win, cfg = run["win"], run["config"]
    calls = delta(win, "engine", "moe_layer_calls_scan")
    if not (seconds and steps and calls and run["peaks"]):
        return None
    s = shapes_moe.dims(cfg)
    rows_slice = sum(
        int(m["steps"]) * int(m.get("decode_rows", 0))
        for m in modules_named(run, "decode_multi") if m.get("steps")
    ) / steps
    rows_window = delta(win, "engine", "moe_assignments_scan") \
        / (s["k"] * calls)
    if not rows_slice or not rows_window:
        return None
    rows = min(rows_slice, rows_window)
    active = delta(win, "engine", "moe_active_experts_scan") / calls \
        * shapes_moe.expected_active_experts(cfg, rows) \
        / shapes_moe.expected_active_experts(cfg, rows_window)
    pairs = s["k"] * rows
    need = shapes.roofline_s(
        s["L"] * shapes_moe.routed_layer_flops(cfg, pairs),
        s["L"] * shapes_moe.routed_layer_bytes(cfg, active, pairs),
        run["peaks"],
    )
    run["notes"]["kernels.moe_gmm_roofline"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "rows_a_step_slice": rows_slice, "rows_a_step_window": rows_window,
        "active_experts_a_call": active,
    }
    return 100.0 * need["seconds"] * steps / seconds
