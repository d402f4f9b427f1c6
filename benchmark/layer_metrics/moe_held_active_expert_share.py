"""Share of the experts this chip holds that a scan's expert layer reads:
the engine's `moe_active_experts_scan` (held experts that received at least
one row, summed over the layer calls of `decode_multi` scans) over
`moe_layer_calls_scan` x the held experts of the configuration
(`n_routed_experts` beside `expert_share`), window delta. A program without
the counters gives nothing to read."""

from harness import shapes_mla
from harness.window import delta


def read(run):
    calls = delta(run["win"], "engine", "moe_layer_calls_scan")
    held = shapes_mla.dims(run["config"])["held"]
    if not calls or not held:
        return None
    return 100.0 * delta(run["win"], "engine", "moe_active_experts_scan") \
        / (calls * held)
