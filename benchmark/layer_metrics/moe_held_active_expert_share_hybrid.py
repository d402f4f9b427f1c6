"""`moe.held_active_expert_share` for a configuration that publishes its
experts as `num_experts` (`harness/shapes_kda.py`): the share of the
experts this chip holds that a scan's expert layer reads, the engine's
`moe_active_experts_scan` (held experts that received at least one row,
summed over the layer calls of `decode_multi` scans) over
`moe_layer_calls_scan` x the held experts of the configuration, window
delta. A program without the counters, or a model with no linear-attention
layer (no `kda_row_steps_scan`), gives nothing to read."""

from harness import shapes_kda
from harness.window import delta


def read(run):
    calls = delta(run["win"], "engine", "moe_layer_calls_scan")
    if not (calls and delta(run["win"], "engine", "kda_row_steps_scan")):
        return None
    held = shapes_kda.dims(run["config"])["held"]
    return 100.0 * delta(run["win"], "engine", "moe_active_experts_scan") \
        / (calls * held) if held else None
