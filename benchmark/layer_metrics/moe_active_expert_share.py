"""Share of the stored experts the scans' expert layers read: the engine's
`moe_active_experts_scan` (experts that received at least one row, summed
over the layer calls of `decode_multi` scans) over `moe_layer_calls_scan`
x the configuration's expert count, window delta. A routed layer reads
only those; 100 % is what a dense einsum over the expert axis reads. A
program without the counters (a dense model, a mesh engine, the parent of
the PR that added them) gives nothing to read."""

from harness import shapes_moe
from harness.window import delta


def read(run):
    calls = delta(run["win"], "engine", "moe_layer_calls_scan")
    experts = shapes_moe.dims(run["config"])["E"]
    if not calls or not experts:
        return None
    return 100.0 * delta(run["win"], "engine", "moe_active_experts_scan") \
        / (calls * experts)
