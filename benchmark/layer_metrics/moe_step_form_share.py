"""Share of the scans' routed expert-layer calls that took the step form
(a scan step's rows as ONE resident tile and one `dgi_moe_gmm_step` call a
layer, no sorted tile layout): the engine's `moe_step_form_calls_scan` over
`moe_layer_calls_scan`, window delta. 100 = every scan step's expert layer
ran the step form; 0 = every one laid its rows out in sorted tiles (a
program without the counter, as the parent of the PR that added it, reads
0, which is the truth there). No expert-layer call in the window's scans
gives nothing to read."""

from harness.window import delta


def read(run):
    calls = delta(run["win"], "engine", "moe_layer_calls_scan")
    return 100.0 * delta(run["win"], "engine", "moe_step_form_calls_scan") \
        / calls if calls else None
