"""Share of the scans' (token, expert) pairs that fell on experts this chip
holds: the engine's `moe_assignments_scan` (pairs routed to held experts)
over `moe_pairs_routed_scan` (every pair the router kept, over all the
published experts), window delta. A chip that holds 16 of 256 experts under
a uniform router reads 6.25 %; it is the load the held experts see, and
what of the routed layer's mathematics this chip computes. A program
without the second counter (a model that holds all its experts, the parent
of the PR that added it) gives nothing to read."""

from harness.window import delta


def read(run):
    pairs = delta(run["win"], "engine", "moe_pairs_routed_scan")
    if not pairs:
        return None
    return 100.0 * delta(run["win"], "engine", "moe_assignments_scan") / pairs
