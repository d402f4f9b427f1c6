"""Share of the rows the scans' grouped expert matmuls ran that held no
(token, expert) pair: 1 - `moe_assignments_scan` /
`moe_rows_dispatched_scan`, the engine's counters, window delta. Every
expert that received a row gets whole row tiles; at a few rows an expert
most of a tile is padding, which costs MXU work but no weight bytes."""

from harness.window import delta


def read(run):
    rows = delta(run["win"], "engine", "moe_rows_dispatched_scan")
    if not rows:
        return None
    return 100.0 * (1.0 - delta(run["win"], "engine",
                                "moe_assignments_scan") / rows)
