"""The 98th percentile of the window's pooled waits, recorded in the cells
that are not judged by it (where it sits on the edge between two round
lengths and spreads past half its bound: PERF.md, section 2)."""


def read(run):
    return run["summary"].get("itl_p98_ms")
