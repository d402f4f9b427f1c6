"""The longest wait between two consecutive events of a stream after its first
token, 90th percentile over the requests due in the window: the stall the
unluckier tenth of the streams saw (a round with two pieces, a scan raised
while a request waited), which TPOT averages away. The median of the same
population, `gap_p50_ms`, is what the one-chip chat cells are judged by
(PERF.md, section 2); the 90th percentile spreads 0.06-0.10 over ten seeds
there, which the admission rule refuses, and is recorded."""


def read(run):
    return run["summary"].get("gap_p90_ms")
