"""Time per output token, 90th percentile over the requests due in the window
that streamed at least 8 tokens. Recorded where its runs do not repeat closely
enough to hold it to a bound (PERF.md, section 2)."""


def read(run):
    return run["summary"].get("tpot_p90_ms")
