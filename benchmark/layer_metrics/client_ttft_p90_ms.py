"""Time to first token, 90th percentile over the requests due in the window,
from the instant each was due. Recorded where its runs do not repeat closely
enough to hold it to a bound (PERF.md, section 2)."""


def read(run):
    return run["summary"].get("ttft_p90_ms")
