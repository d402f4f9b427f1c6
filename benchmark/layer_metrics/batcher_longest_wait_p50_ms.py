"""`gap_p50_ms` as the engine thread saw it: each stream's longest stretch
between the returns of two consecutive rounds that brought it a token
(`batcher.completed`'s `longest_wait_ms` on the request's timeline, kept by
the batcher as one running maximum a stream), median over the requests due
in the window. What `gap_p50_ms` reads above it is delivery and the
generator (`direct.longest_wait_egress_ms`, `client.receive_lag_p50_ms`).
Only traced requests have a timeline; a program that does not note the
wait reads nothing."""

from harness.metrics import percentile


def read(run):
    waits = []
    for row in run["sample"]:
        for name, _, attrs in (row.get("timeline") or {}).get("events") or []:
            if name == "batcher.completed" and attrs \
                    and attrs.get("longest_wait_ms") is not None:
                waits.append(float(attrs["longest_wait_ms"]))
    return percentile(waits, 50)
