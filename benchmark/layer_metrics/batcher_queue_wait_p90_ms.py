"""Time a request waited in the batcher's queue for a slot: from the flight
timeline the request's last event carries (`batcher.enqueued` to
`batcher.admitted`, the program's own monotonic stamps), 90th percentile
over the requests due in the window. Only requests sent with a `trace_id`
have a timeline: the traced run sends one with each."""

from harness.metrics import percentile


def read(run):
    waits = []
    for row in run["sample"]:
        first = {}
        for name, ts, _ in (row.get("timeline") or {}).get("events") or []:
            first.setdefault(name, float(ts))
        if "batcher.enqueued" in first and "batcher.admitted" in first:
            waits.append(
                (first["batcher.admitted"] - first["batcher.enqueued"]) * 1e3
            )
    return percentile(waits, 90)
