"""A request's way in: from the direct server's first sight of it, before
its body is parsed (`direct.accepted`), to its place in the batcher's queue
(`batcher.enqueued`), both on the request's timeline; median over the
requests due in the window, ms. Parsing, the claim, the pump thread's
start, the migration probe, tokenisation and the request's build: a part of
TTFT that `batcher.queue_wait_p90_ms` does not see. Only traced requests
have a timeline; a program without `direct.accepted` reads nothing."""

from harness.metrics import percentile


def read(run):
    took = []
    for row in run["sample"]:
        first = {}
        for name, ts, _ in (row.get("timeline") or {}).get("events") or []:
            first.setdefault(name, float(ts))
        if "direct.accepted" in first and "batcher.enqueued" in first:
            took.append(
                (first["batcher.enqueued"] - first["direct.accepted"]) * 1e3)
    return percentile(took, 50)
