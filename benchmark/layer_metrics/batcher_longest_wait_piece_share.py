"""Share of the streams completed in the window whose longest wait for a
token was ended by a round that carried a prompt piece: `batcher.stats`
`longest_wait_ragged_1` + `longest_wait_ragged_2plus` over all five causes
(`scan_raised`: a scan raised while a request waited for a slot; `scan`;
`other`), window delta, %. The program's own statement of which round sets
`gap_p50_ms`; the detail file keeps the five counts and their seconds under
`counters`. A program without the counters reads nothing."""

from harness.window import delta

CAUSES = ("ragged_1", "ragged_2plus", "scan_raised", "scan", "other")


def read(run):
    by_cause = {c: delta(run["win"], "batcher", f"longest_wait_{c}")
                for c in CAUSES}
    streams = sum(by_cause.values())
    if not streams:
        return None
    return 100.0 * (by_cause["ragged_1"] + by_cause["ragged_2plus"]) / streams
