"""Share of the requests that reached the direct server in the window and
were answered 503 (load control, or a worker that was not serving):
`DirectServer.stats`, read at the window's two ends."""

from harness.window import delta


def read(run):
    rejected = delta(run["win"], "direct", "rejected")
    reached = rejected + delta(run["win"], "direct", "requests")
    return 100.0 * rejected / reached if reached else None
