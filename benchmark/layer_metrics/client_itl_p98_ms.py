"""The 98th percentile of the window's pooled waits, recorded in the cells
that are not judged by it: where it sits on the edge between two round
lengths (rag, tp4) and where, every wait below the tail being one step, the
seed slides it along the cluster of rounds with a piece (the one-chip chat
cells since PR 32; PERF.md, section 2)."""


def read(run):
    return run["summary"].get("itl_p98_ms")
