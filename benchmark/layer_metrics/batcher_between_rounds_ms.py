"""From one engine round's end to the next one's start, both stamped on the
engine thread, while the batcher owned work throughout: the deliver half
of the loop (joins, finished slots, observers), the admit half, and the
thread hops between them. The batcher's `between_rounds_s` over
`between_rounds`, window delta: the program's own reading, over the whole
window, of what the trace charges to "between engine calls, a request in
flight" in a 5 s slice."""

from harness.window import delta


def read(run):
    gaps = delta(run["win"], "batcher", "between_rounds")
    return 1e3 * delta(run["win"], "batcher", "between_rounds_s") / gaps \
        if gaps else None
