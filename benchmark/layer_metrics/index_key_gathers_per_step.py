"""Layer-gathers of index keys into context order a scan step, window mean:
the engine's `index_key_gathers_scan` (a scan of several steps lays every
layer's keys out once before its first step and appends to them: the layers
once a scan; a scan no row of which passes `topk` inside it: none) over the
steps of the window's scans. A path that gathers a layer a step reads the
layers (8 in the docqa cell), one gather a T=4 scan reads a quarter of that.
A program without the counter (every model without an indexer, the parent of
the PR that added it, whose every layer of every step gathered) gives
nothing to read."""

from harness.scans import window_steps
from harness.window import delta


def read(run):
    steps = window_steps(run)
    if not steps or "index_key_gathers_scan" not in \
            run["win"]["c1"]["engine"]:
        return None
    return delta(run["win"], "engine", "index_key_gathers_scan") / steps
