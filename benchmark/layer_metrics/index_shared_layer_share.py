"""Share of the layer calls under a selection that borrowed it from the
layer before them: the engine's `index_layers_shared` over
`index_layers_scored + index_layers_shared` (every scan step and every
ragged round counts its layers that hold an indexer and compute a
selection, and its layers that attend the last one computed), window
delta. Six of nine layers by construction in the docqa cell: it falls if a
shared layer ever scores again. A program without the counters (every model
whose layers all score, the parent of the PR that added them) gives nothing
to read."""

from harness.window import delta


def read(run):
    if "index_layers_shared" not in run["win"]["c1"]["engine"]:
        return None
    shared = delta(run["win"], "engine", "index_layers_shared")
    total = shared + delta(run["win"], "engine", "index_layers_scored")
    return 100.0 * shared / total if total else None
