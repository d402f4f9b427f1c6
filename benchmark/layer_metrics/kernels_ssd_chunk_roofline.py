"""Share of its roofline the state-space mixer's chunk kernel reaches in
ragged rounds: the least time the chip needs for a round's chunks
(`shapes_ssd`: the larger of the pass's operations over the bf16 peak and
its bytes, a chunk's operands and output and a segment's state in and out,
over the HBM peak; every layer) over the kernel's device time in a round
(`kernels.ssd_chunk_round_ms`). The pass multiplies in float32 at the
highest precision, several bf16 passes an operation, so where the
operations bound it the share reads that much lower.

The kernel's time a round comes from the traced slice; the chunks and
segments a round from the window: the engine's `ssd_chunks_ragged` and
`ssd_segments_ragged` (counted at a round's build) over its
`ragged_rounds`."""

from harness import scans, shapes, shapes_ssd
from harness.window import delta

KERNEL = "dgi_ssd_chunk"


def read(run):
    seconds, rounds = scans.op_seconds(run, KERNEL), scans.slice_rounds(run)
    win = run["win"]
    if not (seconds and rounds and run["peaks"]):
        return None
    n = delta(win, "engine", "ragged_rounds")
    chunks = delta(win, "engine", "ssd_chunks_ragged")
    if not (n and chunks):
        return None
    cfg = run["config"]
    layers = shapes_ssd.dims(cfg)["L"]
    segments = delta(win, "engine", "ssd_segments_ragged")
    need = shapes.roofline_s(
        layers * shapes_ssd.ssd_chunk_flops(cfg, chunks / n),
        layers * shapes_ssd.ssd_chunk_bytes(cfg, chunks / n, segments / n),
        run["peaks"])
    run["notes"]["kernels.ssd_chunk_roofline"] = {
        "bound": need["bound"], "least_round_ms": 1e3 * need["seconds"],
        "chunks_a_round": chunks / n, "segments_a_round": segments / n,
    }
    return 100.0 * need["seconds"] * len(rounds) / seconds
