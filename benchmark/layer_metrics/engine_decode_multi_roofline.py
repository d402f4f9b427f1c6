"""Share of its roofline the `decode_multi` program reaches: the least time
one chip could take for a decode step (every weight once, the head, each
row's context from the KV pool; operations over the bf16 peak if that were
more) over the measured `engine.decode_step_ms`. A decode step is bound by
HBM at these batch sizes. Rows are the decoding sequences of the traced
calls, the context is the mean over the decode steps of the run's requests.
This is the program as a whole: per-kernel shares need kernel names in the
trace (the `tracing` issue)."""

from harness import shapes
from harness.layers import modules_named
from harness.metrics import mean_decode_context


def read(run):
    mods = [m for m in modules_named(run, "decode_multi") if m.get("steps")]
    steps = sum(int(m["steps"]) for m in mods)
    ctx = mean_decode_context(run["rows"])
    if not steps or ctx is None or not run["peaks"]:
        return None
    rows = sum(int(m["steps"]) * int(m.get("decode_rows", 0))
               for m in mods) / steps
    geo, tp = run["geometry"], run["geometry"]["tp_size"]
    need = shapes.roofline_s(
        shapes.decode_step_flops(run["config"], rows, ctx, tp),
        shapes.decode_step_bytes(run["config"], rows, ctx, tp,
                                 geo["block_size"])["total"],
        run["peaks"],
    )
    run["notes"]["engine.decode_multi_roofline"] = {
        "bound": need["bound"], "rows": rows, "mean_context": ctx,
        "least_step_ms": 1e3 * need["seconds"],
    }
    return 100.0 * need["seconds"] * steps / sum(m["seconds"] for m in mods)
