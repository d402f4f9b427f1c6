"""Share of its roofline the `ragged_round` program reaches at the widest
bucket the cell's traffic reaches, counting only what was live in the round: the prompt tokens of its
pieces (projections, the experts a token is routed to, causal attention
over the piece) and one decode step for its decode rows, against the bf16
peak, or the weights' bytes against the HBM rate if that were more. The
graph runs the dense work over a `[max_batch_size, bucket]` rectangle
whatever is live, so padding shows here as a low share. Live tokens come
from the benchmark's annotation around `TPUEngine.ragged_round`."""

from harness import shapes
from harness.layers import widest_ragged


def read(run):
    mods = widest_ragged(run)
    if not mods or not run["peaks"]:
        return None
    cfg, geo = run["config"], run["geometry"]
    tp = geo["tp_size"]
    weights = sum(shapes.weight_bytes(cfg, tp).values())
    least, bounds = 0.0, {"mxu": 0, "hbm": 0}
    for m in mods:
        live, rows = int(m["live_prompt_tokens"]), int(m["decode_rows"])
        flops = shapes.prefill_flops(
            cfg, live, 0.0, int(m["admission_rows"]), tp
        ) + shapes.decode_step_flops(cfg, rows, 0.0, tp)
        need = shapes.roofline_s(flops, weights, run["peaks"])
        least += need["seconds"]
        bounds[need["bound"]] += 1
    run["notes"]["engine.ragged_round_roofline"] = {
        "rounds": len(mods), "bound_counts": bounds,
        "mean_live_prompt_tokens":
            sum(int(m["live_prompt_tokens"]) for m in mods) / len(mods),
        "positions_dispatched": geo["max_batch_size"] * mods[0]["bucket"],
    }
    return 100.0 * least / sum(m["seconds"] for m in mods)
