"""Share of its roofline the absorbed latent-attention kernel reaches in
scans: the least time the chip needs for what a scan step's attention must
do (`shapes_mla`: the larger of the cached rows' bytes over the HBM peak
and the absorbed operations over the bf16 peak) over the kernel's device
time in a step (`kernels.mla_decode_step_ms`).

The kernel's time a step comes from the traced slice. The cached tokens a
step attended come from the window (`harness/scans.py` says why):
`mla_context_tokens_scan`, which the engine counts from the tokens each row
really emitted, over the steps of the window's scans (`scans_t<T>`)."""

from harness import scans, shapes, shapes_mla
from harness.window import delta

KERNEL = "dgi_mla_decode"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    tokens = delta(run["win"], "engine", "mla_context_tokens_scan")
    if not (seconds and steps and win_steps and tokens and run["peaks"]):
        return None
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_mla.attention_flops(cfg, tokens / win_steps),
        shapes_mla.attention_bytes(cfg, tokens / win_steps),
        run["peaks"],
    )
    run["notes"]["kernels.mla_decode_roofline"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "context_tokens_a_step": tokens / win_steps,
        "rows_a_step": delta(run["win"], "engine", "mla_row_steps_scan")
        / win_steps,
    }
    return 100.0 * need["seconds"] * steps / seconds
