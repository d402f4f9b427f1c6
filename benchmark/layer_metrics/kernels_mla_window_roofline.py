"""Share of its roofline the windowed latent-attention kernel reaches in
scans: the least time the chip needs for what a scan step's sliding layers
must do (`shapes_mla_window`: the larger of the attended window rows' bytes
over the HBM peak and their absorbed operations over the bf16 peak, all six
sliding layers) over the device time in a step of the operations named
`dgi_mla_window_decode.<n>`.

The kernel's time a step comes from the traced slice; the cached tokens a
step's rows attended inside their windows from the window
(`attn_window_context_tokens_scan`, a sliding layer's count) over the steps
of its scans (`harness/scans.py`). A program whose kernel has no such name
gives nothing to read."""

from harness import scans, shapes, shapes_mla_window
from harness.window import delta

KERNEL = "dgi_mla_window_decode"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    tokens = delta(run["win"], "engine", "attn_window_context_tokens_scan")
    if not (seconds and steps and win_steps and tokens and run["peaks"]):
        return None
    cfg, tokens = run["config"], tokens / win_steps
    need = shapes.roofline_s(
        shapes_mla_window.window_attention_flops(cfg, tokens),
        shapes_mla_window.window_attention_bytes(cfg, tokens), run["peaks"])
    run["notes"]["kernels.mla_window_roofline"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "kernel_step_ms": 1e3 * seconds / steps,
        "window_tokens_a_step": tokens,
    }
    return 100.0 * need["seconds"] * steps / seconds
