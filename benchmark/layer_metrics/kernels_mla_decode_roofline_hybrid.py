"""`kernels.mla_decode_roofline` for a hybrid model: the absorbed
latent-attention kernel's share of its roofline in scans, where only the
layers the configuration lists are latent (`shapes_kda.latent_rows_bytes` /
`latent_attention_flops` count those; `harness/shapes_mla.py` counts every
layer, the all-latent model's). The least time the chip needs for what a
scan step's latent attention must do (the larger of the cached rows' bytes
over the HBM peak and the absorbed operations over the bf16 peak) over the
kernel's device time in a step (`kernels.mla_decode_step_ms`).

The kernel's time a step comes from the traced slice; the cached tokens a
step attended from the window (`mla_context_tokens_scan` over the steps of
the window's scans, `harness/scans.py`). A model with no linear-attention
layer (no `kda_row_steps_scan`) gives nothing to read."""

from harness import scans, shapes, shapes_kda
from harness.window import delta

KERNEL = "dgi_mla_decode"
NAME = "kernels.mla_decode_roofline.hybrid"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    tokens = delta(run["win"], "engine", "mla_context_tokens_scan")
    if not (seconds and steps and win_steps and tokens and run["peaks"]
            and delta(run["win"], "engine", "kda_row_steps_scan")):
        return None
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_kda.latent_attention_flops(cfg, tokens / win_steps),
        shapes_kda.latent_rows_bytes(cfg, tokens / win_steps),
        run["peaks"],
    )
    run["notes"][NAME] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "context_tokens_a_step": tokens / win_steps,
        "rows_a_step": delta(run["win"], "engine", "mla_row_steps_scan")
        / win_steps,
    }
    return 100.0 * need["seconds"] * steps / seconds
