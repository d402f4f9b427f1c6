"""Share of its roofline the paged decode kernel reaches in the scans of a
model of mixed attention kinds: the least time the HBM needs for the K and
V rows the MIXED model must read (`shapes_window.attention_kv_bytes`: a
row's context in every full layer, `min(context, window)` of it in every
sliding one, 4,096 B a token a layer: the engine's
`attn_full_context_tokens_scan` / `attn_window_context_tokens_scan`) over
the seconds of the operations named `dgi_paged_decode.<n>` in a step. What
the kernel walks besides (whole page groups, a block table's width) is not
the yardstick: it shows as a low share.

The kernel's time a step comes from the traced slice, the tokens a step
from the window's counters over the steps of its scans
(`harness/scans.py`)."""

from harness import scans, shapes, shapes_window
from harness.window import delta

KERNEL = "dgi_paged_decode"


def read(run):
    seconds, steps = scans.op_seconds(run, KERNEL), scans.slice_steps(run)
    win_steps = scans.window_steps(run)
    full = delta(run["win"], "engine", "attn_full_context_tokens_scan")
    if not (seconds and steps and win_steps and full and run["peaks"]):
        return None
    windowed = delta(run["win"], "engine", "attn_window_context_tokens_scan")
    cfg = run["config"]
    need = shapes.roofline_s(
        shapes_window.attention_flops(cfg, full / win_steps,
                                      windowed / win_steps),
        shapes_window.attention_kv_bytes(cfg, full / win_steps,
                                         windowed / win_steps),
        run["peaks"])
    run["notes"]["kernels.decode_attention_roofline.mixed"] = {
        "bound": need["bound"], "least_step_ms": 1e3 * need["seconds"],
        "full_tokens_a_step": full / win_steps,
        "window_tokens_a_step": windowed / win_steps,
    }
    return 100.0 * need["seconds"] * steps / seconds
