"""Cached tokens a scan's row-step attends in a SLIDING layer of a model of
mixed attention kinds: the engine's `attn_window_context_tokens_scan` over
`attn_row_steps_scan`, window delta: the window, or the context while it is
shorter. A program without the counters gives nothing to read."""

from harness.window import delta


def read(run):
    steps = delta(run["win"], "engine", "attn_row_steps_scan")
    if not steps:
        return None
    return delta(run["win"], "engine",
                 "attn_window_context_tokens_scan") / steps
