"""Cached tokens a scan's row-step attends in a FULL layer of a model of
mixed attention kinds: the engine's `attn_full_context_tokens_scan` over
`attn_row_steps_scan`, window delta: the mean context of the decoding rows.
A program without the counters gives nothing to read."""

from harness.window import delta


def read(run):
    steps = delta(run["win"], "engine", "attn_row_steps_scan")
    if not steps:
        return None
    return delta(run["win"], "engine",
                 "attn_full_context_tokens_scan") / steps
