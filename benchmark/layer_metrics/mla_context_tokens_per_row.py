"""Cached tokens a scan row attended in a step, window mean: the engine's
`mla_context_tokens_scan` (the cache length of every row at every scan
step, summed) over `mla_row_steps_scan`, window delta. What the absorbed
kernel reads a row: 576 values a token a layer. A program without the
counters (a K/V model, the parent of the PR that added them) gives nothing
to read."""

from harness.window import delta


def read(run):
    row_steps = delta(run["win"], "engine", "mla_row_steps_scan")
    if not row_steps:
        return None
    return delta(run["win"], "engine", "mla_context_tokens_scan") / row_steps
