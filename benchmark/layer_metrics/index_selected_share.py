"""Share of the cached tokens a scan row could attend that its selection
kept, window mean: the engine's `index_selected_tokens_scan` over
`index_context_tokens_scan` (host arithmetic at a scan's commit: a row-step
with `c` cached tokens attends `min(c, topk)`), window delta: `topk` over
the mean cached tokens a row-step when every row is past `topk`. A program
without the counters (every model without an indexer, the parent of the PR
that added them) gives nothing to read."""

from harness.window import delta


def read(run):
    context = delta(run["win"], "engine", "index_context_tokens_scan")
    if not context:
        return None
    return 100.0 * delta(run["win"], "engine",
                         "index_selected_tokens_scan") / context
