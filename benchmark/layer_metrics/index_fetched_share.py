"""Share of the cached tokens a scan row could attend that the decode kernel
fetched for its selection, window mean: the engine's
`index_fetched_tokens_scan` (the pages of the row's table that hold a
selected token, whole, counted on the device from the selection itself, mean
over the layers) over `index_context_tokens_scan`, window delta. Beside
`index.selected_share` it says what the page costs: a selected token brings
the other tokens of its page. A program without the counter (every model
without an indexer, the parent of the PR that added it, whose kernel fetched
every page) gives nothing to read."""

from harness.window import delta


def read(run):
    context = delta(run["win"], "engine", "index_context_tokens_scan")
    if not context or "index_fetched_tokens_scan" not in \
            run["win"]["c1"]["engine"]:
        return None
    return 100.0 * delta(run["win"], "engine",
                         "index_fetched_tokens_scan") / context
