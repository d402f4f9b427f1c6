"""Share of the prompt tokens admitted in the window that the prefix cache
held: the cache manager's `prefix_hit_tokens` over `prefix_total_tokens`
(`engine.get_stats()["kv_cache"]`), window delta. In a cell whose clients
hold one document each, every admission after a client's first finds its
document cached and prefills the question alone; a run in which a document
was evicted and prefilled again reads low and explains itself."""


def read(run):
    win = run["win"]

    def change(key):
        return float(win["c1"]["engine"].get("kv_cache", {}).get(key, 0)) \
            - float(win["c0"]["engine"].get("kv_cache", {}).get(key, 0))

    total = change("prefix_total_tokens")
    return 100.0 * change("prefix_hit_tokens") / total if total else None
