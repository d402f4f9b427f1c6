"""Share of the prefix lookups the full kind matched that the window kind
cut back, or to nothing, for want of the last window's pages: the cache
manager's `prefix_hits_cut_by_window` over `prefix_lookups_matched`
(`engine.get_stats()["kv_cache"]`), window delta. 0 where the window pool
keeps a document's last window until its client's next request; a run that
reads above it prefilled those tokens again and explains a low
`kv.prefix_hit_token_share`. A manager with one kind of pages has neither
counter and gives nothing to read."""


def read(run):
    win = run["win"]

    def change(key):
        return float(win["c1"]["engine"].get("kv_cache", {}).get(key, 0)) \
            - float(win["c0"]["engine"].get("kv_cache", {}).get(key, 0))

    matched = change("prefix_lookups_matched")
    if not matched:
        return None
    return 100.0 * change("prefix_hits_cut_by_window") / matched
