"""Share of their context that live rows hold in the window kind's pool: the
engine's `kv_window_resident_tokens_scan` (window-kind blocks a row holds x
block size, a scan step each) over `attn_full_context_tokens_scan` (the
cached tokens the same row-steps attended in a full layer), window delta. A
model of mixed attention kinds paged per kind reads window / context (~4 %
at 512 of ~19.5k, blocks and a scan's horizon rounded up); a model paged
alike would read 100 %. A program without the counters (the parent of the
PR that added them, any other model) gives nothing to read."""

from harness.window import delta


def read(run):
    context = delta(run["win"], "engine", "attn_full_context_tokens_scan")
    if not context:
        return None
    return 100.0 * delta(run["win"], "engine",
                         "kv_window_resident_tokens_scan") / context
