"""Share of the chips' busy time spent in collective operations
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute, as
the trace names them). It counts their whole duration, hidden behind
compute or not."""


def read(run):
    t = run["trace"]
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
