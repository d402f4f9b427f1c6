"""Share of the traced slice in which no operation ran on the chip: one
minus the union of the device-op intervals over the slice, from the
profiler's device plane; the mean over the chips of the cell."""


def read(run):
    t = run["trace"]
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
