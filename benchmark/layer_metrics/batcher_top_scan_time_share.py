"""Share of the window the engine thread spent in scans of the highest
horizon level (`decode_multi` at T = the last of the configuration's
`horizon_levels`): the batcher's `scan_s_t<T>` over the window's seconds.
During such a scan nothing is admitted and nothing streams. Zero is a
reading (the mix never reached the level); a program that does not count
its levels gives nothing to read."""

from harness.window import delta


def read(run):
    key = f"scan_s_t{max(run['geometry']['horizon_levels'])}"
    win = run["win"]
    if key not in win["c1"]["batcher"]:
        return None
    return 100.0 * delta(win, "batcher", key) / (win["w1"] - win["w0"])
