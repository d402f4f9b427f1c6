"""Share of the window the engine thread spent in decode scans longer than
the level the batcher settled at: the batcher's `scan_s_t<T>` summed over
the levels above the one that ran the most scans of the window
(`scans_t<T>`), over the window's seconds. Since PR 32 every cell settles
at T = 1 and a longer scan is one raised while a request waited for a slot
(`scans_raised_waiting`): its rows wait 4 or 16 steps for their next event,
which competes with a round that carries a piece for a stream's longest
wait. Zero is a reading (no scan was raised); a program that does not count
its levels, or a window without a scan, gives nothing to read."""

from harness.scans import scans_by_level
from harness.window import delta


def read(run):
    scans = scans_by_level(run)
    if not any(scans.values()):
        return None
    settled = max(scans, key=lambda t: (scans[t], -t))
    win = run["win"]
    raised = sum(delta(win, "batcher", f"scan_s_t{t}")
                 for t in scans if t > settled)
    return 100.0 * raised / (win["w1"] - win["w0"])
