"""Share of the decode scans that were dispatched while the scan before
them was still unread on the device, so that the host's work of the round
(build, upload, commit, deliver, admit) ran beside the device's:
`scans_chained` over the scans of every level (`scans_t<T>`), window
delta. A program that reads every scan back in the call that made it has
no such counter and reads 0; no scan in the window reads nothing."""

from harness.scans import scans_by_level
from harness.window import delta


def read(run):
    scans = sum(scans_by_level(run).values())
    return 100.0 * delta(run["win"], "batcher", "scans_chained") / scans \
        if scans else None
