"""Share of the window's fresh admissions that ran while the scan before
their round was still unread on the device, so that the slot, the prefix
lookup and the block allocation cost the decoding rows' wait nothing:
`admissions_ahead` over `ragged_admissions`, window delta. A program that
reads every scan before it admits has no such counter and reads 0; no
admission in the window reads nothing."""

from harness.window import delta


def read(run):
    admissions = delta(run["win"], "batcher", "ragged_admissions")
    return 100.0 * delta(run["win"], "batcher", "admissions_ahead") \
        / admissions if admissions else None
