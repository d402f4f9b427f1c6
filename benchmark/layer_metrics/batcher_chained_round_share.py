"""Share of the window's ragged rounds that were dispatched while the scan
before them was still unread on the device, so that the round's build and
dispatch, the admission pass before it and the delivery of that scan's
tokens cost the decoding rows' wait across the round nothing:
`ragged_rounds_chained` over `ragged_rounds`, window delta. A program that
reads every scan before it builds a round has no such counter and reads 0;
no ragged round in the window reads nothing."""

from harness.window import delta


def read(run):
    rounds = delta(run["win"], "batcher", "ragged_rounds")
    return 100.0 * delta(run["win"], "batcher", "ragged_rounds_chained") \
        / rounds if rounds else None
