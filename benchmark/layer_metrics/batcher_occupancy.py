"""Sequences holding a slot per engine round: the batcher's
`occupancy_sum` over its `decode_rounds`, window delta. Eight is full."""

from harness.window import delta


def read(run):
    rounds = delta(run["win"], "batcher", "decode_rounds")
    return delta(run["win"], "batcher", "occupancy_sum") / rounds \
        if rounds else None
