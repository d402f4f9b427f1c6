"""The reader of `batcher.admit_ahead_share` on counters made by hand: the
window's delta, a window without an admission (nothing to read), and the
parent's program, which has `ragged_admissions` and no `admissions_ahead`
(0, which is the truth: it admits nothing ahead of a read)."""

import pytest

from harness import layers

CELL = {"name": "c", "end_to_end": {"gap_p50_ms": {}}}


def window(batcher0, batcher1):
    ends = lambda b: {"engine": {}, "batcher": b, "direct": {}}  # noqa: E731
    return {"w0": 100.0, "w1": 151.0, "c0": ends(batcher0),
            "c1": ends(batcher1)}


@pytest.mark.parametrize("c0,c1,want", [
    ({"ragged_admissions": 12, "admissions_ahead": 7},
     {"ragged_admissions": 212, "admissions_ahead": 157}, 75.0),
    ({"ragged_admissions": 9, "admissions_ahead": 4},
     {"ragged_admissions": 9, "admissions_ahead": 4}, None),
    ({"ragged_admissions": 12}, {"ragged_admissions": 212}, 0.0),
], ids=["window-delta", "no-admission", "no-counter"])
def test_admit_ahead_share_is_ahead_over_admissions(c0, c1, want):
    entry = {"name": "batcher.admit_ahead_share", "moves": "gap_p50_ms"}
    read = layers.readers(dict(CELL, per_layer=[entry]))[0][1]
    assert read({"win": window(c0, c1)}) == want
