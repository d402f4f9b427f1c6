"""The reader of `batcher.chained_round_share` on counters made by hand: the
window's delta, a window without a ragged round (nothing to read), and the
parent's program, which has `ragged_rounds` and no `ragged_rounds_chained`
(0, which is the truth: it reads every scan before it builds a round)."""

import pytest

from harness import layers

CELL = {"name": "c", "end_to_end": {"gap_p50_ms": {}}}


def window(batcher0, batcher1):
    ends = lambda b: {"engine": {}, "batcher": b, "direct": {}}  # noqa: E731
    return {"w0": 100.0, "w1": 151.0, "c0": ends(batcher0),
            "c1": ends(batcher1)}


@pytest.mark.parametrize("c0,c1,want", [
    ({"ragged_rounds": 20, "ragged_rounds_chained": 11},
     {"ragged_rounds": 420, "ragged_rounds_chained": 251}, 60.0),
    ({"ragged_rounds": 9, "ragged_rounds_chained": 4},
     {"ragged_rounds": 9, "ragged_rounds_chained": 4}, None),
    ({"ragged_rounds": 20}, {"ragged_rounds": 420}, 0.0),
], ids=["window-delta", "no-round", "no-counter"])
def test_chained_round_share_is_chained_over_ragged_rounds(c0, c1, want):
    entry = {"name": "batcher.chained_round_share", "moves": "gap_p50_ms"}
    read = layers.readers(dict(CELL, per_layer=[entry]))[0][1]
    assert read({"win": window(c0, c1)}) == want


def test_the_manifest_lists_it_for_the_two_one_chip_chat_cells():
    import json

    from harness.spec import BENCH

    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(e for e in manifest["per_layer"]
                 if e["name"] == "batcher.chained_round_share")
    assert entry == {
        "name": "batcher.chained_round_share", "unit": "%",
        "better": "higher", "source": "program_counter", "layer": "batcher",
        "moves": "gap_p50_ms",
        "workloads": ["mistral-7b-int8.chat", "olmoe-1b-7b-int8.chat"]}
