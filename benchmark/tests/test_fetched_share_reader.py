"""The reader of `index.fetched_share` on counters made by hand: the
window's delta, a window whose scans took no row-step (nothing to read),
the parent's program, which has `index_context_tokens_scan` and no
`index_fetched_tokens_scan` (nothing to read: its kernel fetched every
page and counted nothing), and a model without an indexer; and that the
manifest lists the metric for the cell that runs the selection."""

import json

import pytest

from harness import layers, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}


def window(engine0, engine1):
    ends = lambda e: {"engine": e, "batcher": {}, "direct": {}}  # noqa: E731
    return {"w0": 100.0, "w1": 151.0, "c0": ends(engine0),
            "c1": ends(engine1)}


@pytest.mark.parametrize("c0,c1,want", [
    # 640 row-steps at 20,000 cached tokens, 11,680 of them fetched
    ({"index_context_tokens_scan": 0, "index_fetched_tokens_scan": 0},
     {"index_context_tokens_scan": 640 * 20000,
      "index_fetched_tokens_scan": 640 * 11680}, 58.4),
    ({"index_context_tokens_scan": 1000, "index_fetched_tokens_scan": 1000},
     {"index_context_tokens_scan": 5000, "index_fetched_tokens_scan": 2000},
     25.0),
    ({"index_context_tokens_scan": 70, "index_fetched_tokens_scan": 64},
     {"index_context_tokens_scan": 70, "index_fetched_tokens_scan": 64},
     None),
    ({"index_context_tokens_scan": 0},
     {"index_context_tokens_scan": 640 * 20000}, None),
    ({"moe_layer_calls_scan": 12}, {"moe_layer_calls_scan": 212}, None),
], ids=["share", "window-delta", "no-row-step", "no-counter", "no-indexer"])
def test_fetched_share_is_fetched_over_context(c0, c1, want):
    entry = {"name": "index.fetched_share", "moves": "out_tok_s"}
    read = layers.readers(dict(CELL, per_layer=[entry]))[0][1]
    got = read({"win": window(c0, c1)})
    assert got == (want if want is None else pytest.approx(want))


def test_the_manifest_lists_it_for_the_cell_that_selects():
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "index.fetched_share"]
    assert entry == manifest["per_layer"][-1]
    assert entry["workloads"] == ["keye-vl-2.0-30b-a3b-8l-int8.docqa"]
    assert (entry["better"], entry["moves"], entry["source"]) == (
        "lower", "out_tok_s", "program_counter")
