"""The reader of `index.key_gathers_per_step` on counters made by hand: a
window whose T=4 scans each laid their keys out once (the layers over four
steps), the window's delta over scans of two lengths, a window with no scan
(nothing to read), the parent's program, which has the scans and no
`index_key_gathers_scan` (nothing to read: every layer of every step
gathered and nothing counted it), and a model without an indexer; and that
the manifest lists the metric, last, for the cell that runs the selection."""

import json

import pytest

from harness import layers, spec

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}


def window(engine0, batcher0, engine1, batcher1):
    ends = lambda e, b: {"engine": e, "batcher": b, "direct": {}}  # noqa: E731
    return {"w0": 100.0, "w1": 151.0, "c0": ends(engine0, batcher0),
            "c1": ends(engine1, batcher1)}


@pytest.mark.parametrize("e0,b0,e1,b1,want", [
    # 500 T=4 scans of an 8-layer model, each gathered once
    ({"index_key_gathers_scan": 0}, {"scans_t4": 0},
     {"index_key_gathers_scan": 8 * 500}, {"scans_t4": 500}, 2.0),
    # the delta: 100 T=4 scans and 50 single steps, every one gathered
    ({"index_key_gathers_scan": 800}, {"scans_t1": 10, "scans_t4": 100},
     {"index_key_gathers_scan": 800 + 8 * 150},
     {"scans_t1": 60, "scans_t4": 200}, 8 * 150 / 450),
    # scans that stayed under topk gathered nothing
    ({"index_key_gathers_scan": 16}, {"scans_t4": 2},
     {"index_key_gathers_scan": 16}, {"scans_t4": 42}, 0.0),
    ({"index_key_gathers_scan": 16}, {"scans_t4": 2},
     {"index_key_gathers_scan": 16}, {"scans_t4": 2}, None),
    ({"index_row_steps_scan": 0}, {"scans_t4": 0},
     {"index_row_steps_scan": 16000}, {"scans_t4": 500}, None),
    ({"moe_layer_calls_scan": 12}, {"scans_t4": 0},
     {"moe_layer_calls_scan": 212}, {"scans_t4": 25}, None),
], ids=["once-a-scan", "window-delta", "under-topk", "no-scan", "no-counter",
        "no-indexer"])
def test_key_gathers_are_counted_over_the_windows_steps(e0, b0, e1, b1, want):
    entry = {"name": "index.key_gathers_per_step", "moves": "out_tok_s"}
    read = layers.readers(dict(CELL, per_layer=[entry]))[0][1]
    got = read({"win": window(e0, b0, e1, b1)})
    assert got == (want if want is None else pytest.approx(want))


def test_the_manifest_lists_it_for_the_cell_that_selects():
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "index.key_gathers_per_step"]
    assert entry["workloads"] == ["keye-vl-2.0-30b-a3b-8l-int8.docqa"]
    assert (entry["unit"], entry["better"], entry["moves"], entry["source"],
            entry["layer"]) == ("gathers", "lower", "out_tok_s",
                                "program_counter", "model")
