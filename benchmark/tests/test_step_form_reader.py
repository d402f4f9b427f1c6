"""The reader of `moe.step_form_share` on counters made by hand: the
window's delta, a window whose scans called no expert layer (nothing to
read), and the parent's program, which has `moe_layer_calls_scan` and no
`moe_step_form_calls_scan` (0, which is the truth: every one of its calls
lays its rows out in tiles)."""

import pytest

from harness import layers

CELL = {"name": "c", "end_to_end": {"out_tok_s": {}}}


def window(engine0, engine1):
    ends = lambda e: {"engine": e, "batcher": {}, "direct": {}}  # noqa: E731
    return {"w0": 100.0, "w1": 151.0, "c0": ends(engine0),
            "c1": ends(engine1)}


@pytest.mark.parametrize("c0,c1,want", [
    ({"moe_layer_calls_scan": 260, "moe_step_form_calls_scan": 260},
     {"moe_layer_calls_scan": 130260, "moe_step_form_calls_scan": 130260},
     100.0),
    ({"moe_layer_calls_scan": 100, "moe_step_form_calls_scan": 0},
     {"moe_layer_calls_scan": 500, "moe_step_form_calls_scan": 100}, 25.0),
    ({"moe_layer_calls_scan": 9, "moe_step_form_calls_scan": 9},
     {"moe_layer_calls_scan": 9, "moe_step_form_calls_scan": 9}, None),
    ({"moe_layer_calls_scan": 12}, {"moe_layer_calls_scan": 212}, 0.0),
], ids=["every-call", "window-delta", "no-call", "no-counter"])
def test_step_form_share_is_step_calls_over_layer_calls(c0, c1, want):
    entry = {"name": "moe.step_form_share", "moves": "out_tok_s"}
    read = layers.readers(dict(CELL, per_layer=[entry]))[0][1]
    assert read({"win": window(c0, c1)}) == want
