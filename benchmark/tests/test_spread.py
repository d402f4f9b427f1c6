"""The admission rule's arithmetic, and a detail file read back."""

import importlib.util
import json

import pytest

from harness import spec

_spec = importlib.util.spec_from_file_location(
    "bench_spread", spec.BENCH / "spread.py")
spread = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(spread)


def test_spread_is_the_quartile_distance_of_statistics_quantiles():
    xs = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    t = spread.spread_of(xs)
    # statistics.quantiles(n=4), exclusive method: 100.75 and 104.25
    assert (t["q1"], t["q3"], t["median"]) == (100.75, 104.25, 102.5)
    assert t["spread"] == pytest.approx(3.5 / 102.5)
    # one far-off run widens the range only while it is counted
    t = spread.spread_of(xs + [150.0])
    assert t["range_without_farthest"] == pytest.approx(5.0 / 103.0)
    assert t["max"] == 150.0


def test_too_few_runs_or_a_zero_median_give_nothing():
    assert spread.spread_of([1.0]) is None
    assert spread.spread_of([None, 2.0]) is None
    assert spread.spread_of([0.0, 0.0, 0.0]) is None


def run_file(tmp_path, seed, times, name="tiny-mistral.chat", trace=0):
    rows = [{"id": f"r{k}", "due": 0.1 * k, "sent": 0.1 * k, "status": 200,
             "t": [0.1 * k + t for t in times], "n": [1] * len(times),
             "asked": len(times), "prompt_tokens": 10,
             "done_at": 100.0, "finish": "length", "usage_out": len(times),
             "id_min": 4, "id_max": 99, "error": None} for k in range(4)]
    rows.append(dict(rows[0], id="late", due=6.5, sent=6.5))  # past the window
    d = {"cell": name, "seed": seed, "seconds": 6.0, "trace": trace,
         "rate_rps": 5.0, "checks": {"probes": True},
         "summary": {"out_tok_s": 10.0}, "requests": rows,
         "end_to_end": {"setup_s": {"value": 50.0 + seed, "unit": "s"}},
         "timing": {"graphs_s": 30.0 + seed},
         "compiles": [{"cache": "hit"}]}
    path = tmp_path / f"{name}.seed{seed}.trace{trace}.json"
    path.write_text(json.dumps(d))


def test_detail_files_are_read_and_their_statistics_recomputed(tmp_path):
    run_file(tmp_path, 1, [1.0, 1.01, 1.02])
    run_file(tmp_path, 2, [1.0, 1.03, 1.06])
    run_file(tmp_path, 3, [1.0, 1.01, 1.02], trace=1)      # traced: left out
    (tmp_path / "other.json").write_text("[1, 2]")           # not a run
    runs = spread.load_runs([str(tmp_path)])
    assert [d["seed"] for d in runs["tiny-mistral.chat"]] == [1, 2]
    one = spread.report(runs)["tiny-mistral.chat"]
    assert one["runs"] == 2 and one["all_correct"]
    assert one["values"]["itl_p99_ms"] == pytest.approx([10.0, 30.0])
    assert one["values"]["ttft_mean_ms"] == pytest.approx([1000.0, 1000.0])
    assert one["table"]["attempted"]["median"] == 4     # the late row is out
    assert one["table"]["setup_s"]["median"] == 51.5
    assert one["table"]["timing.graphs_s"]["n"] == 2
    assert "itl_p99_ms" in one["judged"]
    assert one["values"]["tpot_p50_ms"] == [None, None]  # under 8 tokens
