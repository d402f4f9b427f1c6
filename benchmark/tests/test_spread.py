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
    """Four streams with the events ``times`` after they are due (or each
    with its own, where ``times`` is a list of four) and one past the
    window."""
    each = times if isinstance(times[0], list) else [times] * 4
    rows = [{"id": f"r{k}", "due": 0.1 * k, "sent": 0.1 * k, "status": 200,
             "t": [0.1 * k + t for t in ts], "n": [1] * len(ts),
             "asked": len(ts), "prompt_tokens": 10,
             "done_at": 100.0, "finish": "length", "usage_out": len(ts),
             "id_min": 4, "id_max": 99, "error": None}
            for k, ts in enumerate(each)]
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


def test_the_longest_wait_median_is_tabled_and_judged(tmp_path, capsys,
                                                      monkeypatch):
    # ten runs of a cell the root manifest judges by ``gap_p50_ms``: every
    # stream's longest wait is 38 ms and a little more with the seed, but
    # for the one stream whose 60 ms waits come and go (the pooled tail)
    cell = "mistral-7b-int8.chat"

    def events(waits):
        times, t = [1.0], 1.0
        for w in waits:
            t += w
            times.append(t)
        return times

    for seed in range(1, 11):
        calm = [0.011] * 20 + [0.038 + 0.0001 * seed] + [0.011] * 20
        run_file(tmp_path, seed, [events(calm)] * 3
                 + [events(calm + [0.06] * (seed % 4))], name=cell)
    one = spread.report(spread.load_runs([str(tmp_path)]))[cell]
    assert one["judged"].get("gap_p50_ms") == 0.1
    assert "itl_p98_ms" not in one["judged"]
    assert one["values"]["gap_p50_ms"][0] == pytest.approx(38.1)
    assert one["table"]["gap_p40_ms"]["n"] == 10
    monkeypatch.setattr("sys.argv", ["spread.py", str(tmp_path),
                                     "--bound", "0.08"])
    assert spread.main() == 0
    lines = {ln.split()[0]: ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("  ")}
    assert lines["gap_p50_ms"].endswith("0.1 admitted")
    assert lines["itl_p50_ms"].endswith("0.08 admitted candidate")
    assert lines["itl_p98_ms"].endswith("0.08 NOT ADMITTED candidate")
    assert "0.08" not in lines["n_waits"]
