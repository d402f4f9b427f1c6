"""Percentiles and due-time arithmetic on a synthetic request log."""

import json

import pytest

from harness import metrics, spec

VOCAB = 1000


def row(due, sent, times, asked=None, prompt=10, status=200):
    asked = len(times) if asked is None else asked
    return {"id": "x", "due": due, "sent": sent, "status": status,
            "t": list(times), "n": [1] * len(times), "asked": asked,
            "prompt_tokens": prompt, "done_at": (times[-1] if times else None),
            "finish": "length", "usage_out": asked, "id_min": 4, "id_max": 99,
            "error": None, "ids": [], "timeline": None}


def test_percentile_is_linear_between_order_statistics():
    assert metrics.percentile([], 90) is None
    assert metrics.percentile([5.0], 90) == 5.0
    assert metrics.percentile([0, 10], 50) == 5.0
    xs = list(range(101))
    assert metrics.percentile(xs, 90) == 90.0
    assert abs(metrics.percentile([1, 2, 3, 4], 90) - 3.7) < 1e-12


def test_ttft_counts_from_due_so_a_stall_reaches_those_queued_behind_it():
    # a server that stalls from t=1 to t=3 and then answers at once: three
    # requests due at 1.0, 1.5, 2.0 are all sent late (the generator's
    # peer was blocked) and get their first token at 3.0
    stalled = [row(1.0, 2.9, [3.0, 3.1]), row(1.5, 2.9, [3.0, 3.1]),
               row(2.0, 2.9, [3.0, 3.1])]
    assert [round(metrics.ttft_ms(r)) for r in stalled] == [2000, 1500, 1000]
    # timed from when they were sent, all three would read 100 ms
    assert all(round((r["t"][0] - r["sent"]) * 1e3) == 100 for r in stalled)
    s = metrics.summarize(stalled, 0.0, 10.0, VOCAB)
    assert round(s["gen_late_p90_ms"]) == 1800
    assert s["ttft_p90_ms"] > 1800


def test_tpot_and_gap():
    times = [1.0 + 0.02 * k for k in range(8)] + [2.0, 2.02]
    r = row(0.5, 0.5, times)
    assert abs(metrics.tpot_ms(r) - (2.02 - 1.0) / 9 * 1e3) < 1e-9
    assert abs(metrics.longest_gap_ms(r) - (2.0 - 1.14) * 1e3) < 1e-6
    assert metrics.tpot_ms(row(0, 0, [1, 2, 3])) is None   # under 8 tokens


def test_pooled_gaps_and_mean_ttft():
    # 99 waits of 10 ms and one of 500 ms in one stream, 100 of 10 ms in
    # another: the pooled 99th percentile sits just under the one stall
    a = row(0.0, 0.0, [1.0 + 0.01 * k for k in range(100)] + [1.99 + 0.5])
    b = row(0.5, 0.5, [1.0 + 0.01 * k for k in range(101)])
    s = metrics.summarize([a, b], 0.0, 10.0, VOCAB)
    assert len(metrics.gaps_ms(a)) == 100
    assert 10.0 < s["itl_p99_ms"] < 500.0
    assert abs(metrics.percentile(
        metrics.gaps_ms(a) + metrics.gaps_ms(b), 99) - s["itl_p99_ms"]) < 1e-9
    assert abs(s["ttft_mean_ms"] - 750.0) < 1e-6        # 1000 and 500
    assert abs(s["ttft_p50_ms"] - 750.0) < 1e-6
    assert set(metrics.END_TO_END) <= set(s)


def test_window_sample_throughput_and_failures():
    rows = [
        row(0.5, 0.5, [0.9, 1.1, 1.3]),            # due before the window
        row(1.2, 1.2, [1.5, 1.9, 2.5]),            # one token after it
        row(1.5, 1.5, [], status=503),             # refused
        row(1.8, 1.8, [1.9], asked=4),             # cut short
    ]
    rows[2]["done_at"] = None
    s = metrics.summarize(rows, 1.0, 2.0, VOCAB,
                          {"ttft_ms": 1000, "ttft_ms_per_prompt_token": 1,
                           "tpot_ms": 100})
    assert (s["attempted"], s["failed"], s["refused"]) == (3, 2, 1)
    assert s["out_tok_s"] == 5.0        # 1.1 1.3 1.5 1.9 1.9
    assert abs(s["slo_ok_share"] - 1 / 3) < 1e-12


def test_a_token_outside_the_vocabulary_is_a_failure():
    r = row(0, 0, [1, 2])
    r["id_max"] = VOCAB
    assert not metrics.complete(r, VOCAB)


def test_mean_decode_context():
    r = row(0, 0, [1, 2, 3, 4], prompt=100)
    assert metrics.mean_decode_context([r]) == 101.5


# --------------------------------------------------------------------- #
# the statistics of the waits that cells are judged by: a stall statistic
# where one repeats (PR 36: the median over streams of a stream's longest
# wait; the pooled 98th percentile of PR 30 is recorded beside it), the
# typical wait where none does
# --------------------------------------------------------------------- #

def judged_in(cell):
    """The latency metrics ``cell`` is judged by, with their bounds."""
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    return {m["name"]: m["bound"] for m in manifest["end_to_end"]
            if cell in m.get("workloads", ()) and m["name"] != "setup_s"}


def stall_bound(cell):
    """The bound of the one latency metric ``cell`` is judged by."""
    (bound,) = judged_in(cell).values()
    return bound


def window_of(waits_ms):
    """Rows whose pooled waits are ``waits_ms``: streams of 100 waits."""
    rows = []
    for k in range(0, len(waits_ms), 100):
        times, t = [1.0], 1.0
        for w in waits_ms[k:k + 100]:
            t += w / 1e3
            times.append(t)
        rows.append(row(0.5, 0.5, times))
    return rows


def chat_like(stalls, longer=0.0, extra=()):
    """16,000 waits as the Mistral chat cell's at 4.0 req/s: three quarters
    inside the burst a T=4 scan delivers, a quarter the cadence of those
    scans (45 ms) and the rounds with one piece or two (35.4 and 57 ms,
    ``longer`` by that many), and ``stalls`` waits of a T=16 scan (195 ms) in place of cadence."""
    slow = [195.0] * stalls + list(extra)
    rounds = [35.4 + longer] * 400 + [57.0 + longer] * 200
    cadence = [45.0 + 0.002 * k for k in range(4000 - len(rounds) - len(slow))]
    burst = [0.4 + 0.0001 * k for k in range(12000)]
    waits = burst + cadence + rounds + slow
    assert len(waits) == 16000
    # spread over the streams as the rounds are over a window
    return [waits[(k * 37) % 16000] for k in range(16000)]


def summary_of(waits_ms):
    s = metrics.summarize(window_of(waits_ms), 0.0, 10.0, VOCAB)
    assert s["n_waits"] == len(waits_ms)
    return s


def test_the_judged_statistics_on_hand_made_rows():
    # 200 waits of 1 .. 200 ms in two streams
    a = row(0.0, 0.0, [sum(range(1, k + 1)) / 1e3 for k in range(0, 101)])
    b = row(0.0, 0.0, [10.0 + sum(range(101, k + 1)) / 1e3
                       for k in range(100, 201)])
    s = metrics.summarize([a, b], -1.0, 100.0, VOCAB)
    assert s["n_waits"] == 200
    assert s["itl_p50_ms"] == pytest.approx(100.5)
    assert s["itl_p98_ms"] == pytest.approx(196.02)
    assert s["itl_p99_ms"] == pytest.approx(198.01)
    # (last - first) / (tokens - 1): 5.05 s over 100 waits, 15.05 s over 100
    assert s["tpot_p50_ms"] == pytest.approx((50.5 + 150.5) / 2)
    assert s["ttft_p50_ms"] == pytest.approx(5000.0)    # 0 and 10,000 ms


def test_the_99th_percentile_jumps_where_a_cluster_crosses_it_and_the_98th_holds():
    bound = stall_bound("mistral-7b-int8.chat")
    # the waits of T=16 scans are 0.8 % of all, then 1.2 %: seeds of one
    # program read both (PERF.md, section 2)
    fewer, more = summary_of(chat_like(128)), summary_of(chat_like(192))
    assert more["itl_p99_ms"] > 1.3 * fewer["itl_p99_ms"]
    assert more["itl_p99_ms"] == pytest.approx(195.0)
    assert abs(more["itl_p98_ms"] / fewer["itl_p98_ms"] - 1.0) < bound / 2


def test_the_98th_percentile_does_not_follow_one_long_stall():
    calm = summary_of(chat_like(128))
    # one wait of 2 s in place of one of the cadence: a frozen machine
    hit = summary_of(chat_like(128, extra=[2000.0]))
    assert abs(hit["itl_p98_ms"] / calm["itl_p98_ms"] - 1.0) < 0.01
    # a mean of the slowest 1 % with nothing cut off the top would
    waits = sorted(w for r in window_of(chat_like(128, extra=[2000.0]))
                   for w in metrics.gaps_ms(r))
    calm_top = sorted(w for r in window_of(chat_like(128))
                      for w in metrics.gaps_ms(r))
    assert sum(waits[-160:]) > 1.05 * sum(calm_top[-160:])


@pytest.mark.parametrize("change, moves", [
    # what the 98th percentile has to see: the stalls' share doubling past
    # it, and every round with a piece 24 ms longer (PR 28's parent)
    ({"stalls": 384}, True),
    ({"stalls": 128, "longer": 24.0}, True),
    # and what it is blind to, said plainly: the stalls' share doubling
    # inside the top 2 % (the 99th percentile, recorded, reads that)
    ({"stalls": 256}, False),
], ids=["stall-share-past-2pc", "rounds-24ms-longer", "stall-share-inside-top-2pc"])
def test_what_moves_the_98th_percentile_by_more_than_its_bound(change, moves):
    bound = stall_bound("mistral-7b-int8.chat")
    base, changed = summary_of(chat_like(128)), summary_of(chat_like(**change))
    moved = changed["itl_p98_ms"] / base["itl_p98_ms"] - 1.0
    assert (moved > bound) is moves
    if not moves:
        assert changed["itl_p99_ms"] > (1.0 + bound) * base["itl_p99_ms"]


def stream(due, waits_ms):
    times, t = [due + 0.05], due + 0.05
    for w in waits_ms:
        t += w / 1e3
        times.append(t)
    return row(due, due, times)


def test_gap_p50_is_the_median_over_streams_of_the_longest_wait():
    rows = [stream(0.1, [10.0, 30.0, 10.0]), stream(0.2, [10.0] * 9),
            stream(0.3, [50.0, 10.0]), stream(0.4, [10.0, 90.0]),
            stream(0.5, [])]       # one event: no wait, so no longest one
    s = metrics.summarize(rows, 0.0, 10.0, VOCAB)
    longest = [metrics.longest_gap_ms(r) for r in rows]
    assert longest[-1] is None and s["attempted"] == 5 and s["n_gaps"] == 4
    assert s["gap_p50_ms"] == pytest.approx(40.0)       # 10 30 50 90
    # the population gap_p90_ms has had since PR 22, at another percentile
    assert s["gap_p90_ms"] == pytest.approx(
        metrics.percentile(longest[:4], 90))
    assert s["gap_p40_ms"] < s["gap_p50_ms"] < s["gap_p60_ms"]
    # a failed stream is out of it, as it is out of the pooled waits
    rows[3]["finish"] = "error"
    assert metrics.summarize(rows, 0.0, 10.0, VOCAB)["gap_p50_ms"] \
        == pytest.approx(30.0)
    assert metrics.summarize([rows[4]], 0.0, 10.0, VOCAB)["gap_p50_ms"] is None


def chat_streams(slow_rounds, piece=0.0):
    """100 streams of 160 waits as the Mistral chat cell's since PR 32: a
    wait is a T=1 step (11.2 ms) but for four rounds with one piece a
    stream (37-46 ms, ``piece`` longer: 2.5 % of the waits, so the pooled
    98th percentile lies inside that cluster), and ``slow_rounds`` rounds
    with two pieces (57 ms), each of which six decode rows sat through: the
    same six, the streams in flight while a burst of arrivals came."""
    rows = []
    for k in range(100):
        waits = [11.2] * 160
        for j in range(4):          # 400 waits spread evenly over 37-46 ms
            waits[20 + 30 * j] = 37.0 + piece + 9.0 * (4 * k + j) / 400
        if 40 <= k < 46:
            for j in range(slow_rounds):
                waits[5 + 5 * j] = 57.0 + piece
        rows.append(stream(0.01 * k, waits))
    return rows


def test_a_slow_round_counts_once_a_row_in_the_pooled_tail_and_once_a_stream():
    bound = stall_bound("mistral-7b-int8.chat")
    few, many = (metrics.summarize(chat_streams(n), 0.0, 10.0, VOCAB)
                 for n in (10, 30))
    assert few["n_waits"] == many["n_waits"] == 16000
    # 60 against 180 of 16,000 waits (0.4 and 1.1 %: what seeds of one
    # program read, PERF.md section 2) slide the 98th percentile along the
    # cluster it lies in by more than the rule allows a spread
    assert many["itl_p98_ms"] / few["itl_p98_ms"] - 1.0 > bound / 2
    # the same six streams had a slow round as their longest wait in both
    assert many["gap_p50_ms"] == few["gap_p50_ms"]
    assert many["gap_p90_ms"] == few["gap_p90_ms"]
    # and without any slow round it stands three ranks of a hundred lower
    none = metrics.summarize(chat_streams(0), 0.0, 10.0, VOCAB)
    assert 0.0 < few["gap_p50_ms"] / none["gap_p50_ms"] - 1.0 < bound / 4


def test_the_median_longest_wait_still_reads_the_round_with_a_piece():
    bound = stall_bound("mistral-7b-int8.chat")
    base = metrics.summarize(chat_streams(10), 0.0, 10.0, VOCAB)
    assert 37.0 < base["gap_p50_ms"] < 46.0
    # every round with a piece 8 ms longer (the rectangle's copies S9 would
    # take are 3.3 of 35.4 ms; PR 28 took 24): seen at 1.5 x the bound
    slower = metrics.summarize(chat_streams(10, piece=8.0), 0.0, 10.0, VOCAB)
    assert slower["gap_p50_ms"] / base["gap_p50_ms"] - 1.0 > 1.5 * bound
    # which the typical wait, a step, does not see
    assert slower["itl_p50_ms"] == pytest.approx(base["itl_p50_ms"])
    assert base["itl_p50_ms"] == pytest.approx(11.2)


def test_the_median_wait_reads_the_decode_step_whatever_the_tail_does():
    # the rag cell's waits at 2.24 req/s: 60 % decode steps of 20 ms, 30 %
    # rounds with one piece, the rest two pieces and more (57 / 175 ms)
    def rag_like(wide, step=20.0):
        waits = [step + 0.001 * k for k in range(2100)] + [35.0] * 1050 \
            + [57.0] * (350 - wide) + [175.0] * wide
        return [waits[(k * 37) % 3500] for k in range(3500)]

    bound = judged_in("qwen2.5-7b-int8.rag")["itl_p50_ms"]
    few, many = summary_of(rag_like(18)), summary_of(rag_like(70))
    assert many["itl_p99_ms"] > 2 * few["itl_p99_ms"]       # 57 -> 175
    assert abs(many["itl_p50_ms"] / few["itl_p50_ms"] - 1.0) < 0.01
    slower = summary_of(rag_like(18, step=22.0))             # a step 10 % up
    assert slower["itl_p50_ms"] / few["itl_p50_ms"] - 1.0 > bound


def test_every_open_loop_cell_is_judged_by_one_metric_of_the_waits_or_tpot():
    manifest = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    for w in manifest["workloads"]:
        judged = judged_in(w["name"])
        assert judged, w["name"]
        assert all(n in metrics.END_TO_END for n in judged), judged
