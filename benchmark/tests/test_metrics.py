"""Percentiles and due-time arithmetic on a synthetic request log."""

from harness import metrics

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
