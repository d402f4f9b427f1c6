"""The sweep's decision on stubbed windows: several windows a rate, the
median window decides the rate, the knee lies under the first rate that
was not sustained."""

import importlib.util

import pytest

from harness import spec

_spec = importlib.util.spec_from_file_location(
    "bench_sweep", spec.BENCH / "sweep.py")
sweep = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sweep)


def windows(rate, *verdicts):
    return [{"rate_rps": rate, "sustained": v} for v in verdicts]


@pytest.mark.parametrize("table, knee, rate", [
    # the median window decides: two of three sustain 2.0, one of three 3.0
    (windows(2.0, True, False, True) + windows(3.0, False, True, False),
     2.0, 1.6),
    # an outlier window below the knee does not move it
    (windows(1.0, True, True, True) + windows(2.0, True, True, False)
     + windows(3.0, False, False, False), 2.0, 1.6),
    # in a table that goes on, a rate sustained above a failed one is not
    # the knee
    (windows(1.0, True, True, True) + windows(2.0, False, False, True)
     + windows(3.0, True, True, True) + windows(4.0, False, False, False),
     1.0, 0.8),
    # one window a rate is the old sweep
    (windows(0.7, True) + windows(1.0, False), 0.7, 0.56),
    # an even count needs more than half
    (windows(1.0, True, True) + windows(2.0, True, False), 1.0, 0.8),
], ids=["median", "outlier", "non-monotonic", "one-window", "even"])
def test_a_knee_is_the_rate_under_the_first_that_was_not_sustained(
        table, knee, rate):
    verdict = sweep.decide(table)
    assert verdict["knee_rps"] == knee
    assert verdict["rate_rps"] == rate
    assert [r["rate_rps"] for r in verdict["rates"]] \
        == sorted({w["rate_rps"] for w in table})


@pytest.mark.parametrize("table, where", [
    (windows(1.0, True, True, True) + windows(2.0, True, False, True),
     "above"),
    (windows(1.0, False, False, True) + windows(2.0, True, True, True),
     "below"),
    ([], "above"),
], ids=["all-sustained", "lowest-failed", "empty"])
def test_a_sweep_that_did_not_bracket_the_knee_finds_none(table, where):
    verdict = sweep.decide(table)
    assert verdict["knee_rps"] is None and verdict["rate_rps"] is None
    assert where in verdict["why"] and "NOT found" in verdict["why"]


def test_the_order_of_the_windows_does_not_matter():
    table = windows(3.0, False, False) + windows(1.0, True, True) \
        + windows(2.0, True) + windows(3.0, True) + windows(2.0, True, False)
    assert sweep.decide(table)["knee_rps"] == 2.0
