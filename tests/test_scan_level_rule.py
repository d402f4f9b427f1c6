"""The batcher's one horizon rule (PR 26), on a fake engine and scripted
times: nothing here depends on a device or on the host's clock.

The batcher keeps two measured times — ``s``, a scan's time per step, and
``h``, what a round costs the host whatever it holds — and the level they
amortise at: the smallest T with T · s ≥ 4 · h, with 10 % of hysteresis.
A scan runs that level; one level above it while requests wait for a slot
and no decoding row can end inside the longer scan. Only scans are
measured: a ragged round is as long as the prompt tokens it admits. ``h``
is kept per scan length, because a longer scan's host time grows with it.
"""

import asyncio
import random
import time
import types

import numpy as np
import pytest

from distributed_gpu_inference_tpu.runtime import batcher as batcher_mod
from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
)
from distributed_gpu_inference_tpu.testing.fakes import FakeRaggedEngine
from tests.test_long_context import _req

# (s, h) in ms and the level they must give: the five cells as the chip
# measured them (PERF.md section 6, PR 26: ``step_latency_ema_ms`` and
# ``round_host_ema_ms`` at the window's end), the saturated dense worker's
# one-step round that sat on the edge at c = 3, and the two ends the rule has
# to reach without a configured number
CELLS = {
    "mistral-7b-int8.chat": (11.7, 2.0, 1),
    "qwen2.5-7b-int8.rag": (16.6, 1.7, 1),
    "mixtral-8x7b-int8-tp4.chat": (30.6, 4.0, 1),
    "olmoe-1b-7b-int8.chat": (5.0, 3.4, 4),
    "mistral-7b-int8.decode": (11.5, 7.8, 4),
    "mistral-7b-int8.decode-at-T1": (11.5, 4.2, 4),
    "a-2ms-step": (2.0, 8.0, 16),
    "a-60ms-step": (60.0, 8.0, 1),
}
DENSE = ["mistral-7b-int8.chat", "qwen2.5-7b-int8.rag",
         "mistral-7b-int8.decode", "mistral-7b-int8.decode-at-T1",
         "mixtral-8x7b-int8-tp4.chat"]


class _Budgets:
    """The least of an engine ``_choose_steps`` reads."""

    def __init__(self, budgets=()):
        self.budgets = np.array(budgets, dtype=np.int32)
        self.slots = [None] * max(1, len(self.budgets))
        self.cfg = types.SimpleNamespace()

    def decode_budgets(self):
        return self.budgets


def _batcher(budgets=(), **cfg):
    return ContinuousBatcher(_Budgets(budgets), BatcherConfig(**cfg))


def _feed(b, s_ms, h_ms):
    """One scan at the level the batcher is at, measured as (s, h)."""
    steps = b._levels[b._level]
    b._retune(steps, steps * s_ms / 1e3, h_ms / 1e3)
    return b._levels[b._level]


def test_config_has_no_latency_target_and_no_busy_cap():
    cfg = BatcherConfig()
    assert not hasattr(cfg, "target_step_latency_ms")
    assert not hasattr(cfg, "busy_multi_step")
    assert cfg.horizon_levels == (1, 4, 16, 64)
    # before anything is measured: the level nearest multi_step
    assert _batcher()._horizon == 4.0 and _batcher(multi_step=20)._horizon == 16.0


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_gets_the_level_its_measured_times_amortise_at(cell):
    s, h, want = CELLS[cell]
    b = _batcher()
    seen = {_feed(b, s, h) for _ in range(50)}
    # a constant input settles at once and never flaps
    assert seen == {want}
    assert b.stats["horizon"] == want
    assert b.stats["step_latency_ema_ms"] == pytest.approx(s)
    assert b.stats["round_host_ema_ms"] == pytest.approx(h)
    # the host's share of scan + host is bounded by 1 / (1 + c), unless even
    # the lowest level is more than long enough
    assert want * s >= batcher_mod._HOST_AMORTISE * h * 0.9


@pytest.mark.parametrize("cell", CELLS)
def test_a_noisy_input_changes_the_level_at_most_once(cell):
    s, h, _ = CELLS[cell]
    rng = random.Random(26)
    b = _batcher()
    levels = [b._levels[b._level]]
    for _ in range(500):
        levels.append(_feed(b, s * rng.uniform(0.9, 1.1),
                            h * rng.uniform(0.9, 1.1)))
    changes = sum(1 for a, c in zip(levels, levels[1:]) if a != c)
    assert changes <= 1, levels


def test_a_step_time_that_drifts_with_occupancy_changes_level_at_most_once():
    """OLMoE: 5.7 ms a step at 3.5 rows, 15 ms at 8, and back."""
    b = _batcher()
    drift = np.concatenate([np.linspace(5.7, 15.0, 200),
                            np.linspace(15.0, 5.7, 200)])
    levels = [_feed(b, float(s), 5.6) for s in drift]
    assert sum(1 for a, c in zip(levels, levels[1:]) if a != c) <= 1
    assert set(levels) == {4}


@pytest.mark.parametrize("start", [1, 4, 16, 64])
def test_the_level_is_reached_from_any_start(start):
    b = _batcher(multi_step=start)
    assert b._horizon == start
    for _ in range(30):
        _feed(b, 11.0, 8.0)
    assert b._horizon == 4.0


def test_a_fixed_horizon_keeps_its_one_level():
    b = _batcher(adaptive=False, multi_step=8)
    assert b._levels == (8,)
    for s in (0.5, 11.0, 500.0):
        assert _feed(b, s, 8.0) == 8
    b._heap.append(object())
    assert b._choose_steps() == (8, "amortise")


@pytest.mark.parametrize("waiting, budgets, multi_step, want", [
    (False, [100, 100], 4, (4, "amortise")),
    (True, [100, 50], 4, (16, "raised_waiting")),
    (True, [100, 16], 4, (16, "raised_waiting")),
    (True, [100, 15], 4, (4, "capped_by_budget")),
    # never below amortise, whatever the budget
    (True, [3, 100], 4, (4, "capped_by_budget")),
    (True, [0, 0, 40, 0], 4, (16, "raised_waiting")),
    (True, [4, 9], 1, (4, "raised_waiting")),
    (True, [2, 3], 1, (1, "capped_by_budget")),
    # one level above, never two
    (True, [500, 500], 1, (4, "raised_waiting")),
    # nothing above the top level
    (True, [500, 500], 64, (64, "amortise")),
    # the top level is reached from the one below, at the budget's edge
    (True, [500, 64], 16, (64, "raised_waiting")),
], ids=["idle", "raised", "raised-at-the-budget", "capped", "capped-low",
        "empty-slots-ignored", "raised-from-1", "capped-at-1",
        "one-level-only", "top-level", "raised-to-the-top"])
def test_waiting_raises_one_level_capped_by_the_first_rows_end(
        waiting, budgets, multi_step, want):
    b = _batcher(budgets, multi_step=multi_step)
    if waiting:
        b._heap.append(object())
    assert b._choose_steps() == want


def test_a_level_is_judged_by_its_own_host_cost():
    """The decode cell as the chip showed it (PR 26, calls 1-3): 8 rows,
    11.4 ms a step, and a round costs the host about 6.5 ms + 0.37 ms a
    step (block reservation and streaming grow with the steps). Judged by
    the raised scans' 12.4 ms the level called for T=16 and then T=64, and
    stayed; judged by its own cost it holds at 4, the raised scans at 16."""
    def host_s(steps):
        return (6.5 + 0.37 * steps) / 1e3

    b = _batcher([10**6] * 8)
    b._heap.append(object())
    ran = []
    for i in range(400):
        b.engine.budgets[:] = 10**6 if i % 3 else 7     # a row near its end
        steps, _ = b._choose_steps()
        ran.append(steps)
        b._retune(steps, steps * 0.0114, host_s(steps))
    assert set(ran) == {4, 16} and b._horizon == 4.0
    assert b._host_ms == {4: pytest.approx(6.5 + 0.37 * 4),
                          16: pytest.approx(6.5 + 0.37 * 16)}
    assert b.stats["round_host_ema_ms"] == b._host_ms[4]
    # pushed up a level, it comes straight back: T=4 is known to be enough
    b._level = b._levels.index(16)
    b._retune(16, 16 * 0.0114, host_s(16))
    assert b._horizon == 4.0
    # nothing ran at the level yet: it stays where multi_step put it
    cold = _batcher([10**6] * 8)
    for _ in range(20):
        cold._retune(16, 16 * 0.0114, host_s(16))
    assert cold._horizon == 4.0 and cold.stats["round_host_ema_ms"] == 0
    assert cold.stats["step_latency_ema_ms"] == pytest.approx(11.4)
    # a stale cost from an emptier worker (one row: 2 ms) is corrected by
    # the visit it causes
    cold._host_ms[1] = 2.0
    cold._retune(4, 4 * 0.0114, host_s(4))
    assert cold._horizon == 1.0
    for _ in range(30):
        _feed(cold, 11.4, 4.2 if cold._horizon == 1.0 else 8.0)
    assert cold._horizon == 4.0


def test_a_level_that_never_ran_is_visited_once():
    """Chat on the chip (PR 26, call 4): from the starting level 4,
    11.07 ms a step against 2.5 ms a round sat within the slack of T=1 and
    stayed or left by the run. An unmeasured level is tried without the
    slack; the visit measures it, and what it measured decides."""
    b = _batcher()
    assert _feed(b, 11.07, 2.5) == 1                # tried
    assert {_feed(b, 11.7, 2.0) for _ in range(50)} == {1}      # and kept
    # a visit that fails is not repeated: T=1's own cost is known now
    b = _batcher()
    assert _feed(b, 10.0, 2.4) == 1
    levels = [_feed(b, 10.0, 3.0 if b._horizon == 1.0 else 2.4)
              for _ in range(50)]
    assert levels == [4] * 50
    assert b._host_ms == {4: pytest.approx(2.4), 1: pytest.approx(3.0)}


def test_stalled_rounds_do_not_carry_the_level_past_a_threshold():
    """The decode cell's traced slice (PR 26, call 2): the gaps between two
    T=4 scans were 5.6-10 ms and one in eight 15 ms and more (a third after
    T=16 scans). The level's thresholds are h < 2.6 and h > 12.7 ms; the
    mean stays between."""
    rng = random.Random(2)
    b = _batcher()
    for _ in range(3000):
        h = rng.uniform(13.0, 24.0) if rng.random() < 0.125 \
            else rng.uniform(4.0, 8.0)
        assert _feed(b, 11.4 * rng.uniform(0.97, 1.03), h) == 4
    # one round that stalled for two seconds, one scan that did
    assert _feed(b, 11.4, 2000.0) == 4 and _feed(b, 2000.0, 8.0) == 4
    assert b.stats["round_host_ema_ms"] < 12.7
    assert b.stats["step_latency_ema_ms"] < 2 * 11.4


@pytest.mark.parametrize("cell", DENSE)
def test_no_dense_cell_ever_runs_the_top_level(cell):
    s, h, _ = CELLS[cell]
    b = _batcher([10**6] * 8)
    b._heap.append(object())
    rng = random.Random(7)
    for _ in range(300):
        _feed(b, s * rng.uniform(0.8, 1.25), h * rng.uniform(0.8, 1.25))
        assert b._choose_steps()[0] <= 16


# ------------------------------------------------------------------ #
# the loop, on the fake ragged engine
# ------------------------------------------------------------------ #


def _scripted(b, s_ms=11.0, h_ms=8.0):
    """Every scan of ``b`` reports (s, h) instead of what the clock said;
    returns the log of round kinds and the samples ``_retune`` was given."""
    kinds, samples = [], []
    real_round, real_retune = b._engine_round, b._retune

    def engine_round():
        out = real_round()
        kinds.append("ragged" if out is None else "scan")
        return out and (out[0], out[0] * s_ms / 1e3, h_ms / 1e3)

    def retune(*sample):
        samples.append(sample)
        real_retune(*sample)

    b._engine_round, b._retune = engine_round, retune
    return kinds, samples


@pytest.mark.parametrize("ragged_s", [0.0, 0.03], ids=["short", "long"])
def test_ragged_rounds_move_neither_the_step_time_nor_the_level(ragged_s):
    """A long prompt enters in many chunk rounds beside the row that
    decodes; whatever those rounds take, ``s``, ``h`` and the level stay
    where the scans put them."""
    import time

    eng = FakeRaggedEngine(ragged_chunk=8)
    real_ragged = eng.ragged_round
    eng.ragged_round = lambda *a: (time.sleep(ragged_s), real_ragged(*a))[1]
    b = ContinuousBatcher(eng, BatcherConfig(max_wait_ms=1))
    kinds, samples = _scripted(b)

    async def go():
        b.start()
        first = asyncio.ensure_future(b.submit(_req(range(4), max_new=400)))
        while "scan" not in kinds:          # the first request decodes
            await asyncio.sleep(0.001)
        second = await b.submit(_req(range(100, 196), max_new=2))
        stats = b.get_stats()
        first.cancel()
        await b.stop()
        return second, stats

    second, stats = asyncio.run(go())
    assert second.ok
    # 96 prompt tokens in chunks of 8: twelve rounds with the second
    # request's pieces, one more for the first request's own prompt
    assert kinds.count("ragged") >= 13 and stats["ragged_rounds"] >= 13
    assert "scan" in kinds[: kinds.index("ragged", 1)]
    # only scans were sampled
    assert len(samples) == kinds.count("scan") > 0
    assert stats["step_latency_ema_ms"] == pytest.approx(11.0)
    assert stats["round_host_ema_ms"] == pytest.approx(8.0)
    assert stats["horizon"] == 4.0


def test_the_rules_counters_count():
    """Two slots, three requests of 40 tokens: the third waits while the
    first two decode — scans one level up while both rows have 16 steps
    left, capped near their end — and then decodes alone at the amortise
    level. Each request's last scan runs past its end."""
    eng = FakeRaggedEngine(max_batch_size=2)
    b = ContinuousBatcher(eng, BatcherConfig(max_wait_ms=5))
    kinds, _ = _scripted(b)
    scans = []
    real_scan = eng.decode_multi

    def decode_multi(steps):
        waiting = bool(b._heap)
        out = real_scan(steps)
        scans.append((steps, waiting, out))
        return out

    eng.decode_multi = decode_multi

    async def go():
        b.start()
        resps = await asyncio.gather(
            *[b.submit(_req(range(10 * i, 10 * i + 4), max_new=40))
              for i in range(3)])
        stats = b.get_stats()
        await b.stop()
        return resps, stats

    resps, st = asyncio.run(go())
    assert all(r.ok and r.completion_tokens == 40 for r in resps)
    assert len(scans) == kinds.count("scan")
    # two rows, 39 tokens each after the round that sampled their first:
    # 16 + 16 raised, then 4 + 4 capped (7 and 3 steps left), the last one
    # a step past both rows' ends
    assert [(t, w) for t, w, _ in scans[:4]] == \
        [(16, True), (16, True), (4, True), (4, True)]
    assert st["scans_raised_waiting"] == 2
    assert st["scans_capped_by_budget"] == 2
    # the third request alone, nobody waiting: ten scans of 4
    assert all((t, w) == (4, False) for t, w, _ in scans[4:])
    assert st["scans_amortise"] == len(scans) - 4 == 10
    assert st["scans_t16"] == 2 and st["scans_t4"] == 12
    assert sum(st[f"scans_{r}"] for r in batcher_mod._SCAN_REASONS) == \
        sum(st[f"scans_t{t}"] for t in b._levels)
    masked = sum(max(0, t - len(toks))
                 for t, _, out in scans for toks in out.values())
    assert st["scan_row_steps_masked"] == masked == 3
    assert st["horizon"] == 4.0


def test_a_scan_ten_times_its_usual_length_is_counted_and_logged(caplog):
    """``scans_stalled`` / ``scan_stall_s``: a scan that takes over ten
    times what its steps take by the running mean, with the engine's
    phases in the log; a usual scan counts nothing."""
    b = _batcher([100, 100])
    eng, delay = b.engine, [0.0]
    eng.stats = {"rounds": 0, "round_readback_s": 0.0}

    def decode_multi(steps):
        time.sleep(delay[0])
        eng.stats["round_readback_s"] += delay[0]
        return {0: [1] * steps, 1: [1] * steps}

    eng.decode_multi = decode_multi
    b.stats["step_latency_ema_ms"] = 2.0
    steps, _, _ = b._engine_round()
    assert b.stats["scans_stalled"] == 0 and b.stats["scan_stall_s"] == 0.0
    delay[0] = 10 * steps * 2e-3 + 0.02
    with caplog.at_level("WARNING", logger=batcher_mod.log.name):
        b._engine_round()
    assert b.stats["scans_stalled"] == 1
    assert b.stats["scan_stall_s"] == pytest.approx(
        delay[0] - steps * 2e-3, abs=0.02)
    assert "wait for the device" in caplog.text
