"""Only a scan's latency steers the scan level (PR 24).

A ragged round is as long as the prompt tokens it admits, whatever the
level, so the batcher keeps it out of ``step_latency_ema_ms`` and leaves
``_level`` alone after it; after a scan it retunes as before. Driven on
the fake ragged engine of ``tests/test_long_context.py`` with the round
latencies scripted, so nothing here depends on the host's clock.
"""

import asyncio

import pytest

from distributed_gpu_inference_tpu.runtime.batcher import (
    BatcherConfig,
    ContinuousBatcher,
)
from tests.test_long_context import FakeRaggedEngine, _req


def _scripted(b, latency_ms):
    """Every engine round of ``b`` reports ``latency_ms(kind)`` instead of
    the wall time it took; returns the log of (kind, level after, EMA
    after) that ``_retune``'s call site leaves behind, round by round."""
    log = []
    real_round = b._engine_round

    def engine_round():
        kind = "ragged" if b._ragged else "scan"
        real_round()
        log.append(kind)
        return latency_ms(kind)

    b._engine_round = engine_round
    return log


async def _serve(b, prompts, max_new):
    b.start()
    resps = await asyncio.gather(
        *[b.submit(_req(p, max_new=max_new)) for p in prompts])
    stats = b.get_stats()
    level = b._level
    await b.stop()
    return resps, stats, level


@pytest.mark.parametrize("ragged_ms", [1.0, 5000.0], ids=["short", "long"])
def test_ragged_rounds_leave_level_and_ema_where_the_last_scan_put_them(
        ragged_ms):
    """Scans of 100 ms sit inside the 90-110 ms band: the level stays. A
    long prompt then enters in many chunk rounds beside the row that
    decodes; whatever those rounds take, EMA and level do not move."""
    eng = FakeRaggedEngine(ragged_chunk=8)
    b = ContinuousBatcher(eng, BatcherConfig(max_wait_ms=1))
    start = b._level
    log = _scripted(b, lambda kind: 100.0 if kind == "scan" else ragged_ms)
    seen = []
    retune = b._retune
    b._retune = lambda ms: (retune(ms), seen.append(ms))[0]

    async def go():
        b.start()
        first = asyncio.ensure_future(b.submit(_req(range(4), max_new=400)))
        while "scan" not in log:            # the first request decodes
            await asyncio.sleep(0.001)
        second = await b.submit(_req(range(100, 196), max_new=2))
        stats, level = b.get_stats(), b._level
        first.cancel()
        await b.stop()
        return second, stats, level

    second, stats, level = asyncio.run(go())
    assert second.ok
    # 96 prompt tokens in chunks of 8: twelve rounds with the second
    # request's pieces, one more for the first request's own prompt
    assert log.count("ragged") >= 13 and stats["ragged_rounds"] >= 13
    assert "scan" in log[: log.index("ragged", 1)]
    # only scans were sampled
    assert seen and set(seen) == {100.0}
    assert len(seen) == log.count("scan")
    assert stats["step_latency_ema_ms"] == pytest.approx(100.0)
    assert level == start and stats["horizon"] == b.cfg.horizon_levels[start]


def test_scans_still_move_the_level_both_ways():
    eng = FakeRaggedEngine(max_seq_len=10**7)
    b = ContinuousBatcher(eng, BatcherConfig(max_wait_ms=1))
    levels = b.cfg.horizon_levels
    assert len(levels) > 2
    ms = {"scan": 10.0}
    log = _scripted(b, lambda kind: ms[kind] if kind == "scan" else 1.0)
    seen = []

    async def go():
        b.start()
        task = asyncio.ensure_future(
            b.submit(_req(range(4), max_new=10**6)))
        while log.count("scan") < 2 * len(levels):
            await asyncio.sleep(0.001)
        seen.append(b._level)               # short scans: up to the top
        ms["scan"] = 5000.0
        n = log.count("scan")
        while log.count("scan") < n + 2 * len(levels):
            await asyncio.sleep(0.001)
        seen.append(b._level)               # long scans: down to the bottom
        task.cancel()
        await b.stop()

    asyncio.run(go())
    assert seen == [len(levels) - 1, 0]
