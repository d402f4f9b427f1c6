"""From the generator's request rows to the numbers a user would see.

Every time is taken in the generator process and counted from the instant
a request was *due*, not from when it was sent: a stall that holds the
generator's peer back lengthens the wait of every request queued behind
it, and that wait is what users feel.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

MIN_TOKENS_FOR_TPOT = 8

# what ``summarize`` gives that the manifest may list under ``end_to_end``
# (``setup_s`` is the harness's own); which of them a cell is judged by is
# the manifest's business
END_TO_END = ("ttft_p50_ms", "ttft_mean_ms", "ttft_p90_ms", "tpot_p50_ms",
              "tpot_p90_ms", "itl_p50_ms", "itl_p98_ms", "itl_p99_ms",
              "gap_p50_ms", "gap_p90_ms", "out_tok_s")


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100), linear between order statistics;
    None for no values."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tokens_got(row: Dict[str, Any]) -> int:
    return sum(row["n"])


def complete(row: Dict[str, Any], vocab_size: int) -> bool:
    """The request was answered in full: 200, every token it asked for and
    no more, finish ``length``, ids inside the vocabulary, the stream
    closed by its ``done`` event whose usage agrees."""
    return (
        row["error"] is None and row["status"] == 200
        and row["done_at"] is not None and row["finish"] == "length"
        and tokens_got(row) == row["asked"]
        and row["usage_out"] == row["asked"]
        and row["id_min"] is not None
        and 0 <= row["id_min"] and row["id_max"] < vocab_size
    )


def ttft_ms(row: Dict[str, Any]) -> Optional[float]:
    return (row["t"][0] - row["due"]) * 1e3 if row["t"] else None


def tpot_ms(row: Dict[str, Any]) -> Optional[float]:
    n = tokens_got(row)
    if n < MIN_TOKENS_FOR_TPOT:
        return None
    return (row["t"][-1] - row["t"][0]) / (n - 1) * 1e3


def gaps_ms(row: Dict[str, Any]) -> List[float]:
    """The waits between consecutive events after the first token."""
    t = row["t"]
    return [(b - a) * 1e3 for a, b in zip(t, t[1:])]


def longest_gap_ms(row: Dict[str, Any]) -> Optional[float]:
    """The longest of them: the stall a reader of the stream sees."""
    return max(gaps_ms(row), default=None)


def meets_limits(row: Dict[str, Any], limits: Dict[str, float],
                 vocab_size: int) -> bool:
    """Both latency limits met; a failed or refused request meets none."""
    if not complete(row, vocab_size):
        return False
    ttft, tpot = ttft_ms(row), tpot_ms(row)
    ttft_limit = limits["ttft_ms"] \
        + limits["ttft_ms_per_prompt_token"] * row["prompt_tokens"]
    return ttft is not None and ttft <= ttft_limit \
        and (tpot is None or tpot <= limits["tpot_ms"])


def summarize(rows: List[Dict[str, Any]], w0: float, w1: float,
              vocab_size: int, limits: Optional[Dict[str, float]] = None
              ) -> Dict[str, Any]:
    """The window ``[w0, w1)`` of one run. The sample is the requests due
    inside it; the throughput counts every token that arrived inside it,
    whichever request it belongs to."""
    sample = [r for r in rows if w0 <= r["due"] < w1]
    ok = [r for r in sample if complete(r, vocab_size)]
    tokens_in = sum(
        n for r in rows for t, n in zip(r["t"], r["n"]) if w0 <= t < w1
    )
    late = [(r["sent"] - r["due"]) * 1e3 for r in sample]
    ttft = [ttft_ms(r) for r in ok]
    tpot = [x for x in map(tpot_ms, ok) if x is not None]
    # every wait between two events of every stream, pooled: thousands of
    # readings where the requests are a hundred, so its tail holds still
    waits = sorted(g for r in ok for g in gaps_ms(r))
    # each stream's longest wait, one reading a stream however many slow
    # rounds it sat through: the pooled tail counts each of them once a row
    # and slides with their number, the median over the streams does not
    longest = [x for x in map(longest_gap_ms, ok) if x is not None]
    out: Dict[str, Any] = {
        "attempted": len(sample),
        "failed": len(sample) - len(ok),
        "refused": sum(1 for r in sample if r["status"] == 503),
        "out_tok_s": tokens_in / (w1 - w0),
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "ttft_p90_ms": percentile(ttft, 90),
        "tpot_p50_ms": percentile(tpot, 50),
        "tpot_p90_ms": percentile(tpot, 90),
        "itl_p50_ms": percentile(waits, 50),
        "itl_p98_ms": percentile(waits, 98),
        "itl_p99_ms": percentile(waits, 99),
        "n_waits": len(waits),
        "gap_p50_ms": percentile(longest, 50),
        "gap_p90_ms": percentile(longest, 90),
        # how near the median lies to the edge of its cluster (spread.py)
        "gap_p40_ms": percentile(longest, 40),
        "gap_p60_ms": percentile(longest, 60),
        "n_gaps": len(longest),
        "gen_late_p90_ms": percentile(late, 90),
        "gen_late_max_ms": max(late) if late else None,
        "n_tpot": len(tpot),
        "prompt_tokens": sum(r["prompt_tokens"] for r in sample),
        "output_tokens": sum(tokens_got(r) for r in sample),
    }
    if limits is not None and sample:
        out["slo_ok_share"] = sum(
            1 for r in sample if meets_limits(r, limits, vocab_size)
        ) / len(sample)
    return out


def mean_decode_context(rows: List[Dict[str, Any]]) -> Optional[float]:
    """Mean context length over the decode steps the rows stand for: a
    request of ``p`` prompt and ``n`` output tokens decodes at contexts
    ``p .. p+n-1``."""
    steps = sum(tokens_got(r) for r in rows)
    if not steps:
        return None
    return sum(
        tokens_got(r) * (r["prompt_tokens"] + (tokens_got(r) - 1) / 2.0)
        for r in rows
    ) / steps
