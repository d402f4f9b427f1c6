"""Token lengths for a traffic mix, as a fixed amount of work.

A run draws ``n`` lengths from a distribution by taking its quantiles at
``n`` evenly spaced points (``stratified``). Every seed then offers the
same multiset of lengths (the same tokens of prefill and of decode), so
runs differ by arrival and order, not by how much work the draw happened to
hold. The tails are all there: the top stratum of a lognormal sits at its
1 - 1/(2n) quantile. The open-loop generator shuffles the whole multiset by
the seed.

``blocked`` is the closed loop's order: its clients send in rounds (each
its first request, then each its second), and the sorted quantiles are
dealt like cards, to and fro, into those rounds, so that every round holds
short and long ones in the proportions of the whole and the seed decides
only which client gets which. The decode cell's measurements rest on it
(PERF.md, section 7, lists it as a simplification to take with a new
measurement).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def quantile(spec: Dict[str, Any], u: float) -> int:
    """The ``u``-quantile of a length distribution, clipped to its bounds."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif spec["dist"] == "lognormal":
        x = float(spec["median"]) * math.exp(
            float(spec["sigma"]) * NormalDist().inv_cdf(u)
        )
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(min(max(round(x), lo), hi))


def stratified(spec: Dict[str, Any], n: int,
               rng: np.random.Generator) -> List[int]:
    """``n`` lengths: one from each of ``n`` equal strata, in seeded order."""
    vals = [quantile(spec, (j + 0.5) / n) for j in range(n)]
    return [vals[j] for j in rng.permutation(n)]


def block_sizes(n: int, block: int) -> List[int]:
    """``n`` requests in ``ceil(n / block)`` consecutive blocks whose sizes
    differ by one at most."""
    m = max(1, -(-n // max(int(block), 1)))
    return [len(range(b, n, m)) for b in range(m)]


def blocked(spec: Dict[str, Any], n: int, block: int,
            rng: np.random.Generator) -> List[int]:
    """``n`` lengths in the order they are sent: the sorted strata dealt
    into the blocks of ``block_sizes(n, block)``, seeded order inside each."""
    vals = [quantile(spec, (j + 0.5) / n) for j in range(n)]
    sizes = block_sizes(n, block)
    m = len(sizes)
    hands: List[List[int]] = [[] for _ in range(m)]
    # dealt to and fro, so that the block that got the smaller of one round
    # gets the larger of the next and the sums come out even
    # ... longest first: the blocks of one fewer then lack a short one
    for j, v in enumerate(reversed(vals)):
        turn, at = divmod(j, m)
        hands[at if turn % 2 == 0 else m - 1 - at].append(v)
    hands.sort(key=len, reverse=True)          # as block_sizes orders them
    out: List[int] = []
    for hand in hands:
        out.extend(hand[j] for j in rng.permutation(len(hand)))
    return out


def bounds(spec: Dict[str, Any]) -> range:
    """Every length the distribution can give."""
    return range(int(spec["min"]), int(spec["max"]) + 1)
