"""What a run's rounds did, from the two places that know: the traced
slice (the kernels' operations by name, the annotated round programs) and
the program's counters over the whole window.

A kernel's seconds are known in the 5 s slice only; what the kernel had to
do is counted by the program over the 51 s window only (the harness reads
the counters at the window's two ends). The readers built on this take the
WORK a step (or a live position of a round) from the window and the TIME a
step from the slice. In a closed loop at full occupancy the two are the
same traffic; the slice holds some twenty requests, so the mean cache
length of its rows is within a few per cent of the window's.
"""

from __future__ import annotations

from typing import Any, Dict, List

from .layers import modules_named
from .window import delta


def op_seconds(run: Dict[str, Any], kernel: str) -> float:
    """Seconds in the slice of the operations named ``<kernel>.<n>``."""
    ops = (run.get("trace") or {}).get("op_seconds") or {}
    return sum(s for name, s in ops.items() if name.split(".")[0] == kernel)


def slice_steps(run: Dict[str, Any]) -> int:
    """Steps of the slice's annotated ``decode_multi`` programs."""
    return sum(int(m["steps"]) for m in modules_named(run, "decode_multi")
               if m.get("steps"))


def slice_rounds(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The slice's annotated ``ragged_round`` programs."""
    return [m for m in modules_named(run, "ragged_round")
            if m.get("widest_piece") is not None]


def scans_by_level(run: Dict[str, Any]) -> Dict[int, float]:
    """The window's scans by their length ``T`` (the batcher's
    ``scans_t<T>``); empty for a program that does not count its levels."""
    win = run["win"]
    return {int(key[7:]): delta(win, "batcher", key)
            for key in win["c1"]["batcher"]
            if key.startswith("scans_t") and key[7:].isdigit()}


def window_steps(run: Dict[str, Any]) -> float:
    """Steps the window's scans took: ``T x scans_t<T>`` over the levels
    the batcher counts (``decode_calls`` also counts a ragged round that
    held a decode row)."""
    return sum(t * n for t, n in scans_by_level(run).items())
