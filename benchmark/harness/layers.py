"""The per-layer metrics of a run: for every ``per_layer`` entry of the
manifest that lists the cell, the reader ``layer_metrics/<name, dots as
underscores>.py`` is loaded and its ``read(run)`` called. The entry says
what the metric is (unit, layer, source, what it ``moves``); the file says
how it is read. A new metric is one new file and one new entry.

A metric is read only where the end-to-end metric it ``moves`` is reported:
an entry that lists a cell without it is refused before anything starts.
A reader that finds nothing to read (no round of the widest bucket inside
the traced slice) returns ``None``; the metric is then left out of the line
and named on the line before it.

What a reader is given (``run``):

``summary``   ``metrics.summarize`` of the window
``rows``      the generator's rows of the whole plan; ``sample`` those due
              in the window
``win``       the window: its bounds and the program's counters at both
              ends (``window.delta`` reads them)
``trace``     ``trace_reduce.reduce`` of the traced slice, or None
``cell`` ``config`` ``traffic`` ``geometry`` ``peaks``
``warmed``    the round shapes the cell's traffic reaches (decode scan
              lengths, ragged widths)
``notes``     a dict a reader may add to; it goes into the run's detail file
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from .spec import BENCH, SpecError


def reader_path(name: str) -> Optional[Path]:
    """The file that reads ``name``: ``layer_metrics/<name, dots as
    underscores>.py``, or, for a quantity split by the end-to-end metric
    its cells report (``client.gap_p90_ms.tpot`` beside
    ``client.gap_p90_ms``), the file of the name without its last part."""
    for stem in (name, name.rpartition(".")[0]):
        path = BENCH / "layer_metrics" / f"{stem.replace('.', '_')}.py"
        if stem and path.is_file():
            return path
    return None


def readers(cell: Dict[str, Any]
            ) -> List[Tuple[Dict[str, Any], Callable[[Dict[str, Any]], Any]]]:
    """The cell's per-layer entries, each with its reader."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))     # readers import ``harness.*``
    out = []
    for entry in cell["per_layer"]:
        name = entry["name"]
        if entry["moves"] not in cell["end_to_end"]:
            raise SpecError(f"{name} moves {entry['moves']}, which "
                            f"{cell['name']} does not report")
        path = reader_path(name)
        if path is None:
            raise SpecError("no reader layer_metrics/"
                            f"{name.replace('.', '_')}.py for {name}")
        spec = importlib.util.spec_from_file_location(
            f"layer_metrics.{path.stem}", path
        )
        assert spec is not None and spec.loader is not None
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((entry, mod.read))
    return out


def read_all(run: Dict[str, Any]
             ) -> Tuple[Dict[str, Dict[str, Any]], List[str]]:
    """``{name: {"value", "unit"}}`` for every metric of the cell whose
    reader found something to read, and the names of those that did not."""
    out: Dict[str, Dict[str, Any]] = {}
    not_read: List[str] = []
    for entry, read in readers(run["cell"]):
        value = read(run)
        if value is None:
            not_read.append(entry["name"])
        else:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out, not_read


# ----------------------------------------------------------------------- #
# what several readers share
# ----------------------------------------------------------------------- #

def modules_named(run: Dict[str, Any], part: str) -> List[Dict[str, Any]]:
    """Executions in the traced slice of the jitted programs whose name
    holds ``part`` (``jit_decode_multi(...)``, ``jit_ragged_round(...)``)."""
    trace = run.get("trace") or {}
    return [m for m in trace.get("modules") or [] if part in m["name"]]


def bucket_of(run: Dict[str, Any], piece: int) -> Optional[int]:
    return next((b for b in run["geometry"]["prefill_buckets"]
                 if b >= piece), None)


def widest_ragged(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The ragged rounds of the slice at the widest bucket the cell's
    traffic reaches (the widest one warmed), each with its ``bucket``.
    None in the slice is nothing to read."""
    top = max(run["warmed"]["ragged_widths"])
    mods = []
    for m in modules_named(run, "ragged_round"):
        if m.get("widest_piece") is None:
            continue
        bucket = bucket_of(run, int(m["widest_piece"]))
        if bucket == top:
            mods.append({**m, "bucket": bucket})
    return mods
