"""What the process runs on, stated once: the backend an entry point may
use, the chip's published figures, and where compiled programs are kept.

JAX drops to the CPU with a warning when it finds no accelerator. A server
or benchmark that exists to use the chip must not follow it there: numbers
from the CPU are not device numbers, and a worker that registered as a TPU
and serves from the CPU is a lie the scheduler cannot see. A CPU run is
something the operator asks for (``JAX_PLATFORMS=cpu`` / ``--platform cpu``).
"""

from __future__ import annotations

import functools
import os
import threading
import types
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

_CHECKOUT = Path(__file__).resolve().parents[2]


class NoAcceleratorError(RuntimeError):
    """JAX fell back to the CPU and nobody asked for the CPU."""


def require_backend() -> str:
    """``jax.default_backend()``, refusing the silent CPU fallback: the CPU
    is accepted only when ``jax_platforms`` (the ``JAX_PLATFORMS`` variable,
    or an entry point's ``--platform``) names it."""
    import jax

    backend = jax.default_backend()
    asked = (jax.config.jax_platforms or "").split(",")
    if backend == "cpu" and "cpu" not in asked:
        raise NoAcceleratorError(
            "JAX found no accelerator and fell back to the CPU. This entry "
            "point exists to use the chip; set JAX_PLATFORMS=cpu to run it "
            "on the CPU on purpose."
        )
    return backend


@dataclass(frozen=True)
class ChipSpec:
    """Published per-chip figures (Google Cloud TPU documentation)."""

    chip_type: str            # the generation name schedulers use
    hbm_gb: float
    hbm_gbps: float
    ici_gbps: float           # per link
    peak_bf16_tflops: float


_CHIPS = {
    "v4": ChipSpec("v4", 32.0, 1200.0, 300.0, 275.0),
    "v5e": ChipSpec("v5e", 16.0, 819.0, 400.0, 197.0),
    "v5p": ChipSpec("v5p", 95.0, 2765.0, 600.0, 459.0),
    "v6e": ChipSpec("v6e", 32.0, 1640.0, 900.0, 918.0),
}


def chip_spec(kind: str) -> Optional[ChipSpec]:
    """The chip behind a ``device_kind`` ("TPU v5 lite") or an accelerator
    type ("v5litepod-16"). None for a string this table does not know — an
    unknown chip has no figures, never another chip's."""
    s = kind.lower()
    if "v5p" in s or s.strip() == "tpu v5":
        return _CHIPS["v5p"]
    if "v5 lite" in s or "v5lite" in s or "v5e" in s:
        return _CHIPS["v5e"]
    if "v6" in s:
        return _CHIPS["v6e"]
    if "v4" in s:
        return _CHIPS["v4"]
    return None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the directory and nothing
    here sets another (JAX reads the variable itself). Otherwise the cache
    lives at ``<checkout>/.cache/jax`` — a fixed path, because the path is
    part of how a cold process finds what the last one compiled. Every
    program is kept, however quick its compile (a warm worker start should
    compile nothing), unless the environment sets JAX's own thresholds."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = str(_CHECKOUT / ".cache" / "jax")
        jax.config.update("jax_compilation_cache_dir", directory)
    for option, keep_all in (
        ("jax_persistent_cache_min_entry_size_bytes", 0),
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
    ):
        if option.upper() not in os.environ:
            jax.config.update(option, keep_all)
    return directory


# the three stages of a compile JAX reports (jax/_src/dispatch.py), each as
# a start (``record_scalar``) and an end with its seconds
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}


class CompileLog:
    """Every compile of the process, by stage: tracing a jitted function to
    a jaxpr (``trace_s``), lowering the jaxpr to an MLIR module, the Mosaic
    kernels' lowering inside it (``lower_s``), and the XLA compile requests:
    how many (``count``), their seconds (``seconds``) and how many of them
    missed the persistent cache (``misses``). ``rows`` holds one row a stage
    event: ``fn`` the jitted function, ``secs``, ``stage`` (``trace`` /
    ``lower`` / ``backend``), ``graph`` (the label the thread carried,
    :meth:`label`, else ``fn``) and, a backend row, ``cache``: what the
    persistent cache did with the request (``hit`` = loaded, nothing
    compiled; ``miss`` = compiled and stored).

    The events nest: a jitted function traced inside another reports its
    own trace within the outer one's, and a function a lowering rule traces
    reports inside the lowering. Only an event with no other open on its
    thread is counted into ``trace_s`` / ``lower_s``, given a row and added
    to the thread's label, so a stage's seconds are never counted twice and
    ``trace_s + lower_s + backend_s`` of a label stay under the wall time
    of the block that carried it. ``count`` / ``seconds`` count every
    backend request, as they always have.

    ``jax.monitoring`` listeners cannot be taken off again, so a process
    has one log (:func:`compile_log`); ``rows`` stops growing at
    :data:`ROWS_KEPT`, the totals do not."""

    ROWS_KEPT = 4096

    def __init__(self) -> None:
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        self.trace_s = 0.0
        self.lower_s = 0.0
        self.misses = 0
        self.rows: List[Dict[str, Any]] = []
        # by thread: what the cache did with the request being compiled,
        # the stage events open, and the label with the row it adds to
        self._outcome: Dict[int, str] = {}
        self._open: Dict[int, int] = {}
        self._label: Dict[int, Tuple[str, Dict[str, float]]] = {}
        self._lock = threading.Lock()    # compiles come from any thread
        monitoring.register_event_listener(self._on_event)
        monitoring.register_scalar_listener(self._on_start)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def label(self, graph: str) -> Dict[str, float]:
        """From now until :meth:`unlabel`, the calling thread's stage events
        belong to ``graph``: their seconds are added to the row returned
        (``trace_s``, ``lower_s``, ``backend_s``) and their rows carry the
        name. Set and cleared inside one block of the caller's own body:
        nothing is wrapped round what it measures."""
        row = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0}
        tid = threading.get_ident()
        self._label[tid] = (graph, row)
        self._open.pop(tid, None)       # a block starts with nothing open
        return row

    def unlabel(self) -> None:
        self._label.pop(threading.get_ident(), None)

    def _on_event(self, name: str, **_: Any) -> None:
        if name.endswith("/cache_hits"):
            self._outcome[threading.get_ident()] = "hit"
        elif name.endswith("/cache_misses"):
            self._outcome[threading.get_ident()] = "miss"

    def _on_start(self, name: str, _value: Any, **_: Any) -> None:
        if name in _STAGES:
            tid = threading.get_ident()
            self._open[tid] = self._open.get(tid, 0) + 1

    def _on_duration(self, name: str, secs: float, **kw: Any) -> None:
        stage = _STAGES.get(name)
        if stage is None:
            return
        tid = threading.get_ident()
        nested = self._open.get(tid, 1) > 1
        if nested:
            self._open[tid] -= 1
            if stage != "backend":
                return              # its seconds are inside the outer one's
        else:
            self._open.pop(tid, None)
        fn = str(kw.get("fun_name"))
        graph, into = self._label.get(tid) or (fn, None)
        row = {"fn": fn, "secs": secs, "stage": stage, "graph": graph}
        if into is not None and not nested:
            into[stage + "_s"] += secs
        with self._lock:
            if stage == "backend":
                row["cache"] = self._outcome.pop(tid, "uncached")
                self.count += 1
                self.seconds += secs
                self.misses += row["cache"] == "miss"
            elif stage == "trace":
                self.trace_s += secs
            else:
                self.lower_s += secs
            if len(self.rows) < self.ROWS_KEPT:
                self.rows.append(row)


_compile_log: Optional[CompileLog] = None
_compile_log_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process's one :class:`CompileLog`, started at the first call
    (every ``TPUEngine`` makes it, so a serving process counts from its
    first engine on)."""
    global _compile_log
    with _compile_log_lock:
        if _compile_log is None:
            _compile_log = CompileLog()
        return _compile_log


@functools.cache
def _roomy_frame():
    """``lambda call: call()`` with 64 Ki local slots nobody uses: a frame
    of half a MiB. This leans on CPython internals the language does not
    promise: since 3.11 a frame's locals lie in the thread's data stack,
    which grows in chunks of 16 KiB, doubled until the frame that asked
    fits, so half a MiB and a little gets 1 MiB (``Python/pystate.c``
    ``push_chunk``; read at 3.12, which this repository runs on here and
    on the chip). On 3.10, which ``requires-python`` still admits, frames
    are heap objects: there is no edge to avoid and this frame is half a
    MiB for nothing while it is live (not run there: no 3.10 at hand). A
    later CPython that lays frames out otherwise makes it a plain call
    again: ``tests/test_startup_spans.py`` holds the frame's size and
    depth, the chip's ``startup.graphs_lower_s`` its effect."""
    def roomy_stack(call):
        return call()

    return types.FunctionType(
        roomy_stack.__code__.replace(
            co_varnames=("call",) + tuple(f"_{i}" for i in range(1 << 16)),
            co_nlocals=(1 << 16) + 1),
        globals(), "roomy_stack")


def roomy_stack(call):
    """``call()`` with the frames beneath it in ONE chunk of the thread's
    Python data stack. CPython keeps a thread's frames in chunks of 16 KiB,
    maps a new one when a call finds no room in the last and unmaps it when
    that call returns, so a hot call that happens to sit on a chunk's edge
    pays an ``mmap`` and a ``munmap`` EVERY time it is made; in a process
    that holds a TPU those two cost 0.1-0.9 ms. Tracing a serving graph
    and lowering its kernels is Python a hundred frames deep whose inner
    calls are made thousands of times a graph: which of them falls on an
    edge depends on the thread and on every frame above (one more ``with``
    or helper moved a start by seconds: PERF.md section 7 (64), ROADMAP
    D7), and the fused decode kernel inside ``shard_map`` lowered in 5.3 s
    a scan graph where it takes 0.5 s (PERF.md section 6, PR 58). A frame
    that needs half a MiB makes CPython map a chunk of twice that, and
    every frame beneath it lies in the room left: no edge to fall on. A
    stack that outgrows the room gets further chunks, as ever."""
    return _roomy_frame()(call)
