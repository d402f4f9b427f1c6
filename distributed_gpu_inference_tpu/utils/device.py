"""What the process runs on, stated once: the backend an entry point may
use, the chip's published figures, and where compiled programs are kept.

JAX drops to the CPU with a warning when it finds no accelerator. A server
or benchmark that exists to use the chip must not follow it there: numbers
from the CPU are not device numbers, and a worker that registered as a TPU
and serves from the CPU is a lie the scheduler cannot see. A CPU run is
something the operator asks for (``JAX_PLATFORMS=cpu`` / ``--platform cpu``).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

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


class CompileLog:
    """Every XLA compile request of the process: how many (``count``), their
    seconds (``seconds``) and, in ``rows``, each one's jitted function, its
    seconds and what the persistent cache did with it (``hit`` = loaded,
    nothing compiled; ``miss`` = compiled and stored). ``jax.monitoring``
    listeners cannot be taken off again, so a process has one log
    (:func:`compile_log`); ``rows`` stops growing at :data:`ROWS_KEPT`, the
    totals do not."""

    ROWS_KEPT = 4096

    def __init__(self) -> None:
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        self.rows: List[Dict[str, Any]] = []
        self._outcome: Dict[int, str] = {}
        self._lock = threading.Lock()    # compiles come from any thread
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **_: Any) -> None:
        if name.endswith("/cache_hits"):
            self._outcome[threading.get_ident()] = "hit"
        elif name.endswith("/cache_misses"):
            self._outcome[threading.get_ident()] = "miss"

    def _on_duration(self, name: str, secs: float, **kw: Any) -> None:
        if name != "/jax/core/compile/backend_compile_duration":
            return
        cache = self._outcome.pop(threading.get_ident(), "uncached")
        with self._lock:
            self.count += 1
            self.seconds += secs
            if len(self.rows) < self.ROWS_KEPT:
                self.rows.append({"fn": str(kw.get("fun_name")),
                                  "secs": secs, "cache": cache})


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
