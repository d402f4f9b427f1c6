"""One serving session: the deployed pieces started the way a deployment
starts them, warmed for one cell's traffic, and driven from a generator
process of its own.

The process that makes a ``Session`` owns the cell's chips. It starts the
control plane as a JAX-free child, one real ``worker.main.Worker`` through
``Worker.start()`` (serving mode ``batcher``, direct server on), and the
load generator as a second JAX-free child. ``run.py`` measures one window
in a session, ``sweep.py`` several.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from . import generators
from .spec import CHECKOUT, SpecError, probe_prompts, published, text_of

WORK = CHECKOUT / ".cache" / "benchmark" / "work"   # wiped by every session
API = "/api/v1"


class RunFailed(RuntimeError):
    """The run cannot give a result; the process exits non-zero and prints
    no result line."""


def say(t0: float, msg: str) -> None:
    print(f"[bench +{time.monotonic() - t0:6.1f}s] {msg}", flush=True)


# --------------------------------------------------------------------- #
# compile accounting (copied from chip_smoke.py CompileLog)
# --------------------------------------------------------------------- #

class CompileLog:
    """Every XLA compile request of the process with the instant it ended,
    the jitted function, its seconds and what the persistent cache did
    with it (``hit`` = loaded, ``miss`` = compiled and stored)."""

    def __init__(self) -> None:
        from jax import monitoring

        self.rows: List[Dict[str, Any]] = []
        self._outcome: Dict[int, str] = {}
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **_: Any) -> None:
        if name.endswith("/cache_hits"):
            self._outcome[threading.get_ident()] = "hit"
        elif name.endswith("/cache_misses"):
            self._outcome[threading.get_ident()] = "miss"

    def _on_duration(self, name: str, secs: float, **kw: Any) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.rows.append({
                "at": time.monotonic(), "fn": str(kw.get("fun_name")),
                "secs": secs,
                "cache": self._outcome.pop(threading.get_ident(), "uncached"),
            })

    def between(self, a: float, b: float) -> List[Dict[str, Any]]:
        """Compile requests any part of which ran inside ``[a, b)``."""
        return [r for r in self.rows
                if r["at"] > a and r["at"] - r["secs"] < b]


def compile_cache_dir() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says if it is set (JAX reads it itself and nothing here sets another),
    else ``<checkout>/.cache/jax`` — a fixed path inside the checkout.
    Every program is kept, however quick its compile."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = str(CHECKOUT / ".cache" / "jax")
        jax.config.update("jax_compilation_cache_dir", directory)
    for option, keep_all in (
        ("jax_persistent_cache_min_entry_size_bytes", 0),
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
    ):
        if option.upper() not in os.environ:
            jax.config.update(option, keep_all)
    return directory


# --------------------------------------------------------------------- #
# children
# --------------------------------------------------------------------- #

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env() -> Dict[str, str]:
    return dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(CHECKOUT)] + [p for p in [os.environ.get("PYTHONPATH")] if p]
    ))


def stop_child(proc: Optional[subprocess.Popen]) -> None:
    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=10.0)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10.0)


class LoadGenerator:
    """The generator child and its line protocol."""

    def __init__(self) -> None:
        self.proc = subprocess.Popen(
            [sys.executable, str(Path(__file__).with_name("loadgen.py"))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=_child_env(),
        )

    def send(self, cmd: Dict[str, Any]) -> None:
        assert self.proc.stdin is not None
        self.proc.stdin.write(json.dumps({"op": "play", **cmd}) + "\n")
        self.proc.stdin.flush()

    def receive(self) -> List[Dict[str, Any]]:
        """The rows of the plan last sent; waits until it has been played."""
        assert self.proc.stdout is not None
        line = self.proc.stdout.readline()
        if not line:
            raise RunFailed("the load generator ended without an answer")
        reply = json.loads(line)
        if "error" in reply:
            raise RunFailed(f"the load generator failed: {reply['error']}")
        return reply["rows"]

    def close(self) -> None:
        if self.proc.poll() is None and self.proc.stdin is not None:
            try:
                self.proc.stdin.write('{"op": "exit"}\n')
                self.proc.stdin.flush()
                self.proc.wait(timeout=5.0)
            except (OSError, subprocess.TimeoutExpired):
                pass
        stop_child(self.proc)


class WorkerThread(threading.Thread):
    """``Worker.start()`` — what ``tpu-worker start`` calls — on a thread.
    An exception in it is kept and fails the run."""

    def __init__(self, worker: Any) -> None:
        super().__init__(name="worker", daemon=True)
        self.worker = worker
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            self.worker.start(install_signal_handlers=False)
        except BaseException as exc:  # noqa: BLE001 — re-raised by check()
            self.error = exc

    def check(self) -> None:
        if self.error is not None:
            raise RunFailed(f"the worker failed: {self.error!r}")


# --------------------------------------------------------------------- #
# warm-up shapes
# --------------------------------------------------------------------- #

def piece_widths(n: int, chunk: int, buckets: Sequence[int]) -> List[int]:
    """The round widths one unshared prompt of ``n`` tokens reaches: it
    enters in pieces of at most ``chunk`` tokens, the last piece is what is
    left, and a round is as wide as the bucket of its widest piece."""
    cap = min(max(int(chunk), 1), buckets[-1])
    pieces = ({cap} if n > cap else set()) | {n % cap or min(n, cap)}
    return sorted({next(b for b in buckets if b >= p) for p in pieces})


def ragged_widths(prompt_lengths: Sequence[int], chunk: int,
                  buckets: Sequence[int]) -> Dict[int, int]:
    """Every round width prompts of these lengths reach, each with the
    shortest prompt that reaches it."""
    out: Dict[int, int] = {}
    for n in sorted(set(int(x) for x in prompt_lengths)):
        for w in piece_widths(n, chunk, buckets):
            out.setdefault(w, n)
    return dict(sorted(out.items()))


# --------------------------------------------------------------------- #
# the session
# --------------------------------------------------------------------- #

class Session:
    def __init__(self, cell: Dict[str, Any], t0: float) -> None:
        self.cell, self.cfg, self.t0 = cell, cell["_config"], t0
        self.traffic = cell["_traffic"]
        self.generator = generators.load(self.traffic["generator"])
        self.plane: Optional[subprocess.Popen] = None
        self.loadgen: Optional[LoadGenerator] = None
        self.wt: Optional[WorkerThread] = None
        self.device: Dict[str, Any] = {}
        self.timing: Dict[str, float] = {}
        # what the generator got an HTTP status for, and the 503s among
        # them: the direct server's own counters must say the same
        self.rows_sent = 0
        self.refusals_seen = 0

    # -- start-up --------------------------------------------------------

    def start(self) -> None:
        t0 = self.t0
        shutil.rmtree(WORK, ignore_errors=True)
        WORK.mkdir(parents=True)
        # worker identity and credentials of a run stay in its scratch
        os.environ["HOME"] = str(WORK)
        plane_port, direct_port = free_port(), free_port()
        self.plane_url = f"http://127.0.0.1:{plane_port}"
        self.direct_url = f"http://127.0.0.1:{direct_port}"
        with open(WORK / "plane.log", "w") as log:
            self.plane = subprocess.Popen(
                [sys.executable, "-m",
                 "distributed_gpu_inference_tpu.server.app",
                 "--host", "127.0.0.1", "--port", str(plane_port),
                 "--db", str(WORK / "plane.sqlite")],
                env=_child_env(), stdout=log, stderr=subprocess.STDOUT,
            )
        self.loadgen = LoadGenerator()
        self.timing["children_started_s"] = time.monotonic() - t0

        import jax

        devices = jax.devices()
        # importing JAX and bringing up the chip's runtime, from the start
        self.timing["jax_devices_s"] = time.monotonic() - t0
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}
        say(t0, f"device: {self.device}")
        asked = (jax.config.jax_platforms or "").split(",")
        dry = (self.device["platform"] == "cpu" and "cpu" in asked
               and self.cell["_stand_in"])
        if self.device["platform"] != "tpu" and not dry:
            raise RunFailed(
                f"JAX runs on {self.device['platform']!r}, not on a TPU. The "
                "benchmark measures the chip and does not fall back; only "
                "the stand-in cells under benchmark/testdata/ run on the "
                "CPU, and only when JAX_PLATFORMS asks for it (the dry run)"
            )
        if len(devices) < int(self.cell["chips"]):
            raise RunFailed(f"the cell needs {self.cell['chips']} chips, "
                            f"JAX reports {len(devices)}")
        say(t0, f"compile cache: {compile_cache_dir()}")
        self.compiles = CompileLog()

        self._wait_http(f"{self.plane_url}/health", 60.0)
        self.timing["device_and_plane_ready_s"] = time.monotonic() - t0
        self._start_worker(direct_port)
        self._check_geometry()

    def _wait_http(self, url: str, timeout_s: float) -> None:
        import httpx

        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            if self.plane is not None and self.plane.poll() is not None:
                raise RunFailed("the control plane exited "
                                f"(see {WORK / 'plane.log'})")
            try:
                if httpx.get(url, timeout=2.0).status_code == 200:
                    return
            except httpx.TransportError:
                pass
            time.sleep(0.1)
        raise RunFailed(f"{url} not healthy after {timeout_s:.0f}s")

    def _start_worker(self, direct_port: int) -> None:
        import httpx

        from distributed_gpu_inference_tpu.utils.config import WorkerConfig
        from distributed_gpu_inference_tpu.utils.data_structures import (
            WorkerState,
        )
        from distributed_gpu_inference_tpu.worker.main import Worker

        wcfg = WorkerConfig.model_validate({
            "name": f"bench-{self.cfg['name']}", "task_types": ["llm"],
            "server": {"url": self.plane_url},
            "direct": {"enabled": True, "host": "127.0.0.1",
                       "port": direct_port, "public_url": self.direct_url},
            "engines": {"llm": self.cfg["worker_engine"]},
            "poll_interval_s": 0.2, "heartbeat_interval_s": 2.0,
        })
        t_load = time.monotonic()
        worker = Worker(wcfg)
        self.wt = WorkerThread(worker)
        self.wt.start()
        want = int(self.cfg["max_concurrent_jobs"])
        pushed = False
        while worker.state == WorkerState.INITIALIZING:
            self.wt.check()
            if not self.wt.is_alive():
                raise RunFailed("the worker thread ended before it was ready")
            if not pushed and worker.api.worker_id:
                # the operator's route: the plane holds the worker's remote
                # configuration and the worker fetches it when a heartbeat
                # says it changed — here, at the first one
                r = httpx.put(
                    f"{self.plane_url}{API}/admin/workers/"
                    f"{worker.api.worker_id}/config",
                    json={"load_control": {"max_concurrent_jobs": want}},
                    timeout=10.0,
                )
                if r.status_code != 200:
                    raise RunFailed(f"config push refused: {r.status_code} "
                                    f"{r.text[:200]}")
                pushed = True
            time.sleep(0.05)
        self.timing["load_s"] = time.monotonic() - t_load
        llm = worker.engines.get("llm")
        if llm is None or not llm.loaded:
            raise RunFailed("the llm engine did not load")
        if llm.serving is None or not llm.serving.active:
            raise RunFailed("serving mode is not batcher")
        end = time.monotonic() + 15.0
        while worker.config.load_control.max_concurrent_jobs != want:
            if time.monotonic() > end:
                raise RunFailed("the worker did not fetch max_concurrent_jobs"
                                f"={want} from the plane in 15 s")
            time.sleep(0.05)
        self.worker, self.llm, self.eng = worker, llm, llm.engine
        say(self.t0, f"worker {worker.api.worker_id} ready in "
            f"{self.timing['load_s']:.1f}s; max_concurrent_jobs={want}")

    def _check_geometry(self) -> None:
        """What serves is what the configuration file says: the published
        sizes key by key, and the serving geometry."""
        mc, ec = self.eng.model_cfg, self.eng.cfg
        bad = {k: (getattr(mc, k), v) for k, v in published(self.cfg).items()
               if getattr(mc, k) != v}
        geo = self.cfg["serving_geometry"]
        live = {
            "block_size": ec.block_size, "max_batch_size": ec.max_batch_size,
            "max_seq_len": ec.max_seq_len, "ragged_chunk": ec.ragged_chunk,
            "prefill_buckets": list(ec.prefill_buckets),
            "horizon_levels": list(
                self.llm.serving.batcher.cfg.horizon_levels),
            "quantization": ec.quantization,
            "kv_dtype": str(self.eng.kv_dtype),
            "tp_size": 1 if self.eng.mesh is None
            else int(self.eng.mesh.devices.size),
        }
        bad.update({k: (live.get(k), v) for k, v in geo.items()
                    if live.get(k) != v})
        if bad:
            raise RunFailed("the engine does not hold the configuration of "
                            f"{Path(self.cfg['_path']).name} (live, file): "
                            f"{bad}")
        self.geometry = live

    # -- warm-up ---------------------------------------------------------

    def warm(self) -> None:
        """Compile exactly this cell's round shapes ahead of the window,
        check the probes against the golden file, then serve one request
        per warmed width so that the small programs around a round (slot
        state uploads, finishing a slot) are in memory too."""
        probes = probe_prompts(self.cfg)
        geo = self.geometry
        reach = ragged_widths(
            self.generator.prompt_lengths(self.traffic["params"]),
            geo["ragged_chunk"], geo["prefill_buckets"],
        )
        widths = sorted(set(reach) | set(ragged_widths(
            [len(p["token_ids"]) for p in probes],
            geo["ragged_chunk"], geo["prefill_buckets"],
        )))
        levels = self.geometry["horizon_levels"]
        t1 = time.monotonic()
        mark = len(self.compiles.rows)
        lowered = self.llm.serving.run_exclusive(
            self.eng.lower_serving_graphs, levels, widths
        )
        for name, low in lowered.items():
            t2 = time.monotonic()
            low.compile()
            say(self.t0, f"  {name}: compile {time.monotonic() - t2:.1f}s")
        self.timing["graphs_s"] = time.monotonic() - t1
        rows = self.compiles.rows[mark:]
        say(self.t0, f"round graphs: decode T={levels}, ragged S={widths} in "
            f"{self.timing['graphs_s']:.1f}s "
            f"({sum(r['cache'] == 'hit' for r in rows)} from the cache, "
            f"{sum(r['cache'] == 'miss' for r in rows)} compiled)")
        self.warmed = {"decode_steps": list(levels), "ragged_widths": widths}

        t1 = time.monotonic()
        served = self.play({
            "loop": "serial", "keep_ids": 8,
            "requests": [{"id": p["name"], "prompt": p["prompt"],
                          "prompt_tokens": len(p["token_ids"]),
                          "max_tokens": 8} for p in probes],
        }, limit_s=300.0)
        self.timing["probes_s"] = time.monotonic() - t1
        self.probe_rows = served

        t1 = time.monotonic()
        rng = np.random.default_rng(0)
        warm_reqs = [
            {"id": f"warm{w}", "due_s": 0.0, "prompt": text_of(n, rng),
             "prompt_tokens": n, "max_tokens": 24}
            for w, n in reach.items()
        ]
        self.warm_rows = self.play({"loop": "open", "requests": warm_reqs},
                                   limit_s=300.0)
        self.timing["warm_requests_s"] = time.monotonic() - t1
        bad = [r for r in self.probe_rows + self.warm_rows
               if r["error"] or r["status"] != 200]
        if bad:
            raise RunFailed(f"a warm-up request failed: {bad[0]}")

    # -- driving ---------------------------------------------------------

    def send_play(self, cmd: Dict[str, Any], t0: float, deadline: float
                  ) -> None:
        """Hand the generator a plan that starts at ``t0`` (monotonic);
        ``receive_play`` waits for its rows."""
        assert self.loadgen is not None
        self.loadgen.send({"url": f"{self.direct_url}/inference/stream",
                           "t0": t0, "deadline": deadline, **cmd})

    def receive_play(self) -> List[Dict[str, Any]]:
        assert self.loadgen is not None
        rows = self.loadgen.receive()
        self.rows_sent += sum(1 for r in rows if r["status"] is not None)
        self.refusals_seen += sum(1 for r in rows if r["status"] == 503)
        return rows

    def play(self, cmd: Dict[str, Any], limit_s: float
             ) -> List[Dict[str, Any]]:
        t0 = time.monotonic() + 0.05
        self.send_play(cmd, t0, t0 + limit_s)
        return self.receive_play()

    def counters(self) -> Dict[str, Any]:
        """The program's counters, read now: the batcher's, the engine's,
        the direct server's."""
        return {
            "at": time.monotonic(),
            "batcher": dict(self.llm.serving.get_stats()),
            "engine": dict(self.eng.get_stats()),
            "direct": dict(self.worker._direct.stats),
        }

    def annotate(self) -> None:
        """Traced runs only: put the engine's two round calls on the
        profiler's clock (``jax.profiler.TraceAnnotation``) with what each
        round held, from the benchmark's own files. Spans inside the
        program are a later PR. This reads the program's internals (the
        admissions' ``fresh`` and ``done``, the slots' ``prefilling`` and
        ``finish_reason``, ``cfg.multi_step``): if a change to the program
        moves one of them the run fails and says which, here or through
        ``annotation_errors`` after the window, and gives no line."""
        import inspect

        import jax

        eng = self.eng
        cap = min(max(int(eng.cfg.ragged_chunk), 1),
                  eng.cfg.prefill_buckets[-1])
        self.annotation_errors: List[str] = []

        def decoding() -> int:
            return sum(1 for s in eng.slots if s is not None
                       and s.finish_reason is None and not s.prefilling)

        def ragged_facts(admissions: Sequence[Any] = (), *_: Any,
                         **__: Any) -> Dict[str, int]:
            pieces = [min(len(a.fresh), cap) for a in admissions
                      if not a.done]
            return {"live_prompt_tokens": sum(pieces),
                    "admission_rows": len(pieces),
                    "widest_piece": max(pieces, default=1),
                    "decode_rows": decoding()}

        def decode_facts(num_steps: Optional[int] = None, *_: Any,
                         **__: Any) -> Dict[str, int]:
            return {"steps": int(num_steps or eng.cfg.multi_step),
                    "decode_rows": decoding()}

        def wrap(name: str, first: str,
                 facts: Callable[..., Dict[str, int]]) -> None:
            inner = getattr(eng, name, None)
            given = list(inspect.signature(inner).parameters)[:1] \
                if callable(inner) else None
            if given != [first]:
                raise RunFailed(
                    f"the engine's {name} no longer takes {first!r} first "
                    f"(found {given}): harness/session.py annotate() reads "
                    "it for the engine.* metrics and must follow the program"
                )

            def outer(*a: Any, **kw: Any) -> Any:
                try:
                    held = facts(*a, **kw)
                except (AttributeError, TypeError, ValueError) as exc:
                    self.annotation_errors.append(f"{name}: {exc!r}")
                    held = {}
                with jax.profiler.TraceAnnotation(f"bench.{name}", **held):
                    return inner(*a, **kw)

            setattr(eng, name, outer)

        decoding()                      # the slots' fields, read once now
        wrap("ragged_round", "admissions", ragged_facts)
        wrap("decode_multi", "num_steps", decode_facts)

    def memory_peak_bytes(self) -> Optional[int]:
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()
        ]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    # -- shut-down -------------------------------------------------------

    def close(self) -> None:
        if self.loadgen is not None:
            self.loadgen.close()
        if self.wt is not None and self.wt.is_alive():
            self.wt.worker.request_shutdown()
            self.wt.join(timeout=60.0)
        stop_child(self.plane)

    def __enter__(self) -> "Session":
        try:
            self.start()
        except BaseException:
            self.close()
            raise
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


def check_spec(cell: Dict[str, Any]) -> None:
    """Refuse before anything starts what a run could only fail on late."""
    from .layers import readers
    from .metrics import END_TO_END

    unknown = set(cell["end_to_end"]) - set(END_TO_END) - {"setup_s"}
    if unknown:
        raise SpecError(f"no such end-to-end metric: {sorted(unknown)}")
    if "setup_s" not in cell["end_to_end"]:
        raise SpecError("every cell reports setup_s")
    readers(cell)
    if cell["chips"] not in (1, 4):
        raise SpecError("a cell asks for 1 or 4 chips")
    tp = int((cell["_config"]["worker_engine"].get("extra") or {})
             .get("tp_size") or 1)
    if tp > cell["chips"]:
        raise SpecError(f"tp_size {tp} on a cell of {cell['chips']} chips")
