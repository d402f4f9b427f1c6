#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Serves ``mistral-7b`` (all 32 layers, int8 weights, random from a seed)
through the path users get: a control plane (a JAX-free child process), one
real ``worker.main.Worker`` started the way ``tpu-worker start`` starts it
(register, load engines, direct server, heartbeat, poll loop; serving mode
``batcher``), and ``sdk.InferenceClient`` sending queued, direct and streamed
requests of mixed length, some arriving while others decode. With four
devices the same path runs with ``tp_size: 4``.

It fails (exit code != 0, no result line) when any phase fails:

- the platform is not ``tpu`` (``--platform cpu`` asks for the tiny dry run
  that debugs this script itself: small model, kernels interpreted);
- a Pallas kernel disagrees with its XLA reference at the served shapes;
- the ``llm`` engine did not load, or the plane's worker row does not show
  the devices JAX reports;
- on one chip, the compiled round graphs do not hold ``dgi_ragged_attention``,
  ``dgi_paged_decode`` and ``dgi_qmm``; on a mesh, they hold ``dgi_qmm``
  (a bare ``pallas_call`` has no partitioning rule: the mesh engine's
  projections and experts run the XLA paths) or lack an attention kernel
  (those run a shard of heads a chip under ``shard_map``);
- a request is not answered in full by the entry point it was sent to, the
  same greedy prompt differs queued / direct / streamed, a round raised
  (``engine_errors``), or a round graph compiled after set-up.

One process owns the chip: this one. Its last stdout line is the result.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".cache" / "chip_smoke"       # git-ignored scratch of one run
MATMUL_KERNEL = "dgi_qmm"
KERNELS = ("dgi_ragged_attention", "dgi_paged_decode", MATMUL_KERNEL)
DEADLINE_S = 1150                           # the contract allows 1200


T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def need(ok: Any, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED — {msg}")


def cache_entries(directory: str) -> int:
    return len(os.listdir(directory)) if os.path.isdir(directory) else 0


# --------------------------------------------------------------------- #
# the deployed pieces
# --------------------------------------------------------------------- #

def backend_rows(rows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The compile requests among a compile log's rows (it also keeps a row
    for each tracing and each lowering)."""
    return [r for r in rows if r["stage"] == "backend"]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start_plane(port: int, log: Any) -> subprocess.Popen:
    """The control plane as its own process: ``server/app.py`` imports no
    JAX, so the chip stays with this process."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(ROOT)] + [p for p in [os.environ.get("PYTHONPATH")] if p]
    ))
    return subprocess.Popen(
        [sys.executable, "-m", "distributed_gpu_inference_tpu.server.app",
         "--host", "127.0.0.1", "--port", str(port),
         "--db", str(WORK / "plane.sqlite")],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )


def wait_http(url: str, timeout_s: float, alive) -> None:
    import httpx

    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        need(alive(), f"process behind {url} exited")
        try:
            if httpx.get(url, timeout=2.0).status_code == 200:
                return
        except httpx.TransportError:
            pass
        time.sleep(0.2)
    need(False, f"{url} not healthy after {timeout_s:.0f}s")


class WorkerThread(threading.Thread):
    """``Worker.start()`` — what ``tpu-worker start`` calls — on a thread,
    so this script can be the client too. An exception in it is kept and
    fails the run."""

    def __init__(self, worker: Any) -> None:
        super().__init__(name="worker", daemon=True)
        self.worker = worker
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            self.worker.start(install_signal_handlers=False)
        except BaseException as exc:  # noqa: BLE001 — re-raised by the
            self.error = exc          # main thread (``check``)

    def check(self) -> None:
        if self.error is not None:
            raise self.error


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #

def phase_kernels(dry: bool) -> None:
    """The served subset of the kernel list: ``mistral-7b`` geometry, block
    16, bf16 pools — what the worker path builds."""
    from distributed_gpu_inference_tpu.testing import kernel_parity

    results = kernel_parity.run(
        ["mistral-7b"], [16], ["bf16"],
        ctx=128 if dry else 2048, chunk=32 if dry else 256, interpret=dry,
    )
    for r in results:
        say(f"  {r['case']}: " + (f"err/tol={r['err']}" if "err" in r
                                  else r["error"]))
    bad = [r["case"] for r in results if not r["ok"]]
    need(not bad, f"kernels disagree with their XLA reference: {bad}")
    say(f"kernels: {len(results)} cases agree with XLA "
        f"({'interpreted' if dry else 'compiled'})")


def per_device_gb(tree: Any) -> Dict[str, float]:
    import jax

    out: Dict[str, float] = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            key = str(sh.device)
            out[key] = out.get(key, 0.0) + sh.data.nbytes / 2**30
    return {k: round(v, 3) for k, v in sorted(out.items())}


def phase_graphs(llm: Any, widths: List[int], mesh: bool,
                 dry: bool) -> float:
    """Lower the batcher's round graphs from the engine's own jitted
    functions, print what each resolved to, compile them into the cache."""
    from distributed_gpu_inference_tpu.ops.attention import pallas_kernels

    eng = llm.engine
    levels = llm.serving.batcher.cfg.horizon_levels
    t0 = time.monotonic()
    found_all: set = set()
    for name, lowered in eng.lower_serving_graphs(levels, widths).items():
        found = pallas_kernels(lowered)
        found_all |= found
        attn = sorted(found & set(KERNELS) - {MATMUL_KERNEL})
        t1 = time.monotonic()
        lowered.compile()
        say(f"  {name}: attention={attn[0] if attn else 'xla'} "
            f"matmul={MATMUL_KERNEL if MATMUL_KERNEL in found else 'xla'} "
            f"compile={time.monotonic() - t1:.1f}s")
    if mesh:
        attention = set() if dry else set(KERNELS) - {MATMUL_KERNEL}
        need(found_all & set(KERNELS) == attention,
             f"mesh graphs hold {sorted(found_all)}, not {sorted(attention)}")
        say("  mesh engine: attention a shard of heads a chip "
            f"({eng.stats['decode_attention']}, {eng.stats['ragged_kv_path']}"
            "), projections and experts on the XLA paths (a bare "
            "pallas_call has no partitioning rule)")
    elif not dry:
        need(found_all == set(KERNELS),
             f"one-chip graphs hold {sorted(found_all)}, not {KERNELS}")
    return time.monotonic() - t0


class Traffic:
    """Requests through ``sdk.InferenceClient``, one client per thread."""

    def __init__(self, plane_url: str) -> None:
        self.plane_url = plane_url
        self.results: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    def send(self, kind: str, prompt: str, max_tokens: int,
             stop_on: Optional[int] = None) -> Dict[str, Any]:
        """One greedy request. It runs to ``max_tokens`` whatever it
        generates, unless ``stop_on`` names a token id to stop at."""
        from distributed_gpu_inference_tpu.sdk import InferenceClient

        gen: Dict[str, Any] = {"max_tokens": max_tokens, "temperature": 0.0}
        if stop_on is None:
            gen["ignore_eos"] = True
        else:
            gen["stop_token_ids"] = [stop_on]
        row: Dict[str, Any] = {"kind": kind, "prompt_len": len(prompt),
                               "want": max_tokens, "stop_on": stop_on}
        with InferenceClient(self.plane_url, timeout_s=600.0,
                             max_retries=0) as c:
            if kind == "stream":
                toks: List[int] = []
                text = ""
                for ch in c.stream_chat(prompt=prompt, timeout_s=600.0,
                                        **gen):
                    if ch.get("done"):
                        row["got"] = ch["usage"]["completion_tokens"]
                        row["finish"] = ch.get("finish_reason")
                    else:
                        toks += ch.get("token_ids") or []
                        text += ch.get("text_delta") or ""
                row["token_ids"], row["text"] = toks, text
            else:
                res = c.chat(prompt=prompt, sync=True, timeout_s=600.0,
                             use_direct=(kind == "direct"), **gen)
                row["got"] = res["usage"]["completion_tokens"]
                row["finish"] = res.get("finish_reason")
                row["text"] = res.get("text", "")
        with self._lock:
            self.results.append(row)
        return row

    def wave(self, plan: List[tuple]) -> None:
        """``plan``: (delay_s, kind, prompt, max_tokens) — each on its own
        thread, started at its delay."""
        errors: List[BaseException] = []

        def one(delay: float, *req: Any) -> None:
            time.sleep(delay)
            try:
                self.send(*req)
            except BaseException as exc:  # noqa: BLE001 — re-raised below
                errors.append(exc)

        threads = [threading.Thread(target=one, args=p, daemon=True)
                   for p in plan]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
            need(not t.is_alive(), "a request did not return in 600s")
        if errors:
            raise errors[0]


def text_of(n: int, salt: str) -> str:
    """``n`` bytes (= ``n`` ByteTokenizer tokens), distinct from its first
    block on so no two prompts share a cached prefix."""
    body = (salt + " the quick brown fox jumps over the lazy dog ") * (
        n // 8 + 1
    )
    return body[:n]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="cpu: the tiny dry run that debugs this script")
    args = ap.parse_args()
    dry = args.platform == "cpu"
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    # the program under test is the checkout this script sits in — not a
    # copy installed somewhere else
    need((ROOT / "distributed_gpu_inference_tpu").is_dir(),
         f"no distributed_gpu_inference_tpu/ next to {Path(__file__).name}: "
         "run it from the root of a checkout")
    import jax
    if dry:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    say(f"device: platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']}")
    need(device["platform"] == args.platform,
         f"found platform {device['platform']!r}, not {args.platform!r}: "
         "this script exists to use the chip (--platform cpu is the "
         "script's own dry run)")

    from distributed_gpu_inference_tpu.utils.config import WorkerConfig
    from distributed_gpu_inference_tpu.utils.data_structures import (
        WorkerState,
    )
    from distributed_gpu_inference_tpu.utils.device import (
        chip_spec,
        compile_log,
        enable_compile_cache,
    )
    from distributed_gpu_inference_tpu.worker.main import Worker

    cache_dir = enable_compile_cache()
    entries0 = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({entries0} entries)")
    compiles = compile_log()

    model = "llama3-mini" if dry else "mistral-7b"
    tp = len(dev)
    phase_kernels(dry)

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    # worker identity and credentials of a smoke run stay in its scratch
    os.environ["HOME"] = str(WORK)
    plane_port, direct_port = free_port(), free_port()
    plane_url = f"http://127.0.0.1:{plane_port}"
    with open(WORK / "plane.log", "w") as plane_log:
        plane = start_plane(plane_port, plane_log)
    wt: Optional[WorkerThread] = None
    try:
        wait_http(f"{plane_url}/health", 60.0, lambda: plane.poll() is None)
        say(f"plane: pid {plane.pid} at {plane_url}")

        cfg = WorkerConfig.model_validate({
            "name": "chip-smoke", "task_types": ["llm"],
            "server": {"url": plane_url},
            "direct": {"enabled": True, "host": "127.0.0.1",
                       "port": direct_port,
                       "public_url": f"http://127.0.0.1:{direct_port}"},
            "engines": {"llm": {
                "model": model, "quantization": "int8",
                "serving": {"mode": "batcher"},
                "extra": {"tp_size": tp} if tp > 1 else {},
            }},
            "poll_interval_s": 0.2, "heartbeat_interval_s": 2.0,
        })
        t_load = time.monotonic()
        worker = Worker(cfg)
        wt = WorkerThread(worker)
        wt.start()
        while worker.state == WorkerState.INITIALIZING:
            wt.check()
            need(wt.is_alive(), "worker thread ended before it was ready")
            time.sleep(0.2)
        load_s = time.monotonic() - t_load
        llm = worker.engines.get("llm")
        need(llm is not None and llm.loaded, "the llm engine did not load")
        eng = llm.engine
        need(llm.serving is not None and llm.serving.active,
             "serving mode is not batcher")
        say(f"worker: {worker.api.worker_id} ready in {load_s:.1f}s — "
            f"{model} int8, {eng.model_cfg.num_layers} layers, "
            f"tp_size={tp}, block_size={eng.cfg.block_size}, "
            f"max_batch={eng.cfg.max_batch_size}, "
            f"max_seq_len={eng.cfg.max_seq_len}")
        radix = type(eng.manager.radix).__name__
        say(f"radix index: {'native' if 'Native' in radix else 'python'} "
            f"({radix})")
        w_gb, kv_gb = per_device_gb(eng.params), per_device_gb(eng.kv)
        say(f"weights GiB per device: {w_gb}")
        say(f"kv pool GiB per device: {kv_gb}")
        need(len(w_gb) == tp and len(kv_gb) == tp,
             f"weights/KV sit on {len(w_gb)}/{len(kv_gb)} devices, not {tp}")
        if tp > 1:
            need(max(w_gb.values()) < 0.5 * sum(w_gb.values()),
                 "weights are gathered on one device, not sharded")

        import httpx

        row = httpx.get(
            f"{plane_url}/api/v1/workers/{worker.api.worker_id}", timeout=10
        ).json()
        topo = row.get("topology") or {}
        say(f"plane row: chip_generation={row.get('chip_generation')} "
            f"num_chips={row.get('num_chips')} topology={topo}")
        need(row.get("num_chips") == len(dev)
             and topo.get("num_chips") == len(dev),
             "the plane's worker row does not show the device count")
        spec = chip_spec(device["kind"])
        need(topo.get("chip_type") == ("cpu" if dry else spec.chip_type),
             f"the plane's worker row shows chip_type {topo.get('chip_type')}")

        # prompts: 12 → 16-wide rounds, 50 → 64-wide, 562 → two 256-wide
        # chunks and a 50-token tail (64-wide); a repeated prompt's fresh
        # suffix after the cached prefix is at most one block (16-wide)
        say("round graphs (lowered from the engine's jitted functions):")
        graphs_s = phase_graphs(llm, [16, 64, 256], tp > 1, dry)
        setup_mark = len(compiles.rows)

        traffic = Traffic(plane_url)
        t_warm = time.monotonic()
        for kind in ("queued", "direct", "stream"):
            traffic.send(kind, text_of(12, f"warm {kind}"), 8)
        warm_s = time.monotonic() - t_warm
        warm_mark = len(compiles.rows)
        setup = backend_rows(compiles.rows[:warm_mark])
        say(f"set-up: load {load_s:.1f}s, round graphs {graphs_s:.1f}s, "
            f"first requests {warm_s:.1f}s; compile requests "
            f"{len(setup)} ({sum(r['cache'] == 'hit' for r in setup)} "
            f"cache hits, {sum(r['cache'] == 'miss' for r in setup)} "
            f"compiled, {sum(r['secs'] for r in setup):.1f}s)")

        t_serve = time.monotonic()
        long_p = text_of(562, "long")
        traffic.wave([
            (0.0, "queued", long_p, 64),
            (0.0, "direct", text_of(50, "medium one"), 64),
            (0.6, "stream", text_of(12, "short s"), 32),
            (1.0, "queued", text_of(12, "short q"), 32),
        ])
        # the same greedy prompt through each entry point. Only streams
        # carry token ids (and ByteTokenizer text drops ids past 259, so
        # random weights decode to almost nothing): take a stream's
        # tokens, then have every entry point stop on the token whose FIRST
        # occurrence is latest — each must stop at that very place. The
        # first (cold) stream also fills the prefix cache, so the four
        # requests compared all prefill the same two-token suffix: a cold
        # run prefills other shapes, and at random weights in bf16 that is
        # enough to flip a near-tie argmax (its tokens are reported, not
        # compared).
        same_p = text_of(50, "same prompt")
        cold = traffic.send("stream", same_p, 32)
        ref = traffic.send("stream", same_p, 32)
        toks = ref["token_ids"]
        k = max(i for i, t in enumerate(toks) if t not in toks[:i])
        same = [traffic.send(kind, same_p, 32, stop_on=toks[k])
                for kind in ("queued", "direct", "stream")]
        serve_s = time.monotonic() - t_serve
        wt.check()

        sent = traffic.results
        for r in sent:
            say(f"  {r['kind']:6s} prompt={r['prompt_len']:4d} "
                f"tokens={r['got']}/{r['want']} finish={r['finish']}"
                + (f" stop_on={r['stop_on']}" if r["stop_on"] else ""))
        short = [r for r in sent if r["stop_on"] is None
                 and (r["got"] != r["want"] or r["finish"] != "length")]
        need(not short, f"requests not answered in full: {short}")
        for r in sent:
            if r["kind"] == "stream":
                need(len(r["token_ids"]) == r["got"],
                     "a stream fell back to the queue or dropped tokens")
        agree = next((i for i, (a, b) in enumerate(
            zip(cold["token_ids"], toks)) if a != b), len(toks))
        say(f"same prompt: warm stream tokens {toks}; the cold stream "
            f"agrees on the first {agree} of {len(toks)}; stop on "
            f"{toks[k]} (first seen at {k}) → "
            f"{[(r['kind'], r['got'], r['finish']) for r in same]}")
        need(all(r["finish"] == "stop" and r["got"] == same[0]["got"]
                 and r["text"] == same[0]["text"] for r in same)
             and same[2]["token_ids"] == toks[:same[2]["got"]]
             and k <= same[0]["got"] <= k + 1,
             "greedy output of one prompt differs by entry point")

        n_q = sum(r["kind"] == "queued" for r in sent)
        n_d = len(sent) - n_q
        st = llm.serving.get_stats()
        es = eng.get_stats()
        jobs = httpx.get(f"{plane_url}/health", timeout=10).json()["jobs"]
        ds = worker._direct.stats
        say(f"served in {serve_s:.1f}s: {len(sent)} requests "
            f"({n_q} queued, {n_d} direct/stream); plane jobs {jobs}; "
            f"direct server {ds}; batcher rounds={st['decode_rounds']} "
            f"ragged={st['ragged_rounds']} "
            f"engine_errors={st.get('engine_errors', 0)}; engine "
            f"ragged_rounds={es['ragged_rounds']} "
            f"decode_calls={es['decode_calls']} "
            f"prefill_tokens={es['prefill_tokens']}")
        need(st.get("engine_errors", 0) == 0, "a round raised (engine_errors)")
        need(st["ragged_rounds"] > 0
             and st["decode_rounds"] > st["ragged_rounds"],
             "ragged_round and decode_multi did not both run")
        need(jobs.get("completed") == n_q and sum(jobs.values()) == n_q,
             f"{n_q} queued requests sent, plane jobs are {jobs} (a direct "
             "request fell back to the queue, or a job failed)")
        need(ds["requests"] == n_d and ds["rejected"] == 0,
             f"{n_d} direct requests sent, the direct server saw {ds}")

        late = backend_rows(compiles.rows[warm_mark:])
        say(f"compile requests after warm-up: {len(late)} "
            f"{[(r['fn'], r['cache']) for r in late]}")
        fresh = [r for r in backend_rows(compiles.rows[setup_mark:])
                 if r["cache"] != "hit"
                 and ("decode_multi" in r["fn"] or "ragged_round" in r["fn"])]
        need(not fresh, f"round graphs compiled after set-up: {fresh}")
        say(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} "
            f"entries, {entries0} before)")

        out_dir = ROOT / "chiprun_out"
        out_dir.mkdir(exist_ok=True)
        (out_dir / f"chip_smoke_{device['platform']}{len(dev)}.json"
         ).write_text(json.dumps({
             "device": device, "model": model, "tp_size": tp,
             "requests": sent, "compiles": compiles.rows,
         }, indent=1))
    finally:
        if wt is not None and wt.is_alive():
            wt.worker.request_shutdown()
            wt.join(timeout=60.0)
        plane.terminate()
        try:
            plane.wait(timeout=10.0)
        except subprocess.TimeoutExpired:
            plane.kill()
    wt.check()
    need(not wt.is_alive(), "the worker did not shut down in 60s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
