#!/usr/bin/env python3
"""The load generator: a process of its own, as a deployed client is.

It imports no JAX and nothing of the program. It speaks plain HTTP to the
worker's direct server (``POST /inference/stream``, server-sent events),
sends each request once, never retries and never falls back, and stamps
every event on ``time.monotonic()`` — the clock the harness process reads
too (CLOCK_MONOTONIC is one clock for the whole machine).

Protocol: one JSON object per line on stdin, one per line on stdout.

``{"op": "play", "url": ..., "t0": <monotonic>, "loop": "open" | "closed" |
"serial", "requests" | "clients": ..., "stop_at": <monotonic>, "deadline":
<monotonic>, "extra_params": {...}, "keep_ids": <n>}`` plays a plan and
answers ``{"rows": [...]}``, one row per request sent. ``{"op": "exit"}``
ends the process.

A row: ``id``, ``due`` (the instant it should have been sent), ``sent``,
``status``, ``t`` (arrival of each token-bearing event), ``n`` (tokens in
each), ``done_at``, ``finish``, ``usage_out``, ``id_min``/``id_max``,
``ids`` (the first ``keep_ids`` token ids), ``timeline``, ``error``.
"""

from __future__ import annotations

import asyncio
import gc
import json
import sys
import time
from typing import Any, Dict, List, Optional

import aiohttp


async def one_request(session: aiohttp.ClientSession, url: str,
                      req: Dict[str, Any], due: float, deadline: float,
                      extra: Dict[str, Any], keep_ids: int) -> Dict[str, Any]:
    row: Dict[str, Any] = {
        "id": req["id"], "due": due, "prompt_tokens": req["prompt_tokens"],
        "asked": req["max_tokens"], "t": [], "n": [], "ids": [],
        "id_min": None, "id_max": None, "status": None, "done_at": None,
        "finish": None, "usage_out": None, "timeline": None, "error": None,
    }
    wait = due - time.monotonic()
    if wait > 0:
        await asyncio.sleep(wait)
    params = {"prompt": req["prompt"], "max_tokens": req["max_tokens"],
              "temperature": 0.0, "ignore_eos": True, **extra}
    if "trace_id" in extra:
        params["trace_id"] = f"{extra['trace_id']}-{req['id']}"
    row["sent"] = time.monotonic()

    async def stream() -> None:
        async with session.post(url, json={"type": "llm", "params": params}
                                ) as resp:
            row["status"] = resp.status
            if resp.status != 200:
                row["error"] = (await resp.text())[:200]
                return
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                now = time.monotonic()
                chunk = json.loads(raw[5:])
                if chunk.get("error") is not None:
                    row["error"] = str(chunk["error"])[:200]
                    return
                if chunk.get("done"):
                    row["done_at"] = now
                    row["finish"] = chunk.get("finish_reason")
                    row["usage_out"] = (chunk.get("usage") or {}).get(
                        "completion_tokens")
                    row["timeline"] = chunk.get("timeline")
                    return
                ids = chunk.get("token_ids") or []
                if ids:
                    row["t"].append(now)
                    row["n"].append(len(ids))
                    lo, hi = min(ids), max(ids)
                    row["id_min"] = lo if row["id_min"] is None \
                        else min(lo, row["id_min"])
                    row["id_max"] = hi if row["id_max"] is None \
                        else max(hi, row["id_max"])
                    if len(row["ids"]) < keep_ids:
                        row["ids"].extend(ids[:keep_ids - len(row["ids"])])

    try:
        await asyncio.wait_for(stream(), max(deadline - time.monotonic(), 0.01))
    except asyncio.TimeoutError:
        row["error"] = "not finished at the deadline"
    except (aiohttp.ClientError, ConnectionError, ValueError) as exc:
        row["error"] = f"{type(exc).__name__}: {exc}"[:200]
    return row


async def play(cmd: Dict[str, Any]) -> List[Dict[str, Any]]:
    url, t0 = cmd["url"], float(cmd["t0"])
    deadline = float(cmd["deadline"])
    extra = cmd.get("extra_params") or {}
    keep = int(cmd.get("keep_ids", 0))
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=10.0)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn,
                                     read_bufsize=2**20) as s:
        if cmd["loop"] == "open":
            return list(await asyncio.gather(*[
                one_request(s, url, r, t0 + r["due_s"], deadline, extra, keep)
                for r in cmd["requests"]
            ]))
        if cmd["loop"] == "serial":
            rows = []
            for r in cmd["requests"]:
                rows.append(await one_request(
                    s, url, r, time.monotonic(), deadline, extra, keep))
            return rows
        stop_at = float(cmd["stop_at"])

        async def client(reqs: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
            rows = []
            wait = t0 - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            for r in reqs:
                now = time.monotonic()
                if now >= stop_at:
                    break
                rows.append(await one_request(
                    s, url, r, now, deadline, extra, keep))
            return rows

        per_client = await asyncio.gather(*[client(c) for c in cmd["clients"]])
        return [row for rows in per_client for row in rows]


def main() -> int:
    # the collector must not stop the clock between two tokens
    gc.disable()
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        cmd = json.loads(line)
        if cmd.get("op") == "exit":
            break
        reply: Dict[str, Optional[Any]]
        try:
            reply = {"rows": asyncio.run(play(cmd))}
        except Exception as exc:  # noqa: BLE001 — reported to the harness,
            # which fails the run; the child itself keeps its protocol
            reply = {"error": f"{type(exc).__name__}: {exc}"}
        sys.stdout.write(json.dumps(reply) + "\n")
        sys.stdout.flush()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
