#!/usr/bin/env python
"""Seeded multi-tenant workload generator — the traces production serving
actually sees, for measuring cache-aware routing (and any future cluster
bench) honestly.

Every scenario produces a deterministic, seed-stable open-loop trace: the
same ``(scenario, seed, knobs)`` always generates byte-identical requests
and arrival times, so two benchmark legs (routing ON vs OFF, one replica
vs four) replay the EXACT same offered load.

Scenarios:

- ``chat``     multi-turn conversations with growing shared prefixes: each
               tenant has a system prompt shared by all its conversations;
               each turn's prompt is the previous turn's prompt plus an
               assistant stub and a fresh user message — the prefix a
               radix cache (and a locality router) can reuse grows every
               turn. Turn k+1 depends on turn k (``depends_on`` + think
               time): an open-loop driver must not fire a turn before its
               predecessor's reply exists.
- ``rag``      single-shot requests with long, heterogeneous prompts: a
               document context drawn from a small shared corpus (the
               cacheable part) plus a unique query; prompt lengths are
               lognormal — the long tail is the point.
- ``bursty``   the chat mix, but tenant arrivals modulate through on/off
               bursts (a tenant's whole fleet goes quiet, then floods) —
               the schedule a locality router must not melt under.
- ``storm``    the ANTI-AFFINITY schedule (round 13): a handful of tenants
               with deep shared system prompts take turns flooding the
               fleet — a whole burst of one tenant's requests lands inside
               a fraction of a second, saturating whichever worker is warm
               for that prefix so load-based spillover scatters the tail
               across cold workers. Advisory routing (PR 7) collapses
               here by design; cluster-wide KV migration is measured
               against exactly this trace.
- ``priority`` the rag mix across NAMED tenant tiers (round 12): paid
               (priority 10) over free (priority 0) over batch
               (priority -10), assigned per tenant by index — the tier
               mix the overload-control ladder (server/admission.py)
               sheds and degrades against. Every request carries its
               tenant id and tier in the trace.
- ``longctx``  long-context traffic (round 17): book-length RAG contexts
               (a shared corpus of ~``long_len``-char documents, one per
               request plus a unique query — the 32k shape) interleaved
               with long AGENT TRACES (one conversation whose prompt is
               the full accumulated tool-call transcript, dependency-
               chained like chat turns). A background trickle of SHORT
               chat requests rides the same trace so one run measures
               both the giant prefills and the short-request tails they
               threaten — the mixed-traffic frontier the prefill budget
               exists for.

Any scenario can additionally be generated ``tiered=True``: tenants gain
paid/free/batch tiers (index-derived — NO extra rng draws, so arrival
schedules and prompts stay byte-identical to the untiered trace) and the
matching priorities. Untiered traces omit the ``tier`` field entirely,
keeping their JSONL byte-identical to pre-tier builds.

Usage (CLI emits JSONL for external drivers; ``generate()`` is the
library surface):

    python -m benchmarks.workloads --scenario chat --seed 0
    python -m benchmarks.workloads --scenario rag --seed 3 --requests 64
"""

from __future__ import annotations

import argparse
import json
import sys
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

_LETTERS = "abcdefghijklmnopqrstuvwxyz"

# control-plane priority per named tier — mirrors
# server/admission.py TIER_PRIORITY_BOOST (benchmarks must not import
# server code; the pairing is asserted in tests/test_overload_chaos.py)
TIER_PRIORITY = {"paid": 10, "free": 0, "batch": -10}


def tier_for_tenant(index: int, tenants: int) -> str:
    """Deterministic index-derived tier split: the first quarter of
    tenants (at least one) is paid, the last quarter (when ≥3 tenants)
    is batch, the middle is free. No rng draws — tier assignment can be
    bolted onto an existing trace without moving a single arrival."""
    n_paid = max(1, tenants // 4)
    n_batch = max(1, tenants // 4) if tenants >= 3 else 0
    if index < n_paid:
        return "paid"
    if n_batch and index >= tenants - n_batch:
        return "batch"
    return "free"


def _text(rng: np.random.Generator, n: int) -> str:
    """Deterministic ASCII filler (ByteTokenizer: one token per char)."""
    return "".join(_LETTERS[i] for i in rng.integers(0, 26, int(n)))


@dataclass
class WorkloadRequest:
    """One trace entry. ``arrival_s`` is the open-loop offset from trace
    start; when ``depends_on`` is set the driver must additionally wait
    for that request's completion plus ``think_s`` (multi-turn chat —
    a turn cannot be typed before the previous reply renders)."""

    id: str
    arrival_s: float
    tenant: str
    prompt: str
    max_tokens: int
    priority: int = 0
    conversation: Optional[str] = None
    turn: int = 0
    depends_on: Optional[str] = None
    think_s: float = 0.0
    # named tenant tier (paid/free/batch — round 12 overload control).
    # Empty = untiered: the field is OMITTED from JSONL so pre-tier
    # traces stay byte-identical.
    tier: str = ""


@dataclass
class Workload:
    scenario: str
    seed: int
    requests: List[WorkloadRequest]
    meta: Dict[str, Any] = field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return max((r.arrival_s for r in self.requests), default=0.0)

    def to_jsonl(self) -> str:
        # untiered requests drop the empty tier key: same-seed JSONL for
        # pre-tier scenarios is byte-identical to pre-tier builds
        out = []
        for r in self.requests:
            d = asdict(r)
            if not d.get("tier"):
                d.pop("tier", None)
            out.append(json.dumps(d))
        return "\n".join(out)


def _chat(rng: np.random.Generator, *, requests: int, tenants: int,
          turns: int, rate: float, system_len: int, turn_len: int,
          max_tokens: int, think_s: float,
          priority_for: Optional[Dict[str, int]] = None) -> List[WorkloadRequest]:
    n_convs = max(1, requests // max(1, turns))
    out: List[WorkloadRequest] = []
    sys_prompts = {
        f"t{t}": _text(rng, system_len) for t in range(tenants)
    }
    conv_starts = np.cumsum(rng.exponential(1.0 / rate, n_convs))
    for c in range(n_convs):
        tenant = f"t{int(rng.integers(0, tenants))}"
        conv = f"c{c}"
        history = sys_prompts[tenant]
        prev_id: Optional[str] = None
        # turns arrive dependency-chained; arrival_s spaces conversations
        at = float(conv_starts[c])
        for k in range(turns):
            if len(out) >= requests:
                return out
            user = _text(rng, turn_len)
            prompt = history + user
            rid = f"{conv}.{k}"
            out.append(WorkloadRequest(
                id=rid, arrival_s=round(at, 4), tenant=tenant,
                prompt=prompt, max_tokens=max_tokens,
                priority=(priority_for or {}).get(tenant, 0),
                conversation=conv, turn=k, depends_on=prev_id,
                think_s=round(float(rng.uniform(0.5, 1.5) * think_s), 4)
                if prev_id is not None else 0.0,
            ))
            # the assistant stub stands in for the reply the client would
            # echo back — deterministic, so the grown prefix is stable
            history = prompt + "|" + _text(rng, max_tokens // 2) + "|"
            prev_id = rid
    return out


def _rag(rng: np.random.Generator, *, requests: int, tenants: int,
         rate: float, corpus_docs: int, doc_len: int, query_len: int,
         max_tokens: int,
         priority_for: Optional[Dict[str, int]] = None) -> List[WorkloadRequest]:
    corpus = [_text(rng, max(32, int(rng.lognormal(np.log(doc_len), 0.5))))
              for _ in range(corpus_docs)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, requests))
    out: List[WorkloadRequest] = []
    for i in range(requests):
        tenant = f"t{int(rng.integers(0, tenants))}"
        # zipf-ish doc popularity: a few hot docs dominate — the shareable
        # prefix mass a locality router exists for
        doc = corpus[min(corpus_docs - 1,
                         int(rng.zipf(1.5)) - 1)]
        out.append(WorkloadRequest(
            id=f"r{i}", arrival_s=round(float(arrivals[i]), 4),
            tenant=tenant, prompt=doc + _text(rng, query_len),
            max_tokens=max_tokens,
            priority=(priority_for or {}).get(tenant, 0),
        ))
    return out


def _storm(rng: np.random.Generator, *, requests: int, tenants: int,
           rate: float, system_len: int, turn_len: int, max_tokens: int,
           burst: int,
           priority_for: Optional[Dict[str, int]] = None) -> List[WorkloadRequest]:
    """Anti-affinity tenant storms: each storm picks ONE tenant and fires
    ``burst`` requests sharing that tenant's deep system prompt within a
    ~quarter-second window — faster than any single worker can absorb, so
    a locality router must either queue on the warm worker or spill the
    tail cold. ``rate`` is storms/s."""
    sys_prompts = {f"t{t}": _text(rng, system_len) for t in range(tenants)}
    burst = max(1, burst)
    n_storms = max(1, -(-requests // burst))
    storm_starts = np.cumsum(rng.exponential(1.0 / rate, n_storms))
    # the burst window scales with the burst: requests land far faster
    # than one worker drains them (saturation) while still spanning a few
    # heartbeats — the router SEES the warm worker saturate mid-storm,
    # which is the moment advisory routing starts spilling cold
    span = 0.15 * burst
    out: List[WorkloadRequest] = []
    for s in range(n_storms):
        tenant = f"t{int(rng.integers(0, tenants))}"
        at = float(storm_starts[s])
        offs = np.sort(rng.uniform(0.0, span, burst))
        for j in range(burst):
            if len(out) >= requests:
                return out
            out.append(WorkloadRequest(
                id=f"s{s}.{j}", arrival_s=round(at + float(offs[j]), 4),
                tenant=tenant,
                prompt=sys_prompts[tenant] + _text(rng, turn_len),
                max_tokens=max_tokens,
                priority=(priority_for or {}).get(tenant, 0),
                conversation=f"s{s}",
            ))
    return out


def _longctx(rng: np.random.Generator, *, requests: int, tenants: int,
             rate: float, long_len: int, query_len: int, turn_len: int,
             max_tokens: int, corpus_docs: int, agent_turns: int,
             short_fraction: float,
             priority_for: Optional[Dict[str, int]] = None
             ) -> List[WorkloadRequest]:
    """Long-context mix: ~1/3 book-length RAG one-shots, ~1/3 one long
    agent trace (dependency-chained turns whose prompt accumulates the
    whole transcript toward ``long_len``), and ``short_fraction`` short
    chat requests woven between them. Length jitter is mild (±12%) so a
    trace generated for a 32k deployment actually exercises ~32k paths
    instead of averaging down to 16k."""
    corpus = [
        _text(rng, max(256, int(long_len * float(rng.uniform(0.88, 1.12)))))
        for _ in range(corpus_docs)
    ]
    n_short = int(requests * short_fraction)
    n_agent = min(agent_turns, max(0, (requests - n_short) // 3))
    n_rag = max(0, requests - n_short - n_agent)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, requests))
    out: List[WorkloadRequest] = []
    # book-length RAG one-shots: hot docs dominate (zipf), so prefix
    # caching and affinity routing have something to win at 32k depth
    for i in range(n_rag):
        tenant = f"t{int(rng.integers(0, tenants))}"
        doc = corpus[min(corpus_docs - 1, int(rng.zipf(1.5)) - 1)]
        out.append(WorkloadRequest(
            id=f"L{i}", arrival_s=round(float(arrivals[i]), 4),
            tenant=tenant, prompt=doc + _text(rng, query_len),
            max_tokens=max_tokens,
            priority=(priority_for or {}).get(tenant, 0),
        ))
    # one long agent trace: each turn's prompt is the full transcript so
    # far — the grown prefix marches toward long_len and each turn
    # depends on its predecessor (a tool call cannot fire before the
    # previous observation exists)
    if n_agent:
        tenant = f"t{int(rng.integers(0, tenants))}"
        step = max(turn_len, long_len // max(1, n_agent))
        history = _text(rng, step)
        prev_id: Optional[str] = None
        for k in range(n_agent):
            i = n_rag + k
            rid = f"A0.{k}"
            out.append(WorkloadRequest(
                id=rid, arrival_s=round(float(arrivals[i]), 4),
                tenant=tenant, prompt=history, max_tokens=max_tokens,
                priority=(priority_for or {}).get(tenant, 0),
                conversation="A0", turn=k, depends_on=prev_id,
                think_s=round(float(rng.uniform(0.05, 0.2)), 4)
                if prev_id is not None else 0.0,
            ))
            history = history + "|" + _text(rng, step) + "|"
            prev_id = rid
    # the short-request tail riding alongside: the latency victims the
    # prefill budget protects
    for j in range(requests - len(out)):
        i = len(out)
        tenant = f"t{int(rng.integers(0, tenants))}"
        out.append(WorkloadRequest(
            id=f"s{j}", arrival_s=round(float(arrivals[i]), 4),
            tenant=tenant, prompt=_text(rng, turn_len),
            max_tokens=max_tokens,
            priority=(priority_for or {}).get(tenant, 0),
        ))
    out.sort(key=lambda r: (r.arrival_s, r.id))
    return out


def generate(scenario: str, seed: int = 0, *, requests: int = 32,
             tenants: int = 4, turns: int = 4, rate: float = 2.0,
             system_len: int = 256, turn_len: int = 64,
             doc_len: int = 512, query_len: int = 64,
             corpus_docs: int = 6, max_tokens: int = 32,
             think_s: float = 0.2, tiered: bool = False,
             burst: int = 8, long_len: int = 32768,
             agent_turns: int = 6,
             short_fraction: float = 0.5) -> Workload:
    """Build one seed-stable trace. All randomness flows from ONE
    ``np.random.default_rng(seed)`` consumed in a fixed order — adding a
    scenario must never reorder draws inside an existing one.

    ``tiered=True`` stamps every tenant with a named paid/free/batch tier
    (index-derived, zero extra draws) and the matching priority —
    prompts/arrivals stay byte-identical to the untiered trace. The
    ``priority`` scenario is always tiered."""
    rng = np.random.default_rng(seed)
    kw: Dict[str, Any] = {}
    tier_map = {f"t{t}": tier_for_tenant(t, tenants)
                for t in range(tenants)}
    prio_map = {k: TIER_PRIORITY[v] for k, v in tier_map.items()}
    if scenario == "chat":
        reqs = _chat(rng, requests=requests, tenants=tenants, turns=turns,
                     rate=rate, system_len=system_len, turn_len=turn_len,
                     max_tokens=max_tokens, think_s=think_s,
                     priority_for=prio_map if tiered else None)
    elif scenario == "rag":
        reqs = _rag(rng, requests=requests, tenants=tenants, rate=rate,
                    corpus_docs=corpus_docs, doc_len=doc_len,
                    query_len=query_len, max_tokens=max_tokens,
                    priority_for=prio_map if tiered else None)
    elif scenario == "bursty":
        # chat arrivals pushed through per-tenant on/off bursts: each
        # conversation's start is delayed to its tenant's next ON window
        reqs = _chat(rng, requests=requests, tenants=tenants, turns=turns,
                     rate=rate * 2.0, system_len=system_len,
                     turn_len=turn_len, max_tokens=max_tokens,
                     think_s=think_s,
                     priority_for=prio_map if tiered else None)
        period = {f"t{t}": float(rng.uniform(2.0, 6.0))
                  for t in range(tenants)}
        duty = {f"t{t}": float(rng.uniform(0.3, 0.7))
                for t in range(tenants)}
        for r in reqs:
            p, d = period[r.tenant], duty[r.tenant]
            phase = r.arrival_s % p
            if phase > p * d:   # OFF window: shift to the next ON edge
                r.arrival_s = round(r.arrival_s + (p - phase), 4)
        kw["burst_period_s"] = period
    elif scenario == "storm":
        reqs = _storm(rng, requests=requests, tenants=tenants, rate=rate,
                      system_len=system_len, turn_len=turn_len,
                      max_tokens=max_tokens, burst=burst,
                      priority_for=prio_map if tiered else None)
        kw["burst"] = burst
    elif scenario == "priority":
        # named tenant tiers (round 12 — was a two-level 10/0 split):
        # paid over free over batch, per-tenant ids in every trace row
        tiered = True
        reqs = _rag(rng, requests=requests, tenants=tenants, rate=rate,
                    corpus_docs=corpus_docs, doc_len=doc_len,
                    query_len=query_len, max_tokens=max_tokens,
                    priority_for=prio_map)
        kw["priority_tiers"] = prio_map
    elif scenario == "longctx":
        reqs = _longctx(rng, requests=requests, tenants=tenants, rate=rate,
                        long_len=long_len, query_len=query_len,
                        turn_len=turn_len, max_tokens=max_tokens,
                        corpus_docs=max(2, min(corpus_docs, 4)),
                        agent_turns=agent_turns,
                        short_fraction=short_fraction,
                        priority_for=prio_map if tiered else None)
        kw["long_len"] = long_len
        kw["short_fraction"] = short_fraction
    else:
        raise ValueError(
            f"unknown scenario {scenario!r} "
            "(chat | rag | bursty | storm | priority | longctx)"
        )
    if tiered:
        for r in reqs:
            r.tier = tier_map[r.tenant]
        kw["tenant_tiers"] = tier_map
    return Workload(
        scenario=scenario, seed=seed, requests=reqs,
        meta={"requests": len(reqs), "tenants": tenants, "rate": rate,
              "max_tokens": max_tokens, **kw},
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scenario", default="chat",
                    choices=["chat", "rag", "bursty", "storm", "priority",
                             "longctx"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--turns", type=int, default=4)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="open-loop arrival rate (req/s or conv/s)")
    ap.add_argument("--system-len", type=int, default=256)
    ap.add_argument("--turn-len", type=int, default=64)
    ap.add_argument("--doc-len", type=int, default=512)
    ap.add_argument("--max-tokens", type=int, default=32)
    ap.add_argument("--burst", type=int, default=8,
                    help="requests per tenant storm (storm scenario)")
    ap.add_argument("--long-len", type=int, default=32768,
                    help="target long-prompt chars (longctx scenario; "
                    "ByteTokenizer: 1 char = 1 token)")
    ap.add_argument("--agent-turns", type=int, default=6,
                    help="turns in the longctx agent trace")
    ap.add_argument("--short-fraction", type=float, default=0.5,
                    help="fraction of longctx requests that are short "
                    "chat traffic (the tail-latency victims)")
    ap.add_argument("--tiered", action="store_true",
                    help="stamp paid/free/batch tenant tiers (+matching "
                    "priorities) onto the trace; arrivals/prompts stay "
                    "byte-identical to the untiered run")
    ap.add_argument("--summary", action="store_true",
                    help="print meta only, not the JSONL trace")
    args = ap.parse_args()
    wl = generate(args.scenario, args.seed, requests=args.requests,
                  tenants=args.tenants, turns=args.turns, rate=args.rate,
                  system_len=args.system_len, turn_len=args.turn_len,
                  doc_len=args.doc_len, max_tokens=args.max_tokens,
                  tiered=args.tiered, burst=args.burst,
                  long_len=args.long_len, agent_turns=args.agent_turns,
                  short_fraction=args.short_fraction)
    if args.summary:
        print(json.dumps({"scenario": wl.scenario, "seed": wl.seed,
                          "duration_s": round(wl.duration_s, 3),
                          **wl.meta}))
    else:
        print(wl.to_jsonl())


if __name__ == "__main__":
    main()
