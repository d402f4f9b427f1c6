"""Continuous batching scheduler driving the slot-based jitted engine.

Capability parity with the reference's ``worker/batch_processor.py``
(``ContinuousBatcher.submit``:130 future-based API, priority heap, full-batch
OR max-wait trigger :177-182, prefix-grouped batch selection :267-300, stats
:359, ``AdaptiveBatcher`` latency-targeted tuning :413-431) — re-designed for
TPU serving:

- The reference batches *whole requests* into one engine call per batch; here
  requests are admitted into fixed engine **slots** and every decode step runs
  one compiled graph over all slots (true continuous batching — a request
  joins/leaves the batch between steps, nothing waits for stragglers).
- Prefix grouping doesn't reorder a Python batch; it orders *admission* so
  sequences sharing cached prefix blocks land while those pages are hot.
- What adapts is the **multi-step scan horizon** (device steps per host
  round-trip): deep horizon = throughput, shallow = admission latency. The
  reference tunes batch size ±20% against a latency target; here one rule
  over two measured times chooses every scan's length
  (``ContinuousBatcher._choose_steps``) and no configuration names a number.

Engine calls execute on a single dedicated thread (the engine is not
thread-safe); the asyncio side only schedules and resolves futures.
"""

from __future__ import annotations

import asyncio
import heapq
import itertools
import logging
import threading
import time
from concurrent.futures import Future as _Future
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from distributed_gpu_inference_tpu.runtime import flight
from distributed_gpu_inference_tpu.runtime.engine import (
    ChunkedAdmission,
    PreemptedSequence,
    TPUEngine,
)
from distributed_gpu_inference_tpu.runtime.kv_cache import OutOfBlocksError
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    InferenceResponse,
    compute_prefix_hash,
)
from distributed_gpu_inference_tpu.utils.data_structures import KV_BLOCK_TOKENS

log = logging.getLogger(__name__)

# The horizon rule's one constant (``ContinuousBatcher._retune``): a scan of T
# steps is long enough when it takes at least this many times what a round
# costs the host whatever it holds, so that the host's fixed cost is at most
# 1 / (1 + _HOST_AMORTISE) of the time. Not a configuration key: the step time
# and the host's cost are measured, and a model's own numbers choose its level.
# (Fixed on the v5e, PERF.md PR 26: at 3 a dense 7B with all 8 rows decoding
# sat on the edge between T=1 and T=4, its one-step round costing the host
# 4.0-4.3 ms against an 11.5 ms step.)
_HOST_AMORTISE = 4.0
# a level changes only when that inequality holds or fails with this much to
# spare, so a step time that wanders with the occupancy does not flap it
_LEVEL_SLACK = 0.1
# the two measured times are means (a share of time is bought with the mean
# cost, not the typical one) over about ten scans; one sample counts as at
# most twice and at least half of the mean it joins, so that one stalled
# round (on the v5e a third of a saturated worker's rounds cost the host
# 20 ms where the others cost 6) cannot carry the level past a threshold
_EMA_WEIGHT = 0.1
# what the engine thread pays around the device's work in a plain round
# (``engine.stats``; readback, the wait for the device, is not among them)
_HOST_PHASES = ("round_build_s", "round_dispatch_s", "round_commit_s")
# a scan that takes this many times what its steps take by the running mean
# is counted (``scans_stalled``, ``scan_stall_s``) and logged with the
# engine's phases: on the v5e one scan in some 12 minutes of a long-context
# decode cell waited 5-8 s for the device (PERF.md, PR 31)
_STALL_FACTOR = 10.0
# why a scan got its length, counted as ``scans_<reason>``
_SCAN_REASONS = ("amortise", "raised_waiting", "capped_by_budget")
# why a scan was read back before the next round went out behind it, counted
# as ``chain_breaks_<reason>`` (``ContinuousBatcher._chain_break``)
# (``round``: what was read was a ragged round that had gone out behind the
# scan, which is always read before the next round goes out)
_CHAIN_BREAKS = ("admission", "row_end_waiting", "row_end", "signal",
                 "pressure", "idle", "round")


def _mean(mean: float, sample: float) -> float:
    """``mean`` moved toward ``sample`` by ``_EMA_WEIGHT``; the first sample
    is the mean, a later one counts as at most twice and at least half of it."""
    if mean == 0:
        return sample
    return mean + _EMA_WEIGHT * (min(max(sample, mean / 2), mean * 2) - mean)


class RequestMigrated(Exception):
    """A submitted request was frozen at a step boundary by its *interrupt*
    event (graceful drain): the generation did not fail — it carries a
    portable :class:`PreemptedSequence` the caller hands to the control
    plane so another worker resumes it. The serving layer translates this
    into the worker-level ``JobMigrated``."""

    def __init__(self, pre: PreemptedSequence) -> None:
        super().__init__(
            f"request migrated with {len(pre.generated)} generated tokens"
        )
        self.pre = pre


def synthesize_checkpoint(request: InferenceRequest) -> PreemptedSequence:
    """A zero-token checkpoint for a request the engine never admitted
    (interrupted while still queued, or the admission-time stream record).
    The slot key mirrors ``TPUEngine._bind_slot``'s derivation for seeded
    requests so a resume elsewhere stays seed-stable; unseeded sampling was
    never deterministic, so the (0, 0) fallback loses nothing."""
    seed = request.sampling.seed
    key = (
        ((int(seed) >> 32) & 0xFFFFFFFF, int(seed) & 0xFFFFFFFF)
        if seed is not None else (0, 0)
    )
    return PreemptedSequence(
        request=request,
        prompt_len=len(request.prompt_token_ids or []),
        generated=[],
        slot_key=key,
        start_time=request.arrival_time,
        first_token_time=None,
        cached_tokens=0,
    )


@dataclass
class BatcherConfig:
    max_wait_ms: float = 5.0          # admission latch (reference max_wait)
    multi_step: int = 8               # initial decode horizon
    min_multi_step: int = 1
    max_multi_step: int = 64
    adaptive: bool = True             # False: every scan runs multi_step
    queue_limit: int = 1024
    default_timeout_s: float = 300.0
    # KV-pressure preemption policy: a request preempted more than this
    # many times errors with a distinct ``preempted_too_often`` reason
    # instead of thrashing the pool forever (pool genuinely too small for
    # the working set). Victims are picked (lowest priority first, then
    # most-recently-admitted — LIFO) and requeued at the FRONT of the heap
    # with their full generated context, so resume restores spilled/cached
    # pages instead of recomputing.
    max_preemptions: int = 3
    # per-ROUND prefill token budget for ragged rounds (PR 17, long-context
    # serving): the total prefill-chunk tokens all in-flight admissions may
    # land in one ragged round, split fairly across them (water-fill, with
    # a rotating start so sub-token shares starve nobody). Bounds the
    # matmul work a giant admission adds to each co-dispatched decode
    # round, so decode ITL for short requests stays flat while a 32k
    # prompt streams in over many rounds. 0 = unbudgeted (pre-PR-17
    # behavior: every admission gets a full ``ragged_chunk`` slice per
    # round — byte-identical outputs either way; the budget only shapes
    # WHEN prefill work lands). Live-pushable (serving.prefill_budget).
    prefill_budget: int = 0
    # hopeless-work abandonment (gray-failure round): when ON, the serving
    # loop drops work whose deadline has ALREADY passed and whose projected
    # remaining decode (tokens_left × observed ITL) still cannot land
    # within ``deadline_grace_s`` — resolving the future with a typed
    # ``deadline_abandoned`` error and freeing the blocks at the next step
    # boundary, so a degraded worker stops burning rounds on answers nobody
    # will read. NEVER fires for deadline-less requests (deadline_s=None);
    # OFF (the default) leaves every request byte-identical to the
    # pre-round scheduler.
    abandon_deadlines: bool = False
    deadline_grace_s: float = 0.5
    # predictive abandonment (round 20): when ON (requires
    # abandon_deadlines too), the projection fires BEFORE the deadline
    # passes — a job whose remaining decode (tokens_left × observed ITL)
    # already overruns deadline + grace stops burning ragged-round slots
    # now instead of limping to the deadline first. Same typed
    # ``deadline_abandoned`` error, counted separately
    # (stats["abandoned_predictive"]); OFF keeps the reactive-only round-15
    # behavior byte-identical.
    predictive_abandon: bool = False

    @property
    def horizon_levels(self) -> Tuple[int, ...]:
        """The ONLY decode horizons the batcher may request. decode_multi
        compiles one scan per distinct T — an unquantized adaptive horizon
        triggers an XLA compile mid-serving for nearly every change. Powers
        of four between the min/max bound the graph count at 4."""
        levels = [t for t in (1, 4, 16, 64)
                  if self.min_multi_step <= t <= self.max_multi_step]
        return tuple(levels) or (self.min_multi_step,)


def split_prefill_budget(needs: List[int], budget: int,
                         start: int = 0) -> List[int]:
    """Fair water-fill of a per-round prefill token ``budget`` across
    concurrent admissions. ``needs[i]`` is admission i's remaining demand
    this round (min of its unprefilled tokens and the chunk cap); returns
    per-admission grants summing to <= budget.

    Water-fill: every still-hungry admission repeatedly receives an equal
    share of what is left, so small admissions finish inside their share
    and release the remainder to large ones — a 32k prompt co-admitted
    with a 40-token prompt cannot crowd it out, and N giant prompts split
    the budget evenly instead of first-come-takes-all. When the budget is
    smaller than the admission count the integer share floors to zero;
    the minimum 1-token share plus the rotating ``start`` offset hands
    the scarce tokens to a DIFFERENT admission subset each round
    (starvation-free round-robin). Deterministic: same inputs, same
    grants."""
    n = len(needs)
    grants = [0] * n
    if n == 0 or budget <= 0:
        return grants
    remaining = budget
    order = [(start + k) % n for k in range(n)]
    while remaining > 0:
        hungry = [i for i in order if grants[i] < needs[i]]
        if not hungry:
            break
        share = max(1, remaining // len(hungry))
        for i in hungry:
            if remaining <= 0:
                break
            give = min(share, needs[i] - grants[i], remaining)
            grants[i] += give
            remaining -= give
    return grants


class _StreamWait:
    """What the batcher keeps of a streamed request's waits (one per item
    with an observer, O(1)): the tokens and the round of the last
    notification that brought it one, and the longest stretch between two
    such rounds' ``ready`` with the round that ended it."""

    __slots__ = ("tokens", "ready", "preempts", "gap_s", "round", "cause")

    def __init__(self) -> None:
        self.tokens = 0
        self.ready: Optional[float] = None
        self.preempts = 0
        self.gap_s = 0.0
        self.round: Optional[int] = None
        self.cause = ""

    def progress(self, tokens: int, stamp: flight.RoundStamp,
                 preempts: int) -> None:
        """A notification holds ``tokens`` tokens of the stream after the
        round ``stamp``: where that is more than the last one held, the
        stretch since then ends here."""
        if tokens <= self.tokens:
            return
        if self.ready is not None and stamp.ready - self.ready > self.gap_s:
            self.gap_s = stamp.ready - self.ready
            self.round = stamp.round
            # the first tokens after a preemption waited for the resume,
            # whatever round brought them
            self.cause = stamp.cause if preempts == self.preempts \
                else "other"
        self.tokens, self.ready, self.preempts = tokens, stamp.ready, preempts


@dataclass(order=True)
class _QueueItem:
    # (-priority, deadline_at, arrival_time, seq): EDF *within* a priority
    # band (round 12) — deadline_at is +inf for deadline-less requests, so
    # with no deadlines set every comparison falls through to the
    # arrival/seq components and admission order is byte-identical to the
    # pre-deadline batcher
    sort_key: Tuple[int, float, float, int]
    request: InferenceRequest = field(compare=False)
    future: "asyncio.Future[InferenceResponse]" = field(compare=False)
    enqueued_at: float = field(compare=False, default_factory=time.time)
    # KV-pressure state: a preempted request waits in the heap carrying its
    # frozen sequence; _admit resumes it instead of submitting fresh
    preempted: Optional[PreemptedSequence] = field(compare=False, default=None)
    preempt_count: int = field(compare=False, default=0)
    # consecutive resume failures seen while the engine held NOTHING else:
    # an idle pool that cannot re-admit the sequence never will
    idle_resume_oob: int = field(compare=False, default=0)
    # serving hooks (all optional): ``observer(tokens)`` is called on the
    # event-loop thread after every decode round the sequence survived with
    # the monotonic generated-token list (SSE streaming reads deltas off
    # it); ``cancel`` aborts at the next step boundary (client gone);
    # ``interrupt`` freezes into a checkpoint and fails the future with
    # :class:`RequestMigrated` (graceful drain)
    observer: Optional[Callable[[List[int]], None]] = \
        field(compare=False, default=None)
    cancel: Optional[Any] = field(compare=False, default=None)
    interrupt: Optional[Any] = field(compare=False, default=None)
    # flight recorder (round 14): the request's Timeline, when it carries
    # a trace_id — queue wait, admission, chunk rounds, first token,
    # preempt/resume, and completion are noted at their step boundaries.
    # None for untraced requests: the recorder-off path costs one None
    # check per boundary, nothing per token.
    flight: Optional[Any] = field(compare=False, default=None)
    # a streamed request's waits (``_StreamWait``); None without an observer
    stream: Optional[_StreamWait] = field(compare=False, default=None)


class ContinuousBatcher:
    """Admission queue + decode loop over a :class:`TPUEngine`."""

    def __init__(self, engine: TPUEngine,
                 cfg: Optional[BatcherConfig] = None) -> None:
        self.engine = engine
        self.cfg = cfg or BatcherConfig()
        if getattr(engine, "supports_ragged", True) is False:
            # the one admission path is the ragged round; an engine that
            # says it has none cannot be served (a stub without the
            # attribute is taken to speak the protocol)
            raise ValueError(
                "the batcher admits through ragged rounds and this engine "
                "has none (supports_ragged is False): kv_seq_sharded "
                "engines are fenced — their decode rows read through a "
                "dedicated shard_map op with no ragged variant"
            )
        self._heap: List[_QueueItem] = []
        self._seq = itertools.count()
        self._wake = asyncio.Event()
        self._stopping = False
        self._run_task: Optional[asyncio.Task] = None
        self._exec = ThreadPoolExecutor(max_workers=1, thread_name_prefix="engine")
        self._levels: Tuple[int, ...] = ()
        self._level = 0
        self._horizon = 0.0
        # the horizon rule's ``h`` by scan length: what a round of that
        # many steps costs the host, in ms (``_retune``)
        self._host_ms: Dict[int, float] = {}
        # and what it costs the host, hidden behind a scan or not
        self._cost_ms: Dict[int, float] = {}
        self._rebuild_levels(float(self.cfg.multi_step))
        self._slot_items: Dict[int, _QueueItem] = {}
        # admission stamps for LIFO victim selection (slot indices recycle,
        # so recency must be tracked per admission, not per slot number)
        self._admit_stamp: Dict[int, int] = {}
        self._stamp = itertools.count()
        # after a preemption, resumes pause until one round runs
        # unpressured: the FROZEN slots must reserve the freed blocks
        # first, or the resume takes them straight back and the pressure
        # recurs every round until the victim dies preempted_too_often
        self._resume_hold = False
        # EVERY admission — short or long — is a bound-but-unprefilled
        # engine slot whose chunk rows ride the next ragged round(s)
        # co-dispatched with the active decodes. Several may be in flight
        # at once; an admission leaves this list for _slot_items when its
        # final chunk samples the first token.
        self._ragged: List[Tuple[ChunkedAdmission, _QueueItem]] = []
        # rotating start offset for the per-round prefill-budget split:
        # when the budget floors below one token per admission, a
        # different admission subset receives the scarce tokens each
        # round (split_prefill_budget's starvation-freedom)
        self._prefill_rr = 0
        # the number of the last round dispatched: the engine's ``rounds``
        # count where it keeps one, so that the batcher's and the engine's
        # spans of one round (two threads) carry the same ``round=<n>``
        self._round = 0
        # perf_counter at the last round's end, stamped on the engine
        # thread; None once the loop has parked (no work owned in between)
        self._round_end: Optional[float] = None
        # steps of the scan the last round left unread on the device (the
        # engine's ``decode_multi(..., ahead=True)``), None when every
        # token dispatched has been read; and the host time the chip spent
        # idle since the last scan that was read, for ``_retune``
        self._unread_steps: Optional[int] = None
        self._unread_reason = ""
        # the ragged round the last call left unread behind the scan it
        # read (``TPUEngine.ragged_round``): what its stamp will say (the
        # round's number, its pieces, its cause), None when none is
        self._unread_round: Optional[Tuple[int, int, str]] = None
        self._exposed_s = self._cost_s = 0.0
        # the last round's stamp, made where it returned on the engine
        # thread: what the streams' snapshots of that round carry
        self._ready: Optional[flight.RoundStamp] = None
        # callers waiting to run their own work on the engine thread
        # (``BatcherServing.run_exclusive``): while there are any, every
        # scan is read by the call that made it, so that what they run
        # finds the host mirrors current
        self._foreign = 0
        self._foreign_lock = threading.Lock()
        self.stats: Dict[str, Any] = {
            "submitted": 0, "completed": 0, "rejected": 0, "timeouts": 0,
            "decode_rounds": 0, "admitted": 0, "queue_peak": 0,
            # the horizon rule's two measured times (means over scans): a
            # scan's device time per step, and what a round at the
            # ``horizon`` level costs the host whatever it holds;
            # ``horizon`` is the level they amortise at
            "step_latency_ema_ms": 0.0, "round_host_ema_ms": 0.0,
            "horizon": self._horizon, "occupancy_sum": 0,
            # why each scan got its length, and the row-steps scans ran
            # for rows that had already finished inside them
            **{f"scans_{reason}": 0 for reason in _SCAN_REASONS},
            "scan_row_steps_masked": 0,
            "scans_stalled": 0, "scan_stall_s": 0.0,
            # scans dispatched while the one before was unread, why the
            # others were not, and the host's round time the chip idled
            # through (the gaps between rounds and the engine's phases that
            # ran with no scan on the device)
            "scans_chained": 0,
            **{f"chain_breaks_{why}": 0 for why in _CHAIN_BREAKS},
            "round_host_exposed_s": 0.0,
            # fresh admissions, and those of them bound while the scan
            # before their round was still unread on the device
            "ragged_admissions": 0, "admissions_ahead": 0,
            # ragged rounds, and those of them dispatched behind an unread
            # scan (the call brought that scan's tokens back and left the
            # round's on the device for the next read)
            "ragged_rounds": 0, "ragged_rounds_chained": 0,
            "budgeted_rounds": 0, "budget_skipped_admissions": 0,
            "preemptions": 0, "resumes": 0, "preemption_block_pressure": 0,
            "preempted_too_often": 0,
            "cancelled": 0, "migrated": 0, "adopted": 0,
            "abandoned": 0, "abandoned_predictive": 0,
            # round spans' time counters (docs/observability.md): the gap
            # from one round's end to the next one's start while work was
            # owned throughout, and the loop's two halves that split it
            "between_rounds_s": 0.0, "between_rounds": 0,
            "admit_s": 0.0, "deliver_s": 0.0,
            # streams completed, by the round that ended their longest
            # wait for a token, and those waits' seconds (``_StreamWait``)
            **{f"longest_wait_{c}": 0 for c in flight.WAIT_CAUSES},
            **{f"longest_wait_s_{c}": 0.0 for c in flight.WAIT_CAUSES},
        }
        self._level_counters()

    def _rebuild_levels(self, anchor: float) -> None:
        """THE quantized-horizon level-set derivation (init + live
        reconfigure): adaptive mode exposes the power-of-4 levels, fixed
        mode honors the clamped ``multi_step`` verbatim; the current level
        snaps to the one nearest ``anchor`` (``multi_step`` before anything
        is measured) so no scan ever asks for an uncompiled length."""
        if self.cfg.adaptive:
            levels = self.cfg.horizon_levels
        else:
            # a fixed horizon compiles exactly one graph — honor it verbatim
            levels = (max(self.cfg.min_multi_step,
                          min(self.cfg.multi_step,
                              self.cfg.max_multi_step)),)
        self._levels = levels
        self._level = min(
            range(len(levels)), key=lambda i: abs(levels[i] - anchor)
        )
        self._horizon = float(levels[self._level])
        if hasattr(self, "stats"):
            self.stats["horizon"] = self._horizon
            self._level_counters()

    def _level_counters(self) -> None:
        """``scans_t<T>`` / ``scan_s_t<T>`` for every configured level T:
        scans dispatched at that length and their seconds. The non-zero
        ones are the levels the traffic reached."""
        for t in self._levels:
            self.stats.setdefault(f"scans_t{t}", 0)
            self.stats.setdefault(f"scan_s_t{t}", 0.0)

    # ---------------------------------------------------------------- API

    @staticmethod
    def _note(item: "_QueueItem", name: str, at: Optional[float] = None,
              **attrs: Any) -> None:
        """Flight-recorder boundary note for one request: a None check
        when untraced, a list append when traced. ``at`` records an
        engine-observed wall-clock instant (e.g. the slot's first-token
        time) instead of "now"."""
        f = item.flight
        if f is None:
            return
        if at is not None:
            f.note_at(name, at, **attrs)
        else:
            f.note(name, **attrs)

    async def submit(
        self, request: InferenceRequest, timeout_s: Optional[float] = None,
        *,
        observer: Optional[Callable[[List[int]], None]] = None,
        cancel: Optional[Any] = None,
        interrupt: Optional[Any] = None,
        resume_from: Optional[PreemptedSequence] = None,
        flight: Optional[Any] = None,
    ) -> InferenceResponse:
        """Enqueue and await completion (reference submit:130 semantics:
        future resolves with the response; queue-full and timeout surface as
        errors in the response).

        Serving hooks: ``observer`` receives the monotonic generated-token
        list after every decode round (SSE streaming); ``cancel`` (an
        Event) aborts at the next step boundary; ``interrupt`` (an Event)
        freezes the sequence into a checkpoint and raises
        :class:`RequestMigrated` here instead of resolving (graceful
        drain). ``resume_from`` re-admits a server-held checkpoint instead
        of prefilling from scratch — head-of-line, through the same
        cache/spill-restoring resume path KV-pressure preemptions use."""
        if self._stopping:
            raise RuntimeError("batcher is stopping")
        if len(self._heap) >= self.cfg.queue_limit:
            self.stats["rejected"] += 1
            return InferenceResponse(
                request_id=request.request_id, error="queue full",
                # machine-readable: nothing ran — an overload shed, safe
                # to retry elsewhere (vs request_timeout, which may still
                # be generating here)
                error_code="shed_overload",
            )
        if resume_from is None and not self.engine.request_fits_pool(request):
            # the PROMPT alone cannot fit even an idle pool: no amount of
            # preemption could ever admit it — reject up front. (The check
            # is deliberately not worst-case on max_new_tokens; generation
            # that outgrows the pool is handled dynamically by preemption,
            # bounded by max_preemptions and the idle-resume abort.
            # Checkpoint resumes skip it: they were admitted once and the
            # preempted_too_often cap owns their capacity endgame.)
            self.stats["rejected"] += 1
            return InferenceResponse(
                request_id=request.request_id,
                error="request exceeds KV pool capacity (worst case "
                      "cannot fit even an idle pool)",
                error_code="over_capacity",
            )
        loop = asyncio.get_running_loop()
        fut: "asyncio.Future[InferenceResponse]" = loop.create_future()
        item = _QueueItem(
            sort_key=(-request.priority, request.deadline_at,
                      request.arrival_time, next(self._seq)),
            request=request,
            future=fut,
            observer=observer,
            cancel=cancel,
            interrupt=interrupt,
            preempted=resume_from,
            flight=flight,
            stream=_StreamWait() if observer is not None else None,
        )
        self._note(item, "batcher.enqueued",
                   queue_depth=len(self._heap))
        heapq.heappush(self._heap, item)
        self.stats["submitted"] += 1
        self.stats["queue_peak"] = max(self.stats["queue_peak"], len(self._heap))
        self._wake.set()
        timeout_s = timeout_s or self.cfg.default_timeout_s
        try:
            return await asyncio.wait_for(fut, timeout=timeout_s)
        except asyncio.TimeoutError:
            self.stats["timeouts"] += 1
            return InferenceResponse(
                request_id=request.request_id,
                error=f"timeout after {timeout_s}s",
                # distinct from shed_overload: the caller's wait budget
                # elapsed — the request was (or may still be) running
                error_code="request_timeout",
            )

    async def adopt_slot(self, slot: int,
                         request: Optional[InferenceRequest] = None,
                         flight: Optional[Any] = None
                         ) -> InferenceResponse:
        """Drive an ALREADY-ADMITTED engine slot (PD decode stage: the
        sequence arrived through a KV handoff, not through submit) inside
        the shared decode rounds, and await its completion. The slot joins
        the batch exactly like a submitted request — it can be preempted,
        resumed, and counted — so PD decode no longer monopolizes the
        engine for its whole generation."""
        if self._stopping:
            # same race submit() guards: a stop() between the caller's
            # serving.active check and this coroutine running would leave
            # the item in _slot_items with no run task to ever resolve it
            raise RuntimeError("batcher is stopping")
        s = self.engine.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is empty")
        self.stats["adopted"] += 1
        if s.finish_reason is not None:
            # the sequence already finished (it decoded alongside earlier
            # batcher rounds while awaiting adoption): resolve immediately
            loop = asyncio.get_running_loop()
            resp = await loop.run_in_executor(
                self._exec, self.engine.finish_slot, slot
            )
            if flight is not None:
                flight.note("batcher.adopted", slot=slot)
                flight.note("batcher.completed",
                            finish_reason=resp.finish_reason,
                            tokens=resp.completion_tokens)
            return resp
        req = request or s.request
        loop = asyncio.get_running_loop()
        fut: "asyncio.Future[InferenceResponse]" = loop.create_future()
        item = _QueueItem(
            sort_key=(-req.priority, req.deadline_at, req.arrival_time,
                      next(self._seq)),
            request=req,
            future=fut,
            flight=flight,
        )
        self._note(item, "batcher.adopted", slot=slot)
        self._slot_items[slot] = item
        self._admit_stamp[slot] = next(self._stamp)
        self._wake.set()
        return await fut

    def start(self) -> None:
        if self._run_task is None:
            self._stopping = False
            self._run_task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self, drain: bool = True) -> None:
        """Graceful shutdown: optionally finish queued + active work first
        (reference worker drain semantics, main.py:444). Without drain,
        every still-pending future resolves with an error response so no
        caller is left waiting out its timeout against a dead loop."""
        self._stopping = True
        self._wake.set()
        if drain:
            # drain batcher-OWNED work only: a foreign engine slot (e.g. a
            # PD sequence retained between stages) is not ours to wait on
            while self._heap or self._slot_items or self._ragged:
                await asyncio.sleep(0.01)
        if self._run_task:
            self._run_task.cancel()
            try:
                await self._run_task
            except asyncio.CancelledError:
                pass
            self._run_task = None
        pending = list(self._slot_items.values()) + list(self._heap)
        self._slot_items.clear()
        self._heap.clear()
        loop = asyncio.get_running_loop()
        for adm, rag_item in self._ragged:
            # a request mid prefill is in NEITHER collection above — abort
            # its engine state and resolve it, or its submit() would wait
            # on a dead loop forever
            try:
                await loop.run_in_executor(
                    self._exec, self.engine.abort_chunked, adm
                )
            except Exception:  # noqa: BLE001 — shutdown is best-effort
                pass
            pending.append(rag_item)
        self._ragged = []
        for item in pending:
            if item.future.done():
                continue
            item.future.set_result(InferenceResponse(
                request_id=item.request.request_id,
                error="batcher stopped",
            ))
            self.stats["completed"] += 1
        self._exec.shutdown(wait=False)

    def reconfigure(self, **updates: Any) -> None:
        """Apply server-pushed SLO knobs to a LIVE batcher between rounds:
        any :class:`BatcherConfig` field by name (None values are ignored).
        Horizon-shaping fields (``max_multi_step``, ``min_multi_step``,
        ``multi_step``, ``adaptive``) rebuild the quantized level set; the
        current level snaps to the nearest surviving horizon so no scan
        requests an uncompiled length mid-flight.

        ``ragged_chunk`` is the one ENGINE knob accepted here (PR 17):
        the per-admission chunk-row width of ragged rounds. It is read
        per round, never compile-baked — chunk widths bucket through
        ``prefill_buckets``, so retuning it live only selects among
        already-compiled graph widths. Together with ``prefill_budget``
        it makes the long-context prefill geometry live-pushable."""
        ragged_chunk = updates.pop("ragged_chunk", None)
        if ragged_chunk is not None:
            rc = int(ragged_chunk)
            if rc < 1:
                raise ValueError(
                    f"ragged_chunk must be >= 1, got {ragged_chunk}"
                )
        coerced: Dict[str, Any] = {}
        for key, val in updates.items():
            if val is None or not hasattr(self.cfg, key):
                continue
            cur = getattr(self.cfg, key)
            if isinstance(cur, bool) and isinstance(val, str):
                # remote pushes arrive through an untyped dict and env/YAML
                # tooling stringifies scalars — bool("false") is True, so
                # coerce by content, not constructor
                val = val.strip().lower() in ("1", "true", "yes", "on")
            coerced[key] = type(cur)(val)
        # all-or-nothing: coercion above raised before any cfg mutation,
        # so one bad value can't leave a half-applied retune
        for key, val in coerced.items():
            setattr(self.cfg, key, val)
        if ragged_chunk is not None and \
                hasattr(self.engine.cfg, "ragged_chunk"):
            self.engine.cfg.ragged_chunk = rc
        self._rebuild_levels(self._horizon)

    # ------------------------------------------------------------- internals

    def _admission_order(self) -> List[_QueueItem]:
        """Prefix-grouped admission (reference :267-300): group queued
        requests by their first-block prefix hash; largest group first, then
        priority/FIFO inside the group. Preempted sequences ALWAYS lead:
        their pages are still warm in the prefix cache / spill tiers, and
        head-of-line resume is what bounds a preempted request's extra
        latency to one pressure episode."""
        resumes = sorted(
            (it for it in self._heap if it.preempted is not None),
            key=lambda it: it.sort_key,
        )
        groups: Dict[str, List[_QueueItem]] = {}
        for item in self._heap:
            if item.preempted is not None:
                continue
            ids = item.request.prompt_token_ids or []
            key = (
                compute_prefix_hash(ids, KV_BLOCK_TOKENS)
                if len(ids) >= KV_BLOCK_TOKENS
                else f"solo-{id(item)}"
            )
            groups.setdefault(key, []).append(item)
        ordered: List[_QueueItem] = []
        # largest group first; equal-size groups ordered by their best member
        # (priority, then FIFO) so priority still wins between singletons
        for _, members in sorted(
            groups.items(),
            key=lambda kv: (-len(kv[1]), min(it.sort_key for it in kv[1])),
        ):
            ordered.extend(sorted(members, key=lambda it: it.sort_key))
        return resumes + ordered

    async def _admit(self, ahead: bool = False) -> int:
        """Admit queued requests into free slots. Heap mutation and future
        resolution happen HERE on the event-loop thread (asyncio futures and
        the heap are not thread-safe); only the engine call itself runs on the
        engine executor thread.

        Every fresh admission binds its slot NOW
        (``engine.submit_chunked_start``) and runs no prefill: its prompt
        rides the next ragged round(s) as chunk rows co-dispatched with the
        active decodes — admission IS "append rows to the next round";
        several may be in flight at once, short or long alike.

        ``ahead``: a scan is unread on the device and this pass runs before
        its read (``_admission_pass``). The engine binds a free slot beside
        that scan; what needs the scan read — a resume, or a prompt the pool
        cannot hold until the read gives blocks back — stops the pass with
        the item back in the queue, and the pass after the read takes it up
        in the order and with the pool it would have had."""
        admitted = 0
        if self._resume_hold:
            # the round after a preemption belongs to the FROZEN slots:
            # neither resumes nor fresh admissions may take the freed
            # blocks before they re-reserve, or the pressure recurs every
            # round (thrash) no matter who stole them
            return 0
        free = self.engine.free_slots()
        if not free or not self._heap:
            return 0
        loop = asyncio.get_running_loop()
        requeue: List[_QueueItem] = []

        def _defer(item: "_QueueItem") -> bool:
            """Requeue an item the pool could not hold RIGHT NOW — unless
            its worst case statically can never fit the pool, in which
            case it errors out (the one capacity error that legitimately
            reaches a client). A PREEMPTED sequence is never statically
            rejected: it was admitted once and carries generated tokens —
            requeue it and let the preempted_too_often cap (which returns
            the partial output) decide if the pool can't sustain it.
            Returns True when the item was deferred."""
            if item.preempted is None and \
                    not self.engine.request_fits_pool(item.request):
                if not item.future.done():
                    item.future.set_result(InferenceResponse(
                        request_id=item.request.request_id,
                        error="request exceeds KV pool capacity (worst "
                              "case cannot fit even an idle pool)",
                    ))
                    # same counter as the submit()-time static rejection:
                    # one condition, one metric, wherever it is detected
                    self.stats["rejected"] += 1
                return False
            requeue.append(item)
            return True

        for item in self._admission_order():
            if not free:
                break
            # remove from the queue before any await so a concurrent submit()
            # (which only pushes) can never interleave with a removal
            try:
                self._heap.remove(item)
            except ValueError:
                continue  # already handled
            if item.future.cancelled():
                continue
            if ahead and item.preempted is not None:
                requeue.append(item)
                break
            if item.preempted is not None:
                # resume a preempted sequence: head-of-line, restores
                # cached/spilled pages through the normal allocate+prefill
                # path. Pool still too tight → stop admitting ANYTHING this
                # pass (new work must not steal the blocks the resume
                # needs) and retry next loop.
                try:
                    slot = await loop.run_in_executor(
                        self._exec, self.engine.resume, item.preempted,
                    )
                except OutOfBlocksError:
                    if self.engine.num_active == 0:
                        # an IDLE pool that STATICALLY cannot hold the
                        # sequence never will (nothing left to free):
                        # after a few consecutive tries, deliver the
                        # partial output instead of spinning until the
                        # client's timeout. A statically-fitting resume
                        # keeps retrying — an idle-pool allocation failure
                        # is then transient by construction (cache
                        # eviction in flight, injected chaos pressure),
                        # and aborting would turn a 2-second storm into a
                        # permanently failed request (fleet chaos suite).
                        item.idle_resume_oob += 1
                        if item.idle_resume_oob > 2 and not \
                                self.engine.resume_fits_pool(
                                    item.preempted):
                            pre = item.preempted
                            if not item.future.done():
                                item.future.set_result(InferenceResponse(
                                    request_id=item.request.request_id,
                                    token_ids=list(pre.generated),
                                    finish_reason="abort",
                                    prompt_tokens=pre.prompt_len,
                                    completion_tokens=len(pre.generated),
                                    error="request exceeds KV pool "
                                          "capacity: generated context "
                                          f"({len(pre.generated)} tokens) "
                                          "can no longer be resumed",
                                ))
                                self.stats["completed"] += 1
                            continue
                    else:
                        item.idle_resume_oob = 0
                    if _defer(item):
                        break
                    continue
                except Exception as e:
                    if not item.future.done():
                        item.future.set_result(InferenceResponse(
                            request_id=item.request.request_id,
                            error=f"resume failed: {e}",
                        ))
                        self.stats["completed"] += 1
                    continue
                item.preempted = None
                item.idle_resume_oob = 0
                if slot in free:
                    free.remove(slot)
                self._slot_items[slot] = item
                self._admit_stamp[slot] = next(self._stamp)
                self.stats["resumes"] += 1
                self._note(item, "batcher.resumed", slot=slot)
                admitted += 1
                continue
            try:
                adm = await loop.run_in_executor(
                    self._exec, self.engine.submit_chunked_start,
                    item.request,
                )
            except OutOfBlocksError:
                if ahead:
                    requeue.append(item)
                    break
                _defer(item)
                continue
            except Exception as e:
                if not item.future.done():
                    item.future.set_result(
                        InferenceResponse(
                            request_id=item.request.request_id,
                            error=str(e),
                            # typed admission failures (e.g. the engine's
                            # over_length rejection) stay machine-readable
                            # through the batcher
                            error_code=getattr(e, "error_code", None),
                        )
                    )
                continue
            # consume the slot only on SUCCESS: a failed start rolled the
            # engine back, and burning a free slot for it would under-admit
            # the rest of this pass (the slot leak)
            free.pop(0)
            self._ragged.append((adm, item))
            self.stats["ragged_admissions"] += 1
            # (the engine reads the scan first where its start cannot run
            # beside it: that admission did not run ahead)
            self.stats["admissions_ahead"] += \
                ahead and self.engine.scan_unread
            self._note(item, "batcher.admitted", slot=adm.slot,
                       mode="ragged", round=self._round + 1,
                       tokens=len(item.request.prompt_token_ids or []))

        for item in requeue:
            heapq.heappush(self._heap, item)
        if self._heap:
            heapq.heapify(self._heap)
        self.stats["admitted"] += admitted
        return admitted

    def _note_first_token(self, item: "_QueueItem", slot: int,
                          **attrs: Any) -> None:
        """Note the first-token boundary at the ENGINE's wall-clock stamp
        (``SequenceSlot.first_token_time`` — the instant the token was
        sampled) rather than the loop's observation time, so ttft on the
        timeline matches the engine's own ttft_ms. ``attrs``: the
        ``round`` that sampled it, where a round did."""
        if item.flight is None:
            return
        s = self.engine.slots[slot]
        t = getattr(s, "first_token_time", None) if s is not None else None
        self._note(item, "batcher.first_token", at=t, **attrs)

    async def _check_pressure(self, after_round: bool = False) -> None:
        """Consume the engine's KV-pressure signal and apply the preemption
        policy. Decode-sourced pressure (active slots frozen, progress
        blocked) always preempts a victim; admission-sourced pressure
        preempts only when the waiting work outranks the victim — otherwise
        the deferred admissions simply wait for completions."""
        p = self.engine.take_pressure()
        if p is None:
            if after_round:
                # one full engine round ran unpressured: the frozen slots
                # got their reservations, resumes may flow again
                self._resume_hold = False
            return
        self.stats["preemption_block_pressure"] += 1
        if p.source == "decode":
            # skip if every frozen slot resolved meanwhile (finished this
            # very round and its blocks are already back)
            still_frozen = any(
                (s := self.engine.slots[sl]) is not None
                and s.finish_reason is None
                for sl in p.slots
            )
            if still_frozen:
                await self._preempt_victim(mandatory=True)
        else:
            await self._preempt_victim(mandatory=False)

    async def _preempt_victim(self, mandatory: bool) -> None:
        """Pick and preempt one victim: lowest priority first, then (round
        12, deadline-aware) the slot with the MOST deadline slack —
        deadline-less sequences before late-deadline ones before
        tight-deadline ones — ties broken most-recently-admitted (LIFO —
        the youngest sequence has the least compute invested and the
        warmest prefix to resume from; with no deadlines set the policy is
        byte-identical to the pre-deadline batcher). The frozen sequence
        requeues at the FRONT of the heap; past ``max_preemptions`` the
        request errors with ``preempted_too_often``."""
        cands = []
        for slot, item in self._slot_items.items():
            s = self.engine.slots[slot]
            if s is None or s.finish_reason is not None or s.prefilling:
                continue
            cands.append((item.request.priority,
                          -item.request.deadline_at,
                          -self._admit_stamp.get(slot, -1), slot, item))
        if not cands:
            return
        prio, _, _, slot, item = min(cands)
        if not mandatory:
            # admission pressure: only preempt for strictly higher-priority
            # waiting work — FIFO fairness is not worth a spill round-trip
            waiting = max(
                (it.request.priority for it in self._heap
                 if not it.future.done()),
                default=None,
            )
            if waiting is None or waiting <= prio:
                return
        loop = asyncio.get_running_loop()
        try:
            pre = await loop.run_in_executor(
                self._exec, self.engine.preempt_slot, slot
            )
        except Exception:
            return      # slot finished/changed under us: nothing to preempt
        self._slot_items.pop(slot, None)
        self.stats["preemptions"] += 1
        item.preempt_count += 1
        pre.preempt_count = item.preempt_count
        self._note(item, "batcher.preempted", slot=slot,
                   generated=len(pre.generated))
        if item.preempt_count > self.cfg.max_preemptions:
            self.stats["preempted_too_often"] += 1
            if not item.future.done():
                item.future.set_result(InferenceResponse(
                    request_id=item.request.request_id,
                    token_ids=list(pre.generated),
                    finish_reason="abort",
                    prompt_tokens=pre.prompt_len,
                    completion_tokens=len(pre.generated),
                    error=f"preempted_too_often: evicted "
                          f"{item.preempt_count} times under KV pressure",
                ))
                self.stats["completed"] += 1
            return
        item.preempted = pre
        # resort to the FRONT of the heap: resumes outrank every waiting
        # admission (their pages are warm; head-of-line bounds added
        # latency) — but pause resumes until one round runs unpressured,
        # so the frozen slots reserve the freed blocks first
        self._resume_hold = True
        item.sort_key = (
            -(1 << 20) - item.request.priority,
            item.request.deadline_at,
            item.request.arrival_time,
            next(self._seq),
        )
        heapq.heappush(self._heap, item)

    def _abort_slot(self, slot: int) -> Optional[InferenceResponse]:
        """Runs on the engine executor: mark a live slot aborted and finish
        it (partial tokens included). None when the slot vanished."""
        s = self.engine.slots[slot]
        if s is None:
            return None
        s.finish_reason = s.finish_reason or "abort"
        return self.engine.finish_slot(slot)

    async def _scan_signals(self) -> None:
        """Honor per-request cancel/interrupt events at the loop boundary —
        the only place slot state is quiescent. Cancels resolve with the
        partial output (finish_reason="abort"); interrupts freeze into a
        checkpoint and fail the future with :class:`RequestMigrated` so the
        serving layer migrates the job without burning a retry."""
        loop = asyncio.get_running_loop()
        changed = False
        for item in list(self._heap):
            if item.future.done():
                continue
            if item.cancel is not None and item.cancel.is_set():
                self._heap.remove(item)
                changed = True
                pre = item.preempted
                item.future.set_result(InferenceResponse(
                    request_id=item.request.request_id,
                    token_ids=list(pre.generated) if pre else [],
                    finish_reason="abort",
                    prompt_tokens=pre.prompt_len if pre
                    else len(item.request.prompt_token_ids or []),
                    completion_tokens=len(pre.generated) if pre else 0,
                ))
                self.stats["completed"] += 1
                self.stats["cancelled"] += 1
            elif item.interrupt is not None and item.interrupt.is_set():
                self._heap.remove(item)
                changed = True
                pre = item.preempted or synthesize_checkpoint(item.request)
                pre.preempt_count = item.preempt_count
                item.future.set_exception(RequestMigrated(pre))
                self.stats["migrated"] += 1
        if changed:
            heapq.heapify(self._heap)
        for adm, item in list(self._ragged):
            cancelled = item.cancel is not None and item.cancel.is_set()
            interrupted = item.interrupt is not None \
                and item.interrupt.is_set()
            if not (cancelled or interrupted or item.future.done()):
                continue
            # a request mid prefill holds no resumable engine state yet:
            # abort the admission (frees its slot + staged blocks) and
            # either resolve with an empty abort or migrate with a
            # synthesized zero-token checkpoint — burning the remaining
            # prefill rounds on an abandoned/draining request would stall
            # everyone else; a done future (caller timeout) just releases
            # the engine side
            self._ragged.remove((adm, item))
            try:
                await loop.run_in_executor(
                    self._exec, self.engine.abort_chunked, adm
                )
            except Exception:  # noqa: BLE001 — abort is best-effort
                pass
            if item.future.done():
                continue
            if cancelled:
                item.future.set_result(InferenceResponse(
                    request_id=item.request.request_id,
                    finish_reason="abort",
                    prompt_tokens=len(item.request.prompt_token_ids or []),
                ))
                self.stats["completed"] += 1
                self.stats["cancelled"] += 1
            else:
                pre = synthesize_checkpoint(item.request)
                pre.preempt_count = item.preempt_count
                item.future.set_exception(RequestMigrated(pre))
                self.stats["migrated"] += 1
        for slot, item in list(self._slot_items.items()):
            s = self.engine.slots[slot]
            if s is None or s.finish_reason is not None:
                continue  # the round loop resolves finished slots
            if item.cancel is not None and item.cancel.is_set():
                try:
                    resp = await loop.run_in_executor(
                        self._exec, self._abort_slot, slot
                    )
                except Exception:
                    continue
                self._slot_items.pop(slot, None)
                if resp is not None and not item.future.done():
                    item.future.set_result(resp)
                    self.stats["completed"] += 1
                    self.stats["cancelled"] += 1
            elif item.interrupt is not None and item.interrupt.is_set() \
                    and not s.prefilling:
                try:
                    pre = await loop.run_in_executor(
                        self._exec, self.engine.preempt_slot, slot
                    )
                except Exception:
                    continue  # finished/changed under us — next pass
                self._slot_items.pop(slot, None)
                pre.preempt_count = item.preempt_count
                if not item.future.done():
                    item.future.set_exception(RequestMigrated(pre))
                    self.stats["migrated"] += 1

    def _deadline_hopeless(self, request: InferenceRequest,
                           tokens_left: int, now: float) -> bool:
        """True when ``request`` missed its deadline AND its projected
        remaining decode (``tokens_left`` × observed ITL) cannot land even
        within the grace window — the typed-abandonment trigger. Guarded
        three ways: the feature flag, an explicit ``deadline_s is None``
        check (deadline-less requests must NEVER abandon — asserted by
        tests, not merely implied by the +inf deadline_at), and
        ``tokens_left > 0`` (a sequence about to finish frees nothing by
        aborting)."""
        if not self.cfg.abandon_deadlines:
            return False
        if request.deadline_s is None:
            return False
        if tokens_left <= 0:
            return False
        deadline_at = request.deadline_at
        if now <= deadline_at and not self.cfg.predictive_abandon:
            # reactive mode waits for the deadline to actually pass;
            # predictive mode (round 20) lets the ITL projection below
            # fire EARLY — the projection test is identical either way,
            # so a request reactive mode would carry to its deadline and
            # then drop is dropped now, before burning the rounds
            return False
        # observed time of one step of a decode scan (``_retune``), read as
        # the inter-token latency; floor at 1ms so a cold EMA (no scan yet)
        # still projects SOME forward progress instead of 0
        itl_s = max(float(self.stats["step_latency_ema_ms"]), 1.0) / 1000.0
        return now + tokens_left * itl_s > \
            deadline_at + self.cfg.deadline_grace_s

    def _count_abandon(self, request: InferenceRequest, now: float) -> None:
        """Bump the abandonment counters: every abandonment lands in
        ``abandoned``; one that fired BEFORE the deadline passed (only
        possible with ``predictive_abandon``) also lands in
        ``abandoned_predictive`` — the A/B-visible split."""
        self.stats["completed"] += 1
        self.stats["abandoned"] += 1
        if now <= request.deadline_at:
            self.stats["abandoned_predictive"] += 1

    def _abandon_response(self, request: InferenceRequest,
                          token_ids: List[int],
                          prompt_tokens: int) -> InferenceResponse:
        return InferenceResponse(
            request_id=request.request_id,
            token_ids=list(token_ids),
            finish_reason="abort",
            prompt_tokens=prompt_tokens,
            completion_tokens=len(token_ids),
            error=f"deadline exceeded by {self.cfg.deadline_grace_s:.1f}s "
                  "grace and projected remaining decode cannot land",
            # machine-readable: the WORK was dropped (vs request_timeout,
            # where only the caller's wait budget elapsed and the request
            # may still be generating). Callers must not silently retry a
            # deadline-abandoned request — its deadline already passed.
            error_code="deadline_abandoned",
        )

    async def _scan_deadlines(self) -> None:
        """Abandon hopeless deadline-carrying work at the step boundary —
        queued items resolve immediately; mid-prefill admissions abort
        their staged blocks; active slots free their KV at this quiescent
        point via the same abort path cancels use. No-op (not even a
        clock read) unless ``cfg.abandon_deadlines`` is on."""
        if not self.cfg.abandon_deadlines:
            return
        loop = asyncio.get_running_loop()
        now = time.time()
        changed = False
        for item in list(self._heap):
            if item.future.done():
                continue
            req = item.request
            pre = item.preempted
            tokens_left = max(0, int(req.sampling.max_new_tokens)
                              - (len(pre.generated) if pre else 0))
            if not self._deadline_hopeless(req, tokens_left, now):
                continue
            self._heap.remove(item)
            changed = True
            item.future.set_result(self._abandon_response(
                req, list(pre.generated) if pre else [],
                pre.prompt_len if pre
                else len(req.prompt_token_ids or []),
            ))
            self._count_abandon(req, now)
        if changed:
            heapq.heapify(self._heap)
        for adm, item in list(self._ragged):
            if item.future.done() or not self._deadline_hopeless(
                    item.request,
                    int(item.request.sampling.max_new_tokens), now):
                continue
            self._ragged.remove((adm, item))
            try:
                await loop.run_in_executor(
                    self._exec, self.engine.abort_chunked, adm
                )
            except Exception:  # noqa: BLE001 — abort is best-effort
                pass
            if not item.future.done():
                item.future.set_result(self._abandon_response(
                    item.request, [],
                    len(item.request.prompt_token_ids or []),
                ))
                self._count_abandon(item.request, now)
        for slot, item in list(self._slot_items.items()):
            s = self.engine.slots[slot]
            if s is None or s.finish_reason is not None:
                continue  # the round loop resolves finished slots
            req = item.request
            tokens_left = max(
                0, int(req.sampling.max_new_tokens) - len(s.generated))
            if not self._deadline_hopeless(req, tokens_left, now):
                continue
            try:
                resp = await loop.run_in_executor(
                    self._exec, self._abort_slot, slot
                )
            except Exception:
                continue  # finished/changed under us — next pass
            self._slot_items.pop(slot, None)
            if resp is not None and not item.future.done():
                item.future.set_result(self._abandon_response(
                    req, list(resp.token_ids), resp.prompt_tokens))
                self._count_abandon(req, now)

    def _notify_observers(self, finished: bool = False) -> None:
        """Push per-round progress to streaming observers (loop thread;
        observers must only enqueue). Finished slots are excluded — their
        full token list rides the resolving response — unless ``finished``
        says that response has to wait. A snapshot carries the stamp of
        the round that just returned and the instant of this call."""
        stamp = self._ready
        for slot, item in list(self._slot_items.items()):
            if item.observer is None:
                continue
            s = self.engine.slots[slot]
            if s is None or (s.finish_reason is not None and not finished):
                continue
            snap = flight.Snapshot(s.generated, stamp, time.monotonic())
            if stamp is not None:
                item.stream.progress(len(snap), stamp, item.preempt_count)
            try:
                item.observer(snap)
            except Exception:  # noqa: BLE001 — an observer must never wedge serving
                pass

    def _stream_completed(self, item: "_QueueItem",
                          resp: InferenceResponse) -> Dict[str, Any]:
        """A streamed request resolves: its last tokens ride the response,
        so the response carries their round's stamp (``extra["egress"]``,
        for the pump) and the stream's last stretch ends here. Counts the
        stream under the round that ended its longest wait and returns
        that wait as ``batcher.completed``'s attributes."""
        stamp, w = self._ready, item.stream
        if stamp is None:
            return {}
        w.progress(len(resp.token_ids), stamp, item.preempt_count)
        resp.extra["egress"] = (stamp, time.monotonic())
        if w.round is None:
            return {}       # every token came with one round: no wait
        self.stats[f"longest_wait_{w.cause}"] += 1
        self.stats[f"longest_wait_s_{w.cause}"] += w.gap_s
        return {"longest_wait_ms": round(w.gap_s * 1e3, 3),
                "longest_wait_round": w.round,
                "longest_wait_cause": w.cause}

    def _prefill_chunk_caps(
        self, adms: List[ChunkedAdmission],
    ) -> Optional[Dict[int, int]]:
        """Per-round prefill-budget split (PR 17): the per-admission token
        caps the next ragged round may land, keyed by slot. None when the
        budget is off (``prefill_budget <= 0``) — the engine then runs its
        pre-budget behavior verbatim (every admission gets a full
        ``ragged_chunk`` slice), so budget-OFF is byte-identical to the
        pre-PR scheduler by construction. Runs on the engine thread just
        before the round (``_engine_round``), so the caps always reflect
        the admissions actually dispatched."""
        budget = int(self.cfg.prefill_budget)
        if budget <= 0 or not adms:
            return None
        eng_cfg = self.engine.cfg
        chunk_cap = min(
            max(int(getattr(eng_cfg, "ragged_chunk", budget)), 1),
            eng_cfg.prefill_buckets[-1],
        )
        # a fully-cached admission (empty ``fresh``) still needs ONE
        # budget token to ride a round and sample its first token — a
        # zero need would grant a zero cap and skip it forever
        needs = [max(1, min(len(adm.fresh), chunk_cap)) for adm in adms]
        grants = split_prefill_budget(needs, budget,
                                      start=self._prefill_rr)
        self._prefill_rr += 1
        if sum(grants) < sum(needs):
            self.stats["budgeted_rounds"] += 1
            self.stats["budget_skipped_admissions"] += sum(
                1 for g in grants if g <= 0
            )
        return {adm.slot: g for adm, g in zip(adms, grants)}

    def _choose_steps(self) -> Tuple[int, str]:
        """THE horizon rule: how many steps the next scan runs, and why.

        ``amortise`` is the level ``_retune`` keeps: the shortest scan in
        which the host's fixed cost of a round is a bounded share of the
        time. Nobody waits for a slot: the device has slack, so the scan is
        that short — a stream stalls for one short scan, an arrival waits
        for at most one. Requests wait (the heap is non-empty at a scan:
        every slot is full, or the pool is) and nothing can be admitted
        before a row ends: the device is what is short, so the scan runs one
        level longer to buy the host's share down — unless a decoding row
        can end inside it (its remaining budget, short of a stop token),
        because that is the first moment a slot can come free."""
        levels = self._levels
        amortise = levels[self._level]
        raised = levels[min(self._level + 1, len(levels) - 1)]
        if not self._heap or raised == amortise:
            return amortise, "amortise"
        budgets = self.engine.decode_budgets()
        if raised > budgets[budgets > 0].min(initial=raised):
            return amortise, "capped_by_budget"
        return raised, "raised_waiting"

    @staticmethod
    def _host_phases_s(engine_stats: Dict[str, Any]) -> float:
        return sum(engine_stats.get(k, 0.0) for k in _HOST_PHASES)

    @staticmethod
    def _host_exposed_s(engine_stats: Dict[str, Any]) -> float:
        """The engine's host phases the chip idled through: its own count
        where it keeps one, else all of them (an engine that reads every
        scan back in the call that made it)."""
        if "round_host_exposed_s" in engine_stats:
            return engine_stats["round_host_exposed_s"]
        return ContinuousBatcher._host_phases_s(engine_stats)

    def _signal_pending(self) -> bool:
        """A cancel, an interrupt or a hopeless deadline waits on a
        decoding slot: what ``_scan_signals`` / ``_scan_deadlines`` would
        take to the engine."""
        for item in self._slot_items.values():
            if (item.cancel is not None and item.cancel.is_set()) or \
                    (item.interrupt is not None and item.interrupt.is_set()):
                return True
        if self.cfg.abandon_deadlines:
            now = time.time()
            for slot, item in self._slot_items.items():
                s = self.engine.slots[slot]
                if s is not None and s.finish_reason is None and \
                        self._deadline_hopeless(
                            item.request,
                            int(item.request.sampling.max_new_tokens)
                            - len(s.generated), now):
                    return True
        return False

    def _chain_break(self) -> Optional[str]:
        """Why what was left unread must be read before the loop goes on,
        or None: the next round is another scan over the unread scan's
        rows, which goes out behind it, and the loop's work of this round
        (deliver, admit, both hops to the engine thread, the engine's build
        and upload) runs while the device does. Read from what the batcher
        holds now:

        ``round``: what is unread is a ragged round (it went out behind
        the scan its call read): always read next, whatever else waits;
        ``signal``: a cancel, interrupt or deadline wants a slot, or an
        out-of-band engine call read the scan or waits for the thread;
        ``pressure``: the pool froze a row, or resumes are held;
        ``admission``: a request waits and a slot is free: the next round
        is not a scan. Its admission pass runs beside the unread scan, and
        where that leaves a piece to run and nothing for the read to
        settle, so does the round (``_run``): no read in between;
        ``row_end_waiting``: a request waits and a row's budget ends inside
        the unread scan: a slot coming free is an admission, not a scan
        (``_choose_steps`` never runs a raised scan past that step either);
        ``idle``: no row has a step left after it.
        (``row_end``, in ``_deliver``: a row was found finished.) An
        arrival so waits for at most the scan that went out as it came."""
        if self._unread_round is not None:
            return "round"
        return self._scan_break()

    def _scan_break(self) -> Optional[str]:
        """``_chain_break`` of an unread scan; of an unread round, what
        else wants the engine beside its read (``admission``: an arrival
        may be bound beside it too)."""
        eng = self.engine
        if not eng.scan_unread or self._foreign:
            return "signal"
        if self._resume_hold or eng.pressure_pending:
            return "pressure"
        if self._signal_pending():
            return "signal"
        if self._heap:
            if eng.free_slots():
                return "admission"
            if eng.scan_ends_row():
                return "row_end_waiting"
        if not eng.decode_budgets().any():
            return "idle"
        return None

    def _round_goes_behind(self) -> bool:
        """After the pass ahead of an unread scan's read: the round that
        carries what it admitted goes out behind that scan too (the engine
        reads the scan inside that call), unless the pass stopped at
        something that needs the read (a request still waits beside a free
        slot: a resume, or a prompt the pool cannot hold before the read
        gives blocks back): then the old order."""
        return bool(self._ragged) and not (
            self._heap and self.engine.free_slots()) and bool(
            getattr(self.engine, "supports_scan_ahead", False))

    def _scan_measured(self, steps: Optional[int],
                       emitted: Dict[int, List[int]], gap_s: float,
                       call_s: float, engine_exposed_s: float,
                       cost_s: float, behind: bool, note: str
                       ) -> Optional[Tuple[int, float, float]]:
        """What ``_retune`` gets for the scan of ``steps`` steps whose tokens
        ``emitted`` a call of ``call_s`` seconds just brought back, ``gap_s``
        after the round before ended: (steps, the scan's seconds on the
        device, the host's seconds the chip idled through since the last
        scan read). None when no scan came back (a call that left its own
        unread behind nothing): its times fall to the next that does. What
        the host worked meanwhile (``cost_s``: the gap and the engine's
        phases, hidden or not) joins the scan length's cost (``_cost_ms``).

        The chip's idle time, by what was on it when the round started.
        Nothing (``behind`` false): the gap and what the engine counts of
        its own phases (``engine_exposed_s``), and the rest of the time is
        the scan's. A scan left there by the round before, still running
        when the engine came to read it: the chip had work all the time,
        so only what the engine counts (the commit of a scan nothing went
        out behind), and the time since the read before is the scan's own.
        A scan that had ended by then: the chip has idled since it ended,
        which no clock of the host's saw, so the time less what the scan's
        steps usually take; such a round says nothing new about ``s``
        (taking the rest for the scan's time would feed ``s`` its own
        error back: the figure drifts up to the host's round, and the idle
        time with it to nothing)."""
        st = self.stats
        cycle_s = gap_s + call_s
        usual = (steps or 0) * st["step_latency_ema_ms"] * 1e-3
        timed = True
        if not behind:
            exposed_s = gap_s + engine_exposed_s
        elif getattr(self.engine, "scan_read_running", True):
            exposed_s = engine_exposed_s
        else:
            exposed_s = min(cycle_s, max(engine_exposed_s, cycle_s - usual))
            timed = False
        st["round_host_exposed_s"] += exposed_s
        self._exposed_s += exposed_s
        self._cost_s += cost_s
        if steps is None:
            return None
        # a row that started the scan and emitted fewer tokens than it has
        # steps had finished inside it, or before it where it was chained
        # (a speculative step emits several: never counted negative)
        st["scan_row_steps_masked"] += sum(
            max(0, steps - len(toks)) for toks in emitted.values())
        scan_s = max(cycle_s - exposed_s, 0.0) if timed else usual
        if 0.0 < usual * _STALL_FACTOR < scan_s:
            st["scans_stalled"] += 1
            st["scan_stall_s"] += scan_s - usual
            log.warning(
                "round %d: a %d-step scan of %d rows took %.3f s where "
                "%.3f is usual: %s", self._round, steps, len(emitted),
                scan_s, usual, note)
        host_s, self._exposed_s = self._exposed_s, 0.0
        self._cost_ms[steps] = _mean(
            self._cost_ms.get(steps, 0.0), self._cost_s * 1e3)
        self._cost_s = 0.0
        return steps, scan_s, host_s

    def _engine_round(self) -> Optional[Tuple[int, float, float]]:
        """One engine round on the worker thread. Returns what a scan
        measured for ``_retune`` (``_scan_measured``: the scan whose tokens
        came back, which is the one before the dispatch of this round where
        the engine leaves dispatches unread) and None after a ragged round
        that read itself, which is as long as the prompt tokens it admits,
        whatever the level, and so says nothing about how long a scan
        should be. (The
        gap *before* a scan, not the one after: a row that ends in a scan is followed by
        its slot's next admission, which is no cost of a round; that gap
        falls to the ragged round it precedes. On the v5e, charged to the
        scan, it carried two cells' level to T=16: PERF.md, PR 26.)

        Admissions in flight dispatch ONE ``engine.ragged_round``: every
        active decode slot advances one token and every admission advances
        one prefill chunk in the same invocation — build-ragged-batch →
        dispatch → commit, no competing prefill dispatch. Where a scan is
        unread the engine puts the round BEHIND it and the call brings back
        that scan's tokens, not the round's (``engine.round_unread``): the
        stamp made here is then the scan's, ``_scan_measured`` gets it as a
        chained scan's, and the round's stamp waits in ``_unread_round``
        for its read (``_collect_round``), which is what the loop does
        next. With no
        admission in flight a ragged round degenerates to pure decode, so
        the multi-step scan (horizon amortization of the host RTT) is the
        better dispatch for the identical math and runs instead — left
        unread on the device wherever the engine can do that
        (``supports_scan_ahead``), for the next round to go out behind it:
        whether it does is decided when the next round is known
        (``_chain_break``), from what the loop holds then."""
        t0 = time.perf_counter()
        st = self.stats
        gap = 0.0
        if self._round_end is not None:
            gap = t0 - self._round_end
            st["between_rounds_s"] += gap
            st["between_rounds"] += 1
        engine_stats = getattr(self.engine, "stats", None) or {}
        n = self._round = int(engine_stats.get("rounds", self._round)) + 1
        ragged = bool(self._ragged)
        can = not ragged and bool(
            getattr(self.engine, "supports_scan_ahead", False))
        ahead = can and not self._foreign
        steps, reason = (1, "ragged") if ragged else self._choose_steps()
        # the scan this round's goes out behind; whatever the engine does
        # with the call, it reads that one
        behind, self._unread_steps = self._unread_steps, None
        # what the stamp says of the tokens this call brings back: its own
        # round's, or those of the scan it went out behind
        kind, read, pieces, cause = "ragged" if ragged else "scan", steps, \
            0, "scan"
        if behind is not None and not (
                self.engine.scan_unread and (ahead or ragged)):
            # an out-of-band engine call read it since the loop looked, or
            # waits for the thread: this round's call reads it first
            st["chain_breaks_signal"] += 1
            behind = None
        try:
            with flight.span("dgi.batcher.round", st,
                             None if ragged else f"scan_s_t{steps}",
                             round=n, kind=kind,
                             steps=steps, level=self._levels[self._level],
                             reason=reason, queue_depth=len(self._heap),
                             chained=int(behind is not None
                                         and not self._foreign)) as sp:
                exposed = -self._host_exposed_s(engine_stats)
                cost = gap - self._host_phases_s(engine_stats)
                wait = -engine_stats.get("round_readback_s", 0.0)
                if ragged:
                    adms = [adm for adm, _ in self._ragged]
                    caps = self._prefill_chunk_caps(adms)
                    pieces = len(adms) if caps is None else sum(
                        caps.get(adm.slot, 0) > 0 for adm in adms)
                    cause = "ragged_2plus" if pieces > 1 else "ragged_1"
                    if behind is not None and self._foreign:
                        # an out-of-band call waits for the thread: nothing
                        # is left unread in front of it
                        self.engine.collect_scan()
                    emitted = self.engine.ragged_round(adms, caps)
                    st["ragged_rounds"] += 1
                    if behind is None or not getattr(
                            self.engine, "round_unread", False):
                        # (the engine read the unread scan first: no room
                        # to reserve behind it, or a caller waited)
                        st["chain_breaks_admission"] += behind is not None
                        sp.set(chained=0)
                        return None
                    # the round went out behind the scan and is unread:
                    # what came back, and is stamped, is the scan
                    st["ragged_rounds_chained"] += 1
                    self._unread_round = (n, pieces, cause)
                    back, came = behind, self._unread_reason
                    kind, read, pieces, cause = "scan", behind, 0, "scan"
                else:
                    st[f"scans_t{steps}"] = st.get(f"scans_t{steps}", 0) + 1
                    st[f"scans_{reason}"] += 1
                    st["scans_chained"] += behind is not None
                    # a scan read by the call that made it although the
                    # engine could leave it: an out-of-band call waits for
                    # the thread
                    st["chain_breaks_signal"] += can and not ahead
                    if ahead:
                        emitted, back = self.engine.decode_multi(
                            steps, ahead=True), behind
                        read, came = back or 0, self._unread_reason
                        if self.engine.scan_unread:
                            self._unread_steps = steps
                            self._unread_reason = reason
                    else:
                        emitted, back = self.engine.decode_multi(steps), steps
                        came = reason
                if came == "raised_waiting":
                    cause = "scan_raised"
                exposed += self._host_exposed_s(engine_stats)
                cost += self._host_phases_s(engine_stats)
                wait += engine_stats.get("round_readback_s", 0.0)
            call = time.perf_counter() - t0
            return self._scan_measured(
                back, emitted, gap, call, exposed, cost, behind is not None,
                f"the call {call:.3f} s, its wait for the device "
                f"{wait:.3f} s, the gap before it {gap:.3f} s")
        finally:
            self._round_end = time.perf_counter()
            self._ready = flight.RoundStamp(
                time.monotonic(), n, kind, read, pieces, cause)

    def _collect_round(self, why: str
                       ) -> Optional[Tuple[int, float, float]]:
        """Read what is unread back on the worker thread, because the next
        round does not go out behind it (``why``: ``_chain_break``), and
        return what a scan measured for ``_retune``. A round left unread
        (``round``) is stamped here as the ragged round it is, with its
        pieces and cause, so that a stream's wait across it names it; its
        time says nothing about how long a scan should be."""
        t0 = time.perf_counter()
        st = self.stats
        gap = 0.0
        if self._round_end is not None:
            # the loop's time before this read falls to the round that is
            # dispatched next: one gap counted, both halves in its seconds
            gap = t0 - self._round_end
            st["between_rounds_s"] += gap
        st[f"chain_breaks_{why}"] += 1
        engine_stats = getattr(self.engine, "stats", None) or {}
        steps, self._unread_steps = self._unread_steps, None
        rnd, self._unread_round = self._unread_round, None
        try:
            if not self.engine.scan_unread:
                return None     # the engine read it for another call
            with flight.span("dgi.batcher.round", round=self._round,
                             kind="collect", steps=steps or 1, reason=why,
                             queue_depth=len(self._heap), chained=0):
                exposed = -self._host_exposed_s(engine_stats)
                cost = gap - self._host_phases_s(engine_stats)
                emitted = self.engine.collect_scan()
                exposed += self._host_exposed_s(engine_stats)
                cost += self._host_phases_s(engine_stats)
            if rnd is not None:
                return None
            call = time.perf_counter() - t0
            return self._scan_measured(
                steps, emitted, gap, call, exposed, cost, True,
                f"read back {call:.3f} s after a gap of {gap:.3f} s")
        finally:
            self._round_end = time.perf_counter()
            self._ready = flight.RoundStamp(
                time.monotonic(), self._round, "collect", steps or 0, 0,
                "other") if rnd is None else flight.RoundStamp(
                time.monotonic(), rnd[0], "ragged", 1, rnd[1], rnd[2])

    def _retune(self, steps: int, scan_s: float, host_s: float) -> None:
        """Keep the rule's measured times — ``s``, a scan's time per step,
        and ``h``, what a round costs the host whatever it holds — and the
        level they amortise at: the smallest T with
        T · s ≥ ``_HOST_AMORTISE`` · h, changed only when the inequality
        holds or fails with ``_LEVEL_SLACK`` to spare. Levels only, so the
        set of compiled decode graphs stays bounded; a fixed horizon
        (``adaptive: false``) has one level and keeps it.

        ``h`` is the host's time the CHIP IDLED THROUGH (``host_s``): the
        rule buys the host's share down with longer scans because that time
        is the chip's, so what the host does while a scan runs (scans
        dispatched behind an unread one) is no cost of a round to it, and a
        round whose host work outlasts its scan costs the excess. What a
        round costs the host, hidden or not, is kept beside it
        (``_scan_measured``); where every scan is read by the call that
        made it the two are one number.

        ``h`` is kept per scan length, and a level is judged by its own:
        the host's time around a longer scan also holds work that grows
        with its steps (block reservation, streaming its tokens), which no
        longer scan buys down — judged by a raised scan's cost the level
        would call for a longer scan still, and stay there. The level below
        is judged by its own ``h`` or by the most it can idle the chip
        going by the current level's times, whichever is less: a shorter
        scan costs the host no more, so it idles the chip for what the
        current level does plus, at the most, the part of the current
        round's cost that the shorter scan would not cover (nothing, where
        every scan is read by its own call or the host keeps up: then that
        is the current level's ``h``, and what is measured now corrects a
        figure from a busier or emptier moment) — and without
        the slack while it has never run: one visit measures it. Until the
        current level has run, the level stays. (Nothing refreshes the cost
        of a level the batcher has left upward, so a level that failed in a
        disturbed moment stays failed while the current one costs more:
        letting that figure fade was tried on the v5e and cost the decode
        cell 3 % in visits to T=1; PERF.md section 7.)"""
        st = self.stats
        st["step_latency_ema_ms"] = s = _mean(
            st["step_latency_ema_ms"], scan_s * 1e3 / steps)
        self._host_ms[steps] = _mean(
            self._host_ms.get(steps, 0.0), host_s * 1e3)
        levels, level, h = self._levels, self._level, self._host_ms.get
        inf = float("inf")
        while level + 1 < len(levels) and levels[level] * s < \
                _HOST_AMORTISE * h(levels[level], 0.0) * (1.0 - _LEVEL_SLACK):
            level += 1
        while level > 0 and levels[level - 1] * s >= _HOST_AMORTISE * min(
                h(levels[level - 1], inf),
                h(levels[level], inf) + max(0.0, self._cost_ms.get(
                    levels[level], 0.0) - levels[level - 1] * s)
        ) * (1.0 + _LEVEL_SLACK * (levels[level - 1] in self._host_ms)):
            level -= 1
        self._level = level
        self._horizon = float(levels[level])
        st["horizon"] = self._horizon
        st["round_host_ema_ms"] = h(levels[level], 0.0)

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        latch_until = 0.0
        while True:
            behind_scan = False
            if self._unread_steps is not None \
                    or self._unread_round is not None:
                # a scan is unread on the device. While the next round is
                # another scan over its rows the loop goes straight on to
                # dispatch it behind that one (nothing below touches the
                # engine then); if not, the scan is read and delivered
                # first and the loop is the one it always was. (A round
                # left unread is read here, always.)
                why = self._chain_break()
                if why == "admission" or (
                        why == "round" and self._scan_break() == "admission"):
                    # a request waits, a slot is free and nothing else wants
                    # the engine: the pass that would follow the read runs
                    # now, while that dispatch still runs on the device
                    await self._admission_pass(ahead=True)
                    behind_scan = why == "admission" \
                        and self._round_goes_behind()
                if why is not None and not behind_scan:
                    try:
                        await self._deliver(await loop.run_in_executor(
                            self._exec, self._collect_round, why))
                    except asyncio.CancelledError:
                        raise
                    except Exception as e:
                        await self._fail_in_flight(e)
            if not behind_scan:
                # idle = no batcher-OWNED work. Deliberately not
                # engine.num_active: a foreign slot (PD sequence
                # retained/adopted between stages, awaiting its decode job)
                # must neither keep this loop spinning nor be
                # decoded/finished behind its owner's back — it joins the
                # batch only through adopt_slot().
                if not self._heap and not self._slot_items \
                        and not self._ragged:
                    self._wake.clear()
                    if self._stopping:
                        return
                    self._round_end = None  # parked: the next gap is idle
                    await self._wake.wait()
                    # admission latch: give co-arriving requests a window
                    # to form a batch (reference max_wait trigger :177-199)
                    latch_until = time.time() + self.cfg.max_wait_ms / 1000.0
                while time.time() < latch_until and \
                        len(self._heap) < len(self.engine.slots):
                    await asyncio.sleep(0.001)
                await self._admission_pass()
                if not self._slot_items and not self._ragged:
                    # no batcher-owned slot decodes: no frozen slot of OURS
                    # is waiting on freed blocks, so resumes may flow
                    # immediately (foreign slots are left untouched for
                    # their owner)
                    self._resume_hold = False
                    if self._heap:
                        # deferred (pressured) work with an idle engine:
                        # yield briefly instead of hot-spinning the
                        # admission loop
                        await asyncio.sleep(0.001)
                    continue
            try:
                measured = await loop.run_in_executor(
                    self._exec, self._engine_round
                )
                self.stats["decode_rounds"] += 1
                self.stats["occupancy_sum"] += self.engine.num_active
                await self._deliver(measured)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                await self._fail_in_flight(e)

    async def _admission_pass(self, ahead: bool = False) -> None:
        """The loop's work at a round's boundary before the next round goes
        out. ``ahead``: ``_chain_break`` said ``admission`` of an unread
        scan, and the pass runs before that scan's read instead of after it
        — the same pass in the same order, and the one after the read stays
        for what this one left (``_admit``); so of an unread round, before
        whose read an arrival is bound the same way. Its seconds are no
        cost of the scan it ran beside (``_engine_round``), whose read
        counts them in its gap: they are taken out here (a round's read
        counts none)."""
        t0 = time.perf_counter()
        engine_stats = getattr(self.engine, "stats", None) or {}
        cut = engine_stats.get("prefix_hit_tokens_cut_by_window", 0)
        with flight.span("dgi.batcher.admit", self.stats, "admit_s",
                         queue_depth=len(self._heap),
                         ahead=int(ahead)) as admitted:
            # cancel/interrupt events land at this quiescent boundary:
            # aborted requests release their slots BEFORE admission so
            # the freed capacity admits waiting work this very pass
            await self._scan_signals()
            # hopeless deadline work drops at the same boundary, so its
            # freed blocks admit waiting on-time work this very pass
            await self._scan_deadlines()
            await self._admit(ahead)
            now = engine_stats.get("prefix_hit_tokens_cut_by_window", 0)
            if now != cut:
                # pages per layer kind: tokens of a prefix hit the window
                # kind could not back, prefilled again
                admitted.set(window_cut_tokens=now - cut)
            # admission-sourced KV pressure: deferred requests wait, or
            # a higher-priority arrival preempts the lowest-priority
            # victim
            await self._check_pressure()
        if ahead and self._unread_round is None:
            self._cost_s -= time.perf_counter() - t0

    async def _deliver(self, measured: Optional[Tuple[int, float, float]]
                       ) -> None:
        """What the loop does with what a round brought back: steer the
        scan level, move admissions that sampled their first token into
        the batch, resolve finished slots, stream the others' tokens."""
        loop = asyncio.get_running_loop()
        with flight.span("dgi.batcher.deliver", self.stats,
                         "deliver_s") as delivered:
            finished = 0
            # only what a scan measured steers the scan level
            if measured:
                self._retune(*measured)
            if (self._unread_steps is not None
                    or self._unread_round is not None) and (
                    self.engine.pressure_pending or any(
                        s is not None and s.finish_reason is not None
                        and i in self._slot_items
                        for i, s in enumerate(self.engine.slots))):
                # finishing a slot and preempting one take the engine,
                # which reads what is unread first: read it here, where
                # its tokens are counted, its stamp is made and its times
                # steer the level — after the streams have what this round
                # brought (the finished rows' too: their response waits for
                # the read). What follows is then that read's.
                self._notify_observers(finished=True)
                more = await loop.run_in_executor(
                    self._exec, self._collect_round,
                    "round" if self._unread_round is not None
                    else "pressure" if self.engine.pressure_pending
                    else "row_end")
                if more:
                    self._retune(*more)
            # admission-chunk rounds on the timeline: one bounded
            # note per in-flight traced admission per round
            # (saturates at the per-request event cap on
            # pathological prompts); a scan read back behind an admission
            # that ran ahead of it carried no chunk
            if self._ready.kind == "ragged":
                for adm, item in self._ragged:
                    if item.flight is not None:
                        self._note(item, "batcher.chunk_round",
                                   off=adm.off, round=self._round)
            # ragged admissions whose final chunk sampled its first
            # token this round join the batch (the finished-slot
            # sweep below then resolves any that immediately hit
            # stop/length)
            for adm, item in [p for p in self._ragged if p[0].done]:
                self._ragged.remove((adm, item))
                self._slot_items[adm.slot] = item
                self._admit_stamp[adm.slot] = next(self._stamp)
                self.stats["admitted"] += 1
                self._note_first_token(item, adm.slot,
                                       round=self._round)
            for i, s in enumerate(list(self.engine.slots)):
                if s is not None and s.finish_reason is not None \
                        and i in self._slot_items:
                    # OWNED slots only: a foreign sequence that
                    # finished while sharing our rounds (PD
                    # retained/awaiting adoption) keeps its slot
                    # until its owner collects it — finishing it
                    # here would discard the response
                    resp = await loop.run_in_executor(
                        self._exec, self.engine.finish_slot, i
                    )
                    item = self._slot_items.pop(i, None)
                    finished += 1
                    if item and not item.future.done():
                        waited = self._stream_completed(item, resp) \
                            if item.stream is not None else {}
                        self._note(item, "batcher.completed",
                                   finish_reason=resp.finish_reason,
                                   tokens=resp.completion_tokens, **waited)
                        item.future.set_result(resp)
                        self.stats["completed"] += 1
            # streaming observers see each surviving slot's
            # monotonic token list once per round (finished slots
            # resolved above)
            self._notify_observers()
            # decode-sourced KV pressure: slots froze this round —
            # preempt the policy victim so the next round progresses
            # (completions above may already have freed blocks; the
            # check skips if every frozen slot resolved). An
            # unpressured round releases the resume hold.
            await self._check_pressure(after_round=True)
            delivered.set(finished=finished)

    async def _fail_in_flight(self, e: Exception) -> None:
        """A failed round must not wedge the batcher: fail every in-flight
        request, abort its slot, keep serving the queue."""
        loop = asyncio.get_running_loop()
        self.stats["engine_errors"] = self.stats.get("engine_errors", 0) + 1
        # whatever was unread went with the engine's device state
        self._unread_steps = self._unread_round = None
        # mid-prefill admissions aren't in _slot_items yet —
        # release their slots and resolve their futures here or
        # the callers hang until timeout
        for adm, rag_item in list(self._ragged):
            try:
                await loop.run_in_executor(
                    self._exec, self.engine.abort_chunked, adm
                )
            except Exception:
                pass
            if not rag_item.future.done():
                rag_item.future.set_result(
                    InferenceResponse(
                        request_id=rag_item.request.request_id,
                        error=f"engine error: {e}",
                    )
                )
                self.stats["completed"] += 1
        self._ragged.clear()
        for i in list(self._slot_items):
            # fail OWNED slots only — a foreign slot's owner handles
            # its own engine-error cleanup (PD decode already does)
            if self.engine.slots[i] is not None:
                try:
                    await loop.run_in_executor(
                        self._exec,
                        lambda i=i: self.engine.finish_slot(
                            i, cache=False),
                    )
                except Exception:
                    pass
            item = self._slot_items.pop(i, None)
            if item and not item.future.done():
                item.future.set_result(
                    InferenceResponse(
                        request_id=item.request.request_id,
                        error=f"engine error: {e}",
                    )
                )
                self.stats["completed"] += 1

    def get_stats(self) -> Dict[str, Any]:
        out = dict(self.stats)
        out["queue_depth"] = len(self._heap)
        out["active_slots"] = self.engine.num_active
        out["ragged_in_flight"] = len(self._ragged)
        if getattr(self.engine.cfg, "speculative", None) is not None:
            # engine-integrated speculation: every decode round commits
            # 1..K+1 tokens per slot, so these are THE serving-efficiency
            # numbers for this batcher (accept-rate, weight-stream
            # amortization factor)
            es = self.engine.get_stats()
            out["spec_integrated"] = {
                "accept_rate": es.get("spec_accept_rate", 0.0),
                "tokens_per_step": es.get("spec_tokens_per_step", 0.0),
                "steps": es.get("spec_steps", 0),
                "accepted": es.get("spec_accepted", 0),
                "drafted": es.get("spec_drafted", 0),
            }
        if out["decode_rounds"]:
            out["avg_occupancy"] = out["occupancy_sum"] / out["decode_rounds"]
        return out


class BatcherServing:
    """Thread-hosted serving front-end over a :class:`ContinuousBatcher`.

    The batcher is asyncio-native; the worker's callers are plain threads
    (the poll loop, the direct server's handlers, PD stages, tests). This
    wrapper owns a dedicated event loop thread running ONE batcher and
    exposes a thread-safe surface:

    - :meth:`submit` — blocking submit from any thread (the batcher's
      serving hooks — observer / cancel / interrupt / resume_from — pass
      through), raising :class:`RequestMigrated` on drain.
    - :meth:`adopt_slot` — drive an externally-admitted engine slot (PD
      decode) inside the shared decode rounds.
    - :meth:`run_exclusive` — run an engine-touching callable on the
      batcher's single engine-executor thread, serialized with decode
      rounds (PD prefill / KV-handoff adoption compose with live serving
      without a second lock hierarchy).
    - :meth:`reconfigure` — apply server-pushed SLO knobs between rounds.
    """

    def __init__(self, engine: TPUEngine,
                 cfg: Optional[BatcherConfig] = None) -> None:
        self.engine = engine
        self._cfg = cfg
        self.batcher: Optional[ContinuousBatcher] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._ready = threading.Event()
        self._stopped = False
        self._boot_error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="batcher-serving", daemon=True
        )
        self._thread.start()
        if not self._ready.wait(timeout=10.0):
            raise RuntimeError("batcher serving loop failed to start")
        if self._boot_error is not None:
            raise RuntimeError(
                f"batcher serving loop failed: {self._boot_error}"
            )

    def _run(self) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop

        async def boot() -> None:
            try:
                self.batcher = ContinuousBatcher(self.engine, self._cfg)
                self.batcher.start()
            except BaseException as exc:  # noqa: BLE001 — surfaced to ctor
                self._boot_error = exc
            finally:
                self._ready.set()

        loop.run_until_complete(boot())
        if self._boot_error is None:
            loop.run_forever()
        loop.close()

    # -- thread-safe surface -------------------------------------------------

    def submit_async(self, request: InferenceRequest,
                     timeout_s: Optional[float] = None,
                     **hooks: Any) -> "_Future[InferenceResponse]":
        assert self.batcher is not None and self._loop is not None
        if self._stopped or not self._thread.is_alive():
            # a coroutine scheduled on a dead loop never runs and its
            # future never resolves — fail fast instead of hanging callers.
            # NOT loop.is_running(): that is False in the window between
            # boot() completing and run_forever() starting, and a coroutine
            # scheduled in that window runs fine once the loop spins up.
            raise RuntimeError("batcher serving is stopped")
        return asyncio.run_coroutine_threadsafe(
            self.batcher.submit(request, timeout_s, **hooks), self._loop
        )

    def submit(self, request: InferenceRequest,
               timeout_s: Optional[float] = None,
               **hooks: Any) -> InferenceResponse:
        return self.submit_async(request, timeout_s, **hooks).result()

    def adopt_slot(self, slot: int,
                   request: Optional[InferenceRequest] = None,
                   flight: Optional[Any] = None) -> InferenceResponse:
        assert self.batcher is not None and self._loop is not None
        return asyncio.run_coroutine_threadsafe(
            self.batcher.adopt_slot(slot, request, flight=flight),
            self._loop
        ).result()

    def run_exclusive(self, fn: Callable[..., Any], *args: Any,
                      **kw: Any) -> Any:
        """Run ``fn`` on the batcher's engine-executor thread. Every engine
        call the batcher makes runs on that SAME single thread, so this is
        the serialization point for out-of-band engine work (PD prefill,
        handoff adoption): no lock ordering, no mid-round interleaving —
        the work simply runs between rounds, on host mirrors that are
        current: a scan the loop left unread is read first (the loop counts
        it as a chain broken by a ``signal``), and while a caller waits
        here the loop leaves none (``ContinuousBatcher._foreign``). The read
        is an item of its own in front of ``fn``, not a wrapper around it:
        on the v5e the warm-up's lowering of the round graphs
        (``lower_serving_graphs``, 24 s of tracing) took 2 s longer inside
        one more Python frame (PERF.md, PR 32)."""
        b = self.batcher
        assert b is not None
        with b._foreign_lock:
            b._foreign += 1
        try:
            if hasattr(b.engine, "collect_scan"):
                b._exec.submit(b.engine.collect_scan)
            return b._exec.submit(fn, *args, **kw).result()
        finally:
            with b._foreign_lock:
                b._foreign -= 1

    def reconfigure(self, **updates: Any) -> None:
        """Thread-safe config push: applied on the loop thread between
        iterations (the batcher reads its cfg only at loop boundaries)."""
        if self._loop is None or self.batcher is None:
            return

        def _apply() -> None:
            try:
                self.batcher.reconfigure(**updates)
            except Exception:  # noqa: BLE001 — an operator push must not
                # die in the event loop's default handler unseen
                log.exception("serving config push rejected: %r", updates)

        self._loop.call_soon_threadsafe(_apply)

    def get_stats(self) -> Dict[str, Any]:
        return self.batcher.get_stats() if self.batcher is not None else {}

    @property
    def active(self) -> bool:
        # explicit lifecycle flag, NOT loop.is_running(): the latter is
        # False between boot() and run_forever(), and a request arriving
        # in that window would silently fall through to the legacy
        # engine-lock path while the batcher thread comes up — two
        # drivers on one engine
        return (
            self.batcher is not None
            and self._loop is not None
            and not self._stopped
            and self._thread.is_alive()
        )

    def stop(self, drain: bool = True, timeout_s: float = 30.0) -> None:
        if self._loop is None or self.batcher is None or self._stopped:
            return
        self._stopped = True   # reject new submits before draining old ones
        try:
            asyncio.run_coroutine_threadsafe(
                self.batcher.stop(drain=drain), self._loop
            ).result(timeout=timeout_s)
        except Exception:  # noqa: BLE001 — drain stuck/timed out
            # the loop must NOT die with futures still pending (every
            # thread blocked in submit().result() would hang forever):
            # force a non-drain stop, which resolves all outstanding
            # futures with "batcher stopped" before the loop goes down
            try:
                asyncio.run_coroutine_threadsafe(
                    self.batcher.stop(drain=False), self._loop
                ).result(timeout=5.0)
            except Exception:  # noqa: BLE001 — loop wedged: give up
                pass
        try:
            self._loop.call_soon_threadsafe(self._loop.stop)
        except RuntimeError:   # loop already closed (boot failed earlier)
            pass
        self._thread.join(timeout=5.0)
