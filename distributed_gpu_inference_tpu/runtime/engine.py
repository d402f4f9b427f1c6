"""Single-chip (and later mesh-sharded) serving engine: jitted prefill +
decode over paged KV, slot-based batch state, on-device sampling.

TPU-native replacement for the reference's engine layer (``worker/engines/
llm.py`` HF generate, ``llm_vllm.py`` vLLM wrapper): instead of wrapping a
serving framework, the engine owns

- device KV pools (``models.llama.init_kv_pools``) mutated in-place via
  donated jitted calls,
- a :class:`PagedKVCacheManager` for block accounting / prefix reuse / CoW,
- fixed-shape **slot** state (block tables, lengths, sampling params) so one
  compiled decode graph serves any mix of active requests — the static-shape
  answer to the reference's dynamic Python batches (SURVEY §7 "hard parts"),
- two decode drivers: per-step (host samples stop conditions every token —
  feeds the continuous batcher) and **multi-step** (``lax.scan`` of T decode
  steps with on-device stop masking — amortizes host round-trips; no
  reference analogue, TPU-first).

Prompt lengths are bucketed to powers of two so prefill compiles once per
bucket; decode compiles once per engine.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import json
import logging
import time
import uuid
import zlib
from dataclasses import dataclass, field, replace
from typing import (
    Any, Dict, Iterator, List, Optional, Sequence, Tuple, Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from distributed_gpu_inference_tpu.models.configs import ModelConfig, get_model_config
from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.ops.quantization import quantize_params
from distributed_gpu_inference_tpu.ops.sampling import (
    sample_tokens_per_slot,
)
from distributed_gpu_inference_tpu.runtime import flight
from distributed_gpu_inference_tpu.runtime.kv_cache import (
    HostKVStore,
    OutOfBlocksError,
    PagedKVCacheManager,
    PendingDeviceOps,
)
from distributed_gpu_inference_tpu.runtime.speculative import (
    SpecDecodeConfig,
    draft_apply,
    init_draft_params,
)
from distributed_gpu_inference_tpu.utils.device import (
    compile_log, roomy_stack,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    InferenceResponse,
    SamplingParams,
)

log = logging.getLogger(__name__)

MAX_STOP_IDS = 4
_COPY_BUCKETS = (1, 2, 4, 8, 16, 32)
# core pack layout (int32 columns): last_token, kv_len, slot_key x2,
# stop_ids x MAX_STOP_IDS, top_k
_CORE_I_COLS = 5 + MAX_STOP_IDS
_BIG_BUDGET = 1 << 30
# quantized loads: full-precision trees up to this size init on-device
# (fast) before consume-quantization; larger ones stream/build so they
# never stage full-size in HBM. 8 GB of a 16 GB chip: the transient peak
# is the full-precision tree plus one leaf, and the KV pools and the first
# prefill's workspace allocate right behind it. The value is kept from an
# earlier chip set-up; not measured on the current chip.
_QUANT_DEVICE_BUILD_LIMIT = 8 * 1024**3


def _index_work(before: int, n: int, topk: int) -> Tuple[int, int, int]:
    """``n`` consecutive queries of a row that holds ``before`` tokens ahead
    of them: query ``j`` (1-based) sees ``before + j`` tokens and its
    selection keeps at most ``topk`` of them (ties beyond that are not
    counted) → (pairs seen, pairs kept, queries that see at most ``topk``
    and so select nothing)."""
    dense = min(max(topk - before, 0), n)
    return (n * before + n * (n + 1) // 2,
            dense * before + dense * (dense + 1) // 2 + (n - dense) * topk,
            dense)


# the routed expert layers' counters, in the order a round returns them:
# layer calls that held a live token, live (token, expert) pairs, rows the
# grouped matmul ran (tile padding included), experts with at least one row
# summed over layer calls, layer calls that took the step form (a scan
# step's rows as one resident tile: ops/moe_gmm_pallas.py)
# (the keys of ops/moe_gmm_pallas.expert_stats, spelled here so that a dense
# model's engine does not import that module and Pallas with it at start)
_MOE_COUNTERS = ("layer_calls", "assignments", "rows_dispatched",
                 "active_experts", "step_form_calls")


# one more where the chip holds a share of the experts: every (token, expert)
# pair the router kept, on held experts or not (models/mla.py _experts)
_MOE_SHARE_COUNTERS = _MOE_COUNTERS + ("pairs_routed",)


def _window_pairs(before: int, n: int, window: int) -> int:
    """``sum(min(before + j, window) for j in 1..n)``: the keys ``n``
    consecutive queries see in a sliding layer, the first of them with
    ``before`` tokens cached."""
    rising = max(min(n, window - before), 0)    # queries still under it
    return rising * before + rising * (rising + 1) // 2 \
        + (n - rising) * window


def _moe_vector(out: Any) -> Any:
    """A ``ChunkOutput``'s ``moe`` as one int32 vector, so that a round
    brings its counters back in one transfer; after them, where a scan step
    of a model with an indexer has one, its ``index_fetched``."""
    moe = out.moe
    names = _MOE_SHARE_COUNTERS if "pairs_routed" in moe else _MOE_COUNTERS
    held = [moe[name] for name in names]
    if out.index_fetched is not None:
        held.append(out.index_fetched)
    return jnp.stack(held).astype(jnp.int32)


def _resolve_kv_dtype(kv_cache_dtype: Optional[str], activation_dtype) -> Any:
    """KV pool storage dtype. ``fp8`` = float8_e4m3 (scale-free: post-RoPE
    K and V magnitudes sit well inside e4m3's ±448 range, the same rationale
    as vLLM's unscaled fp8 KV default)."""
    if kv_cache_dtype is None:
        return jnp.dtype(activation_dtype)
    alias = {
        "fp8": jnp.float8_e4m3fn,
        "float8_e4m3fn": jnp.float8_e4m3fn,
        "bf16": jnp.bfloat16,
        "bfloat16": jnp.bfloat16,
        # int8 pools carry per-(page, token) scale pools alongside (on
        # v5e int8→bf16 converts are HW-native, fp8's are emulated; which
        # mode is faster there: not measured on the current chip)
        "int8": jnp.int8,
    }
    if kv_cache_dtype not in alias:
        raise ValueError(
            f"unknown kv_cache_dtype {kv_cache_dtype!r}; use {sorted(alias)}"
        )
    return jnp.dtype(alias[kv_cache_dtype])


@dataclass
class EngineConfig:
    max_batch_size: int = 8
    max_seq_len: int = 2048
    block_size: int = 16
    num_blocks: Optional[int] = None      # default: 1.5x worst-case + pad block
    prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256, 512, 1024, 2048)
    enable_prefix_cache: bool = True
    multi_step: int = 16                  # scan horizon for decode_multi
    dtype: str = "bfloat16"
    # weight-only quantization (ops/quantization.py): int8 | fp8 | None —
    # first-party TPU replacement for the reference's vLLM passthrough flags
    # (worker/engines/llm_vllm.py:83-87 AWQ/GPTQ/FP8/INT8)
    quantization: Optional[str] = None
    # KV-cache storage dtype: None = activation dtype; "fp8" stores pools as
    # float8_e4m3 — half the decode KV read bytes AND double the page
    # capacity (decode streams the whole live context every step, so at
    # serving batch sizes KV reads rival the weight stream; the TPU
    # counterpart of vLLM's --kv-cache-dtype fp8 the reference passes
    # through). Dequant to bf16 happens in VMEM inside the Pallas decode
    # kernel / at the XLA gather.
    kv_cache_dtype: Optional[str] = None
    # spill tiers (reference HBM→CPU→Redis chain): 0 disables the host tier
    spill_host_blocks: int = 0
    spill_remote_store: Optional[Any] = None   # RemoteKVStore-like (L3)
    # persist the quantized weight tree to this dir after first build (orbax),
    # so later cold starts skip quantization entirely — VERDICT r2 #1's
    # startup fix for serving near-HBM-capacity models (8B int8 on 16 GB)
    quant_cache_dir: Optional[str] = None
    # long-context prefill strategy on a mesh with a ``seq`` axis: a fresh
    # prompt longer than the largest prefill bucket runs ONE seq-sharded
    # pass (ring or ulysses attention over the seq axis,
    # parallel/ring_attention.py) instead of single-chip chunking; KV pages
    # land in the same paged pools decode reads (SURVEY §5.7)
    seq_parallel_impl: str = "ring"   # ring | ulysses
    # storage-side sequence parallelism: shard the KV pools' BLOCK axis
    # over ``seq`` so per-device pool memory scales 1/seq (servable context
    # scales with the mesh). Decode reads route through the shard_map
    # partial-softmax op (pages never move). Composes with the prefix
    # cache and chunked/continuation admission since round 4: chunks with
    # prior context read it through the sharded-pool CHUNK op; fresh first
    # chunks keep the cheaper dense path. Sliding-window models fenced.
    kv_seq_sharded: bool = False
    # engine-integrated speculative decoding: chain drafts (EAGLE-style
    # head) amortize the per-step weight stream over several accepted
    # tokens per slot. decode_multi then runs fused draft→verify→accept
    # steps; each slot commits 1..K+1 tokens per step and slots join/leave
    # mid-flight exactly as in plain continuous batching. Greedy outputs
    # are byte-identical to the non-speculative engine (the verify pass is
    # the target's own argmax); sampled slots ride the same graph at one
    # token per step. Single-chip only (no mesh).
    speculative: Optional[SpecDecodeConfig] = None
    # RAGGED rounds (round 6): max prefill-chunk width co-dispatched with
    # decode rows in one ragged_round() invocation: the most prompt tokens
    # one admission adds to a round, so the longest a co-scheduled stream
    # waits for its next token. Clamped to the largest prefill bucket. The
    # plain round runs its dense work over the live tokens packed on one
    # axis (``TPUEngine._ragged_ladder``: five lengths, one graph each);
    # the [B, S] rectangle attention sees buckets through prefill_buckets.
    ragged_chunk: int = 256

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    def resolved_num_blocks(self) -> int:
        if self.num_blocks is not None:
            return self.num_blocks
        worst = self.max_batch_size * self.max_blocks_per_seq
        return int(worst * 1.5) + 1  # +1: reserved pad block 0


@dataclass
class _Slot:
    request: InferenceRequest
    seq_id: str
    prompt_len: int
    generated: List[int] = field(default_factory=list)
    cached_tokens: int = 0
    # TTFT clock origin: the REQUEST's arrival time, not slot-bind time —
    # queue wait is part of time-to-first-token or an SLO claim is a lie
    # (reference single_worker.py:38-73 measures from submission too).
    # Migration paths override with the donor's original start_time.
    start_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_reason: Optional[str] = None
    # True while a chunk-interleaved admission is mid-prefill: the slot's KV
    # is incomplete and its last_token is garbage, so decode rounds MUST
    # skip it until the final chunk samples the first token
    prefilling: bool = False

    def __post_init__(self) -> None:
        if self.start_time is None:
            self.start_time = self.request.arrival_time


@dataclass
class _UnreadScan:
    """A decode scan that was dispatched and whose tokens the host has not
    read back: the one the next scan may be dispatched behind
    (``decode_multi(..., ahead=True)``). ``active_mask`` is the host's view
    of its rows when it went out (a chained scan's rows are those of them
    the device still found live), ``steps`` what each may take of it."""

    num_steps: int
    active_mask: np.ndarray
    steps: np.ndarray
    emitted: jax.Array
    moe: List[jax.Array]


@dataclass
class _UnreadRound:
    """A plain ragged round that was dispatched BEHIND an unread scan and
    whose tokens the host has not read back (``ragged_round``): beside
    ``_UnreadScan``, the other thing ``TPUEngine._unread`` may hold.
    ``active_mask`` is every row the dispatch holds (decode rows and
    pieces), ``kept`` the decode rows the host gave it, of which the device
    ran those it still found live behind the scan (``live``); ``ready`` the
    admissions' pieces, ``tp`` the packed length; a decode row takes one
    step of it."""

    active_mask: np.ndarray
    kept: List[int]
    ready: List[Tuple["ChunkedAdmission", List[int], bool]]
    toks: jax.Array
    live: Optional[jax.Array]
    moe: List[jax.Array]
    tp: int = 0
    num_steps: int = 1


@dataclass
class ChunkedAdmission:
    """In-flight chunked admission (``submit_chunked_start``): a bound
    slot whose prompt is still to be prefilled.

    The batcher hands its admissions to ``ragged_round``, which advances
    each by one piece beside the decoding rows; ``submit_chunked_step``
    runs one piece alone (PD streamed prefill, the tests' reference).
    Either way a long prompt never stalls active decodes longer than one
    chunk (vLLM-style chunked-prefill scheduling)."""

    request: InferenceRequest
    slot: int
    seq_id: str
    fresh: List[int]
    off: int
    mode: str
    done: bool = False


class RequestOverLength(ValueError):
    """Prompt + max_new_tokens exceeds the engine's ``max_seq_len`` — a
    per-request input error, not a capacity condition: no amount of
    waiting, preemption, or retry makes it fit THIS engine geometry.
    Carries the machine-readable ``error_code`` the serving layers thread
    through job results and SSE (like ``shed_overload`` /
    ``request_timeout``), so a client can route the request to a
    longer-context deployment instead of string-matching the message."""

    error_code = "over_length"


@dataclass
class KVPressure:
    """KV-block exhaustion observed at a step boundary — a SCHEDULING event,
    not an error. The engine leaves every sequence in a consistent frozen
    state (nothing decoded for the pressured slots, nothing half-allocated)
    and hands this signal to whoever drives it (``ContinuousBatcher``,
    ``generate``) to pick a preemption victim / requeue admissions.

    ``source``: "decode" means active slots could not reserve their next
    step's blocks (progress REQUIRES freeing blocks — preempt someone);
    "admission" means new work could not allocate (it can simply wait for
    running sequences to finish unless it outranks them).
    """

    source: str
    slots: List[int] = field(default_factory=list)   # slots that froze
    requests: int = 0                                # admissions deferred


#: wire version of the portable checkpoint format. Bump when a field's
#: meaning changes; ``from_wire`` refuses unknown versions so a newer
#: worker's checkpoint can never be silently mis-resumed by an older one.
CHECKPOINT_WIRE_VERSION = 1


@dataclass
class PreemptedSequence:
    """A running sequence frozen by :meth:`TPUEngine.preempt_slot` (or
    snapshotted live by :meth:`TPUEngine.snapshot_slot`).

    Carries everything needed for a byte-identical greedy (and seed-stable
    sampled) continuation through :meth:`TPUEngine.resume`: the original
    request, every token generated so far, and the slot's PRNG key
    material. Device blocks are RELEASED at preempt time — full blocks park
    in the prefix cache (and spill to the host tier under further
    pressure), so resume restores them via the radix index / ``_probe_spill``
    instead of recomputing the whole context.

    The state is also PORTABLE: :meth:`to_wire` / :meth:`from_wire` give a
    versioned JSON-safe encoding workers piggyback on heartbeats to the
    control plane, so a sequence can resume on a DIFFERENT engine after its
    worker dies (KV restored through the prefix cache / spill tiers when
    reachable, deterministic uncached-suffix recompute otherwise).
    """

    request: InferenceRequest
    prompt_len: int
    generated: List[int]
    slot_key: Tuple[int, int]             # threefry key words (hi, lo)
    start_time: Optional[float]
    first_token_time: Optional[float]
    cached_tokens: int
    preempt_count: int = 0                # maintained by the scheduler layer

    @staticmethod
    def _wire_crc(data: Dict[str, Any]) -> int:
        """CRC32 over the canonical JSON of the checkpoint WITHOUT its
        ``crc`` field — the integrity check for a record that crosses HTTP
        and sits in a TEXT column through a store brownout (round 19)."""
        body = {k: v for k, v in data.items() if k != "crc"}
        return zlib.crc32(
            json.dumps(body, sort_keys=True, separators=(",", ":")).encode()
        )

    def to_wire(self) -> Dict[str, Any]:
        """Versioned JSON-safe checkpoint (numbers, strings, lists only —
        it crosses HTTP and lands in a TEXT column). Carries a ``crc``
        field over the canonical JSON body so a torn/corrupted store row is
        DETECTED at resume (caller degrades to recompute) rather than
        resuming a half-written sequence."""
        r = self.request
        data = {
            "v": CHECKPOINT_WIRE_VERSION,
            "request": {
                "request_id": r.request_id,
                "model": r.model,
                "prompt_token_ids": list(r.prompt_token_ids or []),
                "sampling": r.sampling.to_dict(),
                "priority": r.priority,
                "session_id": r.session_id,
                # optional EDF deadline (absolute): resumes are already
                # head-of-line, but the victim policy still reads it
                **({"deadline_at": r.deadline_at}
                   if r.deadline_s is not None else {}),
            },
            "prompt_len": self.prompt_len,
            "generated": list(self.generated),
            "slot_key": [int(self.slot_key[0]), int(self.slot_key[1])],
            "start_time": self.start_time,
            "first_token_time": self.first_token_time,
            "cached_tokens": self.cached_tokens,
            "preempt_count": self.preempt_count,
        }
        data["crc"] = self._wire_crc(data)
        return data

    @classmethod
    def from_wire(cls, data: Dict[str, Any]) -> "PreemptedSequence":
        if not isinstance(data, dict):
            raise ValueError("checkpoint must be a dict")
        ver = data.get("v")
        if ver != CHECKPOINT_WIRE_VERSION:
            raise ValueError(
                f"unsupported checkpoint version {ver!r} (this build "
                f"speaks v{CHECKPOINT_WIRE_VERSION})"
            )
        # verify-when-present: pre-round-19 rows carry no crc and parse as
        # before (mixed-version fleets); a present-but-wrong crc means the
        # row was torn or bit-flipped in the store — refuse to resume it
        if "crc" in data and int(data["crc"]) != cls._wire_crc(data):
            raise ValueError("checkpoint integrity check failed (bad crc)")
        r = data["request"]
        request = InferenceRequest(
            request_id=r["request_id"],
            model=r.get("model"),
            prompt_token_ids=[int(t) for t in (r.get("prompt_token_ids")
                                               or [])],
            sampling=SamplingParams.from_dict(r["sampling"]),
            priority=int(r.get("priority") or 0),
            session_id=r.get("session_id"),
        )
        if r.get("deadline_at") is not None:
            # arrival_time was re-minted by the ctor above: re-derive the
            # relative deadline so deadline_at round-trips the wire
            request.deadline_s = max(
                0.0, float(r["deadline_at"]) - request.arrival_time
            )
        key = data.get("slot_key") or [0, 0]
        return cls(
            request=request,
            prompt_len=int(data["prompt_len"]),
            generated=[int(t) for t in (data.get("generated") or [])],
            slot_key=(int(key[0]), int(key[1])),
            start_time=data.get("start_time"),
            first_token_time=data.get("first_token_time"),
            cached_tokens=int(data.get("cached_tokens") or 0),
            preempt_count=int(data.get("preempt_count") or 0),
        )


class TPUEngine:
    """Paged-KV serving engine for one model on one chip/mesh."""

    def __init__(
        self,
        model_cfg: ModelConfig | str,
        engine_cfg: Optional[EngineConfig] = None,
        params: Optional[llama.Params] = None,
        seed: int = 0,
        eos_token_id: Optional[int] = None,
        mesh: Optional[Any] = None,
        checkpoint_path: Optional[str] = None,
    ) -> None:
        """``mesh``: first-class tensor parallelism — params and KV pools are
        GSPMD-sharded over the mesh's ``model`` axis and XLA inserts the TP
        collectives (the reference only passes tensor_parallel_size through
        to vLLM, SURVEY §2.2). Data parallelism stays request-level at the
        fleet scheduler, so an engine mesh must not carry a data axis.

        ``checkpoint_path``: orbax dir / HF safetensors dir; random init
        when absent (hermetic tests, benchmarks)."""
        self.model_cfg = (
            get_model_config(model_cfg) if isinstance(model_cfg, str) else model_cfg
        )
        self.cfg = engine_cfg or EngineConfig()
        # what the start cost (docs/observability.md, "Start-up"): the
        # seconds of each phase of the load, the instant each opened, and a
        # row a graph ``lower_serving_graphs`` lowered. ``get_stats()``
        # shows it with the sums; nothing after the start writes to it. The
        # load stays in this body under its ``with``: moved into a helper,
        # the one Python frame more cost 0.35-0.7 s of a 4 s load on the
        # v5e's host (PERF.md section 6, PR 55)
        self._startup: Dict[str, Any] = {"at": {}, "graphs": {}}
        self._compile_log = compile_log()
        with flight.phase(
                "dgi.engine.init", self._startup, "init",
                model=self.model_cfg.name,
                quantization=str(self.cfg.quantization),
                chips=1 if mesh is None else int(mesh.devices.size)):
            self.dtype = jnp.dtype(self.cfg.dtype)
            self.kv_dtype = _resolve_kv_dtype(self.cfg.kv_cache_dtype,
                                              self.dtype)
            if (
                self.kv_dtype.itemsize == 1
                and self.cfg.block_size % 32 != 0
                and jax.default_backend() == "tpu"
            ):
                # byte-dtype pool pages tile (32, 128) on TPU: a narrower block
                # would make page slices non-DMA-able in the Pallas kernel
                raise ValueError(
                    f"kv_cache_dtype={self.cfg.kv_cache_dtype!r} needs "
                    f"block_size % 32 == 0 on TPU, got {self.cfg.block_size}"
                )
            # int8 KV composes with meshes AND spill tiers since round 5:
            # scale pools shard with their data pools (replicated under TP —
            # no head axis to shard; block-axis-sharded under seq —
            # parallel/sharding.py kv_scale_sharding*), the shard_map seq ops
            # dequantize their local page shards, the quantize amax reduce
            # over sharded heads lowers to an all-reduce-max (scales stay
            # bit-identical to a single-chip engine), and evicted pages spill
            # int8 codes + scale pages as an atomic pair through L2/L3
            # (runtime/kv_cache.py store_spilled/_probe_spill).
            self.mesh = mesh
            # what ``forward_chunk`` can see of the mesh inside a trace: its
            # head sharding where ``model`` is its only sharded axis, for
            # the attention kernels to run a shard of heads a chip; None on
            # one chip and under a ``seq`` axis (parallel/sharding.py)
            self._heads = None
            if mesh is not None:
                from distributed_gpu_inference_tpu.parallel.sharding import (
                    head_shards,
                )

                self._heads = head_shards(mesh)
            self._seq_axis = 1
            if self.model_cfg.latent_kv:
                self._refuse_latent(mesh)
            if self.model_cfg.index_topk:
                self._refuse_indexed(mesh)
            if self.model_cfg.described_per_layer:
                self._refuse_per_layer(mesh)
            if self.model_cfg.ssm_num_heads:
                self._refuse_state_space()
            if mesh is not None:
                sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
                tp = sizes.get("model", 1)
                self._seq_axis = sizes.get("seq", 1)
            if mesh is not None:
                # general mesh validations (ANY mesh, not just seq-sharded)
                if sizes.get("data", 1) > 1:
                    raise ValueError(
                        "engine mesh must not carry a data axis (DP is "
                        "request-level at the scheduler); got "
                        f"data={sizes['data']}"
                    )
                if self.model_cfg.num_kv_heads % max(tp, 1):
                    raise ValueError(
                        f"num_kv_heads {self.model_cfg.num_kv_heads} not "
                        f"divisible by model axis {tp}"
                    )
                if self.model_cfg.num_experts and \
                        self.model_cfg.num_experts % max(tp, 1):
                    raise ValueError(
                        f"num_experts {self.model_cfg.num_experts} not "
                        f"divisible by model axis {tp} (EP shards experts)"
                    )
            if self.cfg.speculative is not None:
                self.cfg.speculative.validate(self.cfg)
                if mesh is not None:
                    # the draft head would need its own sharding rules and the
                    # verify chunk its own partitioning; keep the mode
                    # single-chip until that exists
                    raise ValueError(
                        "speculative decode mode is single-chip: drop the "
                        "mesh or EngineConfig.speculative"
                    )
            if self.cfg.kv_seq_sharded:
                if self._seq_axis <= 1:
                    raise ValueError(
                        "kv_seq_sharded needs a mesh with a seq axis > 1"
                    )
                # prefix caching and chunked/continuation admission compose
                # with sharded pools since round 4: continuation chunks attend
                # prior context through the shard_map partial-softmax chunk op
                # (parallel/ring_attention.seq_parallel_paged_chunk_attention);
                # only sliding-window models stay fenced (below).
                if self.cfg.resolved_num_blocks() % self._seq_axis:
                    # round the pool UP so the block axis shards evenly
                    blocks = self.cfg.resolved_num_blocks()
                    self.cfg.num_blocks = (
                        -(-blocks // self._seq_axis) * self._seq_axis
                    )
            with flight.phase("dgi.engine.init.params", self._startup,
                              "params"):
                if params is not None:
                    self.params = quantize_params(params,
                                                  self.cfg.quantization)
                    if mesh is not None:
                        from distributed_gpu_inference_tpu.parallel import (
                            sharding as _sh,
                        )

                        self.params = _sh.shard_params(self.params, mesh)
                else:
                    self.params = self._load_params(checkpoint_path, seed)
            self.num_blocks = self.cfg.resolved_num_blocks()
            # a hybrid model's linear-attention layers keep one state row a
            # slot beside the pages: its size follows from max_batch_size
            self._state_rows = (
                self.cfg.max_batch_size if self.model_cfg.num_state_layers
                else 0)
            # the state layers' counters' family, their pools' names and
            # their chunk form's length: the delta rule's, or a mixer's
            self._state_kind = ("", (), 0)
            if self.model_cfg.ssm_num_heads:
                from distributed_gpu_inference_tpu.models import ssd

                self._state_kind = ("ssd", ssd.POOLS,
                                    self.model_cfg.ssm_chunk_size)
            elif self._state_rows:
                from distributed_gpu_inference_tpu.models import kda

                self._state_kind = ("kda", (kda.STATE, kda.CONV), kda.CHUNK)
            # pages per layer kind: the sliding layers' pool follows from the
            # slots and the window, eight windows a slot (a live window with
            # the piece or the scan horizon being written, a retained prefix
            # end, what a reply releases before the next hit touches it, and a
            # third to spare), never more than the full kind's
            self._window_blocks = 0
            if self.model_cfg.mixed_attention:
                self._window_blocks = min(
                    self.num_blocks,
                    1 + self.cfg.max_batch_size * 8 * -(
                        -self.model_cfg.sliding_window // self.cfg.block_size))
            with flight.phase("dgi.engine.init.kv_pools", self._startup,
                              "kv_pools"):
                self.kv = self._init_kv()
                # storage for a scan's index keys in context order (a model
                # with an indexer whose tables can pass topk): a scan of
                # several steps takes it beside the pools, fills it from the
                # pool and hands it back (``_scan_kv``). It is never among
                # ``self.kv``: what it holds is one call's, derived from the
                # pool at every call, and no owner of pages learns of it. It is
                # kept from call to call because an array of its size (403 MB
                # at 8 layers x 8 rows x 24,576 positions) allocated anew
                # inside every scan stalled the device for 1.7-5 s every few
                # hundred scans (PERF.md section 6, PR 47).
                self._scan_keys: Optional[jax.Array] = None
                if self.model_cfg.index_topk:
                    from distributed_gpu_inference_tpu.ops import index_select

                    shape = index_select.scan_keys_shape(
                        self.kv[llama.INDEX_KEYS].shape,
                        self.cfg.max_batch_size, self.cfg.max_blocks_per_seq,
                        self.model_cfg.index_topk)
                    if shape is not None:
                        self._scan_keys = jnp.zeros(
                            shape, self.kv[llama.INDEX_KEYS].dtype)
            host_store = (
                HostKVStore(self.cfg.spill_host_blocks)
                if self.cfg.spill_host_blocks > 0 else None
            )
            spill = (host_store is not None
                     or self.cfg.spill_remote_store is not None)
            self.manager = PagedKVCacheManager(
                self.num_blocks,
                self.cfg.block_size,
                enable_prefix_cache=self.cfg.enable_prefix_cache,
                host_store=host_store,
                remote_store=self.cfg.spill_remote_store,
                spill_on_evict=spill,
                kv_dtype=np.dtype(self.kv_dtype),
                state_rows=self._state_rows,
                window_blocks=self._window_blocks,
                window=self.model_cfg.sliding_window
                if self._window_blocks else None,
            )
            self.eos_token_id = eos_token_id

            b, m = self.cfg.max_batch_size, self.cfg.max_blocks_per_seq
            self.slots: List[Optional[_Slot]] = [None] * b
            # a block table a layer kind, side by side in a row: the window
            # kind's columns start at ``_window_col``
            self._window_col = m if self._window_blocks else 0
            self._table_cols = m + self._window_col
            self._block_tables = np.zeros((b, self._table_cols),
                                          dtype=np.int32)
            self._kv_lens = np.zeros((b,), dtype=np.int32)
            self._last_tokens = np.zeros((b,), dtype=np.int32)
            self._temps = np.zeros((b,), dtype=np.float32)
            self._top_ks = np.zeros((b,), dtype=np.int32)
            self._top_ps = np.ones((b,), dtype=np.float32)
            self._stop_ids = np.full((b, MAX_STOP_IDS), -1, dtype=np.int32)
            # One PRNG key per slot: a seeded request's random stream is
            # independent of which other requests share the batch. Exact token
            # reproduction additionally requires identical logits — i.e. the
            # same dtype and the same prefill split (prefix-cache hits change
            # the suffix bucket, and bf16 reduction order can flip low bits);
            # greedy requests are robust to those effects, sampled ones are
            # reproducible given equal numerics.
            self._slot_keys = np.zeros((b, 2), dtype=np.uint32)
            self._host_rng = np.random.default_rng(seed + 0x5EED)

            # Device-resident core slot state (sampling params, PRNG keys,
            # stop ids, last token, committed length). The host numpy mirrors
            # above stay authoritative for scheduling; their device copies are
            # uploaded ONLY when a host-initiated change lands (admission,
            # adopt, error recovery) — never per decode round: every
            # host→device transfer is a dispatch of its own in front of the
            # round's.
            self._dev_core: Optional[Dict[str, jax.Array]] = None
            self._core_dirty = True
            # The one dispatch whose tokens are still on the device: a scan
            # (``decode_multi`` with ``ahead``) or the ragged round that went
            # out behind one (``ragged_round``). The host mirrors lag by it
            # until ``collect_scan``, which every other entry that reads or
            # writes slots, pool or mirrors calls first. The device orders its
            # own work; only the host's view lags.
            self._unread: Optional[Union[_UnreadScan, _UnreadRound]] = None
            # whether the scan read last was still running when the host came
            # for it: then the chip had work up to the read, and the time since
            # the read before is that scan's own
            self.scan_read_running = True
            # the speculative rounds replay their bookkeeping on the host
            # between dispatches: nothing of theirs can go out ahead
            self.supports_scan_ahead = self.cfg.speculative is None

            # integrated speculative decoding: EAGLE-style draft head weights +
            # per-slot last-verified hidden state (device-resident between
            # rounds, like _dev_core). The hidden starts at zeros for a fresh
            # slot — the first step then drafts garbage and accepts ~nothing,
            # which is CORRECT (emission is target-verified regardless of draft
            # quality) and seeds the real hidden from that verify pass.
            self._draft_params: Optional[Dict[str, jax.Array]] = None
            self._dev_spec_h: Optional[jax.Array] = None
            self._spec_h_zero: set = set()
            if self.cfg.speculative is not None:
                sp = self.cfg.speculative
                self._draft_params = (
                    sp.draft_params if sp.draft_params is not None
                    else init_draft_params(
                        self.model_cfg, jax.random.PRNGKey(sp.draft_seed),
                        dtype=self.dtype,
                    )
                )
                # acceptance-adaptive draft depth: per-slot EMA of the
                # ACCEPTED length (host-side — deterministic float arithmetic
                # over integer accept counts, so same seed → same K
                # schedule). Fresh slots start optimistic at K and converge.
                self._spec_k_ema = np.full((b,), float(sp.num_draft_tokens))
                # oracle-draft fractional-rate accumulator (per slot): a rate
                # whose K-scaled target is fractional dithers deterministically
                # (e.g. 2.4 → 2,3,2,3,2 accepted per round)
                self._spec_oracle_acc = np.zeros((b,))
                # test hook: set to a list and every dispatch appends its
                # [(slot, selected_k), ...] — None (default) records nothing
                self.spec_k_trace: Optional[List[Any]] = None

            with flight.phase("dgi.engine.init.jit_fns", self._startup,
                              "jit_fns"):
                self._build_jit_fns()
            # pending KV-pressure signal (set at step boundaries, consumed by
            # the scheduler layer via take_pressure)
            self._pressure: Optional[KVPressure] = None
            self.stats: Dict[str, Any] = {
                "startup": self._startup,
                "requests": 0, "completed": 0, "generated_tokens": 0,
                "prefill_tokens": 0, "prefill_calls": 0, "decode_calls": 0,
                "preemptions": 0, "resumes": 0, "kv_pressure_events": 0,
                "ragged_rounds": 0,
                # of them, those dispatched behind an unread scan
                "ragged_rounds_chained": 0,
                # the batcher's two round calls (docs/observability.md, "Round
                # spans and counters"): calls, what a ragged rectangle held,
                # and the host's seconds in each phase of a round
                "rounds": 0,
                "ragged_positions_dispatched": 0, "ragged_positions_live": 0,
                "round_build_s": 0.0, "round_dispatch_s": 0.0,
                "round_readback_s": 0.0, "round_commit_s": 0.0,
                # what of a scan's build, dispatch and commit ran while no scan
                # of the engine's was on the device: all of it where every scan
                # is read back by the call that made it, little where the next
                # scan goes out ahead of the readback
                "round_host_exposed_s": 0.0,
                # which KV path the multi-token graphs were built with
                # (in_place / layer_copy) and which attention the scan
                # graphs hold (fused / xla): trace-time facts, from the
                # predicates forward_chunk itself dispatches on
                **{name: path(
                    self.model_cfg,
                    self.cfg.max_blocks_per_seq * self.cfg.block_size,
                    quantized_kv=self.kv_dtype == jnp.int8,
                    pallas=self.mesh is None, heads=self._heads,
                ) for name, path in (
                    ("ragged_kv_path", llama.ragged_kv_path),
                    ("decode_attention", llama.decode_attention_path))},
                # what a cached token is: per-head K and V, or one latent;
                # hybrid: latent pages beside a state row a sequence
                # kv+index: K/V pages and an index key a token beside them
                # latent+index: latent pages and an index key a token a
                # layer that holds an indexer beside them
                # ...+window: pages per layer kind, the sliding kind's in a
                # pool of their own (K/V pages, or latent pages of a width
                # of their own)
                # kv+state: K/V pages beside a state row a sequence
                "kv_layout": "kv+state" if self.model_cfg.ssm_num_heads
                else "hybrid" if self._state_rows
                else ("latent+index" if self.model_cfg.index_topk
                      else "latent") if self.model_cfg.latent_kv
                else "kv+index" if self.model_cfg.index_topk else "kv",
            }
            if self.model_cfg.index_topk:
                # the index-key pool; what the scans' rows selected from (host
                # arithmetic at a scan's commit: a row-step with ``c`` cached
                # tokens attends min(c, topk) of them, and one with at most
                # topk selects nothing); the (query, cached token) pairs the
                # plain ragged rounds scored and kept (at a round's build)
                self.stats.update({
                    "index_pool_bytes": int(self.kv[llama.INDEX_KEYS].nbytes),
                    "index_row_steps_scan": 0, "index_context_tokens_scan": 0,
                    "index_selected_tokens_scan": 0,
                    "index_dense_rows_scan": 0,
                    "index_pairs_ragged": 0, "index_selected_pairs_ragged": 0,
                    # layer-gathers of index keys the scans issued: a scan of
                    # several steps lays every layer's keys out once (L), a
                    # single step once a layer (L), and one no row of which
                    # passes topk inside it not at all (host arithmetic at a
                    # scan's commit, the device's own condition)
                    "index_key_gathers_scan": 0,
                    # layer calls that computed a selection from their own
                    # indexer, and layer calls that attended the selection of
                    # the layer before them (``ModelConfig.index_kinds``:
                    # shared stays 0 where every layer holds an indexer), a
                    # scan step and a ragged round each counting its layers
                    "index_layers_scored": 0, "index_layers_shared": 0,
                })
                if self.model_cfg.num_experts and self.mesh is None:
                    # what the decode kernel fetched for those selections: the
                    # pages that hold a selected token, whole, a row-step, mean
                    # over the layers (each layer selects its own; counted on
                    # the device, read with the scan's tokens)
                    self.stats["index_fetched_tokens_scan"] = 0
            if self._window_blocks:
                # pages per layer kind. Cached tokens the scans' row-steps
                # attended in a full layer and in a sliding one (at most the
                # window) and the window-kind tokens their rows held (host
                # arithmetic at a scan's commit); the (query, key) pairs inside
                # causal reach, and inside the window, of the plain ragged
                # rounds (at a round's build)
                self.stats.update({
                    # latent pages of two kinds: ``latent+index+window``
                    "kv_layout": self.stats["kv_layout"] + "+window",
                    "window_pool_blocks": self._window_blocks,
                    "attn_row_steps_scan": 0,
                    "attn_full_context_tokens_scan": 0,
                    "attn_window_context_tokens_scan": 0,
                    "kv_window_resident_tokens_scan": 0,
                    "attn_pairs_ragged_full": 0, "attn_pairs_ragged_window": 0,
                    # the manager's, as they stood at the last admission
                    "prefix_hits_cut_by_window": 0,
                    "prefix_hit_tokens_cut_by_window": 0,
                })
            self._moe_names = (
                _MOE_SHARE_COUNTERS if self.model_cfg.latent_kv
                or self.model_cfg.held_experts is not None
                else _MOE_COUNTERS
            )
            if self.model_cfg.latent_kv:
                # cached tokens the scans' rows attended, and the row-steps
                # they took: what the absorbed decode kernel read (arithmetic
                # at a scan's commit); what the absorbed kernel of the plain
                # ragged rounds held (at a round's build): its (query, cached
                # token) pairs, and its rows' cached tokens, a row's once
                self.stats.update({"mla_context_tokens_scan": 0,
                                   "mla_row_steps_scan": 0,
                                   "mla_pairs_ragged": 0,
                                   "mla_context_tokens_ragged": 0})
            if self._state_rows:
                # the state pool; live row x step x state layer of the scans
                # (arithmetic at a scan's commit); what the ragged rounds
                # handed the chunk form (at a round's build; every state
                # layer takes it once): live tokens, segments (a row's
                # tokens in a round) and the chunks they are cut into (64
                # tokens of the delta rule, ``kda_*``; ``ssm_chunk_size`` of
                # a state-space mixer, ``ssd_*``)
                family, pools, _ = self._state_kind
                self.stats.update({
                    "state_pool_bytes": sum(
                        int(self.kv[name].nbytes) for name in pools),
                    "state_rows": self._state_rows,
                    f"{family}_row_steps_scan": 0,
                    f"{family}_tokens_ragged": 0,
                    f"{family}_segments_ragged": 0,
                    f"{family}_chunks_ragged": 0,
                })
            if self.model_cfg.num_experts:
                # what the routed expert layers did (models/llama.py
                # _moe_mlp), summed on the device and read back beside a
                # round's tokens; scans and ragged rounds apart. They stay 0
                # under a mesh, where the layer runs dense over the expert axis
                self.stats.update({
                    f"moe_{name}_{kind}": 0
                    for kind in ("scan", "ragged") for name in self._moe_names
                })
            if self.cfg.speculative is not None:
                self.stats.update({
                    "spec_steps": 0, "spec_slot_steps": 0, "spec_drafted": 0,
                    "spec_accepted": 0, "spec_emitted": 0,
                })

    def _refuse_latent(self, mesh: Optional[Any]) -> None:
        """A latent-attention model's cache is one pool of latent pages
        (models/mla.py). What carries K/V pages or a Llama draft refuses it
        here, when the engine is configured, not inside a request."""
        name = self.model_cfg.name
        if mesh is not None:
            raise ValueError(
                f"{name}: a latent-attention model is served on one chip "
                "(no sharding rule for the latent pool or the routed share)")
        if self.cfg.speculative is not None:
            raise ValueError(
                f"{name}: speculative decoding drafts with a Llama head over "
                "K/V pages; this model's multi-token-prediction layer is "
                "not loaded")
        if self.cfg.spill_host_blocks > 0 or \
                self.cfg.spill_remote_store is not None:
            raise ValueError(
                f"{name}: the spill tiers carry K/V pages, not latent pages")
        if self.kv_dtype.itemsize != jnp.dtype(self.dtype).itemsize:
            raise ValueError(
                f"{name}: the latent pool is served in the activation "
                f"dtype, not kv_cache_dtype={self.cfg.kv_cache_dtype!r}")
        if self.model_cfg.num_kda_layers and self.cfg.kv_seq_sharded:
            raise ValueError(
                f"{name}: a state row is whole on one chip (no sequence "
                "sharding of the linear-attention layers)")

    def _refuse_per_layer(self, mesh: Optional[Any]) -> None:
        """A K/V model described per layer (attention kinds mixed layer by
        layer with pages per kind, a dense lead, a shared expert, a held
        share of the experts: models/llama.py) is served on one chip, from
        pools in the activation dtype. What would serve it otherwise
        refuses it here, when the engine is configured."""
        name = self.model_cfg.name
        if mesh is not None:
            raise ValueError(
                f"{name}: a model described per layer is served on one chip "
                "(no sharding rule for its parameter stacks, the held share "
                "of its experts or a pool a layer kind)")
        if self.cfg.kv_seq_sharded:
            raise ValueError(
                f"{name}: pages per layer kind are not sharded over a "
                "sequence axis (the shard_map read takes one block table)")
        if self.cfg.speculative is not None:
            raise ValueError(
                f"{name}: speculative decoding drafts with a Llama head "
                "over one parameter stack and verifies a chain in one "
                "window of one block table")
        if not self.model_cfg.mixed_attention:
            return
        if self.cfg.spill_host_blocks > 0 or \
                self.cfg.spill_remote_store is not None:
            raise ValueError(
                f"{name}: the spill tiers carry one kind's K/V pages, not "
                "the window kind's that belong to them")
        if self.kv_dtype.itemsize != jnp.dtype(self.dtype).itemsize:
            raise ValueError(
                f"{name}: pages per layer kind are served in the "
                f"activation dtype, not kv_cache_dtype="
                f"{self.cfg.kv_cache_dtype!r} (int8 / fp8 pools of two "
                "kinds are not built)")

    def _refuse_state_space(self) -> None:
        """A model with a state-space mixer beside attention keeps a state
        row a slot beside its K/V pages (models/ssd.py). A mesh, a sequence
        axis and a speculative chain are refused with every model described
        per layer (``_refuse_per_layer``), the handoff wire where a worker
        is configured for it (``kv_handoff.require_kv_pages``); the tiers
        that would carry its pages WITHOUT the state that belongs to them,
        and K/V pools in another dtype than the activations', refuse it
        here, when the engine is configured."""
        name = self.model_cfg.name
        if self.cfg.spill_host_blocks > 0 or \
                self.cfg.spill_remote_store is not None:
            raise ValueError(
                f"{name}: the spill tiers carry K/V pages, not the state "
                "row that belongs to them")
        if self.kv_dtype.itemsize != jnp.dtype(self.dtype).itemsize:
            raise ValueError(
                f"{name}: K/V pages beside a state pool are served in the "
                f"activation dtype, not kv_cache_dtype="
                f"{self.cfg.kv_cache_dtype!r} (int8 / fp8 pools beside a "
                "state pool are not built)")

    def _refuse_indexed(self, mesh: Optional[Any]) -> None:
        """A model with an indexer (learned sparse attention) keeps an
        index-key pool beside its K/V pages and computes each query's
        selection from it (ops/index_select.py). What would serve it
        without that pool, or without the selection, refuses it here, when
        the engine is configured."""
        name = self.model_cfg.name
        if mesh is not None:
            raise ValueError(
                f"{name}: a model with an indexer is served on one chip "
                "(no sharding rule for the index-key pool, and the "
                "selection runs in kernels a mesh refuses)")
        if self.cfg.speculative is not None:
            raise ValueError(
                f"{name}: speculative decoding verifies a drafted chain in "
                "one window; the verify read takes no per-query selection")
        if self.cfg.spill_host_blocks > 0 or \
                self.cfg.spill_remote_store is not None:
            raise ValueError(
                f"{name}: the spill tiers carry K/V pages, not the index "
                "keys that belong to them")
        if self.kv_dtype.itemsize != jnp.dtype(self.dtype).itemsize:
            raise ValueError(
                f"{name}: the index-key pool is served in the activation "
                f"dtype, not kv_cache_dtype={self.cfg.kv_cache_dtype!r} "
                "(an int8 / fp8 index key is not built)")
        if self.cfg.kv_seq_sharded:
            raise ValueError(
                f"{name}: a query's selection reads every cached index "
                "key; the pool is not sharded over a sequence axis")

    # -------------------------------------------------- sharded weight init

    def _load_params(self, checkpoint_path: Optional[str], seed: int):
        """Weights land SHARDED when a mesh is set: never materialize the
        full model on one chip (a TP engine must serve models bigger than a
        single chip's HBM — full-size init then reshard would OOM first)."""
        from distributed_gpu_inference_tpu.models.loader import (
            load_or_init_params,
        )

        if self.mesh is None:
            if self.cfg.quantization is None:
                return load_or_init_params(
                    self.model_cfg, checkpoint_path=checkpoint_path,
                    dtype=self.cfg.dtype, seed=seed,
                )
            cached = self._load_quant_cache(checkpoint_path, seed)
            if cached is not None:
                return cached
            # quantized single-chip cold build. Three regimes:
            # - full-precision tree fits HBM transiently → init on device
            #   (fast) and quantize with consume=True, freeing each source
            #   leaf as its replacement lands (peak = full tree + 1 leaf);
            # - it does NOT fit and there is no checkpoint (benchmarks) →
            #   streamed on-device init: each leaf generated + quantized one
            #   layer slice at a time (no host init, no multi-GB upload);
            # - real checkpoint that doesn't fit (llama3-8b bf16 = 16.1 GB
            #   on 16 GB) → build + quantize on host CPU, upload only
            #   quantized bytes.
            fp_bytes = self.model_cfg.param_bytes(jnp.dtype(self.cfg.dtype).itemsize)
            if fp_bytes <= _QUANT_DEVICE_BUILD_LIMIT:
                params = quantize_params(
                    load_or_init_params(
                        self.model_cfg, checkpoint_path=checkpoint_path,
                        dtype=self.cfg.dtype, seed=seed,
                    ),
                    self.cfg.quantization,
                    consume=True,
                )
                # persisting would download the tree from the accelerator
                # (GBs, device→host rate not measured on the current chip),
                # so only host-resident trees are cached
                if jax.default_backend() == "cpu":
                    self._save_quant_cache(params, checkpoint_path, seed)
            elif checkpoint_path is None:
                from distributed_gpu_inference_tpu.models.loader import (
                    init_quantized_streamed,
                )

                # streamed on-device init is itself the fast path; no
                # persistence needed or wanted
                params = init_quantized_streamed(
                    self.model_cfg, self.cfg.quantization,
                    dtype=self.cfg.dtype, seed=seed,
                )
            else:
                cpu = jax.local_devices(backend="cpu")[0]
                with jax.default_device(cpu):
                    host_params = quantize_params(
                        load_or_init_params(
                            self.model_cfg, checkpoint_path=checkpoint_path,
                            dtype=self.cfg.dtype, seed=seed,
                        ),
                        self.cfg.quantization,
                        consume=True,
                    )
                # save BEFORE upload while the tree is host-resident: the
                # next cold start then restores int8 from disk (~1 GB/s
                # upload) instead of re-quantizing the fp checkpoint
                self._save_quant_cache(host_params, checkpoint_path, seed)
                dev = jax.devices()[0]
                params = jax.tree.map(
                    lambda a: jax.device_put(a, dev), host_params
                )
            return params
        if self.cfg.quantization is not None and checkpoint_path is None:
            from distributed_gpu_inference_tpu.models.loader import (
                init_quantized_streamed,
            )

            # the one-chip streamed init, generated straight into the
            # tensor-parallel layout: same seed, same weights, no host build
            return init_quantized_streamed(
                self.model_cfg, self.cfg.quantization,
                dtype=self.cfg.dtype, seed=seed, mesh=self.mesh,
            )
        # build (and quantize) on the host CPU backend, then device_put
        # host→shards direct — int8/fp8 leaves ship half the bytes
        cpu = jax.local_devices(backend="cpu")[0]
        with jax.default_device(cpu):
            host_params = quantize_params(
                load_or_init_params(
                    self.model_cfg, checkpoint_path=checkpoint_path,
                    dtype=self.cfg.dtype, seed=seed,
                ),
                self.cfg.quantization,
            )
        from distributed_gpu_inference_tpu.parallel import sharding as _sh

        return _sh.shard_params(host_params, self.mesh)

    def _quant_cache_path(self, checkpoint_path: Optional[str], seed: int):
        import hashlib
        from pathlib import Path

        if not self.cfg.quant_cache_dir or self.mesh is not None:
            return None
        if checkpoint_path is None:
            src = "rand"
        else:
            # content signature, not just the path: an in-place checkpoint
            # update (same dir, new weights) must invalidate the cache or
            # the engine silently serves the previous model
            p = Path(checkpoint_path)
            sig = hashlib.sha1()
            # recursive: orbax trees keep weights in nested files whose
            # in-place rewrite must invalidate the cache
            for f in sorted(p.rglob("*")):
                try:
                    st = f.stat()
                except OSError:
                    continue
                sig.update(f"{f.name}:{st.st_size}:{st.st_mtime_ns};".encode())
            src = f"{p.name or 'ckpt'}-{sig.hexdigest()[:10]}"
        tag = (
            f"{self.model_cfg.name}-{self.cfg.quantization}-"
            f"{self.cfg.dtype}-{src}-seed{seed}"
        )
        return Path(self.cfg.quant_cache_dir) / tag

    def _load_quant_cache(self, checkpoint_path: Optional[str], seed: int):
        """Restore a previously persisted quantized tree (orbax) straight to
        the device — skips init + quantization on every cold start after the
        first. Corrupt/incompatible caches fall back to a fresh build."""
        p = self._quant_cache_path(checkpoint_path, seed)
        if p is None or not (p / "params").exists():
            return None
        from distributed_gpu_inference_tpu.models.loader import load_checkpoint

        try:
            return load_checkpoint(p)
        except Exception:
            return None

    def _save_quant_cache(self, params, checkpoint_path: Optional[str],
                          seed: int) -> None:
        p = self._quant_cache_path(checkpoint_path, seed)
        if p is None or (p / "params").exists():
            return
        from distributed_gpu_inference_tpu.models.loader import save_checkpoint

        try:
            save_checkpoint(p, params)
        except Exception:
            pass  # cache is best-effort; serving proceeds with live params

    def _init_kv(self) -> llama.KVPools:
        if self.mesh is None:
            return llama.init_kv_pools(
                self.model_cfg, self.num_blocks, self.cfg.block_size,
                self.kv_dtype, state_rows=self._state_rows or None,
                window_blocks=self._window_blocks or None,
            )
        # zeros created directly with the sharded layout (no single-device
        # staging allocation)
        from distributed_gpu_inference_tpu.parallel import sharding as _sh

        s = (
            _sh.kv_sharding_seq(self.mesh)
            if self.cfg.kv_seq_sharded else _sh.kv_sharding(self.mesh)
        )
        out_s = {"k": s, "v": s}
        if self.kv_dtype == jnp.int8:
            ss = (
                _sh.kv_scale_sharding_seq(self.mesh)
                if self.cfg.kv_seq_sharded
                else _sh.kv_scale_sharding(self.mesh)
            )
            out_s["k_scale"] = out_s["v_scale"] = ss
        make = jax.jit(
            lambda: llama.init_kv_pools(
                self.model_cfg, self.num_blocks, self.cfg.block_size,
                self.kv_dtype,
            ),
            out_shardings=out_s,
        )
        return make()

    # ------------------------------------------------------------------ jit

    def _build_jit_fns(self) -> None:
        cfg, bs = self.model_cfg, self.cfg.block_size
        m = self._table_cols
        # a bare Pallas kernel in a serving graph is a custom call with no
        # GSPMD partitioning rule — XLA refuses a sharded graph that holds
        # one — so a mesh engine's projections and experts run the XLA
        # paths, which partition and all-reduce. Its attention runs the
        # kernels all the same where the mesh shards ``model`` alone: inside
        # ``jax.shard_map``, a shard of heads a chip on the stacked pools
        # in place (``llama.attention_kernels``). Kernel dispatch sees the
        # backend, not the mesh: the engine is where the mesh is known, so
        # the engine says both.
        fwd = functools.partial(
            llama.forward_chunk, pallas=self.mesh is None, heads=self._heads
        )
        # what a scan carries beside the pools (a model with an indexer: its
        # rows' index keys in context order, laid out once a scan)
        scan_keys = functools.partial(llama.scan_index_keys, cfg)

        # seq-sharded pools: decode reads go through the shard_map
        # partial-softmax op (a GSPMD gather from an N-sharded pool would
        # all-gather it); prefill attends DENSE over the chunk (fresh
        # prompts: chunk == whole context), so pool pages are never read
        # during admission
        decode_attn_override = None
        prefill_dense_fn = None
        chunk_attn_override = None
        if self.cfg.kv_seq_sharded:
            if cfg.sliding_window is not None:
                raise ValueError(
                    "kv_seq_sharded does not support sliding-window models"
                )
            from distributed_gpu_inference_tpu.ops.attention import (
                dense_causal_attention,
            )
            from distributed_gpu_inference_tpu.parallel.ring_attention import (
                seq_parallel_paged_chunk_attention,
                seq_parallel_paged_decode_attention,
            )

            mesh = self.mesh

            def decode_attn_override(q, layer_k, layer_v, tables, positions,
                                     kv_lens, layer_ks=None, layer_vs=None):
                return seq_parallel_paged_decode_attention(
                    q, layer_k, layer_v, tables, positions, kv_lens, mesh,
                    block_size=bs, k_scale=layer_ks, v_scale=layer_vs,
                )

            def prefill_dense_fn(q, k, v, kv_lens):
                return dense_causal_attention(q, k, v, lengths=kv_lens)

            # continuation/cached chunks: the chunk's KV is in the sharded
            # pool by the time attention runs, so one partial-softmax read
            # covers cached prefix + prior chunks + in-chunk causal keys
            def chunk_attn_override(q, layer_k, layer_v, tables, positions,
                                    kv_lens, layer_ks=None, layer_vs=None):
                return seq_parallel_paged_chunk_attention(
                    q, layer_k, layer_v, tables, positions, kv_lens, mesh,
                    block_size=bs, k_scale=layer_ks, v_scale=layer_vs,
                )

        # --- device-state pack/unpack (ONE upload per packed buffer: slot
        # state crosses in two packed arrays, not ten small ones). On a
        # mesh the unpacked state is placed replicated, the sharding the
        # round graphs hand it back with — uploaded to one device instead,
        # each round graph would compile twice (fresh upload vs carried
        # state are different argument shardings).
        replicated = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(self.mesh, PartitionSpec())

        def unpack_core(ci, cf):
            return {
                "last": ci[:, 0],
                "lens": ci[:, 1],
                "keys": jax.lax.bitcast_convert_type(ci[:, 2:4], jnp.uint32),
                "stops": ci[:, 4:4 + MAX_STOP_IDS],
                "top_ks": ci[:, 4 + MAX_STOP_IDS],
                "temps": cf[:, 0],
                "top_ps": cf[:, 1],
            }

        self._unpack_core_fn = jax.jit(
            unpack_core, out_shardings=replicated
        )

        def unpack_sched(si):
            return si[:, :m], si[:, m] > 0, si[:, m + 1]

        self._unpack_sched_fn = jax.jit(
            unpack_sched, out_shardings=replicated
        )

        def chain_sched(core, ends):
            # The rows and budgets of a scan dispatched BEHIND one the host
            # has not read, from where that one leaves the device core:
            # ``ends`` is the committed length at which each of its rows
            # runs out of budget (0: not a row of it), so a row that used
            # its budget up or whose last token is a stop id is out, as the
            # scan itself masked it, and the others have what is left. The
            # shapes do not depend on the scan length: one program.
            left = ends - core["lens"]
            live = (left > 0) & ~jnp.any(
                core["last"][:, None] == core["stops"], axis=1)
            return live, jnp.where(live, left, 0)

        self._chain_sched_fn = jax.jit(
            chain_sched, out_shardings=replicated
        )

        def merge_core(core, ci, cf, keep):
            # The device core after an admission that ran beside an unread
            # scan: the host's rows for every column, except ``last`` and
            # ``lens`` of that scan's rows (``keep``), where the mirrors
            # lag and the device's own are the current ones.
            host = unpack_core(ci, cf)
            host["last"] = jnp.where(keep, core["last"], host["last"])
            host["lens"] = jnp.where(keep, core["lens"], host["lens"])
            return host

        self._merge_core_fn = jax.jit(merge_core, out_shardings=replicated)

        def chain_round(core, tok_at, lens_last, flag, dec_ends):
            # The decode rows of a ragged round dispatched BEHIND a scan the
            # host has not read, from where that scan leaves the device
            # core, as ``chain_sched`` takes a chained scan's: ``dec_ends``
            # holds each decode row's place on the packed axis (-1: no
            # decode row) and the committed length at which it runs out of
            # budget. A row the scan ended is out of the round (a pad at
            # row b, position -1, not sampled, so the round leaves its core
            # alone); the others feed ``last`` at ``lens``. The pieces'
            # entries are the host's. One program a packed length, beside
            # the round graphs and not in them.
            dec, ends = dec_ends[:, 0], dec_ends[:, 1]
            live, _ = chain_sched(core, ends)
            decodes = dec >= 0
            live = live & decodes
            b, tp = flag.shape[0], tok_at.shape[1]
            rows = jnp.arange(b, dtype=jnp.int32)
            entry = jnp.stack([
                jnp.where(live, core["last"], 0),
                jnp.where(live, core["lens"], -1),
                jnp.where(live, rows, b),
                jnp.zeros_like(rows),
            ])
            tok_at = tok_at.at[:, jnp.where(decodes, dec, tp)].set(
                entry, mode="drop")
            fed = jnp.where(live, jnp.stack([core["lens"] + 1, dec]), 0)
            lens_last = jnp.where(decodes, fed, lens_last)
            flag = jnp.where(decodes, live.astype(flag.dtype), flag)
            return tok_at, lens_last, flag, live

        self._chain_round_fn = jax.jit(chain_round, out_shardings=replicated)
        # On a mesh a round's packed batch is placed replicated before the
        # round takes it, as ``chain_round`` hands it back: a host array
        # there and a placed one here are different argument shardings,
        # and each round graph would compile twice.
        self._place_round_fn = None if replicated is None else jax.jit(
            lambda tok_at, lens_last: (tok_at, lens_last),
            out_shardings=replicated)

        # --- sampling fused into the serving graphs. ``mode`` is static:
        # "greedy" compiles an argmax-only epilogue (no [B, V] sort in the
        # step — the whole batch is temperature 0, the serving common case),
        # "mixed" compiles the full per-slot nucleus sampler. The engine
        # picks the variant per call from the host mirrors.

        def sample_mode(logits, keys, positions, temps, top_ks, top_ps, mode):
            if mode == "greedy":
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return sample_tokens_per_slot(
                logits, keys, positions, temps, top_ks, top_ps
            )

        def prefill_batch(params, kv, toks_pos, tables, lens_after, core,
                          wave, mode):
            out = fwd(
                cfg, params, toks_pos[0], toks_pos[1], kv, tables, lens_after,
                block_size=bs, last_only=True,
                dense_attn_fn=(
                    (lambda q, k, v: prefill_dense_fn(q, k, v, lens_after))
                    if prefill_dense_fn else None
                ),
            )
            first = sample_mode(
                out.logits[:, 0, :], core["keys"], lens_after, core["temps"],
                core["top_ks"], core["top_ps"], mode,
            )
            core = dict(core)
            core["last"] = jnp.where(wave, first, core["last"])
            core["lens"] = jnp.where(wave, lens_after, core["lens"])
            return first, core, out.kv

        self._prefill_batch_fn = jax.jit(
            prefill_batch, static_argnames=("mode",), donate_argnums=(1, 5)
        )

        def prefill_chunk(params, kv, toks_pos, table, kv_len, keys, temps,
                          top_ks, top_ps, mode, sample):
            out = fwd(
                cfg, params, toks_pos[0], toks_pos[1], kv, table, kv_len,
                block_size=bs, last_only=True, with_logits=sample,
                dense_attn_fn=(
                    # fresh single-chunk prompts only in kv_seq_sharded mode
                    # (chunk == whole context; _prefill_one_chunk enforces)
                    (lambda q, k, v: prefill_dense_fn(q, k, v, kv_len))
                    if prefill_dense_fn else None
                ),
            )
            if not sample:
                # intermediate chunk: KV side effects only — no LM head read
                return None, out.kv
            first = sample_mode(
                out.logits[:, 0, :], keys, kv_len, temps, top_ks, top_ps, mode
            )
            return first, out.kv

        self._prefill_chunk_fn = jax.jit(
            prefill_chunk, static_argnames=("mode", "sample"),
            donate_argnums=(1,),
        )

        # continuation/cached chunk prefill over seq-sharded pools: same
        # shape contract as prefill_chunk, but attention reads the pool
        # through the shard_map partial-softmax chunk op (prior context +
        # in-chunk keys; the layer step wrote the chunk's KV first)
        self._prefill_chunk_paged_fn = None
        if chunk_attn_override is not None:
            def prefill_chunk_paged(params, kv, toks_pos, table, kv_len,
                                    keys, temps, top_ks, top_ps, mode,
                                    sample):
                out = fwd(
                    cfg, params, toks_pos[0], toks_pos[1], kv, table, kv_len,
                    block_size=bs, last_only=True, with_logits=sample,
                    attn_override=chunk_attn_override,
                )
                if not sample:
                    return None, out.kv
                first = sample_mode(
                    out.logits[:, 0, :], keys, kv_len, temps, top_ks,
                    top_ps, mode,
                )
                return first, out.kv

            self._prefill_chunk_paged_fn = jax.jit(
                prefill_chunk_paged, static_argnames=("mode", "sample"),
                donate_argnums=(1,),
            )

        def prefill_seq_parallel(params, kv, toks_pos, table, kv_len, keys,
                                 temps, top_ks, top_ps, mode):
            # one seq-sharded pass over the WHOLE long prompt: attention
            # runs ring/ulysses over the mesh's seq axis; KV pages are
            # written to the paged pools exactly as chunked prefill would
            from distributed_gpu_inference_tpu.parallel import ring_attention

            dense = (
                ring_attention.ring_self_attention
                if self.cfg.seq_parallel_impl == "ring"
                else ring_attention.ulysses_self_attention
            )

            def dense_attn(q, k_, v_):
                return dense(q, k_, v_, kv_len, self.mesh)

            out = fwd(
                cfg, params, toks_pos[0], toks_pos[1], kv, table, kv_len,
                block_size=bs, last_only=True, dense_attn_fn=dense_attn,
            )
            first = sample_mode(
                out.logits[:, 0, :], keys, kv_len, temps, top_ks, top_ps,
                mode,
            )
            return first, out.kv

        self._prefill_seq_fn = jax.jit(
            prefill_seq_parallel, static_argnames=("mode",),
            donate_argnums=(1,),
        )

        def decode_multi(params, kv, core, tables, active, budgets,
                         num_steps, mode):
            # One graph serves the per-step path (num_steps=1) and the
            # multi-step scan. Slot state lives in ``core`` (device-resident
            # between rounds); per-slot budgets mask slots out ON DEVICE once
            # they emit their allowance, so one compiled T=multi_step graph
            # serves every call. ``core["lens"]`` is the COMMITTED context
            # length; each non-done step feeds the pending token at position
            # lens (writing its KV) and advances lens by one — on exit the
            # device lens/last match the host mirrors exactly, which is what
            # lets the next round skip the state upload.
            stops = core["stops"]

            def step(carry, _):
                kv, last, lens, done, n_emit = carry
                cur = jnp.where(~done, lens + 1, 0).astype(jnp.int32)
                positions = jnp.where(
                    (~done)[:, None], lens[:, None], -1
                ).astype(jnp.int32)
                out = fwd(
                    cfg, params, last[:, None], positions, kv, tables, cur,
                    block_size=bs, last_only=True,
                    attn_override=decode_attn_override,
                )
                toks = sample_mode(
                    out.logits[:, 0, :], core["keys"], cur, core["temps"],
                    core["top_ks"], core["top_ps"], mode,
                )
                hit_stop = jnp.any(toks[:, None] == stops, axis=1)
                emitted = jnp.where(done, -1, toks)
                new_emit = n_emit + (~done).astype(jnp.int32)
                new_done = done | hit_stop | (new_emit >= budgets)
                new_lens = jnp.where(done, lens, lens + 1)
                new_last = jnp.where(done, last, toks)
                return (out.kv, new_last, new_lens, new_done, new_emit), emitted

            done0 = ~active
            n0 = jnp.zeros_like(core["lens"])
            (kv, last, lens, _done, _), emitted = jax.lax.scan(
                step, (scan_keys(kv, tables, core["lens"], active,
                                 num_steps),
                       core["last"], core["lens"], done0, n0), None,
                length=num_steps,
            )
            core = dict(core)
            core["last"], core["lens"] = last, lens
            return kv, core, emitted.T  # emitted [B, T]

        self._decode_multi_fn = jax.jit(
            decode_multi, static_argnames=("num_steps", "mode"),
            donate_argnums=(1, 2),
        )

        # --- RAGGED round (round 6): ONE dispatch in which decode rows
        # (1 live token each, at position lens) and admission prefill-chunk
        # rows (up to S live tokens) coexist. Admission stops being a
        # competing dispatch: appending a chunk row to the next round IS
        # the admission. The round's live tokens arrive PACKED on one axis
        # of Tp entries (``tok_at``: token id, position, row, column;
        # padding at row B, position -1): the dense work runs over Tp rows,
        # and forward_chunk lays q/k/v out into the [B, width] rectangle
        # only for the page write and attention (on TPU the ragged
        # paged-attention kernel, ops.attention.resolve_impl → "ragged";
        # per-row positions / -1 padding select each row's path there).
        # Per-token math is identical to the split paths (decode rows ≡
        # decode_multi's step, chunk rows ≡ _prefill_chunk_fn), so greedy
        # outputs are byte-identical and seeded sampling is stable (the
        # sampler folds the absolute position, which is per-row here
        # exactly as there). ``width`` is ``_ragged_shape``'s function of
        # Tp: one graph per Tp.
        def ragged_round(params, kv, tok_at, tables, lens_last, core,
                         sample_flag, mode, width):
            lens_after = lens_last[0]
            out = fwd(
                cfg, params, tok_at[0], tok_at[1], kv, tables,
                lens_after, block_size=bs, last_only=True,
                attn_override=chunk_attn_override,
                packing=llama.Packing(tok_at[2], tok_at[3], lens_last[1],
                                      width),
            )
            toks = sample_mode(
                out.logits[:, 0, :], core["keys"], lens_after,
                core["temps"], core["top_ks"], core["top_ps"], mode,
            )
            # rows that sampled (decode rows + FINAL admission chunks)
            # advance the device core exactly as decode_multi / the
            # batched prefill would; intermediate chunk rows only write KV
            sampled = sample_flag > 0
            core = dict(core)
            core["last"] = jnp.where(sampled, toks, core["last"])
            core["lens"] = jnp.where(sampled, lens_after, core["lens"])
            return out.kv, core, toks

        self._ragged_round_fn = jax.jit(
            ragged_round, static_argnames=("mode", "width"),
            donate_argnums=(1, 5),
        )

        if cfg.num_experts and self.mesh is None:
            # A sparse model on one device: the same two rounds, which also
            # sum what the routed expert layers did (``ChunkOutput.moe``) —
            # the scan in its carry — and return it as a fourth value, so
            # that it comes back in the transfer that brings the tokens.
            # They stand BESIDE the plain rounds, not in them: threading an
            # optional value through the plain bodies (a ``None`` or a
            # spliced empty tuple in the carry and the outputs: the traced
            # program was the same) cost a dense model's nine round graphs
            # 5.2-5.5 s of lowering at every start on the v5e (PERF.md
            # section 6), as wrapping them in a decorator had in PR 23.
            def decode_multi_counted(params, kv, core, tables, active,
                                     budgets, num_steps, mode):
                stops = core["stops"]

                def step(carry, _):
                    kv, last, lens, done, n_emit, moe = carry
                    cur = jnp.where(~done, lens + 1, 0).astype(jnp.int32)
                    positions = jnp.where(
                        (~done)[:, None], lens[:, None], -1
                    ).astype(jnp.int32)
                    out = fwd(
                        cfg, params, last[:, None], positions, kv, tables,
                        cur, block_size=bs, last_only=True,
                    )
                    toks = sample_mode(
                        out.logits[:, 0, :], core["keys"], cur,
                        core["temps"], core["top_ks"], core["top_ps"], mode,
                    )
                    hit_stop = jnp.any(toks[:, None] == stops, axis=1)
                    emitted = jnp.where(done, -1, toks)
                    new_emit = n_emit + (~done).astype(jnp.int32)
                    new_done = done | hit_stop | (new_emit >= budgets)
                    new_lens = jnp.where(done, lens, lens + 1)
                    new_last = jnp.where(done, last, toks)
                    return (out.kv, new_last, new_lens, new_done, new_emit,
                            moe + _moe_vector(out)), emitted

                moe0 = jnp.zeros(
                    (len(self._moe_names) + bool(cfg.index_topk),), jnp.int32)
                (kv, last, lens, _done, _, moe), emitted = jax.lax.scan(
                    step, (scan_keys(kv, tables, core["lens"], active,
                                     num_steps),
                           core["last"], core["lens"], ~active,
                           jnp.zeros_like(core["lens"]), moe0),
                    None, length=num_steps,
                )
                core = dict(core)
                core["last"], core["lens"] = last, lens
                return kv, core, emitted.T, moe

            def ragged_round_counted(params, kv, tok_at, tables, lens_last,
                                     core, sample_flag, mode, width):
                lens_after = lens_last[0]
                out = fwd(
                    cfg, params, tok_at[0], tok_at[1], kv, tables,
                    lens_after, block_size=bs, last_only=True,
                    packing=llama.Packing(tok_at[2], tok_at[3],
                                          lens_last[1], width),
                )
                toks = sample_mode(
                    out.logits[:, 0, :], core["keys"], lens_after,
                    core["temps"], core["top_ks"], core["top_ps"], mode,
                )
                sampled = sample_flag > 0
                core = dict(core)
                core["last"] = jnp.where(sampled, toks, core["last"])
                core["lens"] = jnp.where(sampled, lens_after, core["lens"])
                return out.kv, core, toks, _moe_vector(out)

            self._decode_multi_fn = jax.jit(
                decode_multi_counted, static_argnames=("num_steps", "mode"),
                donate_argnums=(1, 2),
            )
            self._ragged_round_fn = jax.jit(
                ragged_round_counted, static_argnames=("mode", "width"),
                donate_argnums=(1, 5),
            )

        # --- integrated speculative decoding: R fused draft→verify→accept
        # rounds per dispatch (lax.scan — the spec analogue of decode_multi's
        # scan, same per-dispatch RTT amortization; the round-2 lesson from
        # the standalone decoder was that one host round per tree round
        # loses to vanilla outright). Per round, the draft head chains K
        # greedy tokens from the last verified hidden; one multi-query
        # target pass (q_len = K+1 per slot — ops.attention's small-q path)
        # verifies them; each slot accepts its longest matching prefix plus
        # the target's bonus token. Chain positions are sequential, so
        # accepted KV is already at its final position and a rejected
        # suffix is dead weight the next round overwrites — no tree
        # compaction, no KV movement. Per-round records (emission order,
        # accept counts, active mask) return to the host, which replays
        # stop/budget bookkeeping EXACTLY as the per-step path would.
        self._spec_rounds_fn = None
        self._spec_ragged_round_fn = None
        if self.cfg.speculative is not None:
            spec_k = self.cfg.speculative.num_draft_tokens

            def draft_chain(params, dp, pending, h):
                # K-token greedy draft chain — shared by the fused scan
                # and the spec ragged round. Draft logits go through
                # project_logits (final norm + head) — the readout
                # distillation trains against (the round-3 tied-embedding
                # finding, runtime/speculative.py). Depth is always the
                # STATIC spec_k; per-slot adaptive depths mask the tail
                # (positions/acceptance), never re-trace.
                toks = [pending]
                hh = h
                for _ in range(spec_k):
                    hh = draft_apply(
                        cfg, dp, hh, llama.embed_tokens(params, toks[-1],
                                                        cfg)
                    )
                    dl = llama.project_logits(cfg, params, hh[:, None, :])
                    toks.append(
                        jnp.argmax(dl[:, 0, :], axis=-1).astype(jnp.int32)
                    )
                return jnp.stack(toks, axis=1)                   # [B, K+1]

            def accept_chain(chunk, target_pred, ks, forced, lens, caps,
                             offs):
                # longest matching prefix (greedy match) bounded by the
                # slot's selected depth; the oracle (forced >= 0)
                # overrides the match — cost stays real, only the
                # decision is forced. Clamped so committed + pending
                # stays inside block coverage.
                match = (chunk[:, 1:] == target_pred[:, :-1]).astype(
                    jnp.int32
                ) * (offs[:, 1:] <= ks[:, None]).astype(jnp.int32)
                n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                n_acc = jnp.where(
                    forced >= 0, jnp.minimum(forced, ks), n_acc
                )
                return jnp.minimum(n_acc, jnp.maximum(caps - lens - 2, 0))

            def spec_rounds(params, dp, kv, core, h_last, tables, active,
                            caps, budgets, ks, forced_rounds, rounds, mode):
                # caps[b] = token positions the slot's reserved blocks
                # cover for the WHOLE dispatch; writes beyond drop to the
                # pad block, acceptance is clamped, and a row freezes when
                # its next window no longer fits (host re-reserves next
                # dispatch). budgets[b] = remaining max_new_tokens.
                # ks[b] = the slot's selected draft depth (= spec_k unless
                # adaptive); forced_rounds[r, b] = oracle accepted length
                # per round (-1 = real acceptance).
                keys, temps = core["keys"], core["temps"]
                top_ks, top_ps, stops = (
                    core["top_ks"], core["top_ps"], core["stops"]
                )
                offs = jnp.arange(spec_k + 1, dtype=jnp.int32)[None, :]

                def body(carry, forced):
                    kv, lens, pending, h, done, n_emit = carry
                    act = ~done
                    b = lens.shape[0]
                    # ---- draft phase
                    chunk = draft_chain(params, dp, pending, h)  # [B, K+1]

                    # ---- verify phase: one target pass over the chain.
                    # t0 (the pending token) commits its KV exactly as a
                    # vanilla step would; drafts write ahead of
                    # verification into reserved blocks (only up to the
                    # slot's selected depth — deeper columns are masked).
                    pos = lens[:, None] + offs
                    pos = jnp.where(
                        act[:, None] & (offs <= ks[:, None])
                        & (pos < caps[:, None]), pos, -1
                    )
                    kv_lens_after = jnp.where(
                        act, lens + ks + 1, 0
                    ).astype(jnp.int32)
                    out = fwd(
                        cfg, params, chunk, pos, kv, tables, kv_lens_after,
                        block_size=bs, last_only=False,
                    )
                    target_pred = jnp.argmax(out.logits, axis=-1).astype(
                        jnp.int32
                    )                                            # [B, K+1]

                    # ---- acceptance
                    n_acc = accept_chain(
                        chunk, target_pred, ks, forced, lens, caps, offs
                    )
                    bonus = jnp.take_along_axis(
                        target_pred, n_acc[:, None], axis=1
                    )[:, 0]
                    if mode == "mixed":
                        # sampled slots ride the same graph at one token
                        # per round: sample from the pending token's logits
                        # exactly as a vanilla step would (same key fold
                        # position), never accept drafts
                        sampled0 = sample_tokens_per_slot(
                            out.logits[:, 0, :], keys, lens + 1, temps,
                            top_ks, top_ps,
                        )
                        is_sampled = temps > 0.0
                        n_acc = jnp.where(is_sampled, 0, n_acc)
                        bonus = jnp.where(is_sampled, sampled0, bonus)

                    # ---- ordered emission [B, K+1]: accepted drafts then
                    # the bonus; -1 pads the rejected tail. The host
                    # replays stop/budget truncation from this record; the
                    # device mirrors it below only to gate later rounds.
                    acc_pad = jnp.concatenate(
                        [chunk[:, 1:], jnp.zeros((b, 1), jnp.int32)],
                        axis=1,
                    )
                    emitted = jnp.where(
                        offs < n_acc[:, None], acc_pad,
                        jnp.where(offs == n_acc[:, None],
                                  bonus[:, None], -1),
                    )
                    emitted = jnp.where(act[:, None], emitted, -1)

                    # ---- device stop/budget masking (gates later rounds;
                    # same construction as the tree decoder's scan)
                    is_stop = (
                        (emitted[:, :, None] == stops[:, None, :]).any(-1)
                        & (emitted >= 0)
                    )
                    cum = jnp.cumsum(is_stop.astype(jnp.int32), axis=1)
                    pre_stop = (cum - is_stop.astype(jnp.int32)) == 0
                    emit_j = (emitted >= 0) & pre_stop & ~is_stop
                    rank = jnp.cumsum(emit_j.astype(jnp.int32), axis=1) \
                        - emit_j.astype(jnp.int32)
                    emit_mask = emit_j & (
                        n_emit[:, None] + rank < budgets[:, None]
                    )
                    n_emit2 = n_emit + emit_mask.sum(axis=1)
                    stop_hit = (is_stop & pre_stop).any(axis=1)

                    # ---- advance slot state; freeze rows whose next
                    # window no longer fits the reservation
                    new_h = jnp.take_along_axis(
                        out.hidden, n_acc[:, None, None].astype(jnp.int32),
                        axis=1,
                    )[:, 0, :]
                    lens2 = jnp.where(act, lens + n_acc + 1, lens)
                    pending2 = jnp.where(act, bonus, pending)
                    h2 = jnp.where(act[:, None], new_h, h)
                    done2 = done | (
                        act & (stop_hit | (n_emit2 >= budgets)
                               | (lens2 + 2 > caps))
                    )
                    rec = (emitted, n_acc, act)
                    return (out.kv, lens2, pending2, h2, done2, n_emit2), rec

                (kv, lens, pending, h_last, _done, _n), recs = jax.lax.scan(
                    body,
                    (kv, core["lens"], core["last"], h_last, ~active,
                     jnp.zeros_like(core["lens"])),
                    forced_rounds, length=rounds,
                )
                core = dict(core)
                core["lens"], core["last"] = lens, pending
                return kv, core, h_last, recs

            self._spec_rounds_fn = jax.jit(
                spec_rounds, static_argnames=("rounds", "mode"),
                donate_argnums=(2, 3, 4),
            )

            # --- spec RAGGED round (round 8): ONE dispatch whose row batch
            # mixes VERIFY rows (the draft chain + pending token,
            # q_len = 2..K+1, one per active decode slot) with admission
            # prefill-chunk rows — the spec engine's analogue of
            # ragged_round, so admission stops being a competing dispatch
            # for speculating engines too. One round per dispatch (the
            # host replays stop/budget bookkeeping from the emission
            # record, exactly like the fused scan's per-round replay);
            # pure-decode moments keep the deeper _spec_rounds_fn scan.
            # The LM head reads a GATHERED [B, K+1] hidden slice (chain
            # offsets for verify rows, the last valid chunk index for
            # admission rows) — never the full [B, S, V] chunk width.
            def spec_ragged_round(params, dp, kv, toks_pos, tables,
                                  lens_after, core, h_last, spec_row,
                                  sample_flag, ks, caps, forced, mode):
                keys, temps = core["keys"], core["temps"]
                top_ks, top_ps = core["top_ks"], core["top_ps"]
                offs = jnp.arange(spec_k + 1, dtype=jnp.int32)[None, :]
                lens = core["lens"]
                b, s_w = toks_pos[0].shape

                # ---- draft + row merge: verify rows overwrite their
                # chunk columns with the chain; chunk rows keep toks_pos
                chunk = draft_chain(params, dp, core["last"], h_last)
                pos_spec = lens[:, None] + offs
                pos_spec = jnp.where(
                    spec_row[:, None] & (offs <= ks[:, None])
                    & (pos_spec < caps[:, None]), pos_spec, -1
                )
                pad = ((0, 0), (0, s_w - (spec_k + 1)))
                chain_w = jnp.pad(chunk, pad)
                pos_spec_w = jnp.pad(pos_spec, pad, constant_values=-1)
                token_ids = jnp.where(
                    spec_row[:, None], chain_w, toks_pos[0]
                )
                positions = jnp.where(
                    spec_row[:, None], pos_spec_w, toks_pos[1]
                )
                kv_lens_row = jnp.where(
                    spec_row, lens + ks + 1, lens_after
                ).astype(jnp.int32)
                out = fwd(
                    cfg, params, token_ids, positions, kv, tables,
                    kv_lens_row, block_size=bs, last_only=False,
                    with_logits=False,
                )

                # ---- gathered logits: chain offsets for verify rows, the
                # last valid index (forward_chunk's last_only rule) for
                # chunk rows — identical arithmetic to the split paths,
                # so greedy chunk rows stay byte-identical to
                # _plain_ragged_round's in-graph sample
                n_valid = jnp.sum((positions >= 0).astype(jnp.int32),
                                  axis=1)
                last_idx = jnp.maximum(n_valid - 1, 0)
                gidx = jnp.where(
                    spec_row[:, None],
                    jnp.minimum(offs, s_w - 1),
                    last_idx[:, None],
                )                                              # [B, K+1]
                hsel = jnp.take_along_axis(
                    out.hidden, gidx[:, :, None].astype(jnp.int32), axis=1
                )                                              # [B, K+1, H]
                logits = llama.project_logits(cfg, params, hsel)
                target_pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)

                # ---- acceptance (verify rows) + sample (chunk-final and
                # sampled rows). Sample positions: lens + 1 for verify
                # rows, lens_after for chunk rows — the split paths' key
                # folds exactly.
                n_acc = accept_chain(
                    chunk, target_pred, ks, forced, lens, caps, offs
                )
                bonus = jnp.take_along_axis(
                    target_pred, n_acc[:, None], axis=1
                )[:, 0]
                samp_pos = jnp.where(spec_row, lens + 1, lens_after)
                tok0 = sample_mode(
                    logits[:, 0, :], keys, samp_pos, temps, top_ks,
                    top_ps, mode,
                )
                if mode == "mixed":
                    # sampled slots ride the round at one token: sample
                    # from the pending token's logits, never accept drafts
                    is_sampled = temps > 0.0
                    n_acc = jnp.where(is_sampled & spec_row, 0, n_acc)
                    bonus = jnp.where(is_sampled, tok0, bonus)

                # ---- ordered emission record [B, K+1] for the host
                # replay: accepted drafts then the bonus; -1 pads
                acc_pad = jnp.concatenate(
                    [chunk[:, 1:], jnp.zeros((b, 1), jnp.int32)], axis=1
                )
                emitted = jnp.where(
                    offs < n_acc[:, None], acc_pad,
                    jnp.where(offs == n_acc[:, None], bonus[:, None], -1),
                )
                emitted = jnp.where(spec_row[:, None], emitted, -1)

                # ---- advance device state: verify rows commit n_acc + 1
                # and carry the bonus pending; chunk-final rows commit
                # their sampled first token; intermediate chunks only
                # wrote KV
                new_h = jnp.take_along_axis(
                    hsel, n_acc[:, None, None].astype(jnp.int32), axis=1
                )[:, 0, :]
                sampled = sample_flag > 0
                core = dict(core)
                core["lens"] = jnp.where(
                    spec_row, lens + n_acc + 1,
                    jnp.where(sampled, lens_after, lens),
                )
                core["last"] = jnp.where(
                    spec_row, bonus,
                    jnp.where(sampled, tok0, core["last"]),
                )
                h2 = jnp.where(spec_row[:, None], new_h, h_last)
                return out.kv, core, h2, tok0, emitted, n_acc

            self._spec_ragged_round_fn = jax.jit(
                spec_ragged_round, static_argnames=("mode",),
                donate_argnums=(2, 6, 7),
            )

            def unpack_spec_sched(si):
                # one packed upload per spec ragged round: tables,
                # spec_row, sample_flag, ks, caps, forced
                return (si[:, :m], si[:, m] > 0, si[:, m + 1],
                        si[:, m + 2], si[:, m + 3], si[:, m + 4])

            self._unpack_spec_sched_fn = jax.jit(unpack_spec_sched)

        state_pools = self._state_kind[1]

        def apply_ops(kv, srcs, dsts):
            # page copies (CoW): dst = -1 entries are dropped. Scale pools
            # (int8 KV) copy with their pages — a page without its scale is
            # garbage
            # (a state pool has rows, not pages: it is no operand of a copy)
            # (nor is the window kind's pool of a model of mixed kinds: a
            # copy-on-write is the full kind's, whose ids these are)
            return {
                name: pool if name in state_pools
                or name.endswith(llama.WINDOW_POOLS)
                else pool.at[:, dsts].set(pool[:, srcs], mode="drop")
                for name, pool in kv.items()
            }

        self._apply_ops_fn = jax.jit(apply_ops, donate_argnums=(0,))

    # ------------------------------------------------------- device helpers

    def _pack_core(self) -> Tuple[np.ndarray, np.ndarray]:
        b = len(self.slots)
        ci = np.zeros((b, _CORE_I_COLS), np.int32)
        ci[:, 0] = self._last_tokens
        ci[:, 1] = self._kv_lens
        ci[:, 2:4] = self._slot_keys.view(np.int32)
        ci[:, 4:4 + MAX_STOP_IDS] = self._stop_ids
        ci[:, 4 + MAX_STOP_IDS] = self._top_ks
        cf = np.stack([self._temps, self._top_ps], axis=1).astype(np.float32)
        return ci, cf

    def _sync_core(self, keep: Optional[np.ndarray] = None
                   ) -> Dict[str, jax.Array]:
        """Upload host slot mirrors to device — only when a host-initiated
        change (admission / adopt / error recovery) made them stale. Decode
        rounds advance the device copy in-graph, so steady-state serving
        never re-uploads. ``keep``: the rows of a scan that is unread, whose
        ``last`` and ``lens`` the mirrors lag behind: those stay the
        device's (``merge_core``), every other entry is the host's."""
        if self._core_dirty or self._dev_core is None:
            ci, cf = self._pack_core()
            if keep is not None and self._dev_core is not None:
                self._dev_core = self._merge_core_fn(
                    self._dev_core, ci, cf, keep)
            else:
                self._dev_core = self._unpack_core_fn(ci, cf)
            self._core_dirty = False
        return self._dev_core

    def _sched_arrays(
        self, active_mask: np.ndarray, budgets: np.ndarray
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Per-round scheduling state (block tables, active mask, budgets)
        as ONE packed upload — tables grow most rounds, so these always ship."""
        mm = self._table_cols
        si = np.zeros((len(self.slots), mm + 2), np.int32)
        si[:, :mm] = self._block_tables
        si[:, mm] = active_mask
        si[:, mm + 1] = budgets
        return self._unpack_sched_fn(si)

    def lower_serving_graphs(
        self, decode_steps: Sequence[int], ragged_widths: Sequence[int],
    ) -> Dict[str, Any]:
        """The batcher's two round graphs, lowered from the engine's own
        jitted functions with the operands a round passes them:
        ``decode_multi`` at each scan length and ``ragged_round`` at every
        packed length a round can reach while its widest prompt piece is
        within ``max(ragged_widths)`` (concurrent admissions included: up
        to ``max_batch_size`` rows of that width), all-greedy. What a
        graph will run is read from the lowered text (``kernel_name =
        "..."`` marks a Pallas call), and ``.compile()`` on an entry
        stores the program in the persistent compile cache, where the
        first real round finds it. With a scan length comes
        ``chain_sched``, the small program that schedules a scan
        dispatched behind an unread one (one program for every length),
        which is also run once here so that it is in memory; with the
        round graphs, where scans come too, the two that put a round
        behind an unread scan: ``merge_core`` and, a packed length,
        ``chain_round[Tp=...]``, run once as well. Plain (non-speculative)
        engines; call while no round is in flight. The tracing and the
        lowering run with their frames in one chunk of the thread's Python
        stack (``utils/device.roomy_stack``): no call of theirs sits on a
        chunk's edge, whichever thread this is and however deep."""
        return roomy_stack(
            lambda: self._lower_serving_graphs(decode_steps, ragged_widths))

    def _lower_serving_graphs(
        self, decode_steps: Sequence[int], ragged_widths: Sequence[int],
    ) -> Dict[str, Any]:
        self.collect_scan()
        b = len(self.slots)
        core = self._sync_core()
        tables, active, budgets = self._sched_arrays(
            np.zeros((b,), bool), np.zeros((b,), np.int32)
        )
        out: Dict[str, Any] = {}
        for t in decode_steps:
            name = f"decode_multi[T={t}]"
            with self._lowering(name, "decode_multi", steps=int(t)):
                out[name] = self._decode_multi_fn.lower(
                    self.params, self._scan_kv(int(t)), core, tables, active,
                    budgets, int(t), "greedy",
                )
        if out:
            with self._lowering("chain_sched", "chain_sched"):
                out["chain_sched"] = self._chain_sched_fn.lower(core, budgets)
                self._chain_sched_fn(core, budgets)
        most, below = b * max(map(int, ragged_widths), default=0), 0
        chains = bool(out)      # a round goes out behind a scan, if any
        for tp in self._ragged_ladder():
            if most <= below:       # the rung below takes every such round
                break
            tok_d, lens_d = self._round_batch(
                np.zeros((4, tp), np.int32), np.zeros((2, b), np.int32))
            name = f"ragged_round[Tp={tp}]"
            with self._lowering(name, "ragged_round", positions=tp):
                out[name] = self._ragged_round_fn.lower(
                    self.params, self.kv, tok_d, tables, lens_d, core,
                    budgets, "greedy", self._ragged_shape(tp)[1],
                )
            if chains:
                patch = (core, np.zeros((4, tp), np.int32),
                         np.zeros((2, b), np.int32), budgets,
                         np.zeros((b, 2), np.int32))
                name = f"chain_round[Tp={tp}]"
                with self._lowering(name, "chain_round", positions=tp):
                    out[name] = self._chain_round_fn.lower(*patch)
                    self._chain_round_fn(*patch)
            below = tp
        if chains and below:
            ci, cf = self._pack_core()
            keep = np.zeros((b,), bool)
            with self._lowering("merge_core", "merge_core"):
                out["merge_core"] = self._merge_core_fn.lower(
                    core, ci, cf, keep)
                self._merge_core_fn(core, ci, cf, keep)
        st = self.get_stats()["startup"]
        log.info("start-up: init %.2fs (params %.2f, kv pools %.2f, jitted "
                 "functions %.2f); %d graphs lowered: trace %.2fs, lower "
                 "%.2fs, backend %.2fs", st["init_s"], st["params_s"],
                 st["kv_pools_s"], st["jit_fns_s"], len(st["graphs"]),
                 st["graphs_trace_s"], st["graphs_lower_s"],
                 st["graphs_backend_s"])
        return out

    @contextlib.contextmanager
    def _lowering(self, graph: str, kind: str, **attrs: int
                  ) -> Iterator[None]:
        """The block that lowers ``graph`` (and runs it, a small program):
        a ``dgi.engine.lower`` span, the compile log's stage events of the
        thread labelled with the graph while it is open, and on exit the
        graph's row of ``stats["startup"]["graphs"]``: ``wall_s`` and, from
        the labelled events, ``trace_s``, ``lower_s``, ``backend_s``. A
        ``with`` in the caller's body and no frame round what it times."""
        row = self._compile_log.label(graph)
        try:
            with flight.span("dgi.engine.lower", row, "wall_s", graph=graph,
                             kind=kind, **attrs):
                yield
        finally:
            self._compile_log.unlabel()
            self._startup["graphs"][graph] = row
            log.info("start-up: %s lowered in %.2fs (trace %.2f, lower "
                     "%.2f, backend %.2f)", graph, row["wall_s"],
                     row["trace_s"], row["lower_s"], row["backend_s"])

    def _scan_kv(self, num_steps: int) -> llama.KVPools:
        """The pools as a scan of ``num_steps`` steps takes them: a scan of
        several steps of a model with an indexer also takes the storage of
        its index keys in context order (donated with the pools; the caller
        takes it back out of what the scan returns). A single step lays
        its keys out a layer at a time, as a round does."""
        if self._scan_keys is None or num_steps == 1:
            return self.kv
        return {**self.kv, llama.INDEX_SCAN_KEYS: self._scan_keys}

    def _decode_mode(self) -> str:
        for i, s in enumerate(self.slots):
            if s is not None and s.finish_reason is None \
                    and not s.prefilling and self._temps[i] > 0:
                return "mixed"
        return "greedy"

    def _invalidate_device_state(self) -> None:
        """A failed donated call may have consumed the device core buffers —
        rebuild from host mirrors on next use. The speculative draft hidden
        rebuilds as zeros: that only lowers the next step's acceptance,
        never correctness (emission is always target-verified)."""
        self._dev_core = None
        self._core_dirty = True
        self._dev_spec_h = None

    def _spec_h_device(self) -> jax.Array:
        """Per-slot last-verified hidden for the draft head, device-resident
        between rounds; rebinds/invalidations zero the affected rows."""
        if self._dev_spec_h is None:
            self._dev_spec_h = jnp.zeros(
                (len(self.slots), self.model_cfg.hidden_size), self.dtype
            )
            self._spec_h_zero.clear()
        elif self._spec_h_zero:
            # fixed-shape mask multiply, NOT .at[rows].set — a dynamic row
            # list would compile one scatter per distinct stale-set size
            keep = np.ones((len(self.slots), 1), np.float32)
            keep[sorted(self._spec_h_zero)] = 0.0
            self._dev_spec_h = self._dev_spec_h * jnp.asarray(
                keep, self.dtype
            )
            self._spec_h_zero.clear()
        return self._dev_spec_h

    def _apply_pending(self) -> None:
        ops = self.manager.take_pending_ops()
        if ops.empty:
            return
        # downloads FIRST: an evicted block's id is about to be reused, so
        # its page must reach the host store before any copy/upload/prefill
        # can overwrite it
        for bid, key in ops.downloads:
            k = np.asarray(self.kv["k"][:, bid])
            v = np.asarray(self.kv["v"][:, bid])
            scale_page = None
            if "k_scale" in self.kv:
                # an int8 page without its scale is garbage: spill them as
                # a pair (manager stores the scale under the paired key)
                ks = np.asarray(self.kv["k_scale"][:, bid])
                vs = np.asarray(self.kv["v_scale"][:, bid])
                scale_page = np.stack([ks, vs], axis=1)
            self.manager.store_spilled(
                key, np.stack([k, v], axis=1), scale_page
            )
        if ops.copies:
            n = len(ops.copies)
            bucket = next(c for c in _COPY_BUCKETS if c >= n) if n <= _COPY_BUCKETS[-1] else n
            srcs = np.zeros((bucket,), np.int32)
            # pad with an OUT-OF-RANGE id (num_blocks): -1 would wrap to the
            # last block instead of being dropped
            dsts = np.full((bucket,), self.num_blocks, np.int32)
            for i, (s, d) in enumerate(ops.copies):
                srcs[i], dsts[i] = s, d
            self.kv = self._apply_ops_fn(self.kv, jnp.asarray(srcs), jnp.asarray(dsts))
        for dst, host_kv in ops.uploads:
            k = jnp.asarray(host_kv[:, 0], dtype=self.kv_dtype)
            v = jnp.asarray(host_kv[:, 1], dtype=self.kv_dtype)
            self.kv = {
                **self.kv,
                "k": self.kv["k"].at[:, dst].set(k),
                "v": self.kv["v"].at[:, dst].set(v),
            }
        for dst, host_sc in ops.scale_uploads:
            ks = jnp.asarray(host_sc[:, 0], jnp.bfloat16)
            vs = jnp.asarray(host_sc[:, 1], jnp.bfloat16)
            self.kv = {
                **self.kv,
                "k_scale": self.kv["k_scale"].at[:, dst].set(ks),
                "v_scale": self.kv["v_scale"].at[:, dst].set(vs),
            }

    def _bucket_len(self, n: int) -> int:
        for b in self.cfg.prefill_buckets:
            if b >= n:
                return b
        raise ValueError(
            f"prompt chunk of {n} tokens exceeds largest prefill bucket "
            f"{self.cfg.prefill_buckets[-1]}"
        )

    # -------------------------------------------------------- slot API

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def num_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    # ------------------------------------------- KV pressure + preemption

    def _signal_pressure(self, source: str, slots: Sequence[int] = (),
                         requests: int = 0) -> None:
        """Record a step-boundary KV-pressure event for the scheduler. One
        ``KVPressure`` accumulates per engine round; "decode" outranks
        "admission" (decode pressure blocks progress, admission can wait)."""
        if self._pressure is None:
            self._pressure = KVPressure(source=source)
            self.stats["kv_pressure_events"] += 1
        elif source == "decode":
            self._pressure.source = "decode"
        for sl in slots:
            if sl not in self._pressure.slots:
                self._pressure.slots.append(sl)
        self._pressure.requests += requests

    def request_fits_pool(self, request: InferenceRequest) -> bool:
        """Static admissibility check: can the request's PROMPT (plus its
        pending first token, and the speculative verify window when the
        engine speculates) fit an idle pool? A request failing this can
        never even be admitted — the one case a capacity error
        legitimately reaches the client immediately.

        Deliberately NOT a worst-case (prompt + max_new_tokens) test:
        max_new_tokens is a cap, not a promise — most generations stop at
        EOS far earlier, so pre-rejecting on the cap would break every
        generous-cap/short-output workload that served fine. Growth beyond
        the pool is a DYNAMIC condition the preemption machinery absorbs,
        bounded by the scheduler's preemption/resume caps."""
        return self._fits_empty_pool(len(request.prompt_token_ids or []) + 1)

    def _fits_empty_pool(self, tokens: int) -> bool:
        """One fit rule for admission AND resume: ``tokens`` context (+
        the speculative verify window) against the whole pool minus the
        reserved pad block — the two callers must never disagree about
        what fits."""
        if self.cfg.speculative is not None:
            tokens += self.cfg.speculative.num_draft_tokens + 1
        need = -(-tokens // self.cfg.block_size)
        return need <= self.num_blocks - 1   # block 0 is the reserved pad

    def resume_fits_pool(self, pre: "PreemptedSequence") -> bool:
        """Static admissibility of a RESUME: the preempted sequence's
        prompt + already-generated context + pending token (+ the spec
        verify window) against an EMPTY pool. Only a sequence failing
        this can never be re-admitted — an allocation failure on a
        statically-fitting resume is a dynamic condition (cache eviction
        in flight, a transient allocator fault injected by chaos, another
        admission racing) and must be retried, not aborted: the fleet
        chaos suite showed a 2-second injected pressure storm permanently
        killing requests the pool could trivially hold a moment later."""
        return self._fits_empty_pool(pre.prompt_len + len(pre.generated) + 1)

    @property
    def pressure_pending(self) -> bool:
        """A pressure signal waits for ``take_pressure`` (a look, for a
        caller that must not consume it yet)."""
        return self._pressure is not None

    def take_pressure(self) -> Optional[KVPressure]:
        """Consume the pending pressure signal (None when the last round
        ran unpressured). The scheduler calls this after every engine round
        / admission attempt and reacts per its preemption policy."""
        p, self._pressure = self._pressure, None
        return p

    def snapshot_slot(self, slot: int) -> PreemptedSequence:
        """Non-destructive checkpoint of a LIVE slot: the same portable state
        :meth:`preempt_slot` captures, but the slot keeps decoding. This is
        the worker-failover checkpoint source — the snapshot rides to the
        control plane and, should this worker die, :meth:`resume` on a
        replacement engine recomputes the uncached suffix and continues
        byte-identically (greedy) / seed-stably (sampled).

        ``generated`` may include the pending token (sampled, KV unwritten);
        resume treats the whole list as prompt suffix and recomputes, so the
        distinction never leaks. Mid-prefill and finished slots have nothing
        useful to checkpoint and are rejected.

        The one reader that does NOT read an unread scan first: it only
        copies host state, the worker calls it from its heartbeat thread
        beside the engine thread, and a checkpoint that lags by a scan is
        the checkpoint of a moment ago, as valid as any."""
        s = self.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is empty")
        if s.prefilling:
            raise ValueError(f"slot {slot} is mid-prefill")
        if s.finish_reason is not None:
            raise ValueError(f"slot {slot} already finished")
        return PreemptedSequence(
            request=s.request,
            prompt_len=s.prompt_len,
            generated=list(s.generated),
            slot_key=(int(self._slot_keys[slot, 0]),
                      int(self._slot_keys[slot, 1])),
            start_time=s.start_time,
            first_token_time=s.first_token_time,
            cached_tokens=s.cached_tokens,
        )

    def preempt_slot(self, slot: int) -> PreemptedSequence:
        """Freeze a RUNNING sequence and release its device blocks — the
        recovery half of KV-pressure handling. Full blocks are freed
        through ``free_sequence(cache=True)``: they park in the prefix
        cache and, when evicted under further pressure, spill to the
        host/remote tiers — so :meth:`resume` restores them via the radix
        index or ``_probe_spill`` instead of recomputing the whole context.

        The sequence's pending token (sampled but its KV not yet written)
        is dropped from the manager's token log first, so only fully
        written blocks can be cached/spilled; it stays in ``generated`` and
        is recomputed by the resume prefill."""
        self.collect_scan()
        s = self.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is empty")
        if s.prefilling:
            raise ValueError(
                f"slot {slot} is mid-prefill (chunked admission) — abort it "
                "with abort_chunked instead of preempting"
            )
        if s.finish_reason is not None:
            raise ValueError(
                f"slot {slot} already finished ({s.finish_reason}) — use "
                "finish_slot"
            )
        seq = self.manager.seq_tokens[s.seq_id]
        committed = int(self._kv_lens[slot])
        while len(seq) > committed:
            seq.pop()
        # drop reserved tail blocks (spec windows, multi-step horizons) so
        # the freed footprint is exactly the committed context
        self.manager.trim_reserved(s.seq_id)
        pre = PreemptedSequence(
            request=s.request,
            prompt_len=s.prompt_len,
            generated=list(s.generated),
            slot_key=(int(self._slot_keys[slot, 0]),
                      int(self._slot_keys[slot, 1])),
            start_time=s.start_time,
            first_token_time=s.first_token_time,
            cached_tokens=s.cached_tokens,
        )
        self.manager.free_sequence(s.seq_id, cache=True)
        self.slots[slot] = None
        self._kv_lens[slot] = 0
        self._core_dirty = True
        if self.cfg.speculative is not None:
            self._spec_h_zero.add(slot)
        self.stats["preemptions"] += 1
        return pre

    def resume(self, pre: PreemptedSequence,
               slot: Optional[int] = None) -> int:
        """Re-admit a preempted sequence through the normal allocation +
        prefill path. The resume prompt is the original prompt plus every
        generated token: ``allocate_sequence`` restores whatever prefix the
        cache/spill tiers still hold and the prefill recomputes only the
        uncached suffix. Greedy continuations are byte-identical to a
        never-preempted run; sampled continuations are seed-stable because
        the slot's PRNG key is restored verbatim and the sampler folds in
        the absolute position.

        Raises OutOfBlocksError (state untouched) when the pool still
        cannot hold the sequence — the scheduler retries later."""
        self.collect_scan()
        sp = pre.request.sampling
        remaining = sp.max_new_tokens - len(pre.generated)
        if remaining <= 0:
            raise ValueError("preempted sequence has no remaining budget")
        token_ids = list(pre.request.prompt_token_ids or []) + \
            list(pre.generated)
        # the preserved key words round-trip through SamplingParams.seed:
        # _bind_slot unpacks PRNGKey-style [seed >> 32, seed & 0xffffffff]
        seed = (pre.slot_key[0] << 32) | pre.slot_key[1]
        derived = replace(
            pre.request,
            prompt_token_ids=token_ids,
            session_id=None,
            sampling=replace(sp, max_new_tokens=remaining, seed=seed),
        )
        slot = self.submit(derived, slot=slot)
        s = self.slots[slot]
        assert s is not None
        # restore the client-visible identity: the ORIGINAL request (decode
        # budgets are max_new_tokens minus the FULL generated list), prompt
        # accounting, and the TTFT clock origin
        s.request = pre.request
        s.prompt_len = pre.prompt_len
        s.generated = list(pre.generated) + s.generated
        s.cached_tokens = pre.cached_tokens
        s.start_time = pre.start_time
        if pre.first_token_time is not None:
            s.first_token_time = pre.first_token_time
        self.stats["requests"] -= 1          # not a new client request
        self.stats["resumes"] += 1
        return slot

    def _validate_request(self, request: InferenceRequest) -> List[int]:
        token_ids = request.prompt_token_ids
        if not token_ids:
            raise ValueError("request has no prompt_token_ids")
        if len(token_ids) + request.sampling.max_new_tokens > self.cfg.max_seq_len:
            raise RequestOverLength(
                f"prompt {len(token_ids)} + max_new {request.sampling.max_new_tokens}"
                f" exceeds max_seq_len {self.cfg.max_seq_len}"
            )
        return token_ids

    def submit(self, request: InferenceRequest, slot: Optional[int] = None) -> int:
        """Admit a request into a slot: allocate blocks (prefix-cache aware),
        run prefill, sample the first token. Returns the slot index."""
        self.collect_scan()
        if slot is None:
            free = self.free_slots()
            if not free:
                raise RuntimeError("no free slots")
            slot = free[0]
        if self.slots[slot] is not None:
            raise RuntimeError(f"slot {slot} busy")
        token_ids = self._validate_request(request)
        seq_id = request.session_id or uuid.uuid4().hex
        try:
            blocks, cached = self.manager.allocate_sequence(seq_id, token_ids)
        except OutOfBlocksError:
            # allocate_sequence scrubbed its own staging: state is clean,
            # the caller sees a pressure signal + typed error, never a
            # half-admitted sequence
            self._signal_pressure("admission", requests=1)
            raise
        try:
            return self._submit_allocated(request, slot, seq_id, token_ids, cached)
        except Exception as exc:
            self.slots[slot] = None
            self._kv_lens[slot] = 0
            self.manager.free_sequence(seq_id, cache=False)
            if isinstance(exc, OutOfBlocksError):
                self._signal_pressure("admission", requests=1)
            raise

    def submit_batch(self, requests: Sequence[InferenceRequest],
                     partial: bool = False) -> List[int]:
        """Admit several requests at once: same-bucket prefills run as ONE
        batched device call (full batch width, inactive rows masked with
        position -1). Per-request prefill serializes admission behind one
        device call each — this path admits a whole wave in one call. Long
        prompts that need chunking fall back to the per-request chunked
        path.

        ``partial``: when KV blocks run out mid-wave, admit the prefix of
        the wave that DID allocate and return only its slots (a pressure
        signal marks the deferred tail) instead of rolling the whole wave
        back — the batcher requeues the tail with no client-visible error.
        With ``partial=False`` (default) exhaustion rolls back the whole
        wave and raises ``OutOfBlocksError`` after signalling pressure;
        state is clean either way."""
        self.collect_scan()
        if not requests:
            return []
        free = self.free_slots()
        if len(requests) > len(free):
            raise RuntimeError(
                f"{len(requests)} requests > {len(free)} free slots"
            )
        max_bucket = self.cfg.prefill_buckets[-1]
        slots_out: List[int] = []
        grouped: Dict[int, List[Tuple[InferenceRequest, int, str, List[int], int]]] = {}
        admitted: List[Tuple[int, str]] = []  # (slot, seq_id) for cleanup
        stats_snapshot = {
            k: self.stats[k]
            for k in ("requests", "prefill_tokens", "prefill_calls",
                      "generated_tokens")
        }
        mgr_stats_snapshot = dict(self.manager.stats.__dict__)
        downloads_before = len(self.manager.pending.downloads)

        def _rollback() -> None:
            for slot, seq_id in admitted:
                self.slots[slot] = None
                self._kv_lens[slot] = 0
                if seq_id in self.manager.seq_blocks:
                    self.manager.free_sequence(seq_id, cache=False)
            # pending device ops staged for now-freed blocks must not apply
            # later: a freed id gets reallocated, and an orphaned upload or
            # CoW copy would clobber the new owner's pages (allocate_sequence
            # scrubs its own staging on OutOfBlocksError the same way).
            # Downloads are NOT filtered: a spill-on-evict download's source
            # block is popped from metas when staged, and dropping it would
            # lose the evicted page's only copy.
            alive = self.manager.metas
            p = self.manager.pending
            p.uploads = [u for u in p.uploads if u[0] in alive]
            p.scale_uploads = [u for u in p.scale_uploads if u[0] in alive]
            p.copies = [
                c for c in p.copies if c[0] in alive and c[1] in alive
            ]
            # stats must not double-count requests a retry will re-admit —
            # engine counters and the manager's cache stats alike. Spills
            # staged by this wave survive the rollback (their downloads are
            # kept above), so those stay counted.
            kept_wave_spills = len(p.downloads) - downloads_before
            self.stats.update(stats_snapshot)
            self.manager.stats.__dict__.update(mgr_stats_snapshot)
            self.manager.stats.spills += max(kept_wave_spills, 0)

        try:
            for request, slot in zip(requests, free):
                token_ids = self._validate_request(request)
                seq_id = request.session_id or uuid.uuid4().hex
                try:
                    _, cached = self.manager.allocate_sequence(
                        seq_id, token_ids
                    )
                    if self._window_blocks and \
                            len(token_ids) - cached <= max_bucket:
                        # a wave writes the whole prompt in one call: the
                        # window kind's blocks for it, or nothing of it
                        try:
                            self.manager.extend_window(
                                seq_id, len(token_ids))
                        except OutOfBlocksError:
                            self.manager.free_sequence(seq_id, cache=False)
                            raise
                except OutOfBlocksError:
                    # step-boundary pressure: allocate_sequence scrubbed its
                    # own staging, nothing of THIS request is admitted
                    deferred = len(requests) - len(slots_out)
                    self._signal_pressure("admission", requests=deferred)
                    if not partial:
                        raise
                    break   # admit the prefix that allocated; tail deferred
                admitted.append((slot, seq_id))
                slots_out.append(slot)
                n_fresh = len(token_ids) - cached
                if n_fresh > max_bucket or (
                    self.cfg.kv_seq_sharded and cached > 0
                ):
                    # chunked long-prompt path (per request). Sharded pools
                    # also route CACHED prompts here: the batched
                    # prefill graph attends dense over the chunk only, which
                    # cannot see a cached prefix — the chunked path reads it
                    # through the sharded-pool chunk op.
                    self._submit_allocated(request, slot, seq_id, token_ids, cached)
                    continue
                bucket = self._bucket_len(max(n_fresh, 1))
                grouped.setdefault(bucket, []).append(
                    (request, slot, seq_id, token_ids, cached)
                )

            b = len(self.slots)
            for bucket, items in sorted(grouped.items()):
                self._apply_pending()
                toks_pos = np.zeros((2, b, bucket), np.int32)
                toks_pos[1] = -1
                lens = np.zeros((b,), np.int32)
                wave = np.zeros((b,), bool)
                for request, slot, seq_id, token_ids, cached in items:
                    s = _Slot(request=request, seq_id=seq_id,
                              prompt_len=len(token_ids),
                              cached_tokens=cached)
                    self._bind_slot(slot, s, kv_len=len(token_ids))
                    fresh = token_ids[cached:]
                    n = len(fresh)
                    toks_pos[0, slot, :n] = fresh
                    toks_pos[1, slot, :n] = np.arange(cached, cached + n)
                    lens[slot] = cached + n
                    wave[slot] = True
                    self.stats["prefill_tokens"] += n
                mode = (
                    "greedy"
                    if all(it[0].sampling.temperature <= 0 for it in items)
                    else "mixed"
                )
                core = self._sync_core()
                first, self._dev_core, self.kv = self._prefill_batch_fn(
                    self.params, self.kv, toks_pos, self._block_tables,
                    lens, core, wave, mode,
                )
                self.stats["prefill_calls"] += 1
                first_np = np.asarray(first)
                for request, slot, seq_id, token_ids, cached in items:
                    self._record_token(
                        slot, int(first_np[slot]), device_synced=True
                    )
        except Exception as exc:
            # a failed wave must not leak: every sequence this call admitted
            # (bound or not) is freed so a retry sees clean state
            self._invalidate_device_state()
            _rollback()
            if isinstance(exc, OutOfBlocksError):
                self._signal_pressure(
                    "admission", requests=len(requests)
                )
            raise
        return slots_out

    def _bind_slot(self, slot: int, s: "_Slot", kv_len: int) -> None:
        """Install slot state (block table, committed length, sampling, stop
        ids) for a sequence already allocated in the manager. Shared by the
        prefill submit path and the PD-handoff adopt path so the two can
        never drift. Writes this slot's rows of the host mirrors and makes
        no device call: beside an unread scan that is safe for a slot the
        scan does not hold, and only then is the scan left unread."""
        self._collect_unless_free(slot)
        self.slots[slot] = s
        self._block_tables[slot] = self.manager.block_table_for(
            s.seq_id, self.cfg.max_blocks_per_seq
        )
        self._kv_lens[slot] = kv_len
        if self._state_rows:
            self.manager.bind_state(slot)
        if self._window_blocks:
            for name in ("prefix_hits_cut_by_window",
                         "prefix_hit_tokens_cut_by_window"):
                self.stats[name] = getattr(self.manager.stats, name)
        sp = s.request.sampling
        self._temps[slot] = sp.temperature
        self._top_ks[slot] = sp.top_k
        self._top_ps[slot] = sp.top_p
        self._stop_ids[slot] = -1
        # ignore_eos (bench/oracle workloads): no stop ids at all — the
        # generation runs to its max_new_tokens budget
        stop = [] if sp.ignore_eos else list(sp.stop_token_ids)[:MAX_STOP_IDS]
        if self.eos_token_id is not None and self.eos_token_id not in stop \
                and len(stop) < MAX_STOP_IDS and not sp.ignore_eos:
            stop.append(self.eos_token_id)
        self._stop_ids[slot, : len(stop)] = stop
        # host-side key material (no device round-trip on the admission hot
        # path): threefry PRNGKey(seed) is [seed >> 32, seed & 0xffffffff]
        if sp.seed is not None:
            seed_val = int(sp.seed)
            self._slot_keys[slot] = (
                (seed_val >> 32) & 0xFFFFFFFF, seed_val & 0xFFFFFFFF
            )
        else:
            self._slot_keys[slot] = self._host_rng.integers(
                0, 2**32, size=2, dtype=np.uint32
            )
        self._core_dirty = True
        if self.cfg.speculative is not None:
            # fresh occupant: its draft feature starts at zeros (stale
            # hidden would only cost acceptance, never correctness — but
            # deterministic stats want a clean start), its adaptive-depth
            # EMA restarts optimistic at K, and its oracle dither resets
            self._spec_h_zero.add(slot)
            self._spec_k_ema[slot] = float(
                self.cfg.speculative.num_draft_tokens
            )
            self._spec_oracle_acc[slot] = 0.0
        self.stats["requests"] += 1

    def _submit_allocated(self, request: InferenceRequest, slot: int,
                          seq_id: str, token_ids: List[int], cached: int) -> int:
        self._apply_pending()
        s = _Slot(request=request, seq_id=seq_id, prompt_len=len(token_ids),
                  cached_tokens=cached)
        self._bind_slot(slot, s, kv_len=len(token_ids))

        # CHUNKED prefill of the uncached suffix: prompts longer than the
        # largest bucket split into full-bucket pieces + a bucketed tail, so
        # long contexts need no giant compile and no dynamic shapes
        # (reference delegates this to vLLM's chunked-prefill flag,
        # llm_vllm.py:61 — first-party here). Each chunk attends to all
        # prior context via kv_len_after; only the final chunk's logits
        # (the last prompt token) are consumed.
        fresh = token_ids[cached:]
        max_bucket = self.cfg.prefill_buckets[-1]
        off = cached
        mode = "greedy" if request.sampling.temperature <= 0 else "mixed"
        if (
            self._seq_axis > 1
            and cached == 0
            and len(fresh) > max_bucket
        ):
            # sequence-parallel long-context prefill (mesh seq axis)
            first = self._prefill_seq_parallel(slot, fresh, mode)
            tok = int(np.asarray(first)[0])
            self._record_token(slot, tok)
            return slot
        first = None
        while True:
            piece = fresh[: max_bucket]
            fresh = fresh[max_bucket:]
            is_last = not fresh
            first = self._prefill_one_chunk(slot, piece, off, is_last, mode)
            off += len(piece)
            if is_last:
                break
            self._release_prefill_window(slot, off)

        tok = int(np.asarray(first)[0])
        self._record_token(slot, tok)
        return slot

    def _prefill_seq_parallel(self, slot: int, fresh: List[int], mode: str):
        """Whole-prompt seq-sharded prefill (mesh ``seq`` axis): ring/ulysses
        attention spreads the S² work over the axis; KV pages land in the
        same paged pools decode reads. Pad length buckets to multiples of
        (seq_axis x block_size) so long prompts compile per bucket, not per
        length."""
        n = len(fresh)
        step = self._seq_axis * max(self.cfg.block_size, 16)
        padded = -(-n // step) * step
        toks_pos = np.zeros((2, 1, padded), np.int32)
        toks_pos[1] = -1
        toks_pos[0, 0, :n] = fresh
        toks_pos[1, 0, :n] = np.arange(n)
        first, self.kv = self._prefill_seq_fn(
            self.params, self.kv, toks_pos,
            self._block_tables[slot : slot + 1],
            np.asarray([n], np.int32),
            self._slot_keys[slot : slot + 1],
            self._temps[slot : slot + 1],
            self._top_ks[slot : slot + 1],
            self._top_ps[slot : slot + 1],
            mode,
        )
        self.stats["prefill_tokens"] += n
        self.stats["prefill_calls"] += 1
        self.stats["seq_parallel_prefills"] = (
            self.stats.get("seq_parallel_prefills", 0) + 1
        )
        return first

    def _prefill_one_chunk(self, slot: int, piece: List[int], off: int,
                           is_last: bool, mode: str):
        """One single-sequence prefill chunk. The final chunk samples the
        first token IN-GRAPH (an eager sampler here is ~15 dispatches of
        its own); intermediate chunks skip
        the LM head entirely."""
        n = len(piece)
        if not self._extend_window(slot, off + n):
            raise OutOfBlocksError(
                "the window kind's pool cannot hold the next piece")
        if self._state_rows:
            return self._prefill_piece_packed(slot, piece, off, is_last, mode)
        bucket = (
            self._bucket_len(max(n, 1)) if is_last
            else self.cfg.prefill_buckets[-1]
        )
        toks_pos = np.zeros((2, 1, bucket), np.int32)
        toks_pos[1] = -1
        toks_pos[0, 0, :n] = piece
        toks_pos[1, 0, :n] = np.arange(off, off + n)
        # seq-sharded pools: a chunk with PRIOR context (cached prefix or an
        # earlier chunk) must read it through the sharded-pool chunk op; a
        # fresh first chunk keeps the cheaper dense path (off == 0 means
        # nothing precedes it)
        prefill_fn = self._prefill_chunk_fn
        if self.cfg.kv_seq_sharded and off > 0:
            prefill_fn = self._prefill_chunk_paged_fn
        first, self.kv = prefill_fn(
            self.params, self.kv, toks_pos,
            # a copy: the window release that follows the dispatch writes
            # this row of the mirror, and a CPU backend may read the
            # operand where it lies
            self._block_tables[slot : slot + 1].copy(),
            np.asarray([off + n], np.int32),
            self._slot_keys[slot : slot + 1],
            self._temps[slot : slot + 1],
            self._top_ks[slot : slot + 1],
            self._top_ps[slot : slot + 1],
            mode, is_last,
        )
        self.stats["prefill_tokens"] += n
        self.stats["prefill_calls"] += 1
        return first

    def _prefill_piece_packed(self, slot: int, piece: List[int], off: int,
                              is_last: bool, mode: str):
        """``_prefill_one_chunk`` where a batch row is a state row (a
        hybrid model): the piece goes out as the one segment of a packed
        round, in its own slot's row, not as row 0 of a one-row batch."""
        cap = self._ragged_chunk_cap()
        first = None
        for lo in range(0, len(piece), cap):
            part = piece[lo:lo + cap]
            last = is_last and lo + len(part) == len(piece)
            self._count_state_ragged(None, [len(part)])
            _tp, operands, round_mode, s_w, *_ = self._pack_ragged(
                [], [(slot, off + lo, part, last, mode)])
            try:
                self.kv, self._dev_core, toks, *_ = self._ragged_round_fn(
                    self.params, self.kv, *operands, round_mode, s_w)
            except Exception:
                self._invalidate_device_state()
                raise
            first = toks[slot:slot + 1]
            self.stats["prefill_tokens"] += len(part)
            self.stats["prefill_calls"] += 1
        # the caller records the token as not yet on the device's core
        self._core_dirty = True
        return first

    # ------------------------------------------- chunk-interleaved admission

    def submit_chunked_start(
        self, request: InferenceRequest, slot: Optional[int] = None
    ) -> ChunkedAdmission:
        """Begin a chunk-interleaved admission: allocate + bind the slot but
        run NO prefill yet. The slot is marked ``prefilling`` so decode
        rounds skip it until ``submit_chunked_step`` finishes the prompt.

        The one entry that may run beside an unread scan (``collect_scan``):
        a slot that is free now is no row of that scan, the blocks the scan
        writes were reserved when it was built, the prefix lookup and the
        allocation (which evicts only cached blocks nobody holds) read
        nothing its commit changes, pool operations queue on ``self.kv``
        behind it, and the mirrors written are this slot's rows. The pool
        the allocation finds then lacks what the read gives back, so beside
        an unread scan ``OutOfBlocksError`` signals no pressure: the caller
        tries again once the scan is read."""
        if slot is None:
            free = self.free_slots()
            if not free:
                raise RuntimeError("no free slots")
            slot = free[0]
        self._collect_unless_free(slot)
        if self.slots[slot] is not None:
            raise RuntimeError(f"slot {slot} busy")
        token_ids = self._validate_request(request)
        seq_id = request.session_id or uuid.uuid4().hex
        try:
            _, cached = self.manager.allocate_sequence(seq_id, token_ids)
        except OutOfBlocksError:
            if self._unread is None:
                self._signal_pressure("admission", requests=1)
            raise
        try:
            if self.manager.pending.downloads:
                # an evicted page's download waits for the device: read the
                # scan where that wait is counted
                self.collect_scan()
            self._apply_pending()
            s = _Slot(request=request, seq_id=seq_id,
                      prompt_len=len(token_ids), cached_tokens=cached,
                      prefilling=True)
            self._bind_slot(slot, s, kv_len=len(token_ids))
        except Exception:
            self.slots[slot] = None
            self._kv_lens[slot] = 0
            self.manager.free_sequence(seq_id, cache=False)
            raise
        return ChunkedAdmission(
            request=request, slot=slot, seq_id=seq_id,
            fresh=list(token_ids[cached:]), off=cached,
            mode="greedy" if request.sampling.temperature <= 0 else "mixed",
        )

    def submit_chunked_step(self, adm: ChunkedAdmission) -> bool:
        """Run ONE prefill chunk of an in-flight admission; True once the
        admission completed (first token sampled). Work per call is bounded
        by the largest bucket, so a scheduler can interleave decode rounds
        between calls and no active slot stalls longer than one chunk."""
        self.collect_scan()
        if adm.done:
            return True
        s = self.slots[adm.slot]
        if s is None or s.seq_id != adm.seq_id:
            raise RuntimeError("chunked admission slot was freed")
        max_bucket = self.cfg.prefill_buckets[-1]
        if len(adm.fresh) <= max_bucket:
            # the upcoming chunk is the LAST one: it samples the first
            # token, whose pending KV block must exist. Pre-reserve it NOW
            # so exhaustion is a step-boundary retry (pressure signal,
            # chunk not consumed, caller steps again once blocks free)
            # instead of OutOfBlocksError aborting a fully-prefilled
            # admission from inside _record_token.
            try:
                if self.manager.reserve_tokens(s.seq_id, 1):
                    self._block_tables[adm.slot] = \
                        self.manager.block_table_for(
                            s.seq_id, self.cfg.max_blocks_per_seq
                        )
            except OutOfBlocksError:
                self.manager.trim_reserved(s.seq_id)
                self._signal_pressure("admission", requests=1)
                return False
        if not self._extend_window(
                adm.slot, adm.off + min(len(adm.fresh), max_bucket)):
            return False
        self._apply_pending()
        piece = adm.fresh[: max_bucket]
        adm.fresh = adm.fresh[max_bucket:]
        is_last = not adm.fresh
        try:
            first = self._prefill_one_chunk(
                adm.slot, piece, adm.off, is_last, adm.mode
            )
        except Exception:
            self.abort_chunked(adm)
            raise
        adm.off += len(piece)
        if is_last:
            s.prefilling = False
            tok = int(np.asarray(first)[0])
            self._record_token(adm.slot, tok)
            adm.done = True
        else:
            self._release_prefill_window(adm.slot, adm.off)
        return adm.done

    def abort_chunked(self, adm: ChunkedAdmission) -> None:
        """Release a failed/cancelled chunked admission's slot and blocks."""
        self.collect_scan()
        s = self.slots[adm.slot]
        adm.done = True
        if s is None or s.seq_id != adm.seq_id:
            return
        self.slots[adm.slot] = None
        self._kv_lens[adm.slot] = 0
        self.manager.free_sequence(adm.seq_id, cache=False)
        self._core_dirty = True

    # ------------------------------------------------------- ragged rounds

    @property
    def supports_ragged(self) -> bool:
        """Ragged rounds serve every paged engine except seq-sharded
        pools (whose decode rows read through a dedicated shard_map op —
        the one remaining split path). Spec-integrated engines serve
        ragged since round 8: their rounds carry VERIFY rows
        (q_len = 2..K+1 — the draft chain plus the pending token) in
        place of plain decode rows, co-dispatched with admission
        prefill-chunk rows in the same invocation, committing 1..K+1
        accepted tokens per slot at the same step boundary."""
        return not self.cfg.kv_seq_sharded

    def ragged_round(
        self, admissions: Sequence[ChunkedAdmission] = (),
        chunk_caps: Optional[Dict[int, int]] = None,
    ) -> Dict[int, List[int]]:
        """One dispatch: every decoding slot advances a token and every
        admission a prompt piece. The plain round packs those live tokens
        on one axis of ``Tp`` entries, the ladder rung that takes them
        (``_ragged_shape``), so its dense work costs what the round holds;
        the speculative round keeps the ``[B, S]`` rectangle.

        ``chunk_caps``: optional per-admission prefill-token caps for
        THIS round, keyed by slot (the scheduler's per-round prefill
        budget — PR 17). A missing slot gets the full ``ragged_chunk``
        cap; a cap <= 0 skips the admission this round entirely (no row,
        no reservation — it retries next round). Chunked prefill is
        chunk-width-invariant, so any cap schedule yields byte-identical
        outputs; caps only shape WHEN prefill work lands.

        Where a plain scan is unread at entry (``decode_multi(...,
        ahead=True)`` left it, its admission ran beside it), the round goes
        out BEHIND it: its decode rows' token, position and liveness are
        taken on the device from where the scan leaves the core
        (``chain_round``), the scan is then read and committed, and the
        call returns THE SCAN'S tokens and leaves the round unread, for
        ``collect_scan`` (``round_unread``). The build, the dispatch and
        whatever the caller does with the scan's tokens so run while the
        device does. The engine decides that from what it holds: the core
        on the device and room to reserve behind the scan; short of either,
        and wherever nothing is unread, the call reads what is unread and
        then its own round, as it always did (a scan's tokens first)."""
        prev = self._unread
        if not (isinstance(prev, _UnreadScan) and self._dev_core is not None
                and self.cfg.speculative is None):
            prev = None
        out = {} if prev is not None else self.collect_scan()
        st = self.stats
        st["rounds"] += 1
        with flight.span("dgi.engine.ragged_round", round=st["rounds"],
                         steps=1) as sp:
            if self.cfg.speculative is not None:
                return self._spec_ragged_round(admissions, chunk_caps, sp)
            return self._merge(out, self._plain_ragged_round(
                admissions, chunk_caps, sp, prev))

    def _ragged_chunk_cap(self) -> int:
        """The most prompt tokens one admission runs in one ragged round."""
        return min(max(int(self.cfg.ragged_chunk), 1),
                   self.cfg.prefill_buckets[-1])

    def _ragged_ladder(self) -> List[int]:
        """The packed lengths ``Tp`` a plain ragged round runs at, one
        graph each: a quarter and a half of a piece for decode rows beside
        a short piece, one full piece beside every other row decoding, two
        of those, and every row a full piece (the ``[B, S]`` rectangle at
        its widest). On the v5e a round of the 7B dense model costs 17.6 /
        21.7 / 35.4 ms at the 64 / 128 / 264 rungs (PERF.md section 5, PR
        28): about what it holds from the third rung on, while the two
        short rungs sit on a floor, so finer rungs there buy little."""
        b, cap = len(self.slots), self._ragged_chunk_cap()
        one = -(-(cap + b - 1) // 8) * 8
        return sorted({min(max(t, 8), b * cap)
                       for t in (cap // 4, cap // 2, one, 2 * one, b * cap)})

    def _ragged_shape(self, live: int) -> Tuple[int, int]:
        """(``Tp``, ``S``) of the plain ragged round holding ``live``
        tokens: the ladder's first rung that takes them, and the width of
        the rectangle attention sees, a function of ``Tp`` alone (a piece
        is at most ``ragged_chunk`` tokens, and at most ``Tp``)."""
        tp = next(t for t in self._ragged_ladder() if t >= live)
        return tp, self._bucket_len(min(tp, self._ragged_chunk_cap()))

    def _count_ragged(self, sp: flight.span, bucket: int, positions: int,
                      decode_rows: int, decode_tokens: int,
                      ready: Sequence[Any]) -> None:
        """What one ragged round held, onto the round's span and into the
        counters. ``positions`` is what the dense work ran over: the
        packed length ``Tp`` of a plain round (``bucket`` is ``Tp`` too),
        rows x bucket of a spec round's rectangle. Of them the decode
        rows' tokens and the admission pieces are live."""
        live_prompt = sum(len(piece) for _, piece, _ in ready)
        sp.set(bucket=bucket, decode_rows=decode_rows,
               admission_rows=len(ready), live_prompt_tokens=live_prompt,
               positions=positions)
        st = self.stats
        st["ragged_positions_dispatched"] += positions
        st["ragged_positions_live"] += decode_tokens + live_prompt

    def _count_state_ragged(self, sp: Optional[flight.span],
                            segments: Sequence[int]) -> None:
        """What a packed round hands the chunk form of the state layers
        (each of them, once): its segments' tokens, the segments, and the
        chunks they are cut into, under the layers' family (``kda_*`` /
        ``ssd_*``)."""
        family, _, chunk = self._state_kind
        held = {f"{family}_tokens": sum(segments),
                f"{family}_segments": len(segments),
                f"{family}_chunks": sum(-(-n // chunk) for n in segments)}
        if sp is not None:
            sp.set(**held)
        for name, v in held.items():
            self.stats[f"{name}_ragged"] += v

    def _count_index_ragged(self, sp: flight.span,
                            segments: Sequence[Tuple[int, int]]) -> None:
        """What a packed round's indexer scored and kept, from ``(cached
        tokens before it, tokens)`` of each row."""
        pairs = kept = ctx = 0
        for off, m in segments:
            seen, selected, _ = _index_work(off, m, self.model_cfg.index_topk)
            pairs, kept, ctx = pairs + seen, kept + selected, ctx + off + m
        sp.set(index_context_tokens=ctx, index_selected_tokens=kept)
        self.stats["index_pairs_ragged"] += pairs
        self.stats["index_selected_pairs_ragged"] += kept
        self._count_index_layers(sp, 1)

    def _count_moe(self, sp: Optional[flight.span], kind: str,
                   moe: Sequence[np.ndarray]) -> None:
        """A round's routed-expert counters (``_MOE_COUNTERS``, summed over
        its layer calls on the device) onto its span (where the read has
        one: a scan read for another entry has none) and into the counters
        of its ``kind``. Empty: the round ran no routed layer."""
        if not moe:
            return
        held = {name: int(v) for name, v in zip(self._moe_names, moe[0])}
        if sp is not None:
            sp.set(**{f"moe_{name}": v for name, v in held.items()})
        for name, v in held.items():
            self.stats[f"moe_{name}_{kind}"] += v

    def _ragged_admission_rows(
        self, admissions: Sequence[ChunkedAdmission], chunk_cap: int,
        chunk_caps: Optional[Dict[int, int]] = None, behind: bool = False,
    ) -> Tuple[List[Tuple[ChunkedAdmission, List[int], bool]], int]:
        """Slice each in-flight admission's next chunk row for a ragged
        round, pre-reserving the sampled first token's block for FINAL
        chunks (``submit_chunked_step``'s step-boundary rule); a
        pressured final chunk skips this round and retries. Shared by
        the plain and spec ragged rounds so the retry contract cannot
        drift. ``chunk_caps`` tightens (never widens) the per-admission
        slice — the scheduler's per-round prefill budget; a cap <= 0
        drops the admission from this round. ``behind``: the round is
        built behind an unread scan, whose read the pool still lacks, so
        ``OutOfBlocksError`` is raised (nothing kept of the failed
        reservation, no pressure signalled) for the caller to read the
        scan first. Returns (ready rows, max chunk width)."""
        ready: List[Tuple[ChunkedAdmission, List[int], bool]] = []
        width = 1
        for adm in admissions:
            s = self.slots[adm.slot]
            assert s is not None
            cap = chunk_cap
            if chunk_caps is not None:
                cap = min(cap, int(chunk_caps.get(adm.slot, cap)))
                if cap <= 0:
                    continue
            piece = adm.fresh[:cap]
            is_last = len(adm.fresh) <= cap
            if is_last:
                try:
                    if self.manager.reserve_tokens(s.seq_id, 1):
                        self._block_tables[adm.slot] = \
                            self.manager.block_table_for(
                                s.seq_id, self.cfg.max_blocks_per_seq
                            )
                except OutOfBlocksError:
                    self.manager.trim_reserved(s.seq_id)
                    if behind:
                        raise
                    self._signal_pressure("admission", requests=1)
                    continue
            if not self._extend_window(adm.slot, adm.off + len(piece),
                                       behind):
                continue    # the window kind is dry: the piece waits
            ready.append((adm, piece, is_last))
            width = max(width, len(piece))
        return ready, width

    def _extend_window(self, slot: int, upto: int,
                       behind: bool = False) -> bool:
        """Pages per layer kind: the window kind's blocks for the positions
        below ``upto`` that a forward pass is about to write for ``slot``
        (``PagedKVCacheManager.extend_window``; the full kind's came with
        the prompt). False where the window pool cannot give them: pressure
        is signalled and nothing was taken, the step-boundary rule of
        every other reservation (``behind`` an unread scan it is raised
        instead: ``_ragged_admission_rows``). One kind of pages: True,
        nothing done."""
        if not self._window_blocks:
            return True
        s = self.slots[slot]
        assert s is not None
        try:
            if self.manager.extend_window(s.seq_id, upto):
                self._block_tables[slot] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq)
        except OutOfBlocksError:
            if behind:
                raise
            self._signal_pressure("admission", requests=1)
            return False
        return True

    def _fill_ragged_admission_rows(
        self, ready, toks_pos: np.ndarray, lens_after: np.ndarray,
        sample_flag: np.ndarray,
    ) -> bool:
        """Write the admission chunk rows into the spec ragged round's
        ``[B, S]`` host batch arrays (the plain round packs its own:
        ``_build_plain_ragged``); True when any admission samples
        non-greedily."""
        mixed = False
        for adm, piece, is_last in ready:
            sl, n = adm.slot, len(piece)
            toks_pos[0, sl, :n] = piece
            toks_pos[1, sl, :n] = np.arange(adm.off, adm.off + n)
            lens_after[sl] = adm.off + n
            sample_flag[sl] = 1 if is_last else 0
            if adm.mode != "greedy":
                mixed = True
        return mixed

    def _commit_ragged_admissions(
        self, ready, toks: np.ndarray, out: Dict[int, List[int]],
    ) -> None:
        """Post-dispatch admission bookkeeping shared by the plain and
        spec ragged rounds: advance chunk offsets, account prefill
        tokens, and record each completed admission's in-graph-sampled
        first token (flipping ``adm.done``)."""
        for adm, piece, is_last in ready:
            s = self.slots[adm.slot]
            assert s is not None
            adm.fresh = adm.fresh[len(piece):]
            adm.off += len(piece)
            self.stats["prefill_tokens"] += len(piece)
            if is_last:
                s.prefilling = False
                tok = int(toks[adm.slot])
                out[adm.slot] = [tok]
                self._record_token(adm.slot, tok, device_synced=True)
                adm.done = True
            else:
                self._release_prefill_window(adm.slot, adm.off)

    def _plain_ragged_round(
        self, admissions: Sequence[ChunkedAdmission],
        chunk_caps: Optional[Dict[int, int]], sp: flight.span,
        prev: Optional[_UnreadScan] = None,
    ) -> Dict[int, List[int]]:
        """ONE device dispatch serving a ragged row batch: every active
        decode slot advances one token AND every in-flight admission
        advances one prefill chunk — "append rows to the next round" in
        place of competing prefill and decode dispatches.

        Per-row semantics are exactly the split paths': decode rows feed
        their pending token at position ``_kv_lens`` (block pre-reserved,
        pressure freezes the row at the step boundary — ``decode_step``'s
        contract), admission rows run their next chunk with the final
        chunk sampling the first token in-graph (``submit_chunked_step``'s
        contract, including the pending-block pre-reservation; a pressured
        final chunk is NOT consumed and retries next round). Returns
        {slot: [token]} for every row that sampled. Admissions are mutated
        in place; ``adm.done`` flips when the first token lands. ``sp`` is
        the round's open span (``ragged_round``): it gets what the round
        held, and the four phases nest inside it.

        ``prev``: the scan that is unread. The round is built and
        dispatched behind it, ``prev`` is read and committed, what the
        round held is counted on the mirrors that commit made current, and
        the call returns ``prev``'s tokens: the readback and commit of the
        round itself are ``collect_scan``'s (``_collect_ragged``)."""
        st = self.stats
        out: Dict[int, List[int]] = {}
        # the build and the dispatch cost the chip nothing while the scan
        # still runs on it (a poll)
        hidden = prev is not None and not prev.emitted.is_ready()
        t0 = time.perf_counter()
        with flight.span("dgi.engine.ragged_round.build", st,
                         "round_build_s"):
            try:
                built = self._build_plain_ragged(admissions, chunk_caps, prev)
            except OutOfBlocksError:
                # no room to reserve the round BEHIND the unread scan: read
                # it first and hand back what the attempt took, then the
                # build is the one a round that follows a read makes
                # (freeze the row, signal the pressure)
                out = self.collect_scan()
                for s in self.slots:
                    if s is not None and not s.prefilling:
                        self.manager.trim_reserved(s.seq_id)
                prev, hidden = None, False
                built = self._build_plain_ragged(admissions, chunk_caps, None)
        if built is None:
            return self._merge(out, self.collect_scan())
        rnd, operands, mode, width = built
        sp.set(chained=int(prev is not None))
        if prev is None:
            self._count_plain_ragged(sp, rnd, rnd.kept)
        with flight.span("dgi.engine.ragged_round.dispatch", st,
                         "round_dispatch_s"):
            try:
                self.kv, self._dev_core, rnd.toks, *rnd.moe = \
                    self._ragged_round_fn(
                        self.params, self.kv, *operands, mode, width,
                    )
            except Exception:
                self._unread = None
                self._invalidate_device_state()
                raise
        if prev is None:
            return self._merge(out, self._collect_ragged(rnd, sp))
        if not hidden:
            st["round_host_exposed_s"] += time.perf_counter() - t0
        st["ragged_rounds_chained"] += 1
        self._unread = rnd
        # (the scan's own counters go where a scan's span is: a round's
        # span holds a round's)
        out = self._collect(prev, None)
        self._count_plain_ragged(
            sp, rnd, [i for i in rnd.kept if (s := self.slots[i]) is not None
                      and s.finish_reason is None])
        return out

    def _collect_ragged(self, rnd: _UnreadRound, sp: Optional[flight.span]
                        ) -> Dict[int, List[int]]:
        """Readback and commit of one dispatched plain ragged round, for
        the decode rows the device ran and every piece."""
        st = self.stats
        with flight.span("dgi.engine.ragged_round.readback", st,
                         "round_readback_s"):
            # the wait for the device; the experts' counters come with it
            try:
                toks, live, moe = jax.device_get(
                    (rnd.toks, rnd.live, rnd.moe))
            except Exception:
                self._invalidate_device_state()
                raise
        self._count_moe(sp, "ragged", moe)
        with flight.span("dgi.engine.ragged_round.commit", st,
                         "round_commit_s"):
            kept = rnd.kept if live is None else \
                [i for i in rnd.kept if live[i]]
            st["ragged_rounds"] += 1
            if kept:
                st["decode_calls"] += 1
            if rnd.ready:
                # ONE device dispatch served every admission row — the
                # counter means device calls everywhere else (wave
                # admission asserts one per bucket), so it must not scale
                # with the row count
                st["prefill_calls"] += 1
            out: Dict[int, List[int]] = {}
            for i in kept:
                self._kv_lens[i] += 1   # the fed token's KV is now committed
                tok = int(toks[i])
                out[i] = [tok]
                self._record_token(i, tok, device_synced=True)
            self._commit_ragged_admissions(rnd.ready, toks, out)
        return out

    def _build_plain_ragged(
        self, admissions: Sequence[ChunkedAdmission],
        chunk_caps: Optional[Dict[int, int]],
        prev: Optional[_UnreadScan] = None,
    ) -> Optional[Tuple[_UnreadRound, Tuple[Any, ...], str, int]]:
        """The host's half of a plain ragged round before the dispatch:
        block reservation, the packed token batch, pending pool ops, the
        uploads. None when no row is left to run. Behind an unread scan
        ``prev`` a decode row reserves ``prev``'s steps and its own from
        the committed length, which lags by ``prev`` (as a chained scan
        does, ``_build_decode_multi``), and the device says which decode
        rows run (``chain_round``); a reservation the pool cannot hold
        raises ``OutOfBlocksError`` there, a piece's too, for the caller to
        read ``prev`` first."""
        admissions = [a for a in admissions if not a.done]
        for adm in admissions:
            s = self.slots[adm.slot]
            if s is None or s.seq_id != adm.seq_id:
                raise RuntimeError("ragged admission slot was freed")
        chunk_cap = self._ragged_chunk_cap()

        # --- decode rows: pre-reserve each pending token's block exactly
        # as decode_step does; exhaustion freezes the row (nothing decoded,
        # pending still pending) and signals step-boundary pressure
        kept: List[int] = []
        pressured: List[int] = []
        for i, s in enumerate(self.slots):
            if s is None or s.finish_reason is not None or s.prefilling:
                continue
            ahead = int(prev.steps[i]) if prev is not None \
                and prev.active_mask[i] else 0
            cur = len(self.manager.seq_tokens[s.seq_id])
            if cur + ahead >= self.cfg.max_seq_len:
                kept.append(i)      # length-finish triggers in _record_token
                continue
            try:
                added = self.manager.reserve_tokens(s.seq_id, ahead + 1)
            except OutOfBlocksError:
                if prev is not None:
                    raise
                self.manager.trim_reserved(s.seq_id)
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
                pressured.append(i)
                continue
            if added:
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
            kept.append(i)
        if pressured:
            self._signal_pressure("decode", slots=pressured)

        # --- admission chunk rows: shared slicing + final-chunk
        # pending-block pre-reservation (``_ragged_admission_rows``)
        ready, _ = self._ragged_admission_rows(
            admissions, chunk_cap, chunk_caps, behind=prev is not None)
        if not kept and not ready:
            return None

        self._apply_pending()
        tp, operands, mode, s_w, rows, live = self._pack_ragged(
            kept, [(adm.slot, adm.off, piece, is_last, adm.mode)
                   for adm, piece, is_last in ready], prev)
        return _UnreadRound(rows, kept, ready, None, live, [], tp=tp), \
            operands, mode, s_w

    def _count_plain_ragged(self, sp: flight.span, rnd: _UnreadRound,
                            kept: Sequence[int]) -> None:
        """What a plain round held, onto its span and into the counters:
        the decode rows ``kept`` that ran, each at its committed length,
        and the pieces. Taken where the mirrors are
        current: before the dispatch, or behind an unread scan once that
        scan is committed (the rows it ended are then known and out)."""
        ready, tp = rnd.ready, rnd.tp
        self._count_ragged(sp, tp, tp, len(kept), len(kept), ready)
        if "mla_pairs_ragged" in self.stats:
            # a decode row's token sees its cache and itself; query j of a
            # piece written at ``off`` sees ``off + j + 1``
            pairs = ctx = sum(int(self._kv_lens[i]) + 1 for i in kept)
            for adm, piece, _ in ready:
                m = len(piece)
                pairs += m * adm.off + m * (m + 1) // 2
                ctx += adm.off + m
            self.stats["mla_pairs_ragged"] += pairs
            self.stats["mla_context_tokens_ragged"] += ctx
        if self._window_blocks:
            # a decode row's token sees its cache and itself; query j of a
            # piece written at ``off`` sees ``off + j + 1``, a sliding
            # layer at most the window of them
            w = self.model_cfg.sliding_window
            spans = [(int(self._kv_lens[i]), 1) for i in kept] + [
                (adm.off, len(piece)) for adm, piece, _ in ready]
            full = sum(m * off + m * (m + 1) // 2 for off, m in spans)
            windowed = sum(_window_pairs(off, m, w) for off, m in spans)
            self.stats["attn_pairs_ragged_full"] += full
            self.stats["attn_pairs_ragged_window"] += windowed
            sp.set(attn_full_context_tokens=full,
                   attn_window_context_tokens=windowed)
        if "index_pairs_ragged" in self.stats:
            self._count_index_ragged(
                sp, [(int(self._kv_lens[i]), 1) for i in kept]
                + [(adm.off, len(piece)) for adm, piece, _ in ready])
        if self._state_rows:
            self._count_state_ragged(
                sp, [1] * len(kept) + [len(piece) for _, piece, _ in ready])

    def _pack_ragged(
        self, kept: Sequence[int],
        pieces: Sequence[Tuple[int, int, Sequence[int], bool, str]],
        prev: Optional[_UnreadScan] = None,
    ) -> Tuple[int, Tuple[Any, ...], str, int, np.ndarray,
               Optional[jax.Array]]:
        """The operands of a plain ragged round that holds the decode rows
        ``kept`` (each its pending token) and ``pieces`` (slot, offset,
        tokens, whether the piece samples, its sampling mode): the round's
        live tokens on one axis, row after row, as token id, position, row
        and column in the rectangle attention sees (padding: row b, which
        every scatter drops, at position -1). Returns the packed length,
        the operands behind ``params`` and ``kv``, the round's mode, the
        rectangle's width, the rows the round holds and, behind ``prev``,
        the device's word on which decode rows run.

        Behind an unread scan ``prev`` the mirrors lag for its rows: the
        device core keeps their ``last`` and ``lens`` through an upload
        (``_sync_core``), and the decode rows' entries are written on the
        device from that core (``chain_round``), which also says which of
        them the scan left live."""
        b = len(self.slots)
        tp, s_w = self._ragged_shape(
            len(kept) + sum(len(piece) for _, _, piece, _, _ in pieces))
        tok_at = np.zeros((4, tp), np.int32)
        tok_at[1], tok_at[2] = -1, b
        lens_last = np.zeros((2, b), np.int32)
        row_mask = np.zeros((b,), dtype=bool)
        sample_flag = np.zeros((b,), np.int32)
        mode = "greedy"
        n = 0
        for i in kept:
            tok_at[:, n] = (self._last_tokens[i], self._kv_lens[i], i, 0)
            lens_last[:, i] = (self._kv_lens[i] + 1, n)
            n += 1
            row_mask[i] = True
            sample_flag[i] = 1
            if self._temps[i] > 0:
                mode = "mixed"
        for sl, off, piece, samples, piece_mode in pieces:
            m = len(piece)
            tok_at[0, n:n + m] = piece
            tok_at[1, n:n + m] = np.arange(off, off + m)
            tok_at[2, n:n + m] = sl
            tok_at[3, n:n + m] = np.arange(m)
            n += m
            lens_last[:, sl] = (off + m, n - 1)
            row_mask[sl] = True
            sample_flag[sl] = 1 if samples else 0
            if piece_mode != "greedy":
                mode = "mixed"
        core = self._sync_core(None if prev is None else prev.active_mask)
        tables, _act, flag_d = self._sched_arrays(row_mask, sample_flag)
        if prev is None:
            tok_d, lens_d = self._round_batch(tok_at, lens_last)
            return tp, (tok_d, tables, lens_d, core, flag_d), mode, s_w, \
                row_mask, None
        # each decode row's place on the packed axis (they come first, in
        # order) and the committed length at which it runs out of budget,
        # as ``_build_decode_multi`` gives a chained scan's
        dec_ends = np.zeros((b, 2), np.int32)
        dec_ends[:, 0] = -1
        rows = np.asarray(kept, np.int64)
        dec_ends[rows, 0] = np.arange(len(rows))
        dec_ends[rows, 1] = (self._kv_lens + self._host_budgets())[rows]
        tok_d, lens_d, flag_d, live = self._chain_round_fn(
            core, tok_at, lens_last, flag_d, dec_ends)
        return tp, (tok_d, tables, lens_d, core, flag_d), mode, s_w, \
            row_mask, live

    def _round_batch(self, tok_at: np.ndarray, lens_last: np.ndarray
                     ) -> Tuple[Any, Any]:
        """A plain round's packed batch as the round graph takes it from
        the host: as it is on one device, placed replicated on a mesh
        (``_place_round_fn``)."""
        if self._place_round_fn is not None:
            return self._place_round_fn(tok_at, lens_last)
        return tok_at, jnp.asarray(lens_last)

    def _spec_ragged_round(
        self, admissions: Sequence[ChunkedAdmission],
        chunk_caps: Optional[Dict[int, int]], sp: flight.span,
    ) -> Dict[int, List[int]]:
        """Spec-integrated ragged round: ONE dispatch serving VERIFY rows
        (per active decode slot: the draft chain + pending token,
        q_len = 2..K+1) alongside admission prefill-chunk rows — the
        round-8 unification that gives a speculating engine PR 6's
        one-dispatch prefill+decode path. Per-row contracts match the
        split paths exactly: verify rows pre-reserve their worst-case
        window and commit 1..K+1 accepted tokens with precise
        ``trim_reserved`` rollback at this same step boundary
        (``_spec_decode_rounds``'s per-round contract — greedy outputs
        stay byte-identical spec on/off and ragged on/off); admission
        rows run their next chunk with the final chunk sampling in-graph
        (``submit_chunked_step``'s contract, pending-block pre-reservation
        included; a pressured final chunk retries next round). Returns
        {slot: [tokens]}; admissions mutate in place."""
        spec = self.cfg.speculative
        assert spec is not None and self._spec_ragged_round_fn is not None
        k = spec.num_draft_tokens
        admissions = [a for a in admissions if not a.done]
        for adm in admissions:
            s = self.slots[adm.slot]
            if s is None or s.seq_id != adm.seq_id:
                raise RuntimeError("ragged admission slot was freed")
        b = len(self.slots)
        chunk_cap = self._ragged_chunk_cap()

        # --- verify rows: per-slot depth selection + worst-case
        # reservation (one round: up to K+1 fed tokens plus the
        # post-round pending token), exactly _spec_decode_rounds at
        # rounds=1; exhaustion freezes the row at the step boundary
        budgets = np.zeros((b,), np.int32)
        cand: List[int] = []
        for i, s in enumerate(self.slots):
            if s is None or s.finish_reason is not None or s.prefilling:
                continue
            rem = s.request.sampling.max_new_tokens - len(s.generated)
            if rem <= 0:
                continue
            budgets[i] = rem
            cand.append(i)
        ks_sel = self._select_spec_ks(cand)
        caps = np.zeros((b,), np.int32)
        spec_rows = np.zeros((b,), bool)
        pressured: List[int] = []
        for i in cand:
            s = self.slots[i]
            assert s is not None
            cur = len(self.manager.seq_tokens[s.seq_id])
            ki = int(ks_sel[i])
            want = min(ki + 1, int(budgets[i])) + ki + 1
            n_res = max(min(want, self.cfg.max_seq_len - cur), 0)
            try:
                if n_res > 0 and self.manager.reserve_tokens(s.seq_id,
                                                             n_res):
                    self._block_tables[i] = self.manager.block_table_for(
                        s.seq_id, self.cfg.max_blocks_per_seq
                    )
            except OutOfBlocksError:
                self.manager.trim_reserved(s.seq_id)
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
                pressured.append(i)
                continue
            spec_rows[i] = True
            caps[i] = cur + n_res
        if pressured:
            self._signal_pressure("decode", slots=pressured)
        if not spec_rows.any():
            # no verify row this round — admission-only (cold-start
            # ramp-up) or every candidate pressured out of its verify
            # window. The PLAIN ragged graph serves chunk rows with
            # byte-identical arithmetic and skips the draft chain + the
            # [B, K+1, V] head projections entirely; pressured slots it
            # can re-admit advance one VANILLA token (a 1-token
            # reservation can fit where K+2 did not — graceful
            # degradation, still target-greedy so outputs are unchanged;
            # only the stale draft hidden costs next-round acceptance).
            return self._plain_ragged_round(admissions, chunk_caps, sp)

        # --- admission chunk rows: identical contract to the plain path
        # (shared helper — the retry/reservation rules cannot drift)
        ready, width = self._ragged_admission_rows(admissions, chunk_cap,
                                                   chunk_caps)

        self._apply_pending()
        # row width: a dedicated K+1 shape serves pure-verify rounds (the
        # steady state) without padding up to the smallest prefill
        # bucket; wider chunk rows bucket as usual — the compiled width
        # set stays {K+1} ∪ buckets
        s_w = k + 1 if width <= k + 1 else self._bucket_len(width)
        self._count_ragged(sp, s_w, b * s_w, int(spec_rows.sum()),
                           int((ks_sel[spec_rows] + 1).sum()), ready)
        toks_pos = np.zeros((2, b, s_w), np.int32)
        toks_pos[1] = -1
        lens_after = np.zeros((b,), np.int32)
        sample_flag = np.zeros((b,), np.int32)
        mode = "greedy"
        for i in np.nonzero(spec_rows)[0]:
            if self._temps[i] > 0:
                mode = "mixed"
        if self._fill_ragged_admission_rows(ready, toks_pos, lens_after,
                                            sample_flag):
            mode = "mixed"
        forced = self._spec_forced(
            [int(i) for i in np.nonzero(spec_rows)[0]], 1, ks_sel
        )[0]
        core = self._sync_core()
        h_last = self._spec_h_device()
        mm = self.cfg.max_blocks_per_seq
        si = np.zeros((b, mm + 5), np.int32)
        si[:, :mm] = self._block_tables
        si[:, mm] = spec_rows
        si[:, mm + 1] = sample_flag
        si[:, mm + 2] = ks_sel
        si[:, mm + 3] = caps
        si[:, mm + 4] = forced
        (tables, spec_d, flag_d, ks_d, caps_d,
         forced_d) = self._unpack_spec_sched_fn(si)
        try:
            (self.kv, self._dev_core, self._dev_spec_h, tok0, emitted,
             n_acc) = self._spec_ragged_round_fn(
                self.params, self._draft_params, self.kv, toks_pos,
                tables, jnp.asarray(lens_after), core, h_last, spec_d,
                flag_d, ks_d, caps_d, forced_d, mode,
            )
        except Exception:
            self._invalidate_device_state()
            raise
        tok0 = np.asarray(tok0)
        emitted = np.asarray(emitted)
        n_acc = np.asarray(n_acc)
        self.stats["ragged_rounds"] += 1
        if spec_rows.any():
            self.stats["decode_calls"] += 1
            self.stats["spec_steps"] += 1
        if ready:
            self.stats["prefill_calls"] += 1
        out: Dict[int, List[int]] = {}
        for i in np.nonzero(spec_rows)[0]:
            i = int(i)
            if spec.adaptive:
                self._spec_ema_update(i, int(n_acc[i]))
            s = self.slots[i]
            assert s is not None
            a = int(n_acc[i])
            # the device committed t0..t_a (fed in the verify pass)
            self._kv_lens[i] += a + 1
            if self._temps[i] <= 0.0:
                self.stats["spec_slot_steps"] += 1
                self.stats["spec_drafted"] += int(ks_sel[i])
                self.stats["spec_accepted"] += a
                self.stats["spec_emitted"] += a + 1
            commit: List[int] = []
            for t in emitted[i]:
                if t < 0 or s.finish_reason is not None:
                    break
                out.setdefault(i, []).append(int(t))
                self._record_token(i, int(t), already_committed=True,
                                   device_synced=True)
                if s.finish_reason is None:
                    commit.append(int(t))
            self.manager.commit_tokens(s.seq_id, commit)
            # precise rollback of the rejected window at the same step
            # boundary (footprint matches a never-speculated engine)
            if self.manager.trim_reserved(s.seq_id):
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
            self._maybe_release_window(i)
        self._commit_ragged_admissions(ready, tok0, out)
        return out

    def _record_token(self, slot: int, tok: int, already_committed: bool = False,
                      device_synced: bool = False) -> None:
        """Account a freshly *sampled* token.

        ``self._kv_lens[slot]`` is the **committed** context length — tokens
        whose KV has been written on device. A sampled token is *pending*: its
        KV is written only when it is fed in the next decode step, at position
        ``_kv_lens``. This method records the sample, checks stop/length, and
        (unless ``already_committed`` — the multi-step scan pre-reserves)
        allocates the block its KV will land in.

        ``device_synced``: the token came from a graph that already advanced
        the device core state identically (decode rounds, batched prefill) —
        the host-mirror update below then does NOT dirty the device copy.
        """
        s = self.slots[slot]
        assert s is not None
        now = time.time()
        if s.first_token_time is None:
            s.first_token_time = now
        if tok in self._stop_ids[slot]:
            s.finish_reason = "stop"
            return
        s.generated.append(tok)
        self.stats["generated_tokens"] += 1
        self._last_tokens[slot] = tok
        if not device_synced:
            self._core_dirty = True
        if len(s.generated) >= s.request.sampling.max_new_tokens:
            s.finish_reason = s.finish_reason or "length"
            return
        if int(self._kv_lens[slot]) >= self.cfg.max_seq_len:
            s.finish_reason = "length"
            return
        if not already_committed:
            new_block = self.manager.append_token(s.seq_id, tok)
            if new_block is not None:
                self._block_tables[slot] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
            self._apply_pending()
            self._maybe_release_window(slot)

    def _release_prefill_window(self, slot: int, off: int) -> None:
        """Sliding-window models, MID-prefill: hand back blocks that every
        REMAINING chunk query is already past, between chunks. Without
        this a 32k prompt on a windowed model holds its entire prompt KV
        until the first decode step (``_maybe_release_window`` only runs
        on token commits) — worst-case pool pressure exactly when a long
        admission is streaming in. The earliest remaining query sits at
        position ``off``, not ``cur - 1`` (``seq_tokens`` already
        holds the WHOLE prompt during prefill), so the window passed to
        the manager widens by the not-yet-queried tail: only keys
        <= off - window release. The attention window mask already
        excludes those positions for every remaining chunk row, so
        pad-block reads are never visible — byte-identical outputs."""
        w = self.model_cfg.sliding_window
        if w is None:
            return
        s = self.slots[slot]
        if s is None:
            return
        cur = len(self.manager.seq_tokens[s.seq_id])
        released = self.manager.release_out_of_window(
            s.seq_id, w + max(cur - off, 0)
        )
        for lb in released:
            self._block_tables[slot, self._window_col + lb] = 0

    def _maybe_release_window(self, slot: int) -> None:
        """Sliding-window models: hand blocks every future query is past back
        to the pool (window-bounded KV memory — SWA's serving payoff). The
        released logical slots point at pad block 0; the attention window
        mask already excludes those positions, so reads stay correct."""
        w = self.model_cfg.sliding_window
        if w is None:
            return
        s = self.slots[slot]
        assert s is not None
        released = self.manager.release_out_of_window(s.seq_id, w)
        for lb in released:
            self._block_tables[slot, self._window_col + lb] = 0

    def decode_step(self) -> Dict[int, int]:
        """One decode step for all active unfinished slots: feeds each slot's
        pending token (writing its KV at position ``_kv_lens``), samples the
        next. Returns {slot: sampled_token} (stop tokens included, then the
        slot finishes)."""
        self.collect_scan()
        active = [
            i for i, s in enumerate(self.slots)
            if s is not None and s.finish_reason is None and not s.prefilling
        ]
        if not active:
            return {}
        # pre-reserve the block this step's SAMPLED token will occupy (and
        # CoW a shared tail) BEFORE the device call: exhaustion then freezes
        # the slot at the step boundary — nothing decoded, pending token
        # still pending, host/device state untouched — and signals the
        # scheduler, instead of OutOfBlocksError unwinding mid-record with a
        # sampled-but-unplaced token
        kept: List[int] = []
        pressured: List[int] = []
        for i in active:
            s = self.slots[i]
            assert s is not None
            if len(self.manager.seq_tokens[s.seq_id]) >= self.cfg.max_seq_len:
                # context full: this step's sample triggers the length
                # finish and is never appended — reserving past the table
                # width would overflow it
                kept.append(i)
                continue
            try:
                added = self.manager.reserve_tokens(s.seq_id, 1)
            except OutOfBlocksError:
                self.manager.trim_reserved(s.seq_id)
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
                pressured.append(i)
                continue
            if added:
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
            kept.append(i)
        if pressured:
            self._signal_pressure("decode", slots=pressured)
        if not kept:
            return {}
        active = kept
        self._apply_pending()
        active_mask = np.zeros(len(self.slots), dtype=bool)
        active_mask[active] = True
        # budgets stay out of the per-step graph's way: stop/length decisions
        # are host-side in _record_token, exactly as before
        budgets = np.where(active_mask, _BIG_BUDGET, 0).astype(np.int32)
        core = self._sync_core()
        tables, act_d, bud_d = self._sched_arrays(active_mask, budgets)
        mode = self._decode_mode()
        try:
            self.kv, self._dev_core, emitted, *_ = self._decode_multi_fn(
                self.params, self.kv, core, tables, act_d, bud_d, 1, mode,
            )
        except Exception:
            self._invalidate_device_state()
            raise
        self.stats["decode_calls"] += 1
        toks = np.asarray(emitted)[:, 0]
        out: Dict[int, int] = {}
        for i in active:
            self._kv_lens[i] += 1  # the fed token's KV is now committed
            tok = int(toks[i])
            out[i] = tok
            self._record_token(i, tok, device_synced=True)
        return out

    # ------------------------------------------- spec depth / oracle helpers

    def _select_spec_ks(self, active: Sequence[int]) -> np.ndarray:
        """Per-slot draft depth for the next dispatch. Non-adaptive: the
        configured K everywhere. Adaptive: the smallest choice from the
        static ``k_choices`` set strictly above the slot's accepted-length
        EMA (always draft a little deeper than the recent accept), capped
        at the largest choice. Depths select masks inside ONE compiled
        graph — never a new trace."""
        sp = self.cfg.speculative
        assert sp is not None
        ks = np.full((len(self.slots),), sp.num_draft_tokens, np.int32)
        if sp.adaptive:
            choices = sp.k_choices()
            for i in active:
                ema = float(self._spec_k_ema[i])
                sel = choices[-1]
                for c in choices:
                    if ema < c:
                        sel = c
                        break
                ks[i] = sel
        if self.spec_k_trace is not None:
            self.spec_k_trace.append([(int(i), int(ks[i])) for i in active])
        return ks

    def _spec_ema_update(self, slot: int, accepted: int) -> None:
        sp = self.cfg.speculative
        assert sp is not None
        a = float(sp.adaptive_ema)
        self._spec_k_ema[slot] = (
            a * float(self._spec_k_ema[slot]) + (1.0 - a) * float(accepted)
        )

    def _spec_forced(self, active: Sequence[int], rounds: int,
                     ks: np.ndarray) -> np.ndarray:
        """Oracle-draft forced accepted lengths, [rounds, B] int32; -1 =
        real acceptance (the production value — also every inactive row).
        Fractional per-round targets (rate × K) dither through a per-slot
        accumulator, so the mean over rounds hits the rate exactly and the
        schedule is deterministic."""
        sp = self.cfg.speculative
        assert sp is not None
        out = np.full((rounds, len(self.slots)), -1, np.int32)
        rate = sp.oracle_accept_rate
        if rate is None:
            return out
        for i in active:
            target = float(rate) * float(ks[i])
            for r in range(rounds):
                self._spec_oracle_acc[i] += target
                f = int(np.floor(self._spec_oracle_acc[i] + 1e-9))
                f = max(0, min(f, int(ks[i])))
                self._spec_oracle_acc[i] -= f
                out[r, i] = f
        return out

    def set_spec_oracle(self, rate: Optional[float]) -> None:
        """Flip the oracle draft's forced acceptance rate on a LIVE engine
        (the bench A/B lever — the oracle is a traced input, so no
        recompile). ``None`` restores real acceptance."""
        sp = self.cfg.speculative
        if sp is None:
            raise ValueError("engine has no speculative config")
        if rate is not None and not (0.0 <= float(rate) <= 1.0):
            raise ValueError(f"oracle rate {rate} must be in [0, 1]")
        sp.oracle_accept_rate = None if rate is None else float(rate)
        self._spec_oracle_acc[:] = 0.0

    def spec_decode_step(self) -> Dict[int, List[int]]:
        """One speculative round for all active slots: draft K tokens per
        slot, verify the chain in one multi-query target pass, commit each
        slot's accepted prefix + bonus (1..K+1 tokens). Returns
        {slot: emitted_tokens} with the same contract as ``decode_multi``
        (a stop token appears in the list, then the slot finishes)."""
        return self._spec_decode_rounds(1)

    def _spec_decode_rounds(self, num_steps: int) -> Dict[int, List[int]]:
        """ONE fused dispatch of up to ``num_steps`` draft→verify→accept
        rounds (a lax.scan with device-resident done/budget/stop state —
        the same per-dispatch amortization decode_multi's scan buys vanilla
        decode). Rounds bucket to powers of two so at most log2 variants
        compile; per-round records replay on the host so cache-manager
        commits and emission bookkeeping exactly match the per-step path."""
        self.collect_scan()
        spec = self.cfg.speculative
        assert spec is not None and self._spec_rounds_fn is not None
        active = [
            i for i, s in enumerate(self.slots)
            if s is not None and s.finish_reason is None and not s.prefilling
        ]
        if not active:
            return {}
        b = len(self.slots)
        active_mask = np.zeros(b, dtype=bool)
        caps = np.zeros(b, dtype=np.int32)
        budgets = np.zeros(b, dtype=np.int32)
        for i in active:
            s = self.slots[i]
            budgets[i] = max(
                s.request.sampling.max_new_tokens - len(s.generated), 0
            )
        active = [i for i in active if budgets[i] > 0]
        if not active:
            return {}
        # every active round commits >= 1 token per slot, so rounds beyond
        # the largest remaining budget are dead weight; bucket to a power
        # of two so the compiled scan-length set stays logarithmic
        rounds = max(1, min(int(num_steps),
                            int(max(budgets[i] for i in active))))
        rounds = 1 << (rounds.bit_length() - 1)
        ks_sel = self._select_spec_ks(active)
        pressured: List[int] = []
        for i in active:
            s = self.slots[i]
            # reserve the dispatch's worst case up front — the device
            # cannot allocate mid-scan: commits are bounded by
            # min(rounds*(K+1), budget), plus K+1 so the final round's full
            # window and the post-dispatch pending token stay covered
            # (K = the slot's SELECTED depth — adaptive shallow slots
            # pre-book proportionally less). Near max_seq_len the window
            # shrinks and the in-graph clamp + freeze honor the smaller
            # cap.
            cur = len(self.manager.seq_tokens[s.seq_id])
            ki = int(ks_sel[i])
            want = min(rounds * (ki + 1), int(budgets[i])) + ki + 1
            n_res = max(min(want, self.cfg.max_seq_len - cur), 0)
            try:
                if n_res > 0 and self.manager.reserve_tokens(s.seq_id, n_res):
                    # table rebuild only when the reservation actually added
                    # blocks (or CoW'd a shared tail)
                    self._block_tables[i] = self.manager.block_table_for(
                        s.seq_id, self.cfg.max_blocks_per_seq
                    )
            except OutOfBlocksError:
                # pool can't hold this slot's verify window: freeze it for
                # this dispatch (step-boundary pressure, scheduler decides
                # who yields) rather than unwind half-reserved
                self.manager.trim_reserved(s.seq_id)
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
                pressured.append(i)
                continue
            active_mask[i] = True
            caps[i] = cur + n_res
        if pressured:
            self._signal_pressure("decode", slots=pressured)
        if not active_mask.any():
            return {}
        self._apply_pending()
        forced = self._spec_forced(
            [i for i in active if active_mask[i]], rounds, ks_sel
        )
        core = self._sync_core()
        h_last = self._spec_h_device()
        tables, act_d, caps_d = self._sched_arrays(active_mask, caps)
        mode = self._decode_mode()
        try:
            (self.kv, self._dev_core, self._dev_spec_h,
             recs) = self._spec_rounds_fn(
                self.params, self._draft_params, self.kv, core, h_last,
                tables, act_d, caps_d, jnp.asarray(budgets),
                jnp.asarray(ks_sel), jnp.asarray(forced), rounds, mode,
            )
        except Exception:
            self._invalidate_device_state()
            raise
        rec_emit, rec_nacc, rec_act = (np.asarray(r) for r in recs)
        self.stats["decode_calls"] += rounds
        adaptive = spec.adaptive
        out: Dict[int, List[int]] = {}
        for r in range(rounds):
            act = rec_act[r]
            if not act.any():
                break
            self.stats["spec_steps"] += 1
            for i in active:
                if not act[i]:
                    continue
                if adaptive:
                    # EMA sees every round the row was live (sampled rows
                    # contribute their structural zeros and converge to
                    # the shallowest depth — less dead verify weight)
                    self._spec_ema_update(i, int(rec_nacc[r, i]))
                s = self.slots[i]
                if s is None or s.finish_reason is not None:
                    continue
                a = int(rec_nacc[r, i])
                # the device committed t0..t_a (fed in the verify pass)
                self._kv_lens[i] += a + 1
                if self._temps[i] <= 0.0:
                    # efficiency counters describe SPECULATING slots only:
                    # sampled slots never accept drafts by design, and
                    # counting their forced zeros would dilute the exported
                    # accept-rate/tokens-per-step gauges under mixed traffic
                    self.stats["spec_slot_steps"] += 1
                    self.stats["spec_drafted"] += int(ks_sel[i])
                    self.stats["spec_accepted"] += a
                    self.stats["spec_emitted"] += a + 1
                commit: List[int] = []
                for t in rec_emit[r, i]:
                    if t < 0 or s.finish_reason is not None:
                        break
                    out.setdefault(i, []).append(int(t))
                    self._record_token(i, int(t), already_committed=True,
                                       device_synced=True)
                    if s.finish_reason is None:
                        # committed-or-pending-with-reserved-block, exactly
                        # as decode_multi's bookkeeping (stop/length
                        # trigger excluded)
                        commit.append(int(t))
                self.manager.commit_tokens(s.seq_id, commit)
        for i in active:
            s = self.slots[i]
            if s is None:
                continue
            # precise rollback of the rejected windows: drop reserved
            # blocks acceptance never reached, so the footprint matches a
            # never-speculated per-step engine
            if self.manager.trim_reserved(s.seq_id):
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
            self._maybe_release_window(i)
        return out

    def distill_draft(self, steps: int = 400, **kw: Any) -> None:
        """Distill the integrated draft head against this engine's own
        target weights (runtime.speculative.distill_draft_params) —
        acceptance goes from ~0 (random head) to task-dependent useful."""
        if self.cfg.speculative is None:
            raise ValueError("engine has no speculative config to distill")
        from distributed_gpu_inference_tpu.runtime.speculative import (
            distill_draft_params,
        )

        self._draft_params = distill_draft_params(
            self.model_cfg, self.params,
            jax.random.PRNGKey(self.cfg.speculative.draft_seed),
            steps=steps, **kw,
        )

    def decode_multi(self, num_steps: Optional[int] = None,
                     ahead: bool = False) -> Dict[int, List[int]]:
        """Run T decode steps in one device call (lax.scan) with on-device
        stop masking; host sees tokens only at the end. TPU-first throughput
        path — amortizes per-token host round-trips.

        ``ahead`` is for the caller that owns the round loop and sees that
        its next round is another scan: the scan is dispatched and LEFT
        UNREAD, and what comes back is the tokens of the scan the last such
        call left (nothing after a call that read its own). Where one is
        unread, the new scan goes out behind it, its rows and budgets taken
        on the device from what the unread one leaves there, so the host's
        build, upload, commit and whatever the caller does between calls
        run while the device does. At most one scan is unread at a time;
        every other entry of the engine reads it first (``collect_scan``).
        Without ``ahead`` the call returns its own tokens, as it always did.

        With ``EngineConfig.speculative`` set, the T steps are fused
        draft→verify→accept rounds instead — each commits 1..K+1 tokens per
        slot, amortizing the weight stream over the accepted tokens."""
        num_steps = int(num_steps or self.cfg.multi_step)
        st = self.stats
        st["rounds"] += 1
        with flight.span("dgi.engine.decode_multi", round=st["rounds"],
                         steps=num_steps) as sp:
            if self.cfg.speculative is not None:
                return self._spec_decode_rounds(num_steps)
            return self._plain_decode_multi(num_steps, sp, ahead)

    @property
    def scan_unread(self) -> bool:
        """A dispatch's tokens are still on the device (a scan's, or those
        of the round that went out behind one): the host mirrors lag."""
        return self._unread is not None

    @property
    def round_unread(self) -> bool:
        """What is unread is a ragged round (``ragged_round`` behind a
        scan): the next thing the round loop does is read it."""
        return isinstance(self._unread, _UnreadRound)

    def scan_ends_row(self) -> bool:
        """A row of the unread scan reaches its budget inside it: its slot
        comes free when the scan is read (short of that, only a stop id
        ends a row, which the host cannot know before it reads)."""
        prev = self._unread
        return prev is not None and bool(
            (prev.active_mask
             & (self._host_budgets() <= prev.num_steps)).any())

    def collect_scan(self, sp: Optional[flight.span] = None
                     ) -> Dict[int, List[int]]:
        """THE collect point: read what is unread back and commit it, so
        that the host mirrors (``_last_tokens``, ``_kv_lens``, the slots'
        ``generated`` and ``finish_reason``, the manager's token lists, and
        after a round the admissions' offsets) are current. What is unread
        is a scan (``decode_multi(..., ahead=True)``) or the ragged round
        that went out behind one (``ragged_round``), never both: the call
        that dispatched the round read the scan. ``{}`` when nothing is
        unread. Every entry that reads or writes slots, pool or mirrors
        calls it first; the round loop calls it when its next round is not
        a scan over the same rows, and always after a round left unread.

        One exception: ``submit_chunked_start`` binds a slot that is free
        and outside the unread dispatch's rows without reading it
        (``_collect_unless_free``; its docstring says why nothing it
        touches is stale), so an arrival is admitted while the scan before
        its round still runs. The engine decides that from what it holds;
        a slot inside the dispatch's rows is read first like everything
        else."""
        unread, self._unread = self._unread, None
        if unread is None:
            return {}
        if isinstance(unread, _UnreadRound):
            return self._collect_ragged(unread, sp)
        return self._collect(unread, sp)

    def _collect_unless_free(self, slot: int) -> None:
        """``collect_scan`` unless ``slot`` is free and no row of the unread
        dispatch: the exception of ``collect_scan``'s rule."""
        prev = self._unread
        if prev is not None and (
                self.slots[slot] is not None or prev.active_mask[slot]):
            self.collect_scan()

    def _plain_decode_multi(self, num_steps: int, sp: flight.span,
                            ahead: bool = False) -> Dict[int, List[int]]:
        """The plain scan of ``decode_multi``, in the four phases of a
        round (build, dispatch, readback, commit), each a span inside
        ``sp`` and a time counter; with ``ahead``, the readback and commit
        are the previous scan's."""
        st = self.stats
        out: Dict[int, List[int]] = {}
        is_scan = isinstance(self._unread, _UnreadScan)
        if self._unread is not None and not (
                ahead and is_scan and not self._core_dirty
                and self._dev_core is not None):
            # (a round left unread is read first: its span is not a scan's)
            out = self.collect_scan(sp if is_scan else None)
        prev = self._unread
        # the build and the dispatch cost the chip nothing while a scan of
        # ours still runs on it (a poll)
        hidden = prev is not None and not prev.emitted.is_ready()
        t0 = time.perf_counter()
        with flight.span("dgi.engine.decode_multi.build", st,
                         "round_build_s"):
            try:
                built = self._build_decode_multi(num_steps, prev)
            except OutOfBlocksError:
                # no room to reserve a scan AHEAD of the unread one: read it
                # first and hand back what the attempt took, then the
                # build is the one a call that reads its own scan makes
                # (freeze the row, signal the pressure)
                out = self.collect_scan(sp)
                for i in np.flatnonzero(prev.active_mask):
                    s = self.slots[i]
                    if s is not None:
                        self.manager.trim_reserved(s.seq_id)
                prev, hidden = None, False
                built = self._build_decode_multi(num_steps, None)
        if built is None:
            # no row is left to run: nothing to leave unread either
            return self._merge(out, self.collect_scan(sp))
        scan, operands, mode = built
        sp.set(decode_rows=int(scan.active_mask.sum()),
               positions=len(self.slots) * num_steps,
               chained=int(prev is not None))
        with flight.span("dgi.engine.decode_multi.dispatch", st,
                         "round_dispatch_s"):
            try:
                self.kv, self._dev_core, scan.emitted, *scan.moe = \
                    self._decode_multi_fn(
                        self.params, self._scan_kv(num_steps), *operands,
                        num_steps, mode,
                    )
                self._scan_keys = self.kv.pop(
                    llama.INDEX_SCAN_KEYS, self._scan_keys)
            except Exception:
                self._unread = None
                self._invalidate_device_state()
                raise
        st["decode_calls"] += num_steps
        if not hidden:
            st["round_host_exposed_s"] += time.perf_counter() - t0
        self._unread = scan
        if prev is not None:
            self._merge(out, self._collect(prev, sp))
        if not ahead:
            self._merge(out, self.collect_scan(sp))
        elif prev is None:
            # a call that waits for no scan still returns only once the
            # device has taken its uploads, the last thing in front of the
            # scan: the scan then starts inside the call's span on a
            # profiler's clock, as every other scan does (it starts when
            # the scan before it ends, which its call waits for)
            jax.block_until_ready(operands[1])
        return out

    @staticmethod
    def _merge(out: Dict[int, List[int]], more: Dict[int, List[int]]
               ) -> Dict[int, List[int]]:
        for slot, toks in more.items():
            out.setdefault(slot, []).extend(toks)
        return out

    def _collect(self, scan: _UnreadScan, sp: Optional[flight.span]
                 ) -> Dict[int, List[int]]:
        """Readback and commit of one dispatched scan."""
        st = self.stats
        with flight.span("dgi.engine.decode_multi.readback", st,
                         "round_readback_s"):
            # [B, T], -1 = masked-out step: the wait for the device; the
            # experts' counters come with it
            self.scan_read_running = not scan.emitted.is_ready()
            try:
                emitted, moe = jax.device_get((scan.emitted, scan.moe))
            except Exception:
                # whatever else is outstanding went out behind this scan
                self._unread = None
                self._invalidate_device_state()
                raise
        t0 = time.perf_counter()
        self._count_moe(sp, "scan", moe)
        if "index_fetched_tokens_scan" in st and moe:
            # the vector's last entry, summed over the layers that walk a
            # selection (every layer but the sliding latent ones)
            fetched = int(moe[0][-1]) // (
                self.model_cfg.num_layers - self.model_cfg.num_window_layers)
            st["index_fetched_tokens_scan"] += fetched
            if sp is not None:
                sp.set(index_fetched_tokens=fetched,
                       index_fetched_pages=fetched // self.cfg.block_size)
        if self._state_rows:
            # a live row's step went through every state layer
            steps = int((emitted >= 0).sum()) \
                * self.model_cfg.num_state_layers
            family = self._state_kind[0]
            st[f"{family}_row_steps_scan"] += steps
            if sp is not None:
                sp.set(**{f"{family}_row_steps": steps})
        with flight.span("dgi.engine.decode_multi.commit", st,
                         "round_commit_s"):
            out: Dict[int, List[int]] = {}
            index_ctx, index_kept, index_most = 0, 0, -1
            attn_ctx = [0, 0]
            for i, s in enumerate(self.slots):
                if not scan.active_mask[i] or s is None:
                    continue
                toks = [int(t) for t in emitted[i] if t >= 0]
                out[i] = toks
                if self._window_blocks and toks:
                    full, windowed = self._count_window_scan(
                        i, s.seq_id, len(toks))
                    attn_ctx[0] += full
                    attn_ctx[1] += windowed
                if "mla_row_steps_scan" in st:
                    # step t of the row attended its cache and the token
                    # the step wrote: len + 1 ... len + n
                    n = len(toks)
                    st["mla_row_steps_scan"] += n
                    st["mla_context_tokens_scan"] += (
                        n * int(self._kv_lens[i]) + n * (n + 1) // 2)
                if "index_row_steps_scan" in st:
                    seen, selected = self._count_index_scan(
                        int(self._kv_lens[i]), len(toks))
                    index_ctx, index_kept = index_ctx + seen, \
                        index_kept + selected
                    if toks:    # a row the device found live
                        index_most = max(index_most, int(self._kv_lens[i]))
                # each emitted token corresponds to one scan step that fed
                # (and thus committed) the previous pending token
                self._kv_lens[i] += len(toks)
                for t in toks:
                    if s.finish_reason is not None:
                        break
                    self._record_token(i, t, already_committed=True,
                                       device_synced=True)
                # manager bookkeeping: seq_tokens ← tokens that are committed
                # or pending-with-reserved-block (stop/length-trigger
                # excluded, as in the per-step path)
                commit = toks if s.finish_reason is None else toks[:-1]
                self.manager.commit_tokens(s.seq_id, commit)
                self._maybe_release_window(i)
        if "index_layers_scored" in st:
            self._count_index_layers(sp, scan.num_steps)
        if index_ctx and sp is not None:
            sp.set(index_context_tokens=index_ctx,
                   index_selected_tokens=index_kept)
        if attn_ctx[0] and sp is not None:
            sp.set(attn_full_context_tokens=attn_ctx[0],
                   attn_window_context_tokens=attn_ctx[1])
        # (its storage exists where a table can pass topk at all)
        if self._scan_keys is not None and index_most >= 0 and \
                index_most + scan.num_steps > self.model_cfg.index_topk:
            st["index_key_gathers_scan"] += self.model_cfg.num_index_layers
        if self._unread is None:
            # nothing went out behind it: the chip waited for this commit
            st["round_host_exposed_s"] += time.perf_counter() - t0
        return out

    def _count_window_scan(self, slot: int, seq_id: str, n: int
                           ) -> Tuple[int, int]:
        """A row's ``n`` scan steps into the counters of a model of mixed
        attention kinds -> the cached tokens they attended in a full layer
        and in a sliding one. Step ``j`` attends the row's cache and the
        token it wrote, ``len + j``; a sliding layer at most the window of
        them. The window-kind tokens the row held are taken as the scan
        ends, a step each."""
        before, w = int(self._kv_lens[slot]), self.model_cfg.sliding_window
        full = n * before + n * (n + 1) // 2
        windowed = _window_pairs(before, n, w)
        st = self.stats
        st["attn_row_steps_scan"] += n
        st["attn_full_context_tokens_scan"] += full
        st["attn_window_context_tokens_scan"] += windowed
        st["kv_window_resident_tokens_scan"] += n * self.cfg.block_size \
            * self.manager.window_resident_blocks(seq_id)
        return full, windowed

    def _count_index_layers(self, sp: Optional[flight.span], passes: int
                            ) -> None:
        """``passes`` forward passes (a scan's steps, or a round: one) into
        the counters of the layers that scored and of those that shared."""
        kinds = self.model_cfg.index_kinds
        scored = passes * kinds.count("full")
        shared = passes * kinds.count("shared")
        self.stats["index_layers_scored"] += scored
        self.stats["index_layers_shared"] += shared
        if sp is not None:
            sp.set(index_layers_scored=scored, index_layers_shared=shared)

    def _count_index_scan(self, cached: int, n: int) -> Tuple[int, int]:
        """A row's ``n`` scan steps over ``cached`` tokens: step ``t``
        attended its cache and the token it wrote. Returns the context
        tokens counted and those of them the selections kept."""
        st = self.stats
        seen, selected, dense = _index_work(cached, n,
                                            self.model_cfg.index_topk)
        st["index_row_steps_scan"] += n
        st["index_dense_rows_scan"] += dense
        st["index_context_tokens_scan"] += seen
        st["index_selected_tokens_scan"] += selected
        return seen, selected

    def _build_decode_multi(self, num_steps: int,
                            prev: Optional[_UnreadScan] = None
                            ) -> Optional[Tuple[_UnreadScan, Tuple[Any, ...],
                                                str]]:
        """The host's half of a scan before the dispatch: who decodes, the
        budgets, block reservation for the horizon, pending pool ops, the
        uploads. None when no row is left to run. Behind an unread scan
        ``prev`` the host's budgets are what is left after ``prev`` at the
        least, the reservation covers ``prev``'s steps and this scan's (the
        manager counts from the committed length, which lags by ``prev``),
        and the rows and budgets the device runs come from the device
        (``chain_sched``); a reservation the pool cannot hold raises
        ``OutOfBlocksError`` there, for the caller to read ``prev`` first."""
        # per-slot token budgets enforced ON DEVICE (scan masks a slot once
        # it emits its allowance) — num_steps stays the compiled constant
        # instead of shrinking to the shortest slot and recompiling per
        # distinct tail length
        raw = self._host_budgets()
        budgets = self._budgets_after(raw, prev)
        active_mask = budgets > 0
        if not active_mask.any():
            return None
        steps = np.minimum(num_steps, budgets)
        # pre-reserve KV blocks for each slot's actual horizon (no host
        # alloc mid-scan). A slot whose reservation exhausts the pool is
        # FROZEN for this round (masked out, partial reservation trimmed
        # back, pending token still pending) and reported as a pressure
        # signal — the step boundary stays consistent instead of the round
        # unwinding with half the batch reserved.
        pressured: List[int] = []
        for i, s in enumerate(self.slots):
            if active_mask[i] and s is not None:
                # clamp the horizon to the context limit: the length-finish
                # trigger token is never appended, so reserving past
                # max_seq_len would only overflow the block-table width
                cur = len(self.manager.seq_tokens[s.seq_id])
                n_res = int(steps[i])
                if prev is not None:
                    n_res += int(prev.steps[i])
                n_res = min(n_res, self.cfg.max_seq_len - cur)
                if n_res <= 0:
                    continue
                try:
                    self.manager.reserve_tokens(s.seq_id, n_res)
                except OutOfBlocksError:
                    if prev is not None:
                        raise
                    self.manager.trim_reserved(s.seq_id)
                    active_mask[i] = False
                    pressured.append(i)
                self._block_tables[i] = self.manager.block_table_for(
                    s.seq_id, self.cfg.max_blocks_per_seq
                )
        if pressured:
            self._signal_pressure("decode", slots=pressured)
        if not active_mask.any():
            return None
        self._apply_pending()
        core = self._sync_core()
        if prev is None:
            tables, act_d, bud_d = self._sched_arrays(
                active_mask, budgets.astype(np.int32)
            )
        else:
            ends = np.where(prev.active_mask, self._kv_lens + raw, 0)
            tables, _, ends_d = self._sched_arrays(
                active_mask, ends.astype(np.int32)
            )
            act_d, bud_d = self._chain_sched_fn(core, ends_d)
        scan = _UnreadScan(num_steps, active_mask, steps, None, [])
        return scan, (core, tables, act_d, bud_d), self._decode_mode()

    def _host_budgets(self) -> np.ndarray:
        """``decode_budgets`` by the host mirrors as they stand: what they
        say is left, which lags by the unread scan."""
        return np.array(
            [
                max(0, min(
                    s.request.sampling.max_new_tokens - len(s.generated),
                    self.cfg.max_seq_len - int(self._kv_lens[i]),
                )) if s is not None and s.finish_reason is None
                and not s.prefilling else 0
                for i, s in enumerate(self.slots)
            ],
            dtype=np.int32,
        )

    @staticmethod
    def _budgets_after(raw: np.ndarray, prev: Optional[_UnreadScan]
                       ) -> np.ndarray:
        if prev is None:
            return raw
        return np.where(prev.active_mask,
                        np.maximum(raw - prev.num_steps, 0), 0)

    def decode_budgets(self) -> np.ndarray:
        """Tokens each slot may still emit, ``[max_batch_size]`` int32: what
        is left of ``max_new_tokens`` and of the context, 0 for a slot that
        does not decode (empty, finished, mid-prefill). The next scan masks
        a row after that many steps; the smallest positive entry is the
        first step at which a slot can come free short of a stop token
        (the batcher's horizon rule reads it). While a scan is unread: what
        is left after it, for its rows."""
        return self._budgets_after(self._host_budgets(), self._unread)

    def finish_slot(self, slot: int, cache: bool = True) -> InferenceResponse:
        # the unread scan may hold the slot's last tokens, and has its
        # blocks in its tables
        self.collect_scan()
        s = self.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} empty")
        self.manager.free_sequence(s.seq_id, cache=cache)
        self.slots[slot] = None
        self._kv_lens[slot] = 0
        self.stats["completed"] += 1
        now = time.time()
        resp = InferenceResponse(
            request_id=s.request.request_id,
            token_ids=list(s.generated),
            finish_reason=s.finish_reason or "abort",
            prompt_tokens=s.prompt_len,
            completion_tokens=len(s.generated),
            cached_tokens=s.cached_tokens,
            ttft_ms=(s.first_token_time - s.start_time) * 1000.0
            if s.first_token_time
            else None,
            e2e_ms=(now - s.start_time) * 1000.0,
        )
        # flight recorder: the engine's own wall-clock boundaries ride the
        # response so timeline events can be anchored at the instant the
        # engine observed them (first token sampled, sequence admitted)
        # rather than when a driver loop got around to noticing
        if s.start_time is not None:
            resp.extra["t_start"] = s.start_time
        if s.first_token_time is not None:
            resp.extra["t_first_token"] = s.first_token_time
        return resp

    # ---------------------------------------------------------- generate

    def generate(
        self,
        requests: Sequence[InferenceRequest],
        use_multi_step: bool = False,
        max_preemptions: int = 8,
    ) -> List[InferenceResponse]:
        """Batch-generate to completion (waves of ≤ max_batch_size).

        KV-pressure safe: admissions the pool cannot hold simply wait,
        decode pressure preempts the most-recently-admitted sequence
        (spill → resume, byte-identical continuation), and a request
        preempted more than ``max_preemptions`` times finishes with a
        ``preempted_too_often`` error instead of livelocking the wave.
        Clients never see an OutOfBlocksError."""
        self.collect_scan()
        pending = []
        responses: Dict[str, InferenceResponse] = {}
        for r in requests:
            if self.request_fits_pool(r):
                pending.append(r)
            else:
                # a prompt that cannot fit an idle pool would head-of-line
                # block the whole wave forever — reject it immediately and
                # keep serving the rest
                responses[r.request_id] = InferenceResponse(
                    request_id=r.request_id,
                    error="request exceeds KV pool capacity (prompt cannot "
                          "fit an idle pool)",
                )
        preempted: List[PreemptedSequence] = []
        stamp = itertools.count()
        admitted_at: Dict[int, int] = {}        # slot → admission stamp
        preempt_counts: Dict[str, int] = {}     # request_id → preemptions
        stalled = 0
        # after a preemption, resumes pause for one unpressured round so
        # the FROZEN slots reserve first — an immediate resume would take
        # back exactly the blocks the preemption freed and the pressure
        # would recur every round until the victim dies preempted_too_often
        hold_resume = False
        while pending or preempted or self.num_active:
            progressed = False
            n_free = len(self.free_slots())
            # resumes outrank fresh admissions: preempted work re-enters
            # at the head of the line
            while preempted and n_free > 0 and not hold_resume:
                try:
                    slot = self.resume(preempted[0])
                except OutOfBlocksError:
                    break               # still pressured; decode frees blocks
                preempted.pop(0)
                admitted_at[slot] = next(stamp)
                n_free -= 1
                progressed = True
            if pending and n_free > 0:
                wave, pending = pending[:n_free], pending[n_free:]
                try:
                    slots = self.submit_batch(wave, partial=True)
                except OutOfBlocksError:
                    # exhaustion in the PREFILL phase (first sampled token's
                    # block): the wave rolled back cleanly — defer it all
                    slots = []
                pending = wave[len(slots):] + pending   # deferred tail waits
                for sl in slots:
                    admitted_at[sl] = next(stamp)
                progressed = progressed or bool(slots)
            if self.num_active:
                out = (
                    self.decode_multi() if use_multi_step
                    else self.decode_step()
                )
                progressed = progressed or bool(out)
            pressure = self.take_pressure()
            if pressure is None:
                hold_resume = False     # unpressured round: resumes may flow
            elif pressure.source == "decode":
                victims = [
                    i for i, s in enumerate(self.slots)
                    if s is not None and s.finish_reason is None
                    and not s.prefilling
                ]
                if victims:
                    victim = max(
                        victims, key=lambda sl: admitted_at.get(sl, -1)
                    )
                    pre = self.preempt_slot(victim)
                    rid = pre.request.request_id
                    count = preempt_counts.get(rid, 0) + 1
                    preempt_counts[rid] = count
                    pre.preempt_count = count
                    if count > max_preemptions:
                        responses[rid] = InferenceResponse(
                            request_id=rid,
                            token_ids=list(pre.generated),
                            finish_reason="abort",
                            prompt_tokens=pre.prompt_len,
                            completion_tokens=len(pre.generated),
                            error="preempted_too_often: KV pool cannot "
                                  f"sustain this sequence ({count} "
                                  "preemptions)",
                        )
                    else:
                        preempted.append(pre)
                        hold_resume = True
                    progressed = True
            if not progressed:
                stalled += 1
                if stalled > 8 and preempted and self.num_active == 0 \
                        and not pending:
                    # an IDLE engine repeatedly failing a resume means the
                    # sequence's generated context alone no longer fits the
                    # pool — nothing will ever free more blocks. Deliver
                    # what it produced instead of wedging forever.
                    pre = preempted.pop(0)
                    rid = pre.request.request_id
                    responses[rid] = InferenceResponse(
                        request_id=rid,
                        token_ids=list(pre.generated),
                        finish_reason="abort",
                        prompt_tokens=pre.prompt_len,
                        completion_tokens=len(pre.generated),
                        error="request exceeds KV pool capacity: generated "
                              f"context ({len(pre.generated)} tokens) can "
                              "no longer be resumed",
                    )
                    stalled = 0
                elif stalled > 32:
                    raise OutOfBlocksError(
                        "generate wedged under KV pressure: "
                        f"{len(pending)} pending, {len(preempted)} "
                        f"preempted, {self.num_active} active — the pool "
                        "cannot hold even one waiting sequence"
                    )
            else:
                stalled = 0
            for i, s in enumerate(list(self.slots)):
                if s is not None and s.finish_reason is not None:
                    resp = self.finish_slot(i)
                    responses[resp.request_id] = resp
        return [responses[r.request_id] for r in requests]

    def get_stats(self) -> Dict[str, Any]:
        out = dict(self.stats)
        out["kv_cache"] = self.manager.get_stats()
        if self._state_rows:
            for name in ("state_binds", "prefix_hits_without_state"):
                out[name] = out["kv_cache"][name]
        out["active_slots"] = self.num_active
        # compiles of the PROCESS (one log for all engines), by stage: the
        # XLA compile requests, those of them that missed the persistent
        # cache, and the seconds tracing and lowering. A worker with no
        # warm-up compiles inside requests, and this shows it
        compiles = self._compile_log
        out["compiles"] = compiles.count
        out["compile_s"] = compiles.seconds
        out["compile_trace_s"] = compiles.trace_s
        out["compile_lower_s"] = compiles.lower_s
        out["compile_misses"] = compiles.misses
        # the start, as the spans of the load and of lower_serving_graphs
        # left it (a copy: a reader keeps what it read, and the heartbeat
        # reads while the graphs are lowered), the graphs' sums, and the
        # process's cache misses as they stand now
        graphs = {name: dict(row)
                  for name, row in list(self._startup["graphs"].items())}
        out["startup"] = {
            **self._startup, "at": dict(self._startup["at"]),
            "graphs": graphs, "compile_misses": compiles.misses,
            **{f"graphs_{stage}_s": sum(g[f"{stage}_s"]
                                        for g in graphs.values())
               for stage in ("trace", "lower", "backend")},
        }
        if self.cfg.speculative is not None:
            drafted = out.get("spec_drafted", 0)
            slot_steps = out.get("spec_slot_steps", 0)
            out["spec_accept_rate"] = (
                out.get("spec_accepted", 0) / drafted if drafted else 0.0
            )
            # tokens emitted per slot per verify step (1..K+1): the weight-
            # stream amortization factor the mode exists for
            out["spec_tokens_per_step"] = (
                out.get("spec_emitted", 0) / slot_steps if slot_steps
                else 0.0
            )
        return out
