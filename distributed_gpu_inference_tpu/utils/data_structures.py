"""Core typed data structures shared across the framework.

Capability parity with the reference's ``common/data_structures.py``
(WorkerRole:13, WorkerState:20, BlockRange:29, WorkerInfo:50,
InferenceState:123, KVCacheBlock:147, InferenceRequest:183,
InferenceResponse:209, SessionConfig:232, ModelShardConfig:257,
compute_prefix_hash:293, estimate_kv_cache_size:299) — re-designed for TPU:

- Workers describe TPU topology (chip generation, chips, HBM per chip, mesh
  axes) instead of CUDA device properties.
- KV-cache metadata describes *pages in a device-resident HBM pool* addressed
  by block index, never host tensors; actual KV bytes live in
  ``runtime/kv_cache.py`` pools and move between chips via ICI collectives.
- Shard configs describe pipeline *stages over a mesh axis*, with the same
  layer-range planning surface the reference exposes for Petals-style
  pipelines.

Everything here is pure-Python (dataclasses + enums), importable without jax,
and hermetically unit-testable on CPU.
"""

from __future__ import annotations

import hashlib
import struct
import time
import uuid
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Dict, List, Optional, Sequence, Tuple


# ---------------------------------------------------------------------------
# Worker identity / roles
# ---------------------------------------------------------------------------


class WorkerRole(str, Enum):
    """Role a worker plays in a disaggregated deployment.

    Parity: reference ``common/data_structures.py:13`` (HYBRID/PREFILL/DECODE);
    we add PIPELINE_STAGE for layer-sharded serving.
    """

    HYBRID = "hybrid"          # both prefill and decode (default)
    PREFILL = "prefill"        # compute-bound pool (DistServe-style)
    DECODE = "decode"          # bandwidth-bound pool
    PIPELINE_STAGE = "pipeline_stage"  # owns a contiguous layer range


class WorkerState(str, Enum):
    """Lifecycle state of a worker (reference ``data_structures.py:20``)."""

    INITIALIZING = "initializing"
    IDLE = "idle"
    BUSY = "busy"
    DRAINING = "draining"       # graceful shutdown: finish running, accept none
    OFFLINE = "offline"
    FAILED = "failed"


class JobType(str, Enum):
    """Task families the platform schedules (reference engine registry types)."""

    LLM = "llm"
    EMBEDDING = "embedding"
    IMAGE_GEN = "image_gen"
    VISION = "vision"
    WHISPER = "whisper"


class JobStatus(str, Enum):
    """Job lifecycle (reference ``server/app/api/jobs.py:229-232``)."""

    QUEUED = "queued"
    RUNNING = "running"
    COMPLETED = "completed"
    FAILED = "failed"
    CANCELLED = "cancelled"


# ---------------------------------------------------------------------------
# Layer / stage ranges
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BlockRange:
    """A contiguous half-open range of transformer layers ``[start, end)``.

    Parity: reference ``common/data_structures.py:29``. Used by the shard
    planner to describe which layers a pipeline stage owns.
    """

    start: int
    end: int

    def __post_init__(self) -> None:
        if self.start < 0 or self.end < self.start:
            raise ValueError(f"invalid layer range [{self.start}, {self.end})")

    @property
    def num_layers(self) -> int:
        return self.end - self.start

    def __contains__(self, layer: int) -> bool:
        return self.start <= layer < self.end

    def overlaps(self, other: "BlockRange") -> bool:
        return self.start < other.end and other.start < self.end

    def to_dict(self) -> Dict[str, int]:
        return {"start": self.start, "end": self.end}

    @classmethod
    def from_dict(cls, d: Dict[str, int]) -> "BlockRange":
        return cls(start=int(d["start"]), end=int(d["end"]))


# ---------------------------------------------------------------------------
# Worker info
# ---------------------------------------------------------------------------


@dataclass
class TpuTopology:
    """Describes a worker's accelerator resources, TPU-first.

    Replaces the reference's GPU fields (gpu_model/gpu_memory_gb in
    ``WorkerInfo``, ``server`` Worker row §2.1) with mesh-aware TPU facts.
    """

    chip_type: str = "v5e"           # v4 / v5e / v5p / v6e / cpu (tests)
    num_chips: int = 1
    hbm_gb_per_chip: float = 16.0
    mesh_shape: Tuple[int, ...] = (1,)
    mesh_axis_names: Tuple[str, ...] = ("data",)
    ici_bandwidth_gbps: float = 400.0   # per-link ICI
    dcn_bandwidth_gbps: float = 25.0    # host-to-host
    peak_bf16_tflops: float = 197.0     # per chip (v5e ≈ 197 bf16 TFLOP/s)

    @property
    def total_hbm_gb(self) -> float:
        return self.num_chips * self.hbm_gb_per_chip

    def to_dict(self) -> Dict[str, Any]:
        return {
            "chip_type": self.chip_type,
            "num_chips": self.num_chips,
            "hbm_gb_per_chip": self.hbm_gb_per_chip,
            "mesh_shape": list(self.mesh_shape),
            "mesh_axis_names": list(self.mesh_axis_names),
            "ici_bandwidth_gbps": self.ici_bandwidth_gbps,
            "dcn_bandwidth_gbps": self.dcn_bandwidth_gbps,
            "peak_bf16_tflops": self.peak_bf16_tflops,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TpuTopology":
        d = dict(d)
        d["mesh_shape"] = tuple(d.get("mesh_shape", (1,)))
        d["mesh_axis_names"] = tuple(d.get("mesh_axis_names", ("data",)))
        return cls(**d)


@dataclass
class WorkerInfo:
    """A worker as seen by schedulers and pipeline routers.

    Parity: reference ``common/data_structures.py:50`` (WorkerInfo) — id,
    address, role, state, layer range, load, perf counters — with TPU topology
    in place of GPU facts.
    """

    worker_id: str = field(default_factory=lambda: uuid.uuid4().hex)
    host: str = "127.0.0.1"
    port: int = 8470
    region: str = "us-central"
    role: WorkerRole = WorkerRole.HYBRID
    state: WorkerState = WorkerState.INITIALIZING
    topology: TpuTopology = field(default_factory=TpuTopology)
    layer_range: Optional[BlockRange] = None
    model_name: Optional[str] = None
    supported_types: List[str] = field(default_factory=lambda: [JobType.LLM.value])
    # load / perf
    active_sessions: int = 0
    max_sessions: int = 32
    tokens_per_second: float = 0.0
    last_heartbeat: float = field(default_factory=time.time)

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    @property
    def is_available(self) -> bool:
        return (
            self.state in (WorkerState.IDLE, WorkerState.BUSY)
            and self.active_sessions < self.max_sessions
        )

    @property
    def load_fraction(self) -> float:
        if self.max_sessions <= 0:
            return 1.0
        return self.active_sessions / self.max_sessions

    def is_stale(self, timeout_s: float = 90.0, now: Optional[float] = None) -> bool:
        """Heartbeat staleness (reference heartbeat_timeout 90 s, config.py:35)."""
        now = time.time() if now is None else now
        return (now - self.last_heartbeat) > timeout_s

    def to_dict(self) -> Dict[str, Any]:
        return {
            "worker_id": self.worker_id,
            "host": self.host,
            "port": self.port,
            "region": self.region,
            "role": self.role.value,
            "state": self.state.value,
            "topology": self.topology.to_dict(),
            "layer_range": self.layer_range.to_dict() if self.layer_range else None,
            "model_name": self.model_name,
            "supported_types": list(self.supported_types),
            "active_sessions": self.active_sessions,
            "max_sessions": self.max_sessions,
            "tokens_per_second": self.tokens_per_second,
            "last_heartbeat": self.last_heartbeat,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "WorkerInfo":
        d = dict(d)
        d["role"] = WorkerRole(d.get("role", "hybrid"))
        d["state"] = WorkerState(d.get("state", "initializing"))
        if d.get("topology"):
            d["topology"] = TpuTopology.from_dict(d["topology"])
        else:
            d["topology"] = TpuTopology()
        if d.get("layer_range"):
            d["layer_range"] = BlockRange.from_dict(d["layer_range"])
        return cls(**d)


# ---------------------------------------------------------------------------
# Inference session state
# ---------------------------------------------------------------------------


@dataclass
class InferenceState:
    """Per-request decode progress tracked by sessions and schedulers.

    Parity: reference ``common/data_structures.py:123``. On TPU the hidden
    states / KV never appear here — they are device-resident; this is pure
    host-side bookkeeping (token counts, positions, timing).
    """

    session_id: str = field(default_factory=lambda: uuid.uuid4().hex)
    request_id: str = field(default_factory=lambda: uuid.uuid4().hex)
    prompt_tokens: int = 0
    generated_tokens: int = 0
    position: int = 0                       # next position to write
    max_new_tokens: int = 256
    finished: bool = False
    finish_reason: Optional[str] = None     # "stop" | "length" | "abort" | "error"
    created_at: float = field(default_factory=time.time)
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None

    def record_token(self, n: int = 1, now: Optional[float] = None) -> None:
        now = time.time() if now is None else now
        if self.first_token_at is None:
            self.first_token_at = now
        self.last_token_at = now
        self.generated_tokens += n
        self.position += n
        if self.generated_tokens >= self.max_new_tokens:
            self.finished = True
            self.finish_reason = self.finish_reason or "length"

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return (self.first_token_at - self.created_at) * 1000.0

    @property
    def tpot_ms(self) -> Optional[float]:
        """Mean time-per-output-token after the first token."""
        if self.last_token_at is None or self.first_token_at is None:
            return None
        if self.generated_tokens <= 1:
            return 0.0
        return (
            (self.last_token_at - self.first_token_at)
            / (self.generated_tokens - 1)
            * 1000.0
        )


# ---------------------------------------------------------------------------
# KV cache block metadata
# ---------------------------------------------------------------------------

KV_BLOCK_TOKENS = 16  # tokens per page (reference kv_cache.py block_size=16)


@dataclass
class KVBlockMeta:
    """Host-side metadata for one page in a device-resident KV pool.

    Parity: reference ``common/data_structures.py:147`` (KVCacheBlock) with
    ref-count CoW semantics (:175-180) — but the payload is an *index into an
    HBM pool array*, not a tensor. Sharing a block = sharing the index;
    copy-on-write allocates a fresh index and copies the page on device.
    """

    block_id: int
    num_tokens: int = 0
    capacity: int = KV_BLOCK_TOKENS
    ref_count: int = 1
    prefix_hash: Optional[str] = None
    last_access: float = field(default_factory=time.time)

    @property
    def is_full(self) -> bool:
        return self.num_tokens >= self.capacity

    @property
    def is_shared(self) -> bool:
        return self.ref_count > 1

    def touch(self, now: Optional[float] = None) -> None:
        self.last_access = time.time() if now is None else now

    def incref(self) -> int:
        self.ref_count += 1
        return self.ref_count

    def decref(self) -> int:
        if self.ref_count <= 0:
            raise ValueError(f"block {self.block_id}: decref below zero")
        self.ref_count -= 1
        return self.ref_count


# ---------------------------------------------------------------------------
# Requests / responses
# ---------------------------------------------------------------------------


@dataclass
class SamplingParams:
    """Decode-time sampling controls (subset the reference exposes via
    ``GenerationConfig``, ``worker/engines/__init__.py:24``)."""

    max_new_tokens: int = 256
    temperature: float = 0.0      # 0 → greedy
    top_k: int = 0                # 0 → disabled
    top_p: float = 1.0            # 1.0 → disabled
    stop_token_ids: Tuple[int, ...] = ()
    seed: Optional[int] = None
    # run to the max_new_tokens budget, honoring NO stop ids (engine eos
    # included) — benchmark/oracle workloads where both A/B legs must
    # generate identical token counts (vLLM's ignore_eos parity knob)
    ignore_eos: bool = False

    def to_dict(self) -> Dict[str, Any]:
        return {
            "max_new_tokens": self.max_new_tokens,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "top_p": self.top_p,
            "stop_token_ids": list(self.stop_token_ids),
            "seed": self.seed,
            "ignore_eos": self.ignore_eos,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SamplingParams":
        d = dict(d)
        d["stop_token_ids"] = tuple(d.get("stop_token_ids", ()))
        d["ignore_eos"] = bool(d.get("ignore_eos", False))
        return cls(**d)


@dataclass
class InferenceRequest:
    """A unit of schedulable work (reference ``data_structures.py:183``)."""

    request_id: str = field(default_factory=lambda: uuid.uuid4().hex)
    job_type: JobType = JobType.LLM
    model: Optional[str] = None
    prompt: Optional[str] = None
    prompt_token_ids: Optional[List[int]] = None
    messages: Optional[List[Dict[str, str]]] = None   # chat format
    sampling: SamplingParams = field(default_factory=SamplingParams)
    priority: int = 0
    session_id: Optional[str] = None
    arrival_time: float = field(default_factory=time.time)
    # relative completion deadline (seconds from arrival). Advisory EDF
    # input for the batcher: WITHIN a priority band, earlier absolute
    # deadlines admit first and later-deadline slots are preferred
    # preemption victims. None (the default) = no deadline — ordering is
    # then byte-identical to the pre-deadline batcher.
    deadline_s: Optional[float] = None
    params: Dict[str, Any] = field(default_factory=dict)  # task-specific extras

    @property
    def deadline_at(self) -> float:
        """Absolute deadline (epoch seconds), +inf when none is set —
        directly usable as an EDF sort component."""
        if self.deadline_s is None:
            return float("inf")
        return self.arrival_time + float(self.deadline_s)

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids) if self.prompt_token_ids else 0


@dataclass
class InferenceResponse:
    """Result of an inference request (reference ``data_structures.py:209``)."""

    request_id: str
    text: Optional[str] = None
    token_ids: List[int] = field(default_factory=list)
    finish_reason: Optional[str] = None
    prompt_tokens: int = 0
    completion_tokens: int = 0
    cached_tokens: int = 0          # prefix-cache hits (reference GenerationResult)
    ttft_ms: Optional[float] = None
    e2e_ms: Optional[float] = None
    error: Optional[str] = None
    # machine-readable error class riding next to the human-readable
    # ``error`` text (round 12): ``request_timeout`` (client-side wait
    # budget elapsed — the request may still be generating), vs
    # ``shed_overload`` (the batcher rejected at admission — nothing ran,
    # safe to retry elsewhere). Surfaced through job results and SSE so
    # clients branch on the class, not on parsing the message.
    error_code: Optional[str] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return self.error is None


# ---------------------------------------------------------------------------
# Session / shard configuration
# ---------------------------------------------------------------------------


@dataclass
class SessionConfig:
    """Configuration of a distributed pipeline session
    (reference ``data_structures.py:232``)."""

    model_name: str = "llama3-8b"
    max_length: int = 8192
    dtype: str = "bfloat16"
    timeout_s: float = 60.0
    max_retries_per_hop: int = 3
    retry_backoff_s: float = 0.5
    compress_dcn: bool = True       # zstd-frame tensors on DCN/WAN hops
    use_ici_collectives: bool = True  # in-slice hops ride XLA collectives


@dataclass
class ModelShardConfig:
    """Stage plan for layer-sharded pipeline serving.

    Parity: reference ``data_structures.py:257`` + ``get_inference_route``:284.
    Stage order == inference route order (embeddings live in stage 0, final
    norm + lm_head in the last stage — reference model_shard.py:163-171).
    """

    model_name: str
    num_layers: int
    stages: List[BlockRange] = field(default_factory=list)
    stage_workers: List[str] = field(default_factory=list)  # worker_id per stage

    def __post_init__(self) -> None:
        if self.stages:
            self.validate()

    def validate(self) -> None:
        if not self.stages:
            raise ValueError("no stages")
        if self.stages[0].start != 0:
            raise ValueError("first stage must start at layer 0")
        if self.stages[-1].end != self.num_layers:
            raise ValueError(
                f"last stage ends at {self.stages[-1].end}, expected {self.num_layers}"
            )
        for a, b in zip(self.stages, self.stages[1:]):
            if a.end != b.start:
                raise ValueError(f"gap/overlap between stages {a} and {b}")
        if self.stage_workers and len(self.stage_workers) != len(self.stages):
            raise ValueError("stage_workers length != stages length")

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    def get_inference_route(self) -> List[Tuple[str, BlockRange]]:
        """Ordered (worker_id, layer_range) hops for a full forward pass."""
        self.validate()
        if not self.stage_workers:
            raise ValueError("no workers assigned to stages")
        return list(zip(self.stage_workers, self.stages))

    def stage_for_layer(self, layer: int) -> int:
        for i, rng in enumerate(self.stages):
            if layer in rng:
                return i
        raise ValueError(f"layer {layer} not in any stage")


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def compute_prefix_hash(token_ids: Sequence[int], upto: Optional[int] = None) -> str:
    """Stable hash of a token prefix for prefix-cache keys.

    Parity: reference ``data_structures.py:293`` (sha256); block-aligned
    callers pass ``upto`` = multiple of KV_BLOCK_TOKENS.
    """
    ids = token_ids if upto is None else token_ids[:upto]
    h = hashlib.sha256()
    for t in ids:
        h.update(int(t).to_bytes(4, "little", signed=False))
    return h.hexdigest()


def block_prefix_hashes(token_ids: Sequence[int], block_size: int,
                        n_blocks: int) -> List[str]:
    """``compute_prefix_hash(token_ids, (i + 1) * block_size)`` for every
    ``i < n_blocks``, in one pass over the tokens: the hash of a prefix is
    carried on to the next block instead of being started over (a chain of
    n blocks otherwise hashes n**2 / 2 blocks of tokens)."""
    ids = token_ids[: n_blocks * block_size]
    raw = struct.pack(f"<{len(ids)}I", *ids)
    h = hashlib.sha256()
    out = []
    for i in range(n_blocks):
        h.update(raw[i * block_size * 4:(i + 1) * block_size * 4])
        out.append(h.copy().hexdigest())
    return out


def estimate_kv_cache_bytes(
    num_layers: int,
    num_kv_heads: int,
    head_dim: int,
    seq_len: int,
    dtype_bytes: int = 2,
    batch: int = 1,
) -> int:
    """Bytes of KV cache for a sequence (reference ``data_structures.py:299``).

    2 (K and V) * layers * kv_heads * head_dim * seq * dtype_bytes * batch.
    """
    return 2 * num_layers * num_kv_heads * head_dim * seq_len * dtype_bytes * batch
