"""Worker-side configuration system.

Capability parity with the reference's ``worker/config.py`` (WorkerConfig:60,
ServerConfig:29, GPUConfig:36 → TpuConfig here, DirectConfig:43,
LoadControlConfig:51; precedence env > yaml > defaults :138-170; dotenv
loader :110-135; per-engine model config from env :173-188;
DEFAULT_ENGINE_CONFIGS:191).

TPU-first deltas: the accelerator section describes a TPU mesh (chip type,
requested mesh shape and axis names for dp/tp/pp/sp) instead of CUDA device
ids; engine defaults point at the JAX engine family rather than
vLLM/SGLang backends.
"""

from __future__ import annotations

import logging
import os
from pathlib import Path
from typing import Any, Dict, List, Optional, Set, Tuple

import yaml
from pydantic import BaseModel, Field, model_validator

from distributed_gpu_inference_tpu.utils.data_structures import KV_BLOCK_TOKENS

log = logging.getLogger(__name__)

ENV_PREFIX = "TPU_WORKER_"

# Serving knobs obsoleted by the ragged serving path (one kernel
# invocation carrying prefill-chunk AND decode rows — admission appends
# rows to the next round instead of scheduling competing dispatches, so
# the admission-stall shaping these knobs tuned no longer exists) and by
# the batcher's horizon rule. They stay ACCEPTED in worker YAML, plain-dict
# engine configs and remote pushes (rolling fleets, saved SLO configs) but
# are warned once per process; nothing reads ``ragged``, ``subwave``,
# ``interleave`` or ``target_step_ms``. The last two entries shaped the
# waves of the standalone tree decoder, which is gone (the engine's chain,
# ``speculative_decode: true``, is the one speculative decoder): they are
# no field of ``ServingConfig`` any more and are dropped on load.
DEPRECATED_SERVING_KEYS: Dict[str, str] = {
    "ragged": (
        "ignored: ragged rounds are the one admission path — the legacy "
        "wave / chunk-interleaved admission this key selected is gone"
    ),
    "subwave": (
        "ignored: admission appends chunk rows to the next round — there "
        "are no admission sub-waves to shape"
    ),
    "interleave": (
        "ignored: prefill chunks co-dispatch WITH decode rows in a ragged "
        "round — there are no separate dispatches left to interleave"
    ),
    "max_horizon": (
        "still caps the pure-decode scan horizon, but it is no longer the "
        "TTFT-shaping knob: admission latency is bounded by the ragged "
        "round itself, not by capping decode-scan depth"
    ),
    "target_step_ms": (
        "ignored: the batcher chooses a scan's length from the step time "
        "and the host's cost per round that it measures (runtime/batcher.py "
        "_choose_steps), not from a latency target"
    ),
    "spec_max_batch": (
        "ignored: the standalone tree decoder whose waves this key sized "
        "is gone — speculative_decode: true (the engine's chain) is the "
        "speculative decoder"
    ),
    "spec_max_active": (
        "ignored: the standalone tree decoder whose waves this key gated "
        "is gone — speculative_decode: true (the engine's chain) is the "
        "speculative decoder"
    ),
}
_deprecated_serving_warned: Set[str] = set()


def warn_deprecated_serving_key(key: str, source: str) -> None:
    """One-time (per process, per key) deprecation warning for obsoleted
    serving knobs — the keys keep working so existing YAML and saved
    remote configs deploy unchanged, but operators learn the knob is
    degenerate under ragged serving."""
    if key not in DEPRECATED_SERVING_KEYS \
            or key in _deprecated_serving_warned:
        return
    _deprecated_serving_warned.add(key)
    log.warning(
        "serving.%s (%s) is deprecated: %s",
        key, source, DEPRECATED_SERVING_KEYS[key],
    )


# Engine keys of the standalone tree decoder, which is gone: the ``engine``
# values that built it and the ``spec_widths`` that shaped its tree. A saved
# worker YAML or engine dict that names them keeps loading — as the plain
# ``jax`` engine — and says so once per process and key.
_TREE_DECODER_ENGINES = ("jax-speculative", "speculative")
_retired_engine_warned: Set[str] = set()


def retire_tree_decoder_keys(cfg: Dict[str, Any], source: str
                             ) -> Dict[str, Any]:
    """``cfg`` (an engine's config as written) without what selected or
    shaped the tree decoder: ``engine: jax-speculative`` / ``speculative``
    reads ``jax`` and ``spec_widths`` is dropped, each warned once."""
    out = dict(cfg)
    retired = {}
    if out.get("engine") in _TREE_DECODER_ENGINES:
        retired["engine"] = out["engine"]
        out["engine"] = "jax"
    if out.pop("spec_widths", None) is not None:
        retired["spec_widths"] = cfg["spec_widths"]
    for key in retired.keys() - _retired_engine_warned:
        _retired_engine_warned.add(key)
        log.warning(
            "%s: %r (%s) is ignored — the standalone tree decoder is gone "
            "and the plain jax engine loads; speculative_decode: true (the "
            "chain inside the engine's rounds) is the speculative decoder",
            key, retired[key], source,
        )
    return out


class ServerConfig(BaseModel):
    """Control-plane endpoint + credentials (reference ServerConfig:29)."""

    url: str = "http://127.0.0.1:8000"
    fallback_urls: List[str] = Field(default_factory=list)
    api_key: Optional[str] = None
    worker_id: Optional[str] = None
    auth_token: Optional[str] = None
    refresh_token: Optional[str] = None
    signing_secret: Optional[str] = None
    request_timeout_s: float = 30.0
    verify_tls: bool = True


class TpuConfig(BaseModel):
    """Accelerator resources (replaces reference GPUConfig:36)."""

    chip_type: str = "auto"             # auto-detect from jax.devices()
    mesh_shape: Optional[List[int]] = None   # None → (num_devices,)
    mesh_axis_names: List[str] = Field(default_factory=lambda: ["data"])
    hbm_utilization: float = 0.9        # fraction of HBM the KV pool may claim
    kv_cache_block_tokens: int = KV_BLOCK_TOKENS
    max_model_len: int = 8192
    dtype: str = "bfloat16"


class DirectConfig(BaseModel):
    """Worker-hosted direct inference endpoint (reference DirectConfig:43)."""

    enabled: bool = False
    host: str = "0.0.0.0"
    port: int = 8471
    public_url: Optional[str] = None


class LoadControlConfig(BaseModel):
    """Volunteer-friendly load shaping (reference LoadControlConfig:51)."""

    acceptance_rate: float = 1.0
    max_concurrent_jobs: int = 4
    max_jobs_per_hour: int = 0          # 0 = unlimited
    hbm_limit_fraction: float = 0.95
    working_hours: Optional[Tuple[int, int]] = None   # (start_h, end_h) local
    job_type_weights: Dict[str, float] = Field(default_factory=dict)
    cooldown_seconds: float = 0.0


class ServingConfig(BaseModel):
    """Batcher-backed serving front-end (``engines.<type>.serving.*``) —
    the SLO knobs, now first-class worker YAML keys
    (``worker/engines/llm.py`` SERVING_DEFAULTS mirrors these).

    The serving path runs RAGGED rounds (prefill chunk rows and decode
    rows in one kernel dispatch) and nothing else, so the keys that chose
    or shaped the old admission — ``ragged`` / ``subwave`` /
    ``interleave`` — and ``target_step_ms`` are accepted and ignored, and
    ``max_horizon`` still caps the pure-decode scan; each logs a one-time
    deprecation warning when set (``DEPRECATED_SERVING_KEYS``).
    ``queue_limit`` / ``max_wait_ms`` / ``max_horizon`` and the rest of
    ``SERVING_REMOTE_KEYS`` are remote-pushable (server
    ``WorkerRemoteConfig.serving``) and retune a LIVE batcher; ``mode``
    applies at engine load only."""

    mode: str = "batcher"               # batcher | direct (legacy driving)
    target_step_ms: Optional[float] = None   # DEPRECATED: read by nothing
    max_horizon: int = 64               # decode-scan cap (DEPRECATED knob)
    min_horizon: int = 1
    multi_step: int = 8                 # initial decode horizon
    adaptive: bool = True               # False: every scan runs multi_step
    max_wait_ms: float = 5.0            # admission latch
    queue_limit: int = 1024
    default_timeout_s: float = 300.0
    max_preemptions: int = 3
    subwave: int = 0                    # DEPRECATED: read by nothing
    interleave: int = 0                 # DEPRECATED: read by nothing
    ragged: Optional[bool] = None       # DEPRECATED: read by nothing
    # per-ROUND prefill token budget for ragged rounds: caps how many fresh
    # prompt tokens all concurrent admissions may prefill in one round
    # combined (fair water-fill split), so a 32k admission streams in over
    # many rounds instead of monopolizing every round's chunk bucket.
    # 0 = unbudgeted (pre-budget behavior). Remote-pushable.
    prefill_budget: int = 0
    # per-admission prefill chunk width override (engine ragged_chunk).
    # Read per-round and bucketed through compiled prefill widths, so it is
    # safe to retune live. None = keep the engine default. Remote-pushable.
    ragged_chunk: Optional[int] = None
    # hopeless-work abandonment (gray-failure round): when True the batcher
    # drops deadline-carrying work whose deadline has passed AND whose
    # projected remaining decode cannot land within ``deadline_grace_s``
    # (typed ``deadline_abandoned`` error; blocks freed at the next step
    # boundary). Never fires for deadline-less requests. Remote-pushable.
    abandon_deadlines: bool = False
    deadline_grace_s: float = 0.5
    # predictive abandonment (round 18): the same ITL projection fires
    # BEFORE the deadline passes, so a job that provably cannot land stops
    # burning ragged-round slots immediately (counted separately as
    # ``abandoned_predictive``). Requires abandon_deadlines. Remote-pushable.
    predictive_abandon: bool = False

    @model_validator(mode="before")
    @classmethod
    def _warn_deprecated(cls, data: Any) -> Any:
        # on the keys as written: the ones that are no field any more are
        # dropped by the model (extra keys are ignored) and warned here
        if isinstance(data, dict):
            for key in data.keys() & DEPRECATED_SERVING_KEYS.keys():
                warn_deprecated_serving_key(key, "worker YAML")
        return data


class EngineModelConfig(BaseModel):
    """Per-task-type engine/model selection (reference :173-188)."""

    engine: str = "jax"                 # jax | echo (tests)
    model: str = "llama3-tiny"
    dtype: str = "bfloat16"
    quantization: Optional[str] = None  # int8 | fp8 | None
    serving: Optional[ServingConfig] = None   # None → engine defaults
    extra: Dict[str, Any] = Field(default_factory=dict)

    @model_validator(mode="before")
    @classmethod
    def _retire_tree_decoder(cls, data: Any) -> Any:
        if isinstance(data, dict):
            return retire_tree_decoder_keys(data, "worker YAML")
        return data


DEFAULT_ENGINE_CONFIGS: Dict[str, EngineModelConfig] = {
    "llm": EngineModelConfig(engine="jax", model="llama3-8b"),
    "embedding": EngineModelConfig(engine="jax-embedding", model="llama3-8b"),
    "vision": EngineModelConfig(engine="jax-vision", model="llama3-8b-vision"),
    "image_gen": EngineModelConfig(engine="jax-diffusion", model="tiny-diffusion"),
    "whisper": EngineModelConfig(engine="jax-whisper", model="tiny-whisper"),
}


class WorkerConfig(BaseModel):
    """Root worker configuration (reference WorkerConfig:60)."""

    name: str = "tpu-worker"
    region: str = "us-central"
    task_types: List[str] = Field(default_factory=lambda: ["llm"])
    # PD disaggregation role (reference pd_scheduler WorkerCapability roles):
    # "prefill" | "decode" | "hybrid". Decode-capable workers should also set
    # pd_data_plane_url so prefill peers can push KV handoffs to them.
    role: str = "hybrid"
    pd_data_plane_url: Optional[str] = None
    server: ServerConfig = Field(default_factory=ServerConfig)
    tpu: TpuConfig = Field(default_factory=TpuConfig)
    direct: DirectConfig = Field(default_factory=DirectConfig)
    load_control: LoadControlConfig = Field(default_factory=LoadControlConfig)
    engines: Dict[str, EngineModelConfig] = Field(default_factory=dict)
    poll_interval_s: float = 2.0
    heartbeat_interval_s: float = 30.0
    log_level: str = "INFO"
    config_version: int = 0             # server-pushed remote config version

    def engine_for(self, task_type: str) -> EngineModelConfig:
        if task_type in self.engines:
            return self.engines[task_type]
        if task_type in DEFAULT_ENGINE_CONFIGS:
            # deep copy: callers may mutate; the process-wide defaults must not
            return DEFAULT_ENGINE_CONFIGS[task_type].model_copy(deep=True)
        raise KeyError(f"no engine config for task type {task_type!r}")


# ---------------------------------------------------------------------------
# Loading: defaults < yaml < env  (reference precedence :138-170)
# ---------------------------------------------------------------------------


def load_dotenv(path: str | Path = ".env", override: bool = False) -> Dict[str, str]:
    """Minimal dotenv loader (reference hand-rolled loader :110-135)."""
    loaded: Dict[str, str] = {}
    p = Path(path)
    if not p.exists():
        return loaded
    for line in p.read_text().splitlines():
        line = line.strip()
        if not line or line.startswith("#") or "=" not in line:
            continue
        key, _, val = line.partition("=")
        key, val = key.strip(), val.strip().strip("'\"")
        if override or key not in os.environ:
            os.environ[key] = val
        loaded[key] = val
    return loaded


def _deep_update(base: Dict[str, Any], upd: Dict[str, Any]) -> Dict[str, Any]:
    for k, v in upd.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _deep_update(base[k], v)
        else:
            base[k] = v
    return base


def _env_overrides(environ: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """TPU_WORKER_SERVER__URL=... → {"server": {"url": ...}} (``__`` nests).

    Values stay strings except JSON/YAML-looking composites — pydantic performs
    the per-field numeric/bool coercion, so a numeric-looking API key or worker
    name is not corrupted into an int.
    """
    environ = os.environ if environ is None else environ
    out: Dict[str, Any] = {}
    for key, raw in environ.items():
        if not key.startswith(ENV_PREFIX):
            continue
        path = key[len(ENV_PREFIX):].lower().split("__")
        val: Any = raw
        if raw.startswith(("[", "{")):
            try:
                val = yaml.safe_load(raw)
            except Exception:
                pass
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = val
    return out


def load_worker_config(
    yaml_path: Optional[str | Path] = None,
    environ: Optional[Dict[str, str]] = None,
    dotenv_path: str | Path = ".env",
    missing_ok: bool = False,
) -> WorkerConfig:
    """Build a WorkerConfig with precedence env > yaml > defaults.

    A ``yaml_path`` that does not exist raises unless ``missing_ok=True``
    (workers booting for the first time pass missing_ok for the default path).
    ``.env`` is only folded into the process environment when reading from it
    (``environ is None``) — an explicit environ mapping keeps the call hermetic.
    """
    if environ is None:
        load_dotenv(dotenv_path)
    data: Dict[str, Any] = {}
    if yaml_path is not None:
        p = Path(yaml_path)
        if p.exists():
            with open(p) as f:
                file_data = yaml.safe_load(f) or {}
            if not isinstance(file_data, dict):
                raise ValueError(f"config file {yaml_path} must contain a mapping")
            _deep_update(data, file_data)
        elif not missing_ok:
            raise FileNotFoundError(f"config file not found: {yaml_path}")
    _deep_update(data, _env_overrides(environ))
    return WorkerConfig.model_validate(data)


def save_worker_config(cfg: WorkerConfig, yaml_path: str | Path) -> None:
    """Persist config (the worker writes issued credentials back after
    registration — reference main.py:133-136). Atomic temp+fsync+rename:
    this file carries ISSUED CREDENTIALS — a crash or disk-full torn write
    mid-save must leave the previous config intact, never a truncated one
    that locks the worker out on restart (round 19)."""
    from distributed_gpu_inference_tpu.runtime.io_guard import (
        atomic_write_text,
    )

    path = Path(yaml_path)
    path.parent.mkdir(parents=True, exist_ok=True)
    atomic_write_text(
        path, yaml.safe_dump(cfg.model_dump(mode="json"), sort_keys=False)
    )


def set_dotted(cfg: WorkerConfig, dotted_key: str, value: Any) -> WorkerConfig:
    """`gpu-worker set server.url http://…` style dotted update
    (reference cli.py:790)."""
    data = cfg.model_dump()
    node = data
    parts = dotted_key.split(".")
    for p in parts[:-1]:
        if p not in node or not isinstance(node[p], dict):
            raise KeyError(f"unknown config section {p!r} in {dotted_key!r}")
        node = node[p]
    if parts[-1] not in node:
        raise KeyError(f"unknown config key {dotted_key!r}")
    node[parts[-1]] = value
    return WorkerConfig.model_validate(data)
