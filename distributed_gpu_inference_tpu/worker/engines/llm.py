"""TPU-native LLM engine: tokenizer + jitted paged-KV serving engine.

The reference's role split was ``worker/engines/llm.py`` (HF Transformers
generate) vs ``llm_vllm.py``/``llm_sglang.py`` (wrapped serving frameworks).
Here there is ONE first-party path: :class:`runtime.engine.TPUEngine` (jitted
prefill + multi-step decode over paged KV with prefix caching) IS the serving
framework, so this module only adds what the reference engines layered on
top — chat templating, tokenization, stop strings, and the
``GenerationResult`` surface.

Tokenizers are pluggable: pass ``tokenizer`` in config (anything with
``encode``/``decode``), name a HF tokenizer via ``tokenizer_id``, or fall
back to a deterministic byte-level tokenizer (hermetic tests / air-gapped
boxes — no network fetch, mirroring the reference's offline-test strategy).
"""

from __future__ import annotations

import asyncio
import queue as _queue_mod
import random
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import httpx

from ...runtime.batcher import (
    BatcherConfig,
    BatcherServing,
    RequestMigrated,
    synthesize_checkpoint,
)
from ...testing import faults as _faults
from ...utils.backoff import full_jitter_delay
from ...runtime.engine import EngineConfig, PreemptedSequence, TPUEngine
from ...runtime.flight import (
    EGRESS_KEY,
    NULL_TIMELINE,
    Egress,
    adopt_phases,
    phase,
    span,
    timeline_for,
)
from ...runtime.prefix_summary import TIER_HOST, TIER_SPILL, PrefixHotSet
from ...utils.config import (
    ServingConfig,
    retire_tree_decoder_keys,
    warn_deprecated_serving_key,
)
from ...utils.data_structures import InferenceRequest, SamplingParams
from .base import (
    EngineLoadError,
    GenerationConfig,
    GenerationResult,
    JobMigrated,
    LLMBaseEngine,
    ServingError,
)


def _raise_serving(resp: Any) -> None:
    """Raise the serving failure carried by an InferenceResponse,
    preserving the machine-readable ``error_code`` (request_timeout /
    shed_overload / …) so job results and SSE error events can surface
    the class, not just the message."""
    raise ServingError(resp.error,
                       error_code=getattr(resp, "error_code", None))

# Worker-YAML / remote-config serving knobs (``engines.llm.serving.*``) —
# THE SLO configuration surface measured by the round-5 frontier. The
# single source of truth for keys AND defaults is the pydantic-validated
# YAML surface, ``utils.config.ServingConfig``; this dict is derived from
# it so plain-dict engine construction (benchmarks, tests) can never
# drift from YAML-configured workers.
SERVING_DEFAULTS: Dict[str, Any] = ServingConfig().model_dump()

# remote-config ``serving`` keys that may retune a LIVE batcher (pushed via
# WorkerRemoteConfig)
SERVING_REMOTE_KEYS: Dict[str, str] = {
    "max_horizon": "max_multi_step",
    "min_horizon": "min_multi_step",
    "multi_step": "multi_step",
    "adaptive": "adaptive",
    "max_wait_ms": "max_wait_ms",
    "queue_limit": "queue_limit",
    "default_timeout_s": "default_timeout_s",
    "max_preemptions": "max_preemptions",
    # long-context round shaping: the per-round prefill token budget and
    # the per-admission chunk width are both read per-round (widths bucket
    # through compiled prefill_buckets), so they retune live without a
    # recompile — push them to trade 32k prefill throughput against
    # co-batched decode ITL
    "prefill_budget": "prefill_budget",
    "ragged_chunk": "ragged_chunk",
    # gray-failure round: hopeless-deadline abandonment is a policy read
    # per step-boundary scan — flip it live to shed doomed work fleet-wide
    "abandon_deadlines": "abandon_deadlines",
    "deadline_grace_s": "deadline_grace_s",
    # round 20: fire the same projection BEFORE the deadline passes
    "predictive_abandon": "predictive_abandon",
}


class ByteTokenizer:
    """Deterministic fallback: UTF-8 bytes offset past special ids.

    vocab = 256 + specials; id 0 = pad/bos, 1 = eos. Keeps the whole stack
    runnable hermetically (tests, benchmarks with random weights).
    """

    eos_token_id = 1
    bos_token_id = 0

    def __init__(self, offset: int = 4) -> None:
        self._offset = offset
        self.vocab_size = 256 + offset

    def encode(self, text: str) -> List[int]:
        return [b + self._offset for b in text.encode("utf-8")]

    def decode(self, ids: List[int]) -> str:
        # ids beyond the byte range (models with vocab > 256+offset emit
        # them under random weights) are dropped, not crashed on
        data = bytes(
            i - self._offset for i in ids
            if self._offset <= i < self._offset + 256
        )
        return data.decode("utf-8", errors="replace")

    def apply_chat_template(self, messages: List[Dict[str, str]]) -> str:
        parts = [f"<|{m.get('role', 'user')}|>{m.get('content', '')}"
                 for m in messages]
        return "".join(parts) + "<|assistant|>"


def _load_hf_tokenizer(tokenizer_id: str):
    try:
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(tokenizer_id)
    except Exception as exc:  # noqa: BLE001 — offline box, bad id, ...
        raise EngineLoadError(f"cannot load tokenizer {tokenizer_id!r}: {exc}")


class _StreamSplicer:
    """Per-snapshot token→chunk derivation shared by BOTH stream drivers
    (batcher-backed ``_stream_serving`` and legacy ``_stream_direct``).

    This is the one block the exactly-once streaming contract requires to
    stay byte-identical across serving modes: resume-splice re-derivation,
    whole-sequence re-decode (multi-byte chars and cross-chunk stop
    strings stay correct), the stop scan, holdback, and delta/new-ids
    emission. The chaos suites assert the two drivers emit identical
    event streams — one implementation, not two copies.

    ``advance(gen, finished)`` consumes a monotonic generated-token
    prefix and returns ``(chunk | None, stop_cut)``; the caller stamps
    and yields the chunk (offset = ``sent_tokens``) and handles the
    driver-specific abort when ``stop_cut`` is True."""

    def __init__(self, tokenizer, cfg, holdback: int,
                 resume_from: int, resume_text: int) -> None:
        self.tokenizer = tokenizer
        self.cfg = cfg
        self.holdback = holdback
        self.resume_text = resume_text
        self.sent_tokens = 0
        self.sent_text = ""
        # splice point of a resumed stream: the client already consumed
        # tokens [0, resume_from) — regenerate silently up to it, then
        # re-derive the exact text the ORIGINAL stream had delivered at
        # that offset (same holdback formula, same deterministic tokens)
        self.splice: Optional[int] = resume_from if resume_from > 0 else None
        self.finish_override: Optional[str] = None

    @staticmethod
    def _trim_partial_tail(text: str, floor: int) -> str:
        """Withhold trailing replacement characters: a U+FFFD at the very
        end of an incremental decode is (usually) an INCOMPLETE multi-byte
        sequence the next token's bytes complete — emitting it now would
        bake the wrong character into the stream, and the whole-stream
        text would diverge from the batch decode of the same tokens
        (fleet chaos suite caught exactly this). Held-back chars are
        delivered once resolved, or verbatim at finish (a genuine lone
        invalid byte still reaches the client). Never trims below
        ``floor`` (text already delivered)."""
        while len(text) > floor and text.endswith("�"):
            text = text[:-1]
        return text

    def advance(self, gen: List[int], finished: bool):
        if self.splice is not None and (len(gen) >= self.splice or finished):
            self.sent_tokens = min(self.splice, len(gen))
            raw = self.tokenizer.decode(gen[: self.sent_tokens])
            self.sent_text = raw
            if self.holdback:
                self.sent_text = self.sent_text[
                    : max(len(self.sent_text) - self.holdback, 0)
                ]
            # mirror the live stream's partial-tail holdback: at offset
            # ``splice`` the original stream had NOT yet delivered a
            # trailing replacement char, so the re-derived consumed text
            # must not count it either
            self.sent_text = self._trim_partial_tail(self.sent_text, 0)
            if self.resume_text > len(self.sent_text):
                # a holdback flush reached the client before the drop:
                # its characters are consumed even though the token
                # offset didn't advance
                self.sent_text = raw[: self.resume_text]
            self.splice = None
        if self.splice is not None or \
                (len(gen) <= self.sent_tokens and not finished):
            return None, False
        # decode the WHOLE sequence: multi-byte characters and
        # cross-chunk stop strings stay correct
        full = self.tokenizer.decode(gen)
        stop_idx = -1
        for st in self.cfg.stop:
            idx = full.find(st)
            if idx >= 0 and (stop_idx < 0 or idx < stop_idx):
                stop_idx = idx
        if stop_idx >= 0:
            target = full[:stop_idx]
            self.finish_override = "stop"
        elif finished:
            target = full
        else:
            target = full[: max(len(full) - self.holdback,
                                len(self.sent_text))]
            target = self._trim_partial_tail(target, len(self.sent_text))
        delta = target[len(self.sent_text):]
        # token ids past a stop cut are not emitted
        new_ids = [] if stop_idx >= 0 else list(gen[self.sent_tokens:])
        self.sent_text = target
        self.sent_tokens = len(gen)
        # emit on new token ids even when the text delta is empty (id
        # outside the tokenizer's decodable range, or held back):
        # exactly-once delivery means every sampled id reaches the client
        # in some chunk — silently skipped ids would desync the splice
        chunk = ({"text_delta": delta, "token_ids": new_ids}
                 if (delta or new_ids) else None)
        return chunk, stop_idx >= 0


class _CheckpointPusher:
    """Latest-wins background pusher for stream-cadence checkpoints.

    The sink is a blocking control-plane HTTP call, which must never stall
    the decode loop (a hung control plane would otherwise freeze every
    live SSE stream for a full timeout per push). One pending entry per
    key is kept — a newer checkpoint supersedes an unsent older one, so a
    slow plane costs checkpoint STALENESS (bounded extra recompute on
    failover), never tokens/sec."""

    def __init__(self, sink) -> None:
        self._sink = sink
        self._latest: Dict[str, Dict[str, Any]] = {}
        self._cv = threading.Condition()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="ckpt-pusher"
        )
        self._thread.start()

    def put(self, entry: Dict[str, Any]) -> None:
        with self._cv:
            self._latest[str(entry.get("key"))] = entry
            self._cv.notify()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._latest:
                    self._cv.wait()
                _, entry = self._latest.popitem()
            try:
                self._sink(entry)
            except Exception:  # noqa: BLE001 — best-effort by contract
                pass


class TPULLMEngine(LLMBaseEngine):
    """config keys: model (name in models/configs registry), tokenizer /
    tokenizer_id, max_batch_size, max_seq_len, multi_step,
    enable_prefix_cache, checkpoint_path (orbax/HF weights via models.loader),
    quantization (int8 | fp8 weight-only, ops/quantization.py).
    """

    task_type = "llm"
    # the worker injects a ``_failover_ctx`` (job id, assignment epoch,
    # server-held checkpoint) only into engines that advertise this — the
    # llm engine then checkpoints in-flight generations to the control
    # plane and resumes a requeued job from its checkpoint
    supports_failover = True

    def __init__(self, config: Optional[Dict[str, Any]] = None) -> None:
        super().__init__(
            retire_tree_decoder_keys(config or {}, "engine config"))
        self.engine: Optional[TPUEngine] = None
        # batcher-backed serving front-end (the DEFAULT worker path since
        # round 6): all queued jobs and direct/SSE requests share decode
        # rounds through one ContinuousBatcher; ``serving.mode: direct``
        # restores the legacy per-request engine driving
        self.serving: Optional[BatcherServing] = None
        self.tokenizer = self.config.get("tokenizer")
        # PD disaggregation: kv_cache_key → (slot, seq, adopted_at) — an
        # adopted (or locally retained) sequence awaiting its decode-stage
        # job. ``seq`` identity-guards late frees (the slot index may be
        # recycled), ``adopted_at`` drives the TTL purge: a decode job that
        # never arrives (decode child swept, parent re-prefilled elsewhere)
        # must not pin its KV blocks for the life of the engine.
        self._pd_slots: Dict[str, tuple] = {}
        self.pd_slot_ttl_s = float(
            self.config.get("pd_slot_ttl_s", 180.0) or 180.0
        )
        # sender/receiver handoff lifecycle counters — cumulative totals,
        # heartbeat engine_stats["pd"] → delta-anchored
        # pd_handoffs_total{outcome} / pd_handoff_bytes_total on the plane
        self.pd_stats: Dict[str, int] = {
            "handoffs_committed": 0,
            "handoffs_failed": 0,
            "handoffs_aborted": 0,
            "handoffs_local": 0,
            "handoff_bytes": 0,
            "piece_retries": 0,
            "adopted_expired": 0,
        }
        # per-piece push robustness knobs (satellite: a transport blip must
        # not fail the whole handoff on the first try)
        self._pd_push_timeout_s = float(
            self.config.get("pd_push_timeout_s", 30.0) or 30.0
        )
        self._pd_push_retries = int(
            self.config.get("pd_push_retries", 3) or 0
        )
        self._pd_push_backoff_s = float(
            self.config.get("pd_push_backoff_s", 0.2) or 0.2
        )
        self._pd_rng = random.Random(0x9D5)
        # serializes engine mutation between the job path and the
        # data-plane KV receiver thread (adoption arrives asynchronously)
        self._engine_lock = threading.Lock()
        # streamed-handoff session machine (created with the engine)
        self._handoff_rx = None
        # crash-safe generation: key → live in-flight generation metadata
        # (request_id to find the slot, kind job|stream, assignment epoch).
        # The heartbeat thread snapshots these via checkpoint_live WITHOUT
        # the engine lock — snapshots read host-side Python/numpy mirrors
        # only, and a torn read degrades to a skipped checkpoint, never a
        # stalled heartbeat behind a whole generation.
        self._live: Dict[str, Dict[str, Any]] = {}
        self._live_lock = threading.Lock()
        # graceful drain: set by interrupt_live(); queued-job drivers freeze
        # their sequence at the next step boundary and raise JobMigrated
        self._interrupt = threading.Event()
        # optional push cadence between heartbeats: the worker points this
        # at its control-plane client; the stream path calls it once at
        # admission and every checkpoint_interval_tokens afterwards
        self.checkpoint_sink = None
        self._ckpt_pusher: Optional[_CheckpointPusher] = None
        # corrupt server-held checkpoints refused at resume (bad crc /
        # unparseable row): each one degrades to a from-scratch recompute —
        # counted here, ships via kv_spill_wire_stats (round 19)
        self.ckpt_corrupt = 0
        self._ckpt_interval = int(
            self.config.get("checkpoint_interval_tokens", 8) or 0
        )
        # cache-aware routing: bounded hot-set of prefix boundary
        # fingerprints (runtime/prefix_summary.py) — rides heartbeats as
        # this worker's radix summary so the control plane can route
        # prefix-sharing requests back here. prefix_summary_top_n=0
        # disables the channel.
        top_n = int(self.config.get("prefix_summary_top_n", 128) or 0)
        self.prefix_hot: Optional[PrefixHotSet] = (
            PrefixHotSet(top_n) if top_n > 0 else None
        )
        self._prefix_evictions_seen = 0
        # cluster-wide KV migration (round 13): pull a hot prefix from a
        # peer's /kv/export instead of re-prefilling, and serve peers'
        # pulls from our own radix + spill tiers. Worker-side default ON;
        # whether any request actually migrates is the ROUTER's per-request
        # cost-model decision (RoutingConfig.kv_migrate, default off).
        self.kv_migrate_enabled = bool(self.config.get("kv_migrate", True))
        self._kvmig_max_blocks = int(
            self.config.get("kv_migrate_max_blocks", 64) or 64
        )
        self._kvmig_timeout_s = float(
            self.config.get("kv_migrate_pull_timeout_s", 20.0) or 20.0
        )
        # migration budget: concurrent pulls beyond this recompute instead
        # of stacking network reads (a migrate-hint storm must degrade to
        # PR 7 behavior, never amplify the overload that caused it)
        self._kvmig_budget = int(self.config.get("kv_migrate_budget", 2) or 2)
        self._kvmig_backoff_s = float(
            self.config.get("kv_migrate_backoff_s", 1.0) or 1.0
        )
        self._kvmig_lock = threading.Lock()
        self._kvmig_inflight = 0
        # peer url → (consecutive failures, monotonic deadline): after a
        # failed pull the peer is skipped under jittered exponential
        # backoff — the PD re-prefill contract shape (first failure falls
        # back immediately, repeats spread past the outage)
        self._kvmig_backoff: Dict[str, tuple] = {}
        self._kvmig_rng = random.Random(0x5CAF)
        # cumulative counters → heartbeat engine_stats["kv_migrate"] →
        # kv_migrations_total{outcome} / kv_migration_bytes_total
        self.kv_migrate_stats: Dict[str, int] = {
            "pulled": 0, "fallback_recompute": 0, "aborted": 0,
            "local_hits": 0,
            "pull_bytes": 0, "pull_blocks": 0,
            "exports": 0, "export_bytes": 0,
            # proactive replication (round 20): plane-hinted prefetch pulls
            "replicated": 0, "replicate_miss": 0, "replicate_aborted": 0,
        }
        # fingerprint → prompt token ids, for fp-keyed exports (round 18
        # proactive replication: the COLD puller knows only the text-space
        # fingerprint the plane hinted; this worker — the warm exporter —
        # resolves it back to the exact token ids its radix is keyed by).
        # Bounded LRU, populated per built request alongside the hot-set
        # note; entries for one prompt share one token list.
        self._kvmig_fp_tokens: "OrderedDict[str, List[int]]" = OrderedDict()
        self._kvmig_fp_cap = 512
        # request flight recorder (round 14): per-request Timelines for
        # traced requests (params carry a trace_id). Completed timelines
        # ride job results (complete_job) AND a bounded heartbeat ring
        # (direct streams never pass complete_job); cumulative counters
        # delta-anchor into flight_timelines_total / events_dropped on the
        # plane. Always advisory — a recorder problem never fails a job.
        self.flight_stats: Dict[str, int] = {
            "timelines": 0, "events_dropped": 0,
        }
        from collections import deque as _deque

        self._flight_recent: Any = _deque(maxlen=8)

    # -- lifecycle -----------------------------------------------------------

    def load_model(self) -> None:
        outer: Dict[str, Any] = {}
        with phase("dgi.llm.load_model", outer, "load_model",
                   model=str(self.config.get("model", "llama3-mini"))):
            model_name = self.config.get("model", "llama3-mini")
            if self.tokenizer is None:
                tok_id = self.config.get("tokenizer_id")
                self.tokenizer = (
                    _load_hf_tokenizer(tok_id) if tok_id else ByteTokenizer()
                )
            # KV spill tiers: host-RAM L2 block budget + optional L3 remote
            # store from a config URL (redis://host:port/db — the real RESP
            # client in runtime/redis_kv.py; memory:// for single-node tests)
            from distributed_gpu_inference_tpu.runtime.redis_kv import (
                remote_store_from_url,
            )

            sv = self._serving_config()
            eng_cfg = EngineConfig(
                max_batch_size=int(self.config.get("max_batch_size", 8)),
                # EngineModelConfig names no context length: a worker's file
                # carries it under ``extra``, as it carries ``tp_size``
                max_seq_len=int(
                    self.config.get("max_seq_len")
                    or (self.config.get("extra") or {}).get("max_seq_len")
                    or 2048),
                multi_step=int(self.config.get("multi_step", 16)),
                enable_prefix_cache=bool(
                    self.config.get("enable_prefix_cache", True)
                ),
                quantization=self.config.get("quantization"),
                # KV-pool storage dtype (int8 | fp8 | None = activation dtype)
                # — previously engine-API-only; spec verify reads int8 pools
                # through the ragged kernel's in-kernel dequant since round 8,
                # so the worker config can finally compose quantized KV with
                # speculative serving
                kv_cache_dtype=self.config.get("kv_cache_dtype"),
                spill_host_blocks=int(
                    self.config.get("kv_spill_host_blocks", 0)),
                spill_remote_store=remote_store_from_url(
                    self.config.get("kv_remote_url"),
                    ttl_s=float(self.config.get("kv_remote_ttl_s", 3600.0)),
                ),
                # long-context pool sizing: the default rule (1.5x batch x
                # max_blocks_per_seq) assumes every slot can run max_seq_len
                # deep — at 32k that is mostly pad, so deployments size the
                # pool for the actual working set instead
                num_blocks=(int(self.config["num_blocks"])
                            if self.config.get("num_blocks") else None),
            )
            if self.config.get("prefill_buckets"):
                eng_cfg.prefill_buckets = tuple(
                    sorted(int(w) for w in self.config["prefill_buckets"])
                )
            # long-context chunk width: per-round knob, so load-time config is
            # just the initial value (remote pushes can retune it live)
            if sv.get("ragged_chunk"):
                eng_cfg.ragged_chunk = int(sv["ragged_chunk"])
            # speculative decoding (EngineConfig.speculative): every decode
            # round runs fused draft→verify→accept steps committing 1..K+1
            # tokens per slot. Greedy outputs stay byte-identical; sampled
            # requests ride the same graph at one token per step.
            if self.config.get("speculative_decode"):
                from ...runtime.speculative import SpecDecodeConfig

                try:
                    oracle = self.config.get("spec_oracle_accept")
                    eng_cfg.speculative = SpecDecodeConfig(
                        num_draft_tokens=int(
                            self.config.get("spec_num_draft_tokens", 4)
                        ),
                        # acceptance-adaptive draft depth (per-slot EMA
                        # selects K from a static set — one compiled graph)
                        adaptive=bool(self.config.get("spec_adaptive", False)),
                        adaptive_min_k=int(
                            self.config.get("spec_adaptive_min_k", 1)
                        ),
                        # bench-only oracle draft: force the acceptance rate
                        # (fraction of drafted tokens) — real cost, forced
                        # decision; outputs are garbage, pair with ignore_eos
                        oracle_accept_rate=(
                            None if oracle is None else float(oracle)
                        ),
                    )
                    eng_cfg.speculative.validate(eng_cfg)
                except (ValueError, TypeError) as exc:
                    raise EngineLoadError(
                        f"speculative_decode config invalid: {exc}"
                    ) from exc
            # first-class TP: tp_size > 1 builds a model-axis mesh over local
            # devices (the reference forwarded tensor_parallel_size to vLLM;
            # here the engine itself shards, llm_vllm.py:56 / SURVEY §2.2)
            mesh = None
            tp = int(self.config.get("tp_size") or
                     (self.config.get("extra") or {}).get("tp_size") or 1)
            if tp > 1:
                import jax

                from ...parallel.mesh import MeshPlan, make_mesh

                devices = jax.local_devices()  # only addressable chips: a mesh
                # over another process's devices would fail or diverge per host
                if len(devices) < tp:
                    raise EngineLoadError(
                        f"tp_size={tp} but only {len(devices)} local devices"
                    )
                mesh = make_mesh(MeshPlan(model=tp), devices[:tp],
                                 keep_trivial_axes=False)
            try:
                self.engine = TPUEngine(
                    model_name,
                    eng_cfg,
                    checkpoint_path=self.config.get("checkpoint_path"),
                    mesh=mesh,
                )
            except ValueError as exc:
                # invalid mesh/model combination must drop the task type, not
                # kill worker startup (load_engines catches EngineLoadError)
                raise EngineLoadError(str(exc)) from exc
            if self.engine.model_cfg.latent_kv:
                # peers pull K/V pages (runtime/kv_handoff.py), not this
                # cache's
                self.kv_migrate_enabled = False
            if eng_cfg.speculative is not None and \
                    int(self.config.get("spec_distill_steps", 0)) > 0:
                # optional on-load draft distillation against the engine's own
                # target weights; a random head is still correct, just ~0
                # acceptance, so failures here must not kill the task type
                try:
                    self.engine.distill_draft(
                        steps=int(self.config["spec_distill_steps"])
                    )
                except Exception as exc:  # noqa: BLE001 — no optax, OOM, ...
                    raise EngineLoadError(
                        f"speculative draft distillation failed: {exc}"
                    ) from exc
            if str(sv["mode"]) == "batcher":
                try:
                    self.serving = BatcherServing(
                        self.engine, self._batcher_config(sv)
                    )
                except (ValueError, RuntimeError) as exc:
                    raise EngineLoadError(
                        f"batcher serving config invalid: {exc}"
                    ) from exc
        adopt_phases(self.engine.stats["startup"], outer)
        self.loaded = True

    def _serving_config(self) -> Dict[str, Any]:
        """Merged serving knobs: defaults < ``config['serving']`` (worker
        YAML ``engines.llm.serving.*``) < ``extra['serving']``."""
        out = dict(SERVING_DEFAULTS)
        for src in (self.config.get("serving"),
                    (self.config.get("extra") or {}).get("serving")):
            if isinstance(src, dict):
                # plain-dict construction (benchmarks, tests) bypasses the
                # pydantic surface, so the obsoleted-knob deprecation
                # warning fires here too — but only for values that differ
                # from the defaults (CLI surfaces pass their whole arg
                # namespace through; a knob nobody touched must not warn)
                for k, v in src.items():
                    if v is not None and v != SERVING_DEFAULTS.get(k):
                        warn_deprecated_serving_key(
                            k, "engine serving config"
                        )
                out.update({k: v for k, v in src.items() if v is not None})
        return out

    @staticmethod
    def _batcher_config(sv: Dict[str, Any]) -> BatcherConfig:
        return BatcherConfig(
            max_wait_ms=float(sv["max_wait_ms"]),
            multi_step=int(sv["multi_step"]),
            min_multi_step=int(sv["min_horizon"]),
            max_multi_step=int(sv["max_horizon"]),
            adaptive=bool(sv["adaptive"]),
            queue_limit=int(sv["queue_limit"]),
            default_timeout_s=float(sv["default_timeout_s"]),
            max_preemptions=int(sv["max_preemptions"]),
            prefill_budget=int(sv.get("prefill_budget") or 0),
            abandon_deadlines=bool(sv.get("abandon_deadlines") or False),
            deadline_grace_s=float(sv.get("deadline_grace_s") or 0.5),
            predictive_abandon=bool(sv.get("predictive_abandon") or False),
        )

    def apply_serving_config(self, updates: Optional[Dict[str, Any]]) -> None:
        """Server-pushed SLO retune (remote config ``serving`` section):
        applied to the LIVE batcher between rounds. ``mode`` is load-time
        only and ignored here, as are the keys nothing reads any more
        (``DEPRECATED_SERVING_KEYS``: warned once)."""
        if self.serving is None or not updates:
            return
        for key in updates:
            warn_deprecated_serving_key(key, "remote config push")
        kw = {
            SERVING_REMOTE_KEYS[k]: v
            for k, v in updates.items()
            if k in SERVING_REMOTE_KEYS and v is not None
        }
        if kw:
            self.serving.reconfigure(**kw)

    def serving_stats(self) -> Optional[Dict[str, Any]]:
        """Live batcher stats (occupancy, queue depth, ragged admissions,
        preemption counters, horizon) — ride the worker heartbeat into the
        control plane's ``/metrics``. None when serving mode is direct."""
        if self.serving is None or not self.serving.active:
            return None
        return self.serving.get_stats()

    def prefix_summary_wire(self) -> Optional[Dict[str, Any]]:
        """Next heartbeat radix-summary payload (full snapshot or delta —
        ``runtime/prefix_summary.py`` wire format), or None when the
        control plane is up to date. Before encoding, cold entries are
        tier-demoted in proportion to pool evictions since the last wire
        — an advertised ``dev`` entry whose block was evicted would
        otherwise overpromise until the staleness TTL."""
        hot = self.prefix_hot   # snapshot vs concurrent disable()
        if hot is None:
            return None
        eng = self.engine
        if eng is not None and getattr(eng, "manager", None) is not None:
            ev = int(eng.manager.stats.evictions or 0)
            delta = ev - self._prefix_evictions_seen
            if delta > 0 and len(hot):
                frac = min(1.0, delta / len(hot))
                if eng.manager.spill_on_evict:
                    # evicted blocks landed in a spill tier: restorable,
                    # but pricier than device-resident — demote to the
                    # tier they ACTUALLY landed in, so the router's cost
                    # model prices a host-RAM pull vs a remote-store one
                    # (host wins when both exist: spill writes through L2
                    # first and probes hit it first)
                    tier = (TIER_HOST if eng.manager.host_store is not None
                            else TIER_SPILL)
                    hot.demote(frac, tier=tier)
                else:
                    # no spill tier: evicted KV is GONE — advertising it
                    # at any weight would over-promise for a full TTL
                    hot.drop(frac)
            self._prefix_evictions_seen = ev
        return hot.wire()

    def prefix_summary_ack(self) -> None:
        hot = self.prefix_hot
        if hot is not None:
            hot.ack()

    def prefix_summary_resync(self) -> None:
        hot = self.prefix_hot
        if hot is not None:
            hot.resync()

    def prefix_summary_disable(self) -> None:
        """The control plane statically rejected our summaries (wire
        version / fingerprint-basis skew): stop shipping them — a
        payload the server can never apply would otherwise ping-pong
        full snapshots on every heartbeat until redeploy."""
        self.prefix_hot = None

    def _exclusive(self, fn: Any) -> Any:
        """Serialize out-of-band engine work (PD stages, handoff adoption)
        with the batcher's decode rounds: the callable runs on the
        batcher's single engine-executor thread. Without a batcher the
        caller's ``_engine_lock`` is the only serialization needed."""
        if self.serving is not None and self.serving.active:
            return self.serving.run_exclusive(fn)
        return fn()

    def unload(self) -> None:
        if self.serving is not None:
            self.serving.stop(drain=False)
            self.serving = None
        self.engine = None
        super().unload()

    # -- core generate ---------------------------------------------------------

    def _to_prompt(self, prompt_or_messages: Any) -> str:
        if isinstance(prompt_or_messages, str):
            return prompt_or_messages
        if isinstance(prompt_or_messages, list):  # chat messages
            tmpl = getattr(self.tokenizer, "apply_chat_template", None)
            if tmpl is not None:
                try:
                    out = tmpl(prompt_or_messages, tokenize=False,
                               add_generation_prompt=True)
                except TypeError:  # ByteTokenizer's simpler signature
                    out = tmpl(prompt_or_messages)
                return out
            return "\n".join(m.get("content", "") for m in prompt_or_messages)
        raise ValueError(f"bad prompt type {type(prompt_or_messages)}")

    def _stop_ids(self, cfg: GenerationConfig) -> tuple:
        ids = list(cfg.stop_token_ids)
        eos = getattr(self.tokenizer, "eos_token_id", None)
        if eos is not None and eos not in ids:
            ids.append(int(eos))
        return tuple(ids[:4])

    def _sampling_from(self, cfg: GenerationConfig) -> SamplingParams:
        """THE GenerationConfig → SamplingParams mapping — every request
        construction path (interactive, batch, PD prefill) goes through
        here so per-request knobs like ``ignore_eos`` cannot be honored on
        one path and dropped on another."""
        return SamplingParams(
            max_new_tokens=cfg.max_new_tokens,
            temperature=cfg.temperature,
            top_k=cfg.top_k,
            top_p=cfg.top_p,
            stop_token_ids=(() if cfg.ignore_eos else self._stop_ids(cfg)),
            seed=cfg.seed,
            ignore_eos=cfg.ignore_eos,
        )

    def _encode_prompt(self, prompt_or_messages: Any,
                       cfg: GenerationConfig) -> List[int]:
        """THE prompt → token-ids mapping (template, tokenize, truncate)
        shared by request building and the KV-migration pull driver — the
        pulled prefix must key on exactly the tokens the admission will
        probe with."""
        text = self._to_prompt(prompt_or_messages)
        token_ids = list(self.tokenizer.encode(text))
        max_prompt = self.engine.cfg.max_seq_len - cfg.max_new_tokens - 1
        if len(token_ids) > max_prompt > 0:
            token_ids = token_ids[-max_prompt:]  # keep the tail (recency)
        return token_ids

    def _build_request(self, prompt_or_messages: Any,
                       cfg: GenerationConfig,
                       token_ids: Optional[List[int]] = None
                       ) -> InferenceRequest:
        """One request builder for the blocking AND streaming paths — the
        two must never diverge on tokenization/truncation/sampling.
        ``token_ids``: pre-encoded prompt (the KV-migration pull driver
        already ran ``_encode_prompt`` on the same inputs — hinted
        requests must not pay template+tokenize twice)."""
        if not self.loaded or self.engine is None:
            raise EngineLoadError("engine not loaded")
        hot = self.prefix_hot   # snapshot: the heartbeat thread may
        if hot is not None and \
                self.engine.cfg.enable_prefix_cache:  # disable() to None
            # every built request's prefix will be radix-cached on
            # completion — record its boundary fingerprints for the
            # heartbeat summary (advisory; one O(prefix) hash pass)
            from ...utils.prefixes import (
                canonical_prompt_text,
                prefix_fingerprints,
            )
            fps = prefix_fingerprints(
                canonical_prompt_text(prompt_or_messages),
                hot.block_chars, hot.max_blocks,
            )
            hot.note_fingerprints(fps)
            if fps and self.kv_migrate_enabled:
                if token_ids is None:
                    token_ids = self._encode_prompt(prompt_or_messages, cfg)
                # fp-keyed export resolution (proactive replication): a
                # cold puller hints only the text-space fingerprint; map
                # every boundary of this prompt to its token ids so
                # kv_export can serve the pull. One shared list per prompt
                with self._kvmig_lock:
                    for fp in fps:
                        self._kvmig_fp_tokens[fp] = token_ids
                        self._kvmig_fp_tokens.move_to_end(fp)
                    while len(self._kvmig_fp_tokens) > self._kvmig_fp_cap:
                        self._kvmig_fp_tokens.popitem(last=False)
        if token_ids is None:
            token_ids = self._encode_prompt(prompt_or_messages, cfg)
        return InferenceRequest(
            prompt_token_ids=token_ids,
            sampling=self._sampling_from(cfg),
            # EDF input: the batcher orders same-priority admissions by
            # absolute deadline and prefers slack-rich preemption victims
            deadline_s=cfg.deadline_s,
        )

    # -- PD disaggregation stages (server/pd_flow.py drives these) ----------

    def inference(self, params: Dict[str, Any]) -> Dict[str, Any]:
        # the lock covers EVERY engine-touching job path, not just the PD
        # stages: the data-plane kv_receiver thread adopts handoffs
        # asynchronously, and an unlocked ordinary generate would race it
        # on the same engine. pd_prefill manages its own lock scope — the
        # KV push is network I/O that must happen OUTSIDE the lock (two
        # hybrid workers pushing to each other while holding their locks
        # would deadlock until the HTTP timeout).
        stage = params.get("pd_stage")
        if stage == "prefill":
            return self.pd_prefill(params)
        if stage is None:
            # flight recorder: the request's Timeline is minted here (the
            # single entry point for non-PD inference) and stashed through
            # params so the migrate hook and the terminal driver share it
            tl = self._flight_timeline(params)
            if tl.enabled:
                params["_flight_tl"] = tl
            # router-hinted KV migration: pull the hot prefix from the
            # named peer BEFORE admission (never under the engine lock —
            # the peer's export serializes on ITS engine; ours adopts the
            # frames through kv_receiver's own serialization)
            self._maybe_migrate_kv(params)
        if self.serving is not None and self.serving.active:
            # batcher-backed serving: the batcher owns engine serialization
            # (every engine call runs on its one executor thread), so
            # concurrent jobs/streams need no engine lock — they share
            # decode rounds instead of queueing on it
            if stage == "decode":
                return self.pd_decode(params)
            ctx = params.get("_failover_ctx")
            if isinstance(ctx, dict):
                return self._job_inference(params, ctx)
            return self._serving_inference(params)
        with self._engine_lock:
            if stage == "decode":
                return self.pd_decode(params)
            ctx = params.get("_failover_ctx")
            if isinstance(ctx, dict):
                # queued-job failover path: interruptible driver that
                # registers for heartbeat checkpointing and resumes from a
                # server-held checkpoint when the claim carries one
                return self._job_inference(params, ctx)
            tl = params.pop("_flight_tl", NULL_TIMELINE)
            tl.note("worker.start", path="legacy")
            out = super().inference(params)
        tl.note("worker.done")
        self._flight_finish(tl, out if isinstance(out, dict) else None)
        return out

    def _serving_inference(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Blocking request through the batcher front-end (direct server /
        plain jobs): same tokenization, stop handling, and result payload
        as the legacy ``_generate`` path, but concurrent callers share
        decode rounds via slot-level continuous batching."""
        cfg = GenerationConfig.from_params(params)
        tl = params.pop("_flight_tl", NULL_TIMELINE)
        tl.note("worker.start", path="serving")
        req = self._build_request(
            params.get("messages") or params.get("prompt") or "", cfg,
            token_ids=params.pop("_kvmig_token_ids", None),
        )
        if params.get("priority") is not None:
            req.priority = int(params.get("priority") or 0)
        if params.get("speculative") is False:
            req.params["speculative"] = False
        # hedged dispatch: the direct server mints a cancel event for
        # requests carrying a hedge key — the losing racer's abort rides
        # the batcher's step-boundary cancel path (partial output with
        # finish_reason="abort", never an error)
        cancel = params.pop("_cancel_evt", None)
        t0 = time.perf_counter()
        resp = self.serving.submit(req, cancel=cancel,
                                   flight=tl if tl.enabled else None)
        if resp.error is not None:
            _raise_serving(resp)
        tl.note("worker.done")
        payload = self._finish_payload(
            list(resp.token_ids), resp.prompt_tokens, resp.cached_tokens,
            resp.finish_reason or "stop", cfg, resp.ttft_ms,
            time.perf_counter() - t0,
        )
        self._flight_finish(tl, payload)
        return payload

    def _pd_push(self, client: Any, url: str, content: bytes) -> Any:
        """POST one handoff message with a per-piece timeout and a bounded
        full-jitter retry ladder (``utils.backoff`` — the same formula as
        the APIClient's): a transport blip or transient 5xx must not fail
        the whole handoff on its first occurrence. Receiver-side begin and
        commit are idempotent (duplicate-delivery tolerant), and piece
        re-staging is a no-op on already-staged blocks, so retrying any
        message kind is safe. Retries are counted (``piece_retries``) so a
        flaky link is VISIBLE in /metrics, not silently absorbed."""
        from distributed_gpu_inference_tpu.runtime.kv_handoff import (
            message_kind,
        )

        kind = message_kind(content)
        attempt = 0
        while True:
            try:
                r = _faults.wrap_http(
                    "worker.pd.push",
                    lambda: client.post(
                        url, content=content,
                        headers={"content-type": "application/octet-stream"},
                        timeout=self._pd_push_timeout_s,
                    ),
                    worker=str(getattr(self, "fault_tag", "") or ""),
                    kind=kind,
                )
                if r.status_code < 500:
                    r.raise_for_status()   # 4xx: receiver rejected — no retry
                    return r
                last = RuntimeError(
                    f"KV push {kind} answered HTTP {r.status_code}: "
                    f"{r.text[:200]}"
                )
            except httpx.TransportError as exc:
                last = exc
            if attempt >= self._pd_push_retries:
                raise last
            delay = full_jitter_delay(
                self._pd_push_backoff_s, attempt, self._pd_rng
            )
            time.sleep(delay or 0.0)
            attempt += 1
            self.pd_stats["piece_retries"] += 1

    def _purge_stale_pd_slots(self) -> None:
        """Free adopted/retained PD slots whose decode-stage job never
        arrived within ``pd_slot_ttl_s`` (decode child swept, parent
        re-prefilled elsewhere, stale attempt completing late) — an
        orphaned adoption must not pin its KV blocks for the life of the
        engine. Caller holds ``_engine_lock``; frees run serialized with
        decode rounds and are identity-guarded against slot recycling."""
        if not self._pd_slots:
            return
        now = time.monotonic()
        eng = self.engine
        for key, (slot, seq, adopted_at) in list(self._pd_slots.items()):
            if now - adopted_at <= self.pd_slot_ttl_s:
                continue
            # pop-to-claim: pd_decode pops WITHOUT the engine lock, so
            # the dict pop is the one atomic arbiter — if the decode
            # stage won the entry between our snapshot and now, the
            # sequence is live (being adopted into the batch) and is NOT
            # ours to free
            if self._pd_slots.pop(key, None) is None:
                continue
            self.pd_stats["adopted_expired"] += 1
            if eng is not None:
                self._release_adopted_slot(eng, slot, seq)

    def pd_maintain(self) -> None:
        """Periodic PD housekeeping (worker heartbeat cadence): age out
        adopted slots whose decode stage never came — a re-prefilled flow
        cancels its stale decode child, but the KV its prefill already
        pushed would otherwise sit adopted until message-driven purging
        happens to run. Non-blocking: a busy engine lock skips this beat
        (the next one retries)."""
        if not self._pd_slots or self.engine is None:
            return
        if not self._engine_lock.acquire(blocking=False):
            return
        try:
            self._purge_stale_pd_slots()
        finally:
            self._engine_lock.release()

    def pd_prefill(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Prefill stage: run the prompt, sample the first token (TTFT),
        export the sequence's KV pages, and push them to the decode worker's
        data plane (``/kv/transfer`` — HTTP twin of grpc TransferKVCache).
        When this worker IS the decode target (KV affinity), the slot is
        simply retained — zero migration bytes."""
        from distributed_gpu_inference_tpu.runtime.kv_handoff import (
            export_slot_kv,
            serialize_handoff,
        )

        if not self.loaded or self.engine is None:
            raise EngineLoadError("engine not loaded")
        cfg = GenerationConfig.from_params(params)
        prompt = params.get("prompt_token_ids") or params.get("messages") \
            or params.get("prompt") or ""
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], int):
            req = InferenceRequest(
                prompt_token_ids=[int(t) for t in prompt],
                sampling=self._sampling_from(cfg),
            )
        else:
            req = self._build_request(prompt, cfg)
        key = params.get("kv_cache_key") or f"pd-{req.request_id}"
        # the key rides IN the handoff (session_id) so the receiver can
        # index the adopted slot for the decode-stage job
        req.session_id = key
        # flight recorder: the prefill child's events merge into the PD
        # parent's trace (children inherit parent params, trace_id included)
        tl = self._flight_timeline(params)
        tl.note("pd.prefill.start", key=key)
        decode_url = params.get("decode_url")
        local = not decode_url or params.get("decode_worker") in (
            None, params.get("target_worker"),
        )
        # streamed push (VERDICT r3 #3): chunk the export per page range and
        # overlap the wire hop with remaining prefill compute. Default on
        # for cross-host pushes; sliding-window models fall back to the
        # one-shot blob (the streamed protocol rejects them).
        stream_ok = (
            not local
            and bool(params.get("pd_stream",
                                self.config.get("pd_stream", True)))
            and self.engine.model_cfg.sliding_window is None
        )
        if stream_ok:
            return self._pd_prefill_streamed(
                req, key, decode_url,
                piece_blocks=int(
                    params.get("pd_stream_piece_blocks")
                    or self.config.get("pd_stream_piece_blocks", 4)
                ),
                tl=tl,
            )
        def _prefill_and_export():
            # engine-touching block: under a batcher it runs on the engine
            # executor thread (serialized with live decode rounds) — the
            # admitted slot composes with concurrently-decoding slots
            slot = self.engine.submit_batch([req])[0]
            s = self.engine.slots[slot]
            first_token = int(self.engine._last_tokens[slot])
            ttft_ms = (
                (s.first_token_time - s.start_time) * 1000.0
                if s.first_token_time else None
            )
            prompt_tokens = s.prompt_len
            if local:
                # KV affinity: this worker decodes too — retain the slot.
                # A re-run of the same child (lost completion report)
                # supersedes its previous retained slot — free it or it
                # leaks with no TTL entry (we're on the engine executor:
                # freeing directly is serialized with decode rounds).
                prev = self._pd_slots.get(key)
                if prev is not None and prev[0] != slot and \
                        self.engine.slots[prev[0]] is prev[1]:
                    self.pd_stats["adopted_expired"] += 1
                    self.engine.finish_slot(prev[0], cache=False)
                self._pd_slots[key] = (slot, s, time.monotonic())
                return slot, first_token, ttft_ms, prompt_tokens, None
            try:
                handoff = export_slot_kv(self.engine, slot)
                return slot, first_token, ttft_ms, prompt_tokens, \
                    serialize_handoff(handoff)
            finally:
                # donor side is done with the sequence once the bytes are
                # serialized: free the slot before the network hop so a
                # failed or slow push cannot leak it
                self.engine.finish_slot(slot)

        with self._engine_lock:
            slot, first_token, ttft_ms, prompt_tokens, raw = \
                self._exclusive(_prefill_and_export)
        tl.note("pd.prefill.done", ttft_ms=ttft_ms)
        if local:
            self.pd_stats["handoffs_local"] += 1
            tl.note("handoff.local")
            out = {
                "pd_stage": "prefill", "kv_cache_key": key,
                "first_token": first_token, "ttft_ms": ttft_ms,
                "migration_bytes": 0, "migration_ms": 0.0,
                "decode_slot": slot, "local": True,
                # prefill compute billed on this child; the decode child
                # bills the completion (usage shape = units_from_result)
                "usage": {"prompt_tokens": prompt_tokens,
                          "completion_tokens": 0,
                          "total_tokens": prompt_tokens},
            }
            self._flight_finish(tl, out)
            return out
        # network push OUTSIDE the engine lock: a peer pushing to US can
        # adopt concurrently (kv_receiver takes the lock the engine work
        # above released) — no crossed-push deadlock
        t0 = time.perf_counter()
        tl.note("handoff.begin", bytes=len(raw))
        try:
            with httpx.Client() as client:
                resp = self._pd_push(
                    client, decode_url.rstrip("/") + "/kv/transfer", raw
                )
        except Exception:
            self.pd_stats["handoffs_failed"] += 1
            tl.note("handoff.failed")
            self._flight_finish(tl)   # ships via the heartbeat ring
            raise
        migration_ms = (time.perf_counter() - t0) * 1000.0
        remote = resp.json()
        self.pd_stats["handoffs_committed"] += 1
        self.pd_stats["handoff_bytes"] += len(raw)
        tl.note("handoff.commit", bytes=len(raw))
        out = {
            "pd_stage": "prefill", "kv_cache_key": key,
            "first_token": first_token, "ttft_ms": ttft_ms,
            "migration_bytes": len(raw), "migration_ms": migration_ms,
            "decode_slot": remote.get("slot"), "local": False,
            "usage": {"prompt_tokens": prompt_tokens,
                      "completion_tokens": 0,
                      "total_tokens": prompt_tokens},
        }
        self._flight_finish(tl, out)
        return out

    def _pd_prefill_streamed(self, req: InferenceRequest, key: str,
                             decode_url: str,
                             piece_blocks: int = 4,
                             tl: Any = NULL_TIMELINE) -> Dict[str, Any]:
        """Streamed prefill stage: pages cross the wire WHILE the prompt is
        still computing (``runtime.kv_handoff.StreamedExport``). A sender
        thread drains the message queue so network I/O never runs under the
        engine lock (same no-crossed-push-deadlock stance as the one-shot
        path); ``migration_ms`` is the decode-ready delay — first token
        sampled → commit acked — the number the one-shot path pays in full
        after prefill."""
        import queue as _queue

        from distributed_gpu_inference_tpu.runtime.kv_handoff import (
            StreamedExport,
            abort_message,
        )

        url = decode_url.rstrip("/") + "/kv/transfer"
        exp = StreamedExport(self.engine, req, key,
                             piece_blocks=piece_blocks)
        q: "_queue.SimpleQueue" = _queue.SimpleQueue()
        state: Dict[str, Any] = {"exc": None, "last": None, "t_ack": None}

        def _sender() -> None:
            with httpx.Client(timeout=60.0) as client:
                while True:
                    item = q.get()
                    if item is None:
                        return
                    if state["exc"] is not None:
                        continue        # drain after failure
                    try:
                        # per-piece timeout + bounded jittered retry
                        # (_pd_push): a transport blip mid-stream retries
                        # the piece instead of failing the whole handoff
                        r = self._pd_push(client, url, item)
                        state["last"] = r.json()
                        state["t_ack"] = time.perf_counter()
                    except Exception as exc:  # noqa: BLE001
                        state["exc"] = exc

        sender = threading.Thread(target=_sender, daemon=True,
                                  name="pd-stream-sender")
        sender.start()
        t_prefill_end = None

        def _abort_remote() -> None:
            # direct POST, not via the queue — the sender drains (skips)
            # queued items once state["exc"] is set, and the receiver's
            # half-built session would otherwise pin its KV blocks.
            # Each failed handoff is counted EXACTLY ONCE across the
            # pd_handoffs_total outcome labels: "aborted" = a streamed
            # handoff failed and its abort was sent (this path);
            # "failed" = a one-shot push failed (no session to abort).
            self.pd_stats["handoffs_aborted"] += 1
            try:
                httpx.post(url, content=abort_message(key), timeout=10.0)
            except Exception:  # noqa: BLE001
                pass

        gen = exp.messages()

        def _drive_export() -> Optional[float]:
            # the generator's cleanup (abort_chunked/finish_slot)
            # mutates the engine, so it must run INSIDE the serialized
            # region — close explicitly rather than leaving it to GC
            # (it would race the kv_receiver thread / a decode round)
            t_end = None
            try:
                for msg in gen:
                    if state["exc"] is not None:
                        # fail fast: the push is already doomed — stop
                        # prefilling/gathering and release the engine
                        raise state["exc"]
                    if t_end is None and exp.first_token is not None:
                        t_end = time.perf_counter()
                    q.put(msg)
            finally:
                gen.close()
            return t_end

        tl.note("handoff.begin", streamed=True)
        try:
            with self._engine_lock:
                t_prefill_end = self._exclusive(_drive_export)
        except Exception:
            q.put(None)
            sender.join(timeout=60.0)
            _abort_remote()
            tl.note("handoff.failed")
            self._flight_finish(tl)   # ships via the heartbeat ring
            raise
        q.put(None)
        # generous wire budget: bytes / ~1 MB/s, floor 120 s — a slower link
        # is treated as failed, never silently reported as success
        sender.join(timeout=max(120.0, exp.bytes_sent / 1e6))
        if sender.is_alive():
            state["exc"] = state["exc"] or TimeoutError(
                f"streamed KV push did not finish ({exp.bytes_sent} bytes)"
            )
        if state["exc"] is not None:
            _abort_remote()
            tl.note("handoff.failed")
            self._flight_finish(tl)
            raise state["exc"]
        remote = state["last"] or {}
        self.pd_stats["handoffs_committed"] += 1
        self.pd_stats["handoff_bytes"] += exp.bytes_sent
        migration_ms = (
            (state["t_ack"] - t_prefill_end) * 1000.0
            if state["t_ack"] is not None and t_prefill_end is not None
            else None
        )
        # perf_counter stamps → wall clock for the timeline (one shared
        # offset; sub-ms drift over a handoff is noise)
        wall_minus_perf = time.time() - time.perf_counter()
        if t_prefill_end is not None:
            tl.note_at("pd.prefill.done", t_prefill_end + wall_minus_perf,
                       ttft_ms=exp.ttft_ms)
        else:
            tl.note("pd.prefill.done", ttft_ms=exp.ttft_ms)
        if state["t_ack"] is not None:
            tl.note_at("handoff.commit", state["t_ack"] + wall_minus_perf,
                       bytes=exp.bytes_sent, pieces=exp.pieces_sent)
        else:
            tl.note("handoff.commit", bytes=exp.bytes_sent)
        out = {
            "pd_stage": "prefill", "kv_cache_key": key,
            "first_token": exp.first_token, "ttft_ms": exp.ttft_ms,
            "migration_bytes": exp.bytes_sent,
            "migration_ms": migration_ms,
            "pd_streamed": True,
            "pieces": exp.pieces_sent,
            "bytes_before_first_token": exp.bytes_before_first_token,
            "decode_slot": remote.get("slot"), "local": False,
            "usage": {"prompt_tokens": exp.prompt_tokens,
                      "completion_tokens": 0,
                      "total_tokens": exp.prompt_tokens},
        }
        self._flight_finish(tl, out)
        return out

    def pd_decode(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Decode stage: resume the adopted (or retained) slot and stream
        the rest of the generation. TTFT/E2E stay end-to-end truthful — the
        handoff carries the original start/first-token times."""
        if not self.loaded or self.engine is None:
            raise EngineLoadError("engine not loaded")
        key = params.get("kv_cache_key") or ""
        tl = self._flight_timeline(params)
        tl.note("pd.decode.start", key=key)
        if tl.enabled and self._handoff_rx is not None:
            # adopt the receiver-side handoff instants (begin/commit were
            # observed by the data-plane thread, which knows only the
            # session key) into this request's timeline
            tl.extend_at(self._handoff_rx.pop_flight(key))
        entry = self._pd_slots.pop(key, None)
        if entry is None:
            raise RuntimeError(
                f"no adopted KV for key {key!r} — handoff never arrived"
            )
        slot, _adopted_seq, _adopted_at = entry
        eng = self.engine
        if eng.slots[slot] is not _adopted_seq:
            # the adoption was reclaimed (TTL purge raced this claim, or
            # the slot was recycled after an engine-side abort): the KV is
            # gone — fail like a lost handoff so the flow re-prefills
            raise RuntimeError(
                f"adopted KV for key {key!r} was reclaimed before the "
                "decode stage claimed it"
            )
        if self.serving is not None and self.serving.active:
            # batcher-backed: the adopted slot joins the shared decode
            # rounds instead of monopolizing the engine for its whole
            # generation (it preempts/resumes like any other sequence)
            seq = eng.slots[slot]
            try:
                resp = self.serving.adopt_slot(
                    slot, flight=tl if tl.enabled else None
                )
            except Exception:
                self._release_adopted_slot(eng, slot, seq)
                raise
            if resp.error is not None:
                self._release_adopted_slot(eng, slot, seq)
                _raise_serving(resp)
        else:
            try:
                while eng.slots[slot] is not None and \
                        eng.slots[slot].finish_reason is None:
                    eng.decode_multi()
                    self._raise_if_pressured(eng, slot)
            except Exception:
                # the job fails, so the adopted slot MUST be released — a
                # leaked slot would hold its KV blocks forever and compound
                # the very pressure that aborted it
                if eng.slots[slot] is not None:
                    eng.finish_slot(slot, cache=False)
                raise
            resp = eng.finish_slot(slot)
        text = self.tokenizer.decode(resp.token_ids) if self.tokenizer else ""
        tl.note("pd.decode.done", tokens=resp.completion_tokens)
        out = {
            "pd_stage": "decode", "kv_cache_key": key,
            "text": text,
            "token_ids": list(resp.token_ids),
            "prompt_tokens": resp.prompt_tokens,
            "completion_tokens": resp.completion_tokens,
            "finish_reason": resp.finish_reason,
            "ttft_ms": resp.ttft_ms,
            "e2e_ms": resp.e2e_ms,
            # decode child bills the completion (prefill child billed the
            # prompt — together they equal the non-PD job's total)
            "usage": {"prompt_tokens": 0,
                      "completion_tokens": resp.completion_tokens,
                      "total_tokens": resp.completion_tokens},
        }
        self._flight_finish(tl, out)
        return out

    def _release_adopted_slot(self, eng: TPUEngine, slot: int,
                              seq: Any) -> None:
        """Legacy-path parity: a failed PD decode MUST free its adopted
        slot — leaked KV blocks would hold their pages for the life of the
        engine. Identity-guarded: batcher error paths that already released
        the slot (preemption cap, engine-error abort) may have recycled the
        index for another sequence, which is not ours to finish."""
        def _free() -> None:
            if eng.slots[slot] is seq:
                eng.finish_slot(slot, cache=False)

        try:
            if self.serving is not None and self.serving.active:
                # serialize with live decode rounds
                self.serving.run_exclusive(_free)
                return
        except Exception:  # noqa: BLE001 — loop stopping: free directly
            pass
        try:
            _free()
        except Exception:  # noqa: BLE001 — release is best-effort
            pass

    @staticmethod
    def _raise_if_pressured(eng: TPUEngine, slot: int) -> None:
        """Single-sequence drivers (PD decode, token streaming) have no
        scheduler above them to preempt a victim for: when the engine
        freezes THIS slot at a pressure boundary, surface the pre-existing
        OutOfBlocksError contract instead of spinning on empty rounds.
        (The continuous batcher path recovers gracefully via
        preempt → spill → resume; these paths report the job as failed
        exactly as they did before pressure became a scheduling event.)"""
        from ...runtime.kv_cache import OutOfBlocksError

        p = eng.take_pressure()
        if p is not None and slot in p.slots:
            raise OutOfBlocksError(
                f"KV pool exhausted while decoding slot {slot} and no "
                "scheduler is attached to preempt for it"
            )

    def kv_receiver(self, raw: bytes) -> Dict[str, Any]:
        """Data-plane ``/kv/transfer`` hook: adopt a pushed handoff into this
        engine and index the slot by the kv_cache_key. Handles both the
        one-shot blob AND the streamed begin/piece/commit/abort messages
        (``runtime.kv_handoff.HandoffReceiver`` dispatches on the frame
        magic) — one endpoint, two wire modes."""
        from distributed_gpu_inference_tpu.runtime.kv_handoff import (
            HandoffReceiver,
        )

        if not self.loaded or self.engine is None:
            raise EngineLoadError("engine not loaded")
        with self._engine_lock:
            if self._handoff_rx is None or \
                    self._handoff_rx.engine is not self.engine:
                self._handoff_rx = HandoffReceiver(self.engine)
            # orphaned adoptions (decode job never came) age out here, on
            # the same serialized path that created them
            self._purge_stale_pd_slots()
            # adoption mutates the engine (block allocation + slot bind):
            # under a batcher it runs on the engine executor thread,
            # serialized with live decode rounds
            result = self._exclusive(lambda: self._handoff_rx.handle(raw))
            if result.get("slot") is not None:
                slot = result["slot"]
                key = result["kv_cache_key"]
                # pop-to-claim (same arbiter as the TTL purge): a decode
                # stage that already popped this key owns its sequence
                prev = self._pd_slots.pop(key, None)
                if prev is not None and prev[0] != slot:
                    # a re-run of the same prefill child (requeued after
                    # its completion report was lost post-commit) pushed
                    # the SAME key again: the new adoption supersedes the
                    # old one — free the superseded slot NOW. Overwriting
                    # the index without freeing would orphan it with no
                    # TTL entry, leaking the slot for the engine's life.
                    self.pd_stats["adopted_expired"] += 1
                    self._release_adopted_slot(self.engine, prev[0],
                                               prev[1])
                self._pd_slots[key] = (
                    slot, self.engine.slots[slot], time.monotonic()
                )
        return result

    # -- cluster-wide KV migration (round 13) --------------------------------

    def kv_export(self, raw: bytes) -> bytes:
        """Data-plane ``/kv/export`` hook: a cold peer asks for the longest
        locally-cached full-block prefix of its request's token ids. The
        answer is a framed sequence of the SAME chaos-hardened streamed
        handoff messages the ``/kv/transfer`` push path uses (prefix-only
        begin/piece/commit — ``runtime.kv_handoff.export_prefix_frames``),
        sourced from the device radix AND the host/remote spill tiers. An
        empty body means "nothing cached" and the peer recomputes."""
        from distributed_gpu_inference_tpu.runtime.kv_handoff import (
            _frame_blobs,
            export_prefix_frames,
            unpack_export_request,
        )

        if not self.loaded or self.engine is None:
            raise EngineLoadError("engine not loaded")
        if not self.kv_migrate_enabled:
            raise ValueError("kv migration disabled on this worker")
        req = unpack_export_request(raw)
        eng = self.engine
        if req.get("model_name") != eng.model_cfg.name:
            raise ValueError(
                f"model mismatch: engine={eng.model_cfg.name} "
                f"request={req.get('model_name')}"
            )
        if int(req.get("block_size") or 0) != eng.cfg.block_size:
            raise ValueError("block_size mismatch between engines")
        if bool(req.get("int8_kv")) != ("k_scale" in eng.kv):
            raise ValueError(
                "kv_cache_dtype mismatch: int8 pools can only export to "
                "int8 pools (and vice versa)"
            )
        max_blocks = min(
            self._kvmig_max_blocks, int(req.get("max_blocks") or 64)
        )
        token_ids = req.get("token_ids") or []
        if not token_ids and req.get("fp"):
            # fp-keyed pull (proactive replication): the cold puller never
            # saw the prompt — resolve the hinted fingerprint back to the
            # token ids our radix is keyed by. A miss (LRU churn, restart)
            # answers empty: an honest "nothing cached", never an error
            with self._kvmig_lock:
                token_ids = self._kvmig_fp_tokens.get(
                    str(req["fp"])) or []
        with self._engine_lock:
            frames, info = self._exclusive(lambda: export_prefix_frames(
                eng, token_ids, str(req.get("key") or ""),
                max_blocks=max_blocks,
                start_block=int(req.get("start_block") or 0),
            ))
        body = _frame_blobs(*frames) if frames else b""
        if frames:
            self.kv_migrate_stats["exports"] += 1
            self.kv_migrate_stats["export_bytes"] += len(body)
        return body

    def _kvmig_peer_allowed(self, url: str) -> bool:
        """Budget + per-peer backoff gate (taken together under one lock):
        a pull is only attempted when the concurrent-pull budget has room
        AND the peer is not inside a failure backoff window."""
        with self._kvmig_lock:
            _, until = self._kvmig_backoff.get(url, (0, 0.0))
            if time.monotonic() < until or \
                    self._kvmig_inflight >= self._kvmig_budget:
                return False
            self._kvmig_inflight += 1
            return True

    # a peer that REJECTED a pull (4xx: model/dtype/geometry mismatch or
    # migration disabled) is pinned out for this long — retrying a
    # permanent incompatibility after every backoff window would burn an
    # HTTP round-trip per hinted request forever
    _KVMIG_REJECT_PIN_S = 600.0

    def _kvmig_peer_result(self, url: str, ok: bool,
                           permanent: bool = False) -> None:
        with self._kvmig_lock:
            self._kvmig_inflight = max(0, self._kvmig_inflight - 1)
            if ok:
                self._kvmig_backoff.pop(url, None)
                return
            fails, _ = self._kvmig_backoff.get(url, (0, 0.0))
            fails += 1
            if permanent:
                self._kvmig_backoff[url] = (
                    fails, time.monotonic() + self._KVMIG_REJECT_PIN_S
                )
                return
            # PD re-prefill shape: the FIRST failure only falls back (no
            # wait — the request recomputes immediately); repeats arm a
            # jittered exponential window so a storm of hinted requests
            # doesn't hammer a dead peer
            delay = full_jitter_delay(
                self._kvmig_backoff_s, fails - 1, self._kvmig_rng
            ) if fails > 1 else 0.0
            self._kvmig_backoff[url] = (fails, time.monotonic() + delay)

    def _maybe_migrate_kv(self, params: Dict[str, Any]) -> None:
        """Honor a router ``kv_migrate_from`` hint: pull the hot prefix
        from the named peer BEFORE admission, landing it in our radix so
        the ragged prefill that follows reuses it. Every failure mode —
        peer dead mid-pull, corrupt piece, budget/backoff, no match —
        falls back to a plain recompute; a migration can never fail the
        request (counted: pulled / aborted / fallback_recompute)."""
        # never trust an inbound stash (the key is worker-internal: the
        # admission reuses the token ids THIS method encodes)
        params.pop("_kvmig_token_ids", None)
        hint = params.get("kv_migrate_from")
        if not isinstance(hint, dict):
            return
        tl = params.get("_flight_tl") or NULL_TIMELINE
        url = str(hint.get("data_plane_url") or "").rstrip("/")
        stats = self.kv_migrate_stats
        if not url or not self.kv_migrate_enabled or not self.loaded \
                or self.engine is None \
                or not self.engine.cfg.enable_prefix_cache:
            stats["fallback_recompute"] += 1
            tl.note("kv_migrate.fallback", reason="disabled")
            return
        if not self._kvmig_peer_allowed(url):
            stats["fallback_recompute"] += 1
            tl.note("kv_migrate.fallback", reason="budget_or_backoff")
            return
        import uuid as _uuid

        from distributed_gpu_inference_tpu.runtime.kv_handoff import (
            abort_message,
            pack_export_request,
            split_frames,
        )

        eng = self.engine
        key = f"kvmig-{_uuid.uuid4().hex[:12]}"
        begun = False
        try:
            cfg = GenerationConfig.from_params(params)
            token_ids = self._encode_prompt(
                params.get("messages") or params.get("prompt") or "", cfg
            )
            # hand the encode to the admission that follows (the request
            # builder skips its own template+tokenize pass)
            params["_kvmig_token_ids"] = token_ids
            if len(token_ids) < eng.cfg.block_size:
                stats["fallback_recompute"] += 1
                tl.note("kv_migrate.fallback", reason="short_prompt")
                self._kvmig_peer_result(url, ok=True)
                return
            # already warm locally? The router hints until OUR summary
            # advertises the prefix (a heartbeat cadence away — 30 s in
            # production), and a storm means MANY hinted requests for one
            # prefix: re-pulling what the first pull landed would
            # re-transfer the whole prefix per request and stall the warm
            # peer's decode rounds under its export executor. Probe the
            # local radix first (serialized like any engine read) and skip
            # when it already covers the request's full-block prefix (the
            # final block is forgone at worst — admission's
            # keep-one-token-fresh rule usually recomputes it anyway).
            bs = eng.cfg.block_size
            n_full = len(token_ids) // bs

            def _local_depth() -> int:
                return len(eng.manager.radix.match_prefix(token_ids))

            with self._engine_lock:
                local = self._exclusive(_local_depth)
            if local >= max(1, n_full - 1):
                stats["local_hits"] += 1
                tl.note("kv_migrate.local_hit", blocks=local)
                self._kvmig_peer_result(url, ok=True)
                return
            tl.note("kv_migrate.begin", peer=hint.get("worker_id"),
                    matched_blocks=hint.get("matched_blocks"))
            # source tier the router priced the pull at (validated — the
            # hint crosses the wire): keys the per-tier bandwidth counters
            # the plane's cost calibration delta-anchors
            tier = hint.get("tier")
            if tier not in ("dev", "host", "spill"):
                tier = "dev"
            t_pull = time.monotonic()
            req_raw = pack_export_request(
                key=key, token_ids=token_ids,
                model_name=eng.model_cfg.name,
                block_size=eng.cfg.block_size,
                int8_kv="k_scale" in eng.kv,
                max_blocks=self._kvmig_max_blocks,
                # the peer ships only what we are missing — our cached
                # leading blocks satisfy the commit coverage check locally
                start_block=local,
            )
            r = _faults.wrap_http(
                "worker.kv.pull",
                lambda: httpx.post(
                    url + "/kv/export", content=req_raw,
                    headers={"content-type": "application/octet-stream"},
                    timeout=self._kvmig_timeout_s,
                ),
                worker=str(getattr(self, "fault_tag", "") or ""),
            )
            r.raise_for_status()
            frames = split_frames(r.content)
            if not frames:
                # peer has nothing cached (evicted since the router's
                # summary): an honest miss, not a peer failure
                stats["fallback_recompute"] += 1
                tl.note("kv_migrate.fallback", reason="peer_miss")
                self._kvmig_peer_result(url, ok=True)
                return
            committed = None
            for frame in frames:
                # each frame runs through our own HandoffReceiver (via
                # kv_receiver — the chaos seam, duplicate tolerance, and
                # corrupt-piece session aborts all apply to pulls too)
                begun = True
                res = self.kv_receiver(frame)
                if res.get("state") == "committed":
                    committed = res
            if committed is None:
                raise ValueError("kv export response ended without commit")
            stats["pulled"] += 1
            # blocks the pull actually DELIVERED: the session chain minus
            # what our own cache already covered (partial-overlap pulls
            # ship only the missing tail)
            stats["pull_blocks"] += max(0, int(committed.get("blocks") or 0)
                                        - (int(committed.get("cached_tokens")
                                               or 0)
                                           // eng.cfg.block_size))
            pull_bytes = sum(len(f) for f in frames)
            stats["pull_bytes"] += pull_bytes
            # per-tier measured transfer: cumulative (bytes, wall-ms)
            # pairs whose heartbeat deltas give the plane one bandwidth
            # sample per pull (server/calibration.py)
            pull_ms = max(1, int((time.monotonic() - t_pull) * 1000.0))
            stats[f"pull_bytes_{tier}"] = (
                stats.get(f"pull_bytes_{tier}", 0) + pull_bytes)
            stats[f"pull_ms_{tier}"] = (
                stats.get(f"pull_ms_{tier}", 0) + pull_ms)
            tl.note("kv_migrate.pulled",
                    blocks=int(committed.get("blocks") or 0),
                    bytes=pull_bytes)
            self._kvmig_peer_result(url, ok=True)
        except Exception as exc:  # noqa: BLE001 — migration is best-effort
            stats["aborted"] += 1
            tl.note("kv_migrate.aborted")
            # a 4xx is the peer REJECTING the pull (incompatible engine,
            # migration disabled) — pin it out instead of re-knocking
            # after every backoff window (mirrors _pd_push's no-retry-4xx)
            permanent = (
                isinstance(exc, httpx.HTTPStatusError)
                and exc.response is not None
                and 400 <= exc.response.status_code < 500
            )
            self._kvmig_peer_result(url, ok=False, permanent=permanent)
            if begun:
                # drop a half-built session NOW instead of letting it pin
                # blocks until the receiver's TTL purge
                try:
                    self.kv_receiver(abort_message(key))
                except Exception:  # noqa: BLE001 — abort is best-effort
                    pass

    def kv_replicate(self, hints: Any) -> int:
        """Plane-hinted proactive prefix replication (round 20): the
        heartbeat response named hot prefixes this worker does NOT hold
        that a warm peer exports — pull them NOW, ahead of the predicted
        storm, over the same chaos-hardened ``/kv/export`` protocol the
        reactive migrate driver uses (same budget, same per-peer backoff,
        same recompute-on-any-failure stance). Pulls run on a daemon
        thread — a prefetch must never sit in the heartbeat loop. Returns
        the number of hints accepted (0 = all malformed/disabled; a
        budget-full drop happens later, on the thread, and the plane
        simply re-hints after its cooldown)."""
        if not self.kv_migrate_enabled or not self.loaded \
                or self.engine is None \
                or not self.engine.cfg.enable_prefix_cache:
            return 0
        todo = []
        for h in hints if isinstance(hints, list) else []:
            if not isinstance(h, dict):
                continue
            fps = h.get("fps")
            url = str(h.get("data_plane_url") or "").rstrip("/")
            if not url or not isinstance(fps, list) or not fps \
                    or not all(isinstance(f, str) for f in fps):
                continue
            todo.append((h, url, [str(f) for f in fps]))
        if not todo:
            return 0
        threading.Thread(
            target=self._kv_replicate_run, args=(todo,),
            name="kv-replicate", daemon=True,
        ).start()
        return len(todo)

    def _kv_replicate_run(self, todo: List[tuple]) -> None:
        for hint, url, fps in todo:
            try:
                self._kv_replicate_pull(hint, url, fps)
            except Exception:  # noqa: BLE001 — prefetch is best-effort
                pass

    def _kv_replicate_pull(self, hint: Dict[str, Any], url: str,
                           fps: List[str]) -> None:
        eng = self.engine
        hot = self.prefix_hot
        stats = self.kv_migrate_stats
        if eng is None:
            return
        if hot is not None and fps[-1] in hot.snapshot():
            return   # a racing request already landed it — nothing to do
        if not self._kvmig_peer_allowed(url):
            return   # budget/backoff: drop; the plane re-hints past its
            #          cooldown, and prefetch must never amplify load
        import uuid as _uuid

        from distributed_gpu_inference_tpu.runtime.kv_handoff import (
            abort_message,
            pack_export_request,
            split_frames,
        )

        key = f"kvrep-{_uuid.uuid4().hex[:12]}"
        tier = hint.get("tier")
        if tier not in ("dev", "host", "spill"):
            tier = "dev"
        begun = False
        try:
            t_pull = time.monotonic()
            # fp-keyed: we never saw the prompt — the warm exporter
            # resolves the fingerprint to its own token ids, and the
            # begin frame carries them back, so our HandoffReceiver
            # commits into the radix keyed exactly as an admission probes
            req_raw = pack_export_request(
                key=key, token_ids=[],
                model_name=eng.model_cfg.name,
                block_size=eng.cfg.block_size,
                int8_kv="k_scale" in eng.kv,
                max_blocks=self._kvmig_max_blocks,
                fp=fps[-1],
            )
            r = _faults.wrap_http(
                "worker.kv.pull",
                lambda: httpx.post(
                    url + "/kv/export", content=req_raw,
                    headers={"content-type": "application/octet-stream"},
                    timeout=self._kvmig_timeout_s,
                ),
                worker=str(getattr(self, "fault_tag", "") or ""),
            )
            r.raise_for_status()
            frames = split_frames(r.content)
            if not frames:
                # the exporter's fp→tokens map churned it out, or its
                # cache evicted: an honest miss, not a peer failure
                stats["replicate_miss"] += 1
                self._kvmig_peer_result(url, ok=True)
                return
            committed = None
            for frame in frames:
                begun = True
                res = self.kv_receiver(frame)
                if res.get("state") == "committed":
                    committed = res
            if committed is None:
                raise ValueError("kv export response ended without commit")
            stats["replicated"] += 1
            pull_bytes = sum(len(f) for f in frames)
            stats["pull_bytes"] += pull_bytes
            pull_ms = max(1, int((time.monotonic() - t_pull) * 1000.0))
            stats[f"pull_bytes_{tier}"] = (
                stats.get(f"pull_bytes_{tier}", 0) + pull_bytes)
            stats[f"pull_ms_{tier}"] = (
                stats.get(f"pull_ms_{tier}", 0) + pull_ms)
            if hot is not None:
                # advertise the adopted prefix so the next summary stops
                # the hints (advisory like every entry: a shallower-than-
                # hinted pull costs at most one partial re-prefill)
                hot.note_fingerprints(fps)
            self._kvmig_peer_result(url, ok=True)
        except Exception as exc:  # noqa: BLE001 — prefetch is best-effort
            stats["replicate_aborted"] += 1
            permanent = (
                isinstance(exc, httpx.HTTPStatusError)
                and exc.response is not None
                and 400 <= exc.response.status_code < 500
            )
            self._kvmig_peer_result(url, ok=False, permanent=permanent)
            if begun:
                try:
                    self.kv_receiver(abort_message(key))
                except Exception:  # noqa: BLE001 — abort is best-effort
                    pass

    def kv_migrate_wire_stats(self) -> Optional[Dict[str, int]]:
        """Cumulative KV-migration counters (pull outcomes + export
        service) — heartbeat ``engine_stats["kv_migrate"]``, delta-anchored
        into ``kv_migrations_total{outcome}`` / ``kv_migration_bytes_total``
        on the control plane. None when this engine never migrated."""
        out = {k: int(v) for k, v in self.kv_migrate_stats.items() if v}
        rx = self._handoff_rx
        if rx is not None:
            v = int(rx.stats.get("prefix_commits", 0) or 0)
            if v:
                out["prefix_commits"] = v
        return out or None

    def kv_spill_wire_stats(self) -> Optional[Dict[str, int]]:
        """Cumulative spill-tier IO health counters (put/get errors,
        corrupt-entry quarantines, breaker states/trips) plus refused
        corrupt checkpoints — heartbeat ``engine_stats["kv_spill"]``,
        delta-anchored into ``kv_spill_errors_total{tier}`` /
        ``spill_quarantined_total{tier,reason}`` / ``io_breaker_state``
        on the control plane. None when every counter is zero and all
        breakers are closed (no payload bloat)."""
        eng = self.engine
        mgr = getattr(eng, "manager", None) if eng is not None else None
        out: Dict[str, int] = {}
        if mgr is not None:
            ws = mgr.spill_wire_stats()
            out.update({k: int(v) for k, v in ws.items() if v})
            if out:
                # once anything has fired, ship breaker states INCLUDING
                # zeros: a recovered breaker must drive the plane's
                # io_breaker_state gauge back to healthy, not freeze it
                # at its sickest reading
                out.update({k: int(v) for k, v in ws.items()
                            if k.endswith("_state")})
        if self.ckpt_corrupt:
            out["ckpt_corrupt"] = int(self.ckpt_corrupt)
        return out or None

    def _ckpt_from_wire(self, ckpt: Any) -> Optional[PreemptedSequence]:
        """Parse a claim's server-held checkpoint, degrading CORRUPTION to
        a fresh recompute: a torn/bit-flipped store row (bad crc, missing
        fields, wrong version) returns None — the driver falls through to
        its from-scratch path — instead of failing the whole resumed job.
        Mirrors the spill-tier quarantine contract: persisted state is an
        optimization, never a single point of failure."""
        if not isinstance(ckpt, dict):
            return None
        try:
            return PreemptedSequence.from_wire(ckpt)
        except Exception:  # noqa: BLE001 — ValueError + anything torn JSON does
            self.ckpt_corrupt += 1
            return None

    # -- request flight recorder (round 14) ---------------------------------

    def _flight_timeline(self, params: Dict[str, Any]) -> Any:
        """A Timeline for the request iff it carries a ``trace_id`` (the
        shared no-op NULL_TIMELINE otherwise — hot paths note
        unconditionally). Adopts the poll-pickup instant the worker claim
        path stamped into params before dispatch."""
        tl = timeline_for(
            params, source=str(getattr(self, "fault_tag", "") or "")
        )
        ts = params.pop("_flight_picked_up_ts", None)
        accepted = params.pop("_flight_accepted", None)
        if tl.enabled:
            if accepted is not None:
                # the direct server's first sight of the request, before
                # its body was parsed (time.monotonic())
                tl.note_at("direct.accepted", tl.at(accepted))
            if ts is not None:
                tl.note_at("worker.picked_up", ts)
        return tl

    def _flight_finish(self, tl: Any,
                       payload: Optional[Dict[str, Any]] = None) -> None:
        """Close one request's timeline: count it, retain it in the
        bounded heartbeat ring (the channel direct streams ship through),
        and attach the wire to the result payload when one is given (the
        complete_job channel). Never raises — the recorder is advisory."""
        try:
            if not getattr(tl, "enabled", False):
                return
            wire = tl.wire(done=True)
            if wire is None:
                return
            self.flight_stats["timelines"] += 1
            if tl.dropped:
                self.flight_stats["events_dropped"] += int(tl.dropped)
            self._flight_recent.append(wire)
            if payload is not None:
                payload["timeline"] = wire
        except Exception:  # noqa: BLE001 — never fail a request for this
            pass

    def flight_wire_stats(self) -> Optional[Dict[str, Any]]:
        """Heartbeat ``engine_stats["flight"]`` payload: cumulative
        counters (delta-anchored on the plane, restart re-anchors) plus
        the bounded ring of recently-completed timelines. The ring is
        re-shipped every beat — the plane's ingest unions events per
        (trace, source) keyed by name+timestamp, so duplicate delivery
        is a no-op.
        None while nothing was ever traced (no payload bloat)."""
        if not self.flight_stats["timelines"]:
            return None
        return {
            "timelines": int(self.flight_stats["timelines"]),
            "events_dropped": int(self.flight_stats["events_dropped"]),
            "recent": list(self._flight_recent),
        }

    # -- crash-safe generation: live checkpoints + resumable drivers --------

    @property
    def handoff_sessions_purged(self) -> int:
        """Cumulative abandoned streamed-handoff sessions purged by this
        engine's receiver — rides the heartbeat into
        ``kv_handoff_sessions_purged_total``."""
        rx = self._handoff_rx
        return int(rx.stats.get("sessions_purged", 0)) if rx is not None else 0

    def pd_wire_stats(self) -> Optional[Dict[str, int]]:
        """Cumulative PD handoff lifecycle counters (sender outcomes +
        receiver abort/purge reasons) — heartbeat ``engine_stats["pd"]``,
        delta-anchored into ``pd_handoffs_total{outcome}`` /
        ``pd_handoff_bytes_total`` on the control plane. None when this
        engine never touched a handoff (no payload bloat)."""
        out = {k: int(v) for k, v in self.pd_stats.items() if v}
        rx = self._handoff_rx
        if rx is not None:
            for src, dst in (("rx_aborts", "rx_aborts"),
                             ("purged_ttl", "rx_purged_ttl"),
                             ("purged_no_progress", "rx_purged_no_progress"),
                             ("purged_cap", "rx_purged_cap")):
                v = int(rx.stats.get(src, 0) or 0)
                if v:
                    out[dst] = v
        return out or None

    def _register_live(self, key: str, kind: str, epoch: int,
                       request_id: str) -> None:
        with self._live_lock:
            self._live[key] = {
                "kind": kind, "epoch": int(epoch), "request_id": request_id,
            }

    def _unregister_live(self, key: str) -> None:
        with self._live_lock:
            self._live.pop(key, None)

    def interrupt_live(self) -> None:
        """Graceful drain: queued-job drivers freeze at the next step
        boundary and raise :class:`JobMigrated` with their checkpoint.
        Direct streams keep running to completion (they checkpoint
        continuously, so a client of a worker that then vanishes resumes
        from the last checkpoint on a failover peer)."""
        self._interrupt.set()

    def _snapshot_live(self, key: str,
                       info: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """Portable checkpoint entry for one live generation, or None when
        the slot is gone/finished/unreadable. Runs WITHOUT the engine lock
        (heartbeat thread): a torn read mid-finish degrades to a skipped
        sample — the next heartbeat retries."""
        eng = self.engine
        if eng is None:
            return None
        try:
            for slot, s in enumerate(list(eng.slots)):
                if s is None or s.request.request_id != info["request_id"]:
                    continue
                pre = eng.snapshot_slot(slot)
                if pre.request.request_id != info["request_id"]:
                    # the slot was freed and reused by ANOTHER request
                    # between the scan and the snapshot (we read without
                    # the engine lock): a foreign sequence must never be
                    # checkpointed under this key — skip the sample
                    return None
                return {
                    "kind": info["kind"], "key": key,
                    "epoch": info["epoch"], "state": pre.to_wire(),
                }
        except Exception:  # noqa: BLE001 — checkpointing must never break serving
            return None
        return None

    def checkpoint_live(self) -> List[Dict[str, Any]]:
        """Checkpoint entries for every in-flight generation — the payload
        the worker piggybacks on heartbeats (``checkpoints`` field)."""
        with self._live_lock:
            live = dict(self._live)
        out = []
        for key, info in live.items():
            entry = self._snapshot_live(key, info)
            if entry is not None:
                out.append(entry)
        return out

    def _push_checkpoint(self, entry: Optional[Dict[str, Any]],
                         sync: bool = False) -> None:
        """Push one checkpoint through the configured sink (control-plane
        client); sink failures are swallowed — a flaky control plane must
        never abort the generation it is trying to protect.

        ``sync=True`` blocks (the one-time ADMISSION checkpoint: a kill at
        token 1 must already find a resumable record, and the pre-first-
        token cost is noise next to prefill). Cadence pushes go through
        the latest-wins background pusher so the decode loop never waits
        on the control plane."""
        if self.checkpoint_sink is None or entry is None:
            return
        if sync:
            try:
                self.checkpoint_sink(entry)
            except Exception:  # noqa: BLE001
                pass
            return
        if self._ckpt_pusher is None:
            self._ckpt_pusher = _CheckpointPusher(self._sink_now)
        self._ckpt_pusher.put(entry)

    def _sink_now(self, entry: Dict[str, Any]) -> None:
        sink = self.checkpoint_sink          # resolved at drain time
        if sink is not None:
            sink(entry)

    def _job_inference(self, params: Dict[str, Any],
                       ctx: Dict[str, Any]) -> Dict[str, Any]:
        """Queued-job driver with failover support: submits (or RESUMES from
        the claim's server-held checkpoint), registers for heartbeat
        checkpointing, decodes in bounded multi-step rounds so a drain
        interrupt lands at a step boundary, and raises :class:`JobMigrated`
        with the frozen state instead of finishing when interrupted.

        Continuations are byte-identical greedy / seed-stable sampled: the
        resume path restores the PRNG key words and recomputes only the
        suffix the prefix cache / spill tiers don't still hold."""
        cfg = GenerationConfig.from_params(params)
        key = str(ctx.get("key") or "")
        epoch = int(ctx.get("epoch") or 0)
        ckpt = ctx.get("checkpoint")
        eng = self.engine
        if eng is None or not self.loaded:
            raise EngineLoadError("engine not loaded")
        if self.serving is not None and self.serving.active:
            return self._job_inference_serving(params, cfg, key, epoch, ckpt)
        tl = params.pop("_flight_tl", NULL_TIMELINE)
        tl.note("worker.start", path="job")
        t0 = time.perf_counter()
        pre = self._ckpt_from_wire(ckpt)
        if pre is not None:
            remaining = (pre.request.sampling.max_new_tokens
                         - len(pre.generated))
            if remaining <= 0:
                # the checkpoint already holds the whole generation: the
                # previous worker died between its last decode and its
                # complete_job — deliver without touching the engine
                return self._finish_payload(
                    list(pre.generated), pre.prompt_len,
                    pre.cached_tokens, "length", cfg, None,
                    time.perf_counter() - t0,
                )
            slot = eng.resume(pre)
            request_id = pre.request.request_id
        else:
            req = self._build_request(
                params.get("messages") or params.get("prompt") or "", cfg,
                token_ids=params.pop("_kvmig_token_ids", None),
            )
            slot = eng.submit(req)
            request_id = req.request_id
        self._register_live(key, "job", epoch, request_id)
        try:
            while eng.slots[slot] is not None and \
                    eng.slots[slot].finish_reason is None:
                if self._interrupt.is_set():
                    pre = eng.preempt_slot(slot)
                    raise JobMigrated(pre.to_wire(),
                                      tokens=len(pre.generated))
                eng.decode_multi()
                slot = self._ride_out_pressure(eng, slot)
        except JobMigrated:
            raise
        except Exception:
            if eng.slots[slot] is not None:
                eng.finish_slot(slot, cache=False)
            raise
        finally:
            self._unregister_live(key)
        resp = eng.finish_slot(slot)
        if tl.enabled and resp.extra.get("t_first_token") is not None:
            # engine-observed instant, not loop-observed
            tl.note_at("batcher.first_token", resp.extra["t_first_token"])
        tl.note("worker.done")
        payload = self._finish_payload(
            list(resp.token_ids), resp.prompt_tokens, resp.cached_tokens,
            resp.finish_reason or "stop", cfg, resp.ttft_ms,
            time.perf_counter() - t0,
        )
        self._flight_finish(tl, payload)
        return payload

    def _job_inference_serving(self, params: Dict[str, Any],
                               cfg: GenerationConfig, key: str, epoch: int,
                               ckpt: Any) -> Dict[str, Any]:
        """Queued-job driver through the batcher front-end: resumes from
        the claim's server-held checkpoint, shares decode rounds with every
        other in-flight request, registers for heartbeat checkpointing, and
        converts a drain interrupt (``interrupt_live``) into
        :class:`JobMigrated` — the batcher freezes the sequence at the next
        step boundary and hands back the portable checkpoint."""
        t0 = time.perf_counter()
        tl = params.pop("_flight_tl", NULL_TIMELINE)
        tl.note("worker.start", path="job_serving")
        pre = self._ckpt_from_wire(ckpt)
        if pre is not None:
            remaining = (pre.request.sampling.max_new_tokens
                         - len(pre.generated))
            tl.note("worker.resume_from_checkpoint",
                    tokens=len(pre.generated))
            if remaining <= 0:
                # the checkpoint already holds the whole generation: the
                # previous worker died between its last decode and its
                # complete_job — deliver without touching the engine
                payload = self._finish_payload(
                    list(pre.generated), pre.prompt_len,
                    pre.cached_tokens, "length", cfg, None,
                    time.perf_counter() - t0,
                )
                self._flight_finish(tl, payload)
                return payload
            req = pre.request
        else:
            req = self._build_request(
                params.get("messages") or params.get("prompt") or "", cfg,
                token_ids=params.pop("_kvmig_token_ids", None),
            )
            if params.get("priority") is not None:
                req.priority = int(params.get("priority") or 0)
        self._register_live(key, "job", epoch, req.request_id)
        try:
            resp = self.serving.submit(
                req, resume_from=pre, interrupt=self._interrupt,
                flight=tl if tl.enabled else None,
            )
        except RequestMigrated as mig:
            raise JobMigrated(mig.pre.to_wire(),
                              tokens=len(mig.pre.generated)) from None
        finally:
            self._unregister_live(key)
        if resp.error is not None:
            _raise_serving(resp)
        tl.note("worker.done")
        payload = self._finish_payload(
            list(resp.token_ids), resp.prompt_tokens, resp.cached_tokens,
            resp.finish_reason or "stop", cfg, resp.ttft_ms,
            time.perf_counter() - t0,
        )
        self._flight_finish(tl, payload)
        return payload

    def _ride_out_pressure(self, eng: TPUEngine, slot: int) -> int:
        """Queued-job KV-pressure recovery without a batcher above us:
        when the engine freezes THIS slot at a pressure boundary, preempt
        it (releasing reserved tails and parking its blocks in the
        evictable prefix cache) and resume immediately — that recovers
        every self-caused squeeze the batcher path would. No wait loop:
        this runs UNDER the engine lock, and the paths that free
        externally-pinned blocks (handoff adopt-sessions, retained PD
        slots) need that same lock, so sleeping here could never observe
        a free. If the pool still cannot hold the sequence the blocks are
        genuinely pinned — fail the job honestly. A drain interrupt
        converts the frozen state into :class:`JobMigrated` instead (the
        checkpoint is already in hand)."""
        from ...runtime.kv_cache import OutOfBlocksError

        p = eng.take_pressure()
        if p is None or slot not in p.slots:
            return slot
        pre = eng.preempt_slot(slot)
        if self._interrupt.is_set():
            raise JobMigrated(pre.to_wire(), tokens=len(pre.generated))
        try:
            return eng.resume(pre)
        except OutOfBlocksError:
            raise OutOfBlocksError(
                "KV pool cannot hold the queued job's sequence even after "
                "preempt/evict — blocks are pinned by concurrent sessions"
            ) from None

    def _finish_payload(self, token_ids: List[int], prompt_tokens: int,
                        cached_tokens: int, finish_reason: str,
                        cfg: GenerationConfig, ttft_ms: Optional[float],
                        e2e_s: float) -> Dict[str, Any]:
        """Result payload shared by the fresh and resumed queued paths —
        same decode + stop-string truncation as ``_generate``."""
        out_text = self.tokenizer.decode(token_ids) if self.tokenizer else ""
        finish = finish_reason
        for s in cfg.stop:
            idx = out_text.find(s)
            if idx >= 0:
                out_text = out_text[:idx]
                finish = "stop"
                break
        return GenerationResult(
            text=out_text,
            prompt_tokens=prompt_tokens,
            completion_tokens=len(token_ids),
            cached_tokens=cached_tokens,
            finish_reason=finish,
            ttft_ms=ttft_ms if ttft_ms is not None else e2e_s * 1000.0,
        ).to_result_payload()

    def _generate(self, prompt_or_messages: Any,
                  cfg: GenerationConfig) -> GenerationResult:
        req = self._build_request(prompt_or_messages, cfg)
        t0 = time.perf_counter()
        resp = self.engine.generate([req], use_multi_step=True)[0]
        e2e_ms = (time.perf_counter() - t0) * 1000.0
        out_text = self.tokenizer.decode(resp.token_ids)
        finish = resp.finish_reason or "stop"
        for s in cfg.stop:  # host-side stop strings (tokenizer-agnostic)
            idx = out_text.find(s)
            if idx >= 0:
                out_text = out_text[:idx]
                finish = "stop"
                break
        return GenerationResult(
            text=out_text,
            prompt_tokens=resp.prompt_tokens,
            completion_tokens=resp.completion_tokens,
            cached_tokens=resp.cached_tokens,
            finish_reason=finish,
            ttft_ms=resp.ttft_ms if resp.ttft_ms is not None else e2e_ms,
        )

    # -- token streaming (reference SSE path, llm_sglang.py:358-416) ---------

    def stream(self, params: Dict[str, Any],
               cancel: Optional[Any] = None):
        """Sync generator of SSE chunks — dispatches to the batcher-backed
        serving stream (default: the sequence SHARES decode rounds with
        every other in-flight request) or the legacy per-step engine driver
        (``serving.mode: direct``). Both emit the same chunk contract:
        ``{"text_delta", "token_ids", "offset"}...`` then a final
        ``{"done": True, "finish_reason", "usage", "offset"}``."""
        if self.serving is not None and self.serving.active:
            return self._stream_serving(params, cancel=cancel)
        tl = self._flight_migrate(params)
        if tl.enabled:
            return self._stream_direct_traced(tl, params, cancel)
        return self._stream_direct(params, cancel=cancel)

    def _flight_migrate(self, params: Dict[str, Any]) -> Any:
        """A stream's first two steps: its timeline, and the KV-migration
        probe, which notes on it."""
        tl = self._flight_timeline(params)
        if tl.enabled:
            params["_flight_tl"] = tl
        self._maybe_migrate_kv(params)
        params.pop("_flight_tl", None)
        return tl

    def _stream_direct_traced(self, tl: Any, params: Dict[str, Any],
                              cancel: Optional[Any] = None):
        """Traced wrapper for the legacy per-step stream driver: the
        driver itself predates the recorder, so the wrapper notes the
        stream boundaries and closes the timeline — attaching the wire to
        the final chunk exactly like ``_stream_serving`` does (streams
        never pass ``complete_job``; the heartbeat ring ships it too)."""
        tl.note("worker.stream.start", path="direct")
        done = False
        try:
            for chunk in self._stream_direct(params, cancel=cancel):
                if isinstance(chunk, dict) and chunk.get("done"):
                    done = True
                    tl.note("worker.stream.done",
                            finish_reason=chunk.get("finish_reason"))
                    self._flight_finish(tl, chunk)
                yield chunk
        finally:
            if not done:
                # abandoned stream (client hung up / chaos kill): the
                # partial timeline still ships via the heartbeat ring
                tl.note("worker.stream.done", finish_reason="abandoned")
                self._flight_finish(tl)

    def _stream_checkpoint_tail(self, pre: PreemptedSequence,
                                cfg: GenerationConfig, stamp: Any,
                                holdback: int, resume_from: int,
                                resume_text: int):
        """Serve the un-consumed tail of a COMPLETE checkpoint (the donor
        died between its last decode and the final SSE flush) straight from
        it, through the SAME stop-string/holdback machinery the live loop
        uses — the client must receive exactly the text an undropped run
        would have (incl. the held-back chars and the stop-truncated
        finish)."""
        gen = list(pre.generated)
        m = min(resume_from, len(gen))
        full = self.tokenizer.decode(gen)
        stop_idx = -1
        for st_ in cfg.stop:
            idx = full.find(st_)
            if idx >= 0 and (stop_idx < 0 or idx < stop_idx):
                stop_idx = idx
        finish = "length"
        target = full
        if stop_idx >= 0:
            target = full[:stop_idx]
            finish = "stop"
        raw_prev = self.tokenizer.decode(gen[:m])
        prev = raw_prev
        if holdback:
            prev = prev[:max(len(prev) - holdback, 0)]
        if resume_text > len(prev):
            # the client already received part of the held-back tail (a
            # flush crossed before the drop) — never re-deliver those
            # characters
            prev = target[:resume_text]
        delta = target[len(prev):] if len(prev) < len(target) else ""
        tail = [] if stop_idx >= 0 else gen[m:]
        if delta or tail:
            yield stamp({"text_delta": delta, "token_ids": tail}, len(gen))
        yield stamp({
            "done": True, "finish_reason": finish,
            "usage": {
                "prompt_tokens": pre.prompt_len,
                "completion_tokens": len(gen),
                "total_tokens": pre.prompt_len + len(gen),
                "cached_tokens": pre.cached_tokens,
            },
        }, len(gen))

    def _stream_serving(self, params: Dict[str, Any],
                        cancel: Optional[Any] = None):
        """Batcher-backed token streaming: the request is submitted to the
        serving front-end with a per-round observer; deltas are derived
        from the observer's monotonic token snapshots with the exact
        stop-string/holdback/splice machinery of the legacy per-step
        driver, so exactly-once token offsets and checkpoint/resume hold
        while the sequence shares decode rounds with other slots.

        A token's way out is stamped as it passes (runtime/flight.py,
        ``EGRESS_KEY``): a chunk carries the stamp of the round that brought
        its token, the instant the batcher's loop handed the snapshot over
        and the instant it is yielded here, for the direct server to sum
        where it writes the event; ``dgi.worker.stream.open`` spans the
        way in, ``dgi.worker.stream.pump`` each snapshot."""
        with span("dgi.worker.stream.open") as opened:
            tl = self._flight_migrate(params)
            cfg = GenerationConfig.from_params(params)
            tl.note("worker.stream.start")
            ctx = params.get("_failover_ctx")
            ctx = ctx if isinstance(ctx, dict) else {}
            key = str(ctx.get("key") or params.get("stream_id") or "") \
                or None
            epoch = int(ctx.get("epoch") or 0)
            resume_from = int(ctx.get("offset") or 0)
            resume_text = int(ctx.get("text_offset") or 0)

            def stamp(chunk: Dict[str, Any], offset: int) -> Dict[str, Any]:
                if key is not None:
                    chunk["stream_id"] = key
                    chunk["offset"] = offset
                return chunk

            holdback = max((len(s) for s in cfg.stop), default=0)
            holdback = max(holdback - 1, 0)
            pre = self._ckpt_from_wire(ctx.get("checkpoint"))
            # a checkpoint that already holds the whole generation is
            # served from itself, below: nothing is submitted
            whole = pre is not None and len(pre.generated) >= \
                pre.request.sampling.max_new_tokens
            if not whole:
                if pre is not None:
                    req = pre.request
                else:
                    req = self._build_request(
                        params.get("messages") or params.get("prompt")
                        or "", cfg,
                        token_ids=params.pop("_kvmig_token_ids", None),
                    )
                    if params.get("priority") is not None:
                        req.priority = int(params.get("priority") or 0)
                request_id = req.request_id
                snaps: "_queue_mod.Queue" = _queue_mod.Queue()
                _DONE = object()
                # batcher-side abort (cancel / stop cut)
                stop_evt = threading.Event()
                fut = self.serving.submit_async(
                    req, observer=snaps.put,
                    cancel=stop_evt, resume_from=pre,
                    flight=tl if tl.enabled else None,
                )
                fut.add_done_callback(lambda f: snaps.put(_DONE))
                opened.set(req=request_id)
                if tl.enabled:
                    opened.set(trace_id=tl.trace_id)
        if whole:
            yield from self._stream_checkpoint_tail(
                pre, cfg, stamp, holdback, resume_from, resume_text
            )
            return
        live_info = {"kind": "stream", "epoch": epoch,
                     "request_id": request_id}

        last_ckpt = len(pre.generated) if pre is not None else 0
        if key is not None:
            self._register_live(key, "stream", epoch, request_id)
            # admission checkpoint (synchronous): even a worker killed
            # before its first heartbeat leaves a resumable record. The
            # request may still be QUEUED, so the record is synthesized
            # engine-free (the resumed prefix when resuming, zero tokens
            # when fresh) — cadence pushes below carry live slot state.
            self._push_checkpoint({
                "kind": "stream", "key": key, "epoch": epoch,
                "state": (pre or synthesize_checkpoint(req)).to_wire(),
            }, sync=True)
        sp = _StreamSplicer(self.tokenizer, cfg, holdback,
                            resume_from, resume_text)
        stopping = False               # stop string matched: drain silently
        final = None
        try:
            while True:
                try:
                    item = snaps.get(timeout=0.05) if cancel is not None \
                        else snaps.get()
                except _queue_mod.Empty:
                    # cancel-poll timeout: honor a client disconnect even
                    # while the request is still queued (no snapshots yet)
                    if cancel is not None and cancel.is_set():
                        stop_evt.set()
                    continue
                if item is _DONE:
                    final = fut.result()   # raises on engine/submit failure
                    if final.error is not None:
                        _raise_serving(final)
                    gen = list(final.token_ids)
                    finished = True
                    rstamp, notified = final.extra.get("egress") \
                        or (None, 0.0)
                else:
                    gen = list(item)
                    finished = False
                    rstamp = getattr(item, "round", None)
                    notified = getattr(item, "notified", 0.0)
                # a round snapshot may carry SEVERAL new tokens — process
                # them one at a time so the SSE cadence (one event per
                # token, each stamped with its offset) is identical to the
                # legacy per-step driver: clients, resume splices, and the
                # chaos kill points all count events
                ks = list(range(sp.sent_tokens + 1, len(gen) + 1))
                if not ks and finished:
                    ks = [len(gen)]       # flush held-back chars at EOS
                with span("dgi.worker.stream.pump", req=request_id,
                          round=rstamp.round if rstamp else -1):
                    for k in ks:
                        if stopping:
                            break
                        fin_k = finished and k == len(gen)
                        chunk, stop_cut = sp.advance(gen[:k], fin_k)
                        if chunk is not None:
                            if rstamp is not None:
                                chunk[EGRESS_KEY] = Egress(
                                    rstamp, notified, time.monotonic(),
                                    request_id)
                            yield stamp(chunk, sp.sent_tokens)
                        if stop_cut and not fin_k:
                            # release the slot; the final (abort) response
                            # still carries the full usage accounting
                            stopping = True
                            stop_evt.set()
                if finished:
                    break
                if cancel is not None and cancel.is_set():
                    stop_evt.set()
                if key is not None and self._ckpt_interval > 0 \
                        and len(gen) - last_ckpt >= self._ckpt_interval:
                    self._push_checkpoint(
                        self._snapshot_live(key, live_info)
                    )
                    last_ckpt = len(gen)
        finally:
            stop_evt.set()     # no-op when already resolved; aborts a run
            #                    abandoned by a closed generator
            if key is not None:
                self._unregister_live(key)
        finish = sp.finish_override or final.finish_reason
        tl.note("worker.stream.done", finish_reason=finish)
        done_chunk = {
            "done": True,
            "finish_reason": finish,
            "usage": {
                "prompt_tokens": final.prompt_tokens,
                "completion_tokens": final.completion_tokens,
                "total_tokens": final.prompt_tokens
                + final.completion_tokens,
                "cached_tokens": final.cached_tokens,
            },
        }
        # the final SSE chunk carries the worker-side timeline (streams
        # never pass complete_job) — the heartbeat ring ships it to the
        # plane too, so either consumer can attribute the stream's phases
        self._flight_finish(tl, done_chunk)
        yield stamp(done_chunk, sp.sent_tokens)
        # NOTE: as in the legacy driver, the server-held checkpoint is NOT
        # retired on completion — the worker cannot know the final SSE
        # bytes reached the client; the control plane ages streams out.

    def _stream_direct(self, params: Dict[str, Any],
                       cancel: Optional[Any] = None):
        """Legacy per-step engine driver (``serving.mode: direct``).
        Sync generator of chunks:
        ``{"text_delta", "token_ids", "offset"}...`` then a final
        ``{"done": True, "finish_reason", "usage", "offset"}``. Drives the
        engine per-step so tokens flush as they are sampled.

        ``cancel``: a ``threading.Event``-like object; when set, generation
        stops at the next step boundary and the slot is released (client
        disconnects must not keep burning decode budget).

        Stop-string handling matches the blocking path exactly: the last
        ``len(longest_stop) - 1`` characters are held back until the stop
        scan clears them, so a stop sequence spanning chunk boundaries never
        leaks its prefix.

        Crash-safe streams: when the caller supplies a ``_failover_ctx``
        (direct server) the stream registers for heartbeat checkpointing,
        pushes checkpoints through ``checkpoint_sink`` at admission and
        every ``checkpoint_interval_tokens``, and stamps every event with a
        monotonic token ``offset``. A resume context (checkpoint + the
        client's consumed offset) restores the sequence via
        ``TPUEngine.resume`` and SPLICES: tokens the client already holds
        are regenerated (deterministically) but never re-emitted — no gap,
        no duplicate."""
        cfg = GenerationConfig.from_params(params)
        ctx = params.get("_failover_ctx")
        ctx = ctx if isinstance(ctx, dict) else {}
        key = str(ctx.get("key") or params.get("stream_id") or "") or None
        epoch = int(ctx.get("epoch") or 0)
        ckpt = ctx.get("checkpoint")
        resume_from = int(ctx.get("offset") or 0)
        # characters the client already consumed: holdback flushes advance
        # text WITHOUT advancing the token offset, so the token splice
        # alone could re-deliver (or withhold) the flushed tail
        resume_text = int(ctx.get("text_offset") or 0)
        eng = self.engine

        def stamp(chunk: Dict[str, Any], offset: int) -> Dict[str, Any]:
            if key is not None:
                chunk["stream_id"] = key
                chunk["offset"] = offset
            return chunk

        holdback = max((len(s) for s in cfg.stop), default=0)
        holdback = max(holdback - 1, 0)
        pre = self._ckpt_from_wire(ckpt)
        if pre is not None:
            remaining = (pre.request.sampling.max_new_tokens
                         - len(pre.generated))
            if remaining <= 0:
                # the checkpoint already holds the full generation (the
                # donor died between its last decode and the final SSE
                # flush): serve the un-consumed tail straight from it
                yield from self._stream_checkpoint_tail(
                    pre, cfg, stamp, holdback, resume_from, resume_text
                )
                return
            slot = eng.resume(pre)
            request_id = pre.request.request_id
        else:
            req = self._build_request(
                params.get("messages") or params.get("prompt") or "", cfg,
                token_ids=params.pop("_kvmig_token_ids", None),
            )
            slot = eng.submit(req)
            request_id = req.request_id
        live_info = {"kind": "stream", "epoch": epoch,
                     "request_id": request_id}
        last_ckpt = len(eng.slots[slot].generated)
        if key is not None:
            self._register_live(key, "stream", epoch, request_id)
            # admission checkpoint (synchronous): even a worker killed
            # before its first heartbeat leaves a resumable record (the
            # replacement regenerates from the prompt and splices)
            self._push_checkpoint(self._snapshot_live(key, live_info),
                                  sync=True)
        sp = _StreamSplicer(self.tokenizer, cfg, holdback,
                            resume_from, resume_text)
        try:
            while True:
                s = eng.slots[slot]
                gen = list(s.generated)
                finished = s.finish_reason is not None
                chunk, stop_cut = sp.advance(gen, finished)
                if chunk is not None:
                    yield stamp(chunk, sp.sent_tokens)
                if stop_cut:
                    s.finish_reason = "stop"
                    finished = True
                if finished:
                    break
                if cancel is not None and cancel.is_set():
                    s.finish_reason = s.finish_reason or "abort"
                    break
                if eng.cfg.speculative is not None:
                    # one draft→verify→accept round per flush: up to K+1
                    # tokens reach the stream per device round instead of 1
                    # (same emission contract incl. stop handling)
                    eng.spec_decode_step()
                else:
                    eng.decode_step()
                self._raise_if_pressured(eng, slot)
                if key is not None and self._ckpt_interval > 0:
                    s2 = eng.slots[slot]
                    n = len(s2.generated) if s2 is not None else last_ckpt
                    if n - last_ckpt >= self._ckpt_interval:
                        self._push_checkpoint(
                            self._snapshot_live(key, live_info)
                        )
                        last_ckpt = n
        finally:
            if key is not None:
                self._unregister_live(key)
            resp = self.engine.finish_slot(slot)
        finish = sp.finish_override or resp.finish_reason
        yield stamp({
            "done": True,
            "finish_reason": finish,
            "usage": {
                "prompt_tokens": resp.prompt_tokens,
                "completion_tokens": resp.completion_tokens,
                "total_tokens": resp.prompt_tokens + resp.completion_tokens,
                "cached_tokens": resp.cached_tokens,
            },
        }, sp.sent_tokens)
        # NOTE: the server-held checkpoint is deliberately NOT retired on
        # completion. The worker cannot know the final SSE bytes reached
        # the client (TCP buffers): a client that lost the tail must still
        # be able to resume, with the last checkpoint regenerating (stop)
        # or serving (length) the missing suffix. The control plane ages
        # stream checkpoints out instead (sweep_stale_stream_checkpoints).

    async def stream_inference(self, params: Dict[str, Any]):
        """Async wrapper: the sync per-step generator runs in a worker
        thread; chunks flow through a queue as they are produced. Closing
        this generator early (client disconnect) signals the pump thread to
        abort AND waits for it — the engine is guaranteed quiet when control
        returns to the caller."""
        import threading

        loop = asyncio.get_running_loop()
        q: "asyncio.Queue" = asyncio.Queue()
        _END = object()
        cancel = threading.Event()

        def pump():
            try:
                for chunk in self.stream(params, cancel=cancel):
                    loop.call_soon_threadsafe(q.put_nowait, chunk)
            except Exception as exc:  # noqa: BLE001 - surface to consumer
                chunk = {"error": str(exc)}
                code = getattr(exc, "error_code", None)
                if code:
                    # machine-readable class rides the SSE error event
                    # (request_timeout vs shed_overload — round 12)
                    chunk["error_code"] = code
                loop.call_soon_threadsafe(q.put_nowait, chunk)
            finally:
                loop.call_soon_threadsafe(q.put_nowait, _END)

        fut = loop.run_in_executor(None, pump)
        try:
            while True:
                chunk = await q.get()
                if chunk is _END:
                    break
                yield chunk
        finally:
            cancel.set()
            await fut  # engine quiet before the caller releases the claim

    # -- batch path straight through the engine (one compiled graph) ----------

    def batch_inference(self, batch: List[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
        if not self.loaded or self.engine is None:
            raise EngineLoadError("engine not loaded")
        reqs, cfgs = [], []
        for params in batch:
            cfg = GenerationConfig.from_params(params)
            cfgs.append(cfg)
            text = self._to_prompt(
                params.get("messages") or params.get("prompt") or ""
            )
            reqs.append(
                InferenceRequest(
                    prompt_token_ids=list(self.tokenizer.encode(text)),
                    sampling=self._sampling_from(cfg),
                )
            )
        resps = self.engine.generate(reqs, use_multi_step=True)
        out = []
        for resp, cfg in zip(resps, cfgs):
            text = self.tokenizer.decode(resp.token_ids)
            for s in cfg.stop:
                idx = text.find(s)
                if idx >= 0:
                    text = text[:idx]
                    break
            out.append(
                GenerationResult(
                    text=text,
                    prompt_tokens=resp.prompt_tokens,
                    completion_tokens=resp.completion_tokens,
                    cached_tokens=resp.cached_tokens,
                    finish_reason=resp.finish_reason or "stop",
                    ttft_ms=resp.ttft_ms,
                ).to_result_payload()
            )
        return out

    def health(self) -> Dict[str, Any]:
        h = super().health()
        if self.engine is not None:
            h["engine_stats"] = self.engine.get_stats()
        stats = self.serving_stats()
        if stats is not None:
            h["serving_stats"] = stats
        return h
