"""Speculative decoding: EAGLE-style feature-level draft head + token-tree
verification, executed as ONE jitted device step (draft → verify → accept →
KV-compact) with no host round-trips inside the step.

Capability parity with the reference's ``worker/engines/speculative.py``
(DraftHead:59 predicting the next hidden from [hidden; tok-emb]:98-125 and
sharing the target's embedding/LM head:94, token tree with ancestor-visibility
attention mask:184-213, longest-accepted-path trace:215-245,
draft→verify→accept loop decode_step:305-365, greedy match acceptance
:445-453, adaptive depth on accept-rate:456-463, MedusaHead:474-513) —
re-designed TPU-first (SURVEY §7 item 5, BASELINE north star: "rewrite the
EAGLE-3 draft/verify loop as a single XLA computation with on-device tree
verification"):

- The reference drafts token-by-token in Python and verifies with a dynamic
  mask built per step; here the tree SHAPE is static (widths per depth), so
  the whole draft+verify+accept step is one compiled graph.
- Tree-node KV lands in the same paged pools the engine serves from, written
  at node-indexed slots; the accepted path is compacted on device (gather →
  scatter of the winning pages), so a speculative step leaves the cache
  exactly as 1+A committed decode steps would have.
- **Greedy-equivalence invariant**: with temperature 0 the emitted stream is
  bit-identical to vanilla greedy decode regardless of draft quality — the
  draft only affects speed. Tests enforce this.
"""

from __future__ import annotations

import functools
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import ModelConfig, get_model_config
from distributed_gpu_inference_tpu.runtime.kv_cache import PagedKVCacheManager
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    InferenceResponse,
)


# ---------------------------------------------------------------------------
# Static token-tree topology
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TreeTopology:
    """Node 0 is the root (the pending token); ``widths[d]`` children per
    frontier node at depth d+1. Static → the step compiles once per shape."""

    widths: Tuple[int, ...] = (4, 2)

    @functools.cached_property
    def parents(self) -> np.ndarray:
        parents = [-1]
        frontier = [0]
        for w in self.widths:
            nxt: List[int] = []
            for p in frontier:
                for _ in range(w):
                    parents.append(p)
                    nxt.append(len(parents) - 1)
            frontier = nxt
        return np.asarray(parents, np.int32)

    @functools.cached_property
    def depths(self) -> np.ndarray:
        d = np.zeros(len(self.parents), np.int32)
        for i, p in enumerate(self.parents):
            if p >= 0:
                d[i] = d[p] + 1
        return d

    @property
    def num_nodes(self) -> int:
        return len(self.parents)

    @property
    def max_depth(self) -> int:
        return len(self.widths)

    @functools.cached_property
    def ancestor_mask(self) -> np.ndarray:
        """mask[i, j] = node i attends node j (ancestor-or-self)."""
        n = self.num_nodes
        m = np.zeros((n, n), bool)
        for i in range(n):
            cur = i
            while cur >= 0:
                m[i, cur] = True
                cur = int(self.parents[cur])
        return m

    @functools.cached_property
    def level_slices(self) -> List[Tuple[int, int]]:
        """[(start, end)] node-index range per depth level (root excluded)."""
        out = []
        start = 1
        count = 1
        for w in self.widths:
            count *= w
            out.append((start, start + count))
            start += count
        return out


@dataclass
class SpecDecodeConfig:
    """Engine-integrated speculative decoding (``TPUEngine`` decode mode).

    Unlike :class:`SpeculativeConfig` (the standalone tree decoder), this
    drives CHAIN drafts inside the continuous-batching engine: every active
    slot drafts ``num_draft_tokens`` greedily with the EAGLE-style head,
    then ONE multi-query target pass (q_len = K+1 per slot) verifies the
    chain and each slot commits 1..K+1 tokens. Chain positions are
    sequential, so accepted KV is already in place and a rejected suffix is
    simply overwritten by the next step — no tree compaction, and it
    composes with prefix caching, CoW, int8 KV, and sliding windows.
    """

    # K drafted tokens per slot per step; the verify pass scores K+1
    # queries. Since round 6 the verify pass dispatches through the ragged
    # paged-attention kernel (ops.attention.resolve_impl → "ragged"), which
    # stages pages once per query TILE — the old small-q path's q_len <= 8
    # cap (pages re-staged per query) is gone, so K is bounded only by the
    # block-growth checks below.
    num_draft_tokens: int = 4
    # EAGLE-style head weights (init_draft_params layout). None = random
    # init from ``draft_seed`` — near-zero acceptance but still CORRECT
    # (greedy outputs are target-verified regardless of draft quality);
    # distill with ``TPUEngine.distill_draft`` / distill_draft_params.
    draft_params: Optional[Dict[str, jax.Array]] = None
    draft_seed: int = 1
    # acceptance-adaptive draft depth (round 8): a per-slot EMA of the
    # ACCEPTED length selects each slot's draft depth from
    # ``k_choices()`` — a small static set, so every depth runs through
    # the SAME compiled graph (``num_draft_tokens`` stays the drafted
    # width; per-slot depths beyond a slot's selected K are masked, never
    # re-traced). Slots that accept little draft shallow (less wasted
    # verify KV + reservation pressure — sampled slots, which never
    # accept, converge to depth ``adaptive_min_k``); slots on a roll
    # draft deep. The selection is host-side float arithmetic over
    # integer accept counts: same seed → same K schedule, bit-for-bit.
    adaptive: bool = False
    adaptive_min_k: int = 1
    adaptive_ema: float = 0.8            # EMA weight on the PREVIOUS value
    adaptive_k_choices: Optional[Tuple[int, ...]] = None  # None = powers
    #   of two from adaptive_min_k up, plus num_draft_tokens itself
    # ORACLE draft (round 8, VERDICT r5 #3): force the per-round accepted
    # length to ``rate * K`` (fractional rates dither deterministically)
    # instead of matching against the target. Draft cost, verify cost, KV
    # writes, commits, and rollback are all REAL — only the acceptance
    # decision is forced — so a benchmark cell can measure the
    # tok/s-vs-acceptance curve without trained draft weights (ROADMAP R5's
    # ``*.spec``; not measured on the chip yet). Committed tokens are the
    # (garbage) drafts: outputs are meaningless, pair with ignore_eos
    # requests. None = real acceptance (the only production value).
    oracle_accept_rate: Optional[float] = None

    def k_choices(self) -> Tuple[int, ...]:
        """The static set adaptive depth selects from (ascending, ending
        at ``num_draft_tokens`` — ``validate`` rejects custom sets whose
        top choice is below K, since the chain always DRAFTS K tokens and
        a lower ceiling would make part of every round structurally
        unacceptable; cap ``num_draft_tokens`` instead)."""
        if self.adaptive_k_choices is not None:
            return tuple(sorted(set(int(c) for c in self.adaptive_k_choices)))
        lo = max(1, int(self.adaptive_min_k))
        out = []
        c = lo
        while c < self.num_draft_tokens:
            out.append(c)
            c *= 2
        out.append(self.num_draft_tokens)
        return tuple(out)

    def validate(self, engine_cfg: Any) -> None:
        """Reject configs whose worst-case per-step block growth cannot fit
        the engine's per-sequence block table. A step writes K+1 new KV
        rows and keeps one pending token, so the worst case touches
        ``ceil((K+2)/block_size) + 1`` blocks (straddle) on top of nothing —
        that must fit ``max_blocks_per_seq`` or the very first speculative
        step on a fresh sequence would outgrow its table."""
        k = self.num_draft_tokens
        if k < 1:
            raise ValueError(
                f"SpecDecodeConfig.num_draft_tokens={k}: need at least 1 "
                "drafted token (0 would be vanilla decode — disable "
                "speculative instead)"
            )
        bs = engine_cfg.block_size
        m = engine_cfg.max_blocks_per_seq
        # per-step worst case: K+1 fed tokens + 1 pending bonus, straddling
        # a block boundary
        growth = -(-(k + 2) // bs) + 1
        if growth > m:
            raise ValueError(
                f"SpecDecodeConfig.num_draft_tokens={k}: worst-case "
                f"per-step block growth {growth} exceeds max_blocks_per_seq="
                f"{m} (max_seq_len={engine_cfg.max_seq_len} / block_size="
                f"{bs}); num_draft_tokens is the limiting field — reduce it "
                "or raise max_seq_len"
            )
        if k + 2 >= engine_cfg.max_seq_len:
            raise ValueError(
                f"SpecDecodeConfig.num_draft_tokens={k}: a verify window of "
                f"{k + 1} tokens does not fit max_seq_len="
                f"{engine_cfg.max_seq_len}; num_draft_tokens is the "
                "limiting field"
            )
        if getattr(engine_cfg, "kv_seq_sharded", False):
            # name the fence instead of silently falling back to split
            # paths: seq-sharded pools read decode rows through a
            # dedicated shard_map partial-softmax op with no multi-token
            # verify-window variant, and the in-graph draft chain has no
            # sharded-pool read path either
            raise ValueError(
                "speculative + kv_seq_sharded is fenced: the seq-sharded "
                "pool decode read (shard_map partial-softmax op) has no "
                "multi-query verify-window variant, so draft/verify "
                "rounds cannot read sharded pools — drop kv_seq_sharded "
                "or EngineConfig.speculative"
            )
        if self.oracle_accept_rate is not None and not (
            0.0 <= float(self.oracle_accept_rate) <= 1.0
        ):
            raise ValueError(
                f"SpecDecodeConfig.oracle_accept_rate="
                f"{self.oracle_accept_rate}: must be in [0, 1] (fraction "
                "of drafted tokens force-accepted per round)"
            )
        if self.adaptive:
            if not (0.0 <= float(self.adaptive_ema) < 1.0):
                raise ValueError(
                    f"SpecDecodeConfig.adaptive_ema={self.adaptive_ema}: "
                    "must be in [0, 1)"
                )
            if not (1 <= int(self.adaptive_min_k) <= k):
                # k_choices() would silently collapse to (K,) — pinning
                # every slot at full depth while the config promises a
                # floor — so reject instead
                raise ValueError(
                    f"SpecDecodeConfig.adaptive_min_k="
                    f"{self.adaptive_min_k}: must be in "
                    f"[1, num_draft_tokens={k}]"
                )
            choices = self.k_choices()
            if choices[0] < 1 or choices[-1] != k:
                # a top choice above K is unreachable; one BELOW K would
                # silently waste draft/verify work every round (the chain
                # always drafts K tokens) — lower num_draft_tokens instead
                raise ValueError(
                    f"SpecDecodeConfig adaptive depth choices {choices} "
                    f"must lie in [1, num_draft_tokens={k}] and end at "
                    f"num_draft_tokens; adaptive_min_k/adaptive_k_choices "
                    "are the limiting fields"
                )


@dataclass
class SpeculativeConfig:
    """Reference SpeculativeConfig:28 analogue."""

    widths: Tuple[int, ...] = (4, 2)
    adaptive: bool = True
    min_accept_rate: float = 0.3       # shrink depth below this
    grow_accept_rate: float = 0.7      # grow depth above this
    min_depth: int = 1
    max_depth: int = 4
    ema: float = 0.8
    # draft→verify→accept rounds fused into ONE device dispatch (a lax.scan
    # with device-resident done/budget/stop state, exactly how the vanilla
    # engine's decode_multi amortizes the host round across 16-64
    # steps). 1 = one host round per tree round (the round-2 behavior that
    # lost to vanilla at 0.90x, VERDICT r2 weak #2). Effective depth is
    # bucketed to powers of two so at most log2 variants compile.
    rounds_per_dispatch: int = 8
    # EAGLE-3-style multi-layer draft features (VERDICT r3 #1b): indices of
    # target LAYERS whose post-layer hiddens concat into the draft input
    # (e.g. low/mid/high). None = last-layer-only (EAGLE-1 behavior). The
    # draft gains a learned [k*H, H] input projection; verify forwards
    # collect the same layers so the recursion stays consistent.
    feature_layers: Optional[Tuple[int, ...]] = None

    def validate_blocks(self, max_blocks_per_seq: int,
                        block_size: int) -> None:
        """Reject width/depth combinations whose worst-case per-round block
        growth (the verify tree — including adaptive depth growth — plus
        the pending root) exceeds the per-sequence block table: the first
        round of a fresh sequence would outgrow it mid-flight otherwise."""
        widths = tuple(self.widths)
        if not widths or any(w < 1 for w in widths):
            raise ValueError(
                f"SpeculativeConfig.widths={self.widths}: every tree level "
                "needs width >= 1; widths is the limiting field"
            )
        worst = widths
        if self.adaptive:
            worst = worst + (1,) * max(0, self.max_depth - len(worst))
        nodes = TreeTopology(worst).num_nodes
        growth = -(-(nodes + 1) // block_size) + 1
        if growth > max_blocks_per_seq:
            adapt = (
                f" (adaptive depth growth to max_depth={self.max_depth})"
                if self.adaptive else ""
            )
            raise ValueError(
                f"SpeculativeConfig.widths={self.widths}{adapt}: worst-case "
                f"verify tree of {nodes} nodes needs {growth} blocks per "
                f"round, exceeding max_blocks_per_seq={max_blocks_per_seq} "
                f"(block_size={block_size}); widths/max_depth are the "
                "limiting fields"
            )


# ---------------------------------------------------------------------------
# Draft heads
# ---------------------------------------------------------------------------


def init_draft_params(
    cfg: ModelConfig, key: jax.Array, dtype: Optional[jnp.dtype] = None,
    num_feature_layers: int = 1,
) -> Dict[str, jax.Array]:
    """EAGLE-style draft net: h_next = W2 · silu(W1 · [h ; e(tok)]).

    Shares the target's embedding and LM head (reference :94) — only the
    fusion MLP is new (~2·H² params). ``num_feature_layers > 1`` adds the
    EAGLE-3 multi-layer input projection W_feat: [k·H] features (concat of
    k target layers' hiddens) project to H before fusion; deeper draft
    levels feed the head's own H-dim predictions, so only the projection
    sees the wide input."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    h = cfg.hidden_size
    k1, k2, k3 = jax.random.split(key, 3)
    dp = {
        "w_fuse": (jax.random.normal(k1, (2 * h, h), jnp.float32) * (2 * h) ** -0.5
                   ).astype(dtype),
        "w_out": (jax.random.normal(k2, (h, h), jnp.float32) * h**-0.5
                  ).astype(dtype),
        "norm": jnp.ones((h,), dtype),
    }
    if num_feature_layers > 1:
        kh = num_feature_layers * h
        dp["w_feat"] = (
            jax.random.normal(k3, (kh, h), jnp.float32) * kh**-0.5
        ).astype(dtype)
    return dp


def draft_apply(
    cfg: ModelConfig, dp: Dict[str, jax.Array], hidden: jax.Array, tok_emb: jax.Array
) -> jax.Array:
    """[..., H or k·H] × [..., H] → predicted next hidden [..., H].

    A k·H-wide input (multi-layer features from a verify pass) goes through
    the learned W_feat projection first; H-wide inputs (the draft's own
    deeper-level predictions) skip it — static shape dispatch."""
    if "w_feat" in dp and hidden.shape[-1] == dp["w_feat"].shape[0]:
        hidden = (hidden @ dp["w_feat"].astype(hidden.dtype))
    x = jnp.concatenate([hidden, tok_emb], axis=-1)
    x = jax.nn.silu(x @ dp["w_fuse"]) @ dp["w_out"]
    return llama.rms_norm(x, dp["norm"], cfg.rms_norm_eps)


def distill_draft_params(
    cfg: ModelConfig,
    params: llama.Params,
    key: jax.Array,
    steps: int = 400,
    batch: int = 8,
    seq_len: int = 64,
    num_batches: int = 8,
    lr: float = 2e-3,
    ce_weight: float = 0.2,
    feature_layers: Optional[Tuple[int, ...]] = None,
    on_policy: bool = False,
    data_stream=None,
) -> Dict[str, jax.Array]:
    """EAGLE-style draft-head distillation against the frozen target.

    The reference assumes pretrained EAGLE/Medusa weights exist
    (``worker/engines/speculative.py`` only runs inference); here the head
    can be fit on-device in seconds: teacher-force the target over token
    streams, then regress ``draft(h_t, e(x_{t+1})) → h_{t+1}`` with a
    feature MSE plus a CE term against the target's next-token distribution
    (the EAGLE recipe: feature-level supervision dominates, logits align
    the part that matters for acceptance).

    Teacher hidden states are precomputed once for ``num_batches`` fixed
    streams; the training loop then runs ``steps`` cheap MLP updates
    jitted on device. Returns draft params in the model dtype.

    EAGLE-3 knobs (VERDICT r3 #1b):
    - ``feature_layers``: distill the draft on CONCATENATED hiddens of
      these target layers (adds the ``w_feat`` projection; pass the same
      tuple as ``SpeculativeConfig.feature_layers`` at serving).
    - ``on_policy``: draw the distill streams from the TARGET's own
      sampled generations instead of uniform-random tokens — the
      distribution the draft must match at serving time.
    - ``data_stream``: ``fn(key, batch, seq_len) -> [B, S] int32`` custom
      stream sampler (e.g. the toy-task chain); overrides both defaults.
    """
    import optax

    bs = 16
    kd, kt = jax.random.split(key)
    m = -(-seq_len // bs)
    positions = jnp.tile(jnp.arange(seq_len, dtype=jnp.int32), (batch, 1))
    lens = jnp.full((batch,), seq_len, jnp.int32)
    tables = jnp.asarray(
        np.arange(1, 1 + batch * m, dtype=np.int32).reshape(batch, m)
    )

    # ---- distill streams: custom sampler > on-policy rollouts > random
    if data_stream is not None:
        tokens_all = jnp.stack([
            data_stream(jax.random.fold_in(kt, i), batch, seq_len)
            for i in range(num_batches)
        ]).astype(jnp.int32)
    elif on_policy:
        @jax.jit
        def rollout(params, kk):
            k0, kseq = jax.random.split(kk)
            first = jax.random.randint(k0, (batch,), 0, cfg.vocab_size,
                                       jnp.int32)
            kvp = llama.init_kv_pools(cfg, 1 + batch * m, bs)

            def step(carry, ks_):
                kvp, tok, pos = carry
                out = llama.forward_chunk(
                    cfg, params, tok[:, None], pos[:, None], kvp, tables,
                    pos + 1, block_size=bs, last_only=True,
                )
                nxt = jax.random.categorical(
                    ks_, out.logits[:, 0].astype(jnp.float32), axis=-1
                ).astype(jnp.int32)
                return (out.kv, nxt, pos + 1), nxt

            keys = jax.random.split(kseq, seq_len - 1)
            (_, _, _), rest = jax.lax.scan(
                step, (kvp, first, jnp.zeros((batch,), jnp.int32)), keys
            )
            return jnp.concatenate([first[:, None], rest.T], axis=1)

        tokens_all = jnp.stack([
            rollout(params, jax.random.fold_in(kt, i))
            for i in range(num_batches)
        ])
    else:
        tokens_all = jax.random.randint(
            kt, (num_batches, batch, seq_len), 0, cfg.vocab_size, jnp.int32
        )

    # teacher labels are TOP-K only: a full [N, B, S, V] float32 log-prob
    # table is ~20 GB at Llama-3/Qwen vocab sizes (this OOM'd 0.5B-scale
    # distillation on a 16 GB chip); the CE term only needs the head of the
    # teacher distribution, which at a sharply-trained target carries
    # essentially all the mass
    label_k = min(64, cfg.vocab_size)

    # params ride as jit ARGUMENTS, not closure constants: traced closures
    # over multi-GB pytrees get inlined as IR constants (host-materialized),
    # which OOMs at 0.5B+ scale
    collect = tuple(feature_layers) if feature_layers else None

    @jax.jit
    def teacher(params, tokens):
        kv = llama.init_kv_pools(cfg, 1 + batch * m, bs)
        out = llama.forward_chunk(
            cfg, params, tokens, positions, kv, tables, lens,
            block_size=bs, last_only=False, collect_layers=collect,
        )
        # target next-token distribution at every position (frozen labels)
        logits = llama.project_logits(cfg, params, out.hidden)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        top_lp, top_idx = jax.lax.top_k(logp, label_k)
        h32 = out.hidden.astype(jnp.float32)
        feats = out.features.astype(jnp.float32) if collect else h32
        return h32, feats, top_lp, top_idx

    hiddens, featss, top_lps, top_idxs = [], [], [], []
    for i in range(num_batches):
        h, f, lp, idx = teacher(params, tokens_all[i])
        hiddens.append(h)
        featss.append(f)
        top_lps.append(lp)
        top_idxs.append(idx)
    hiddens = jnp.stack(hiddens)   # [N, B, S, H] float32
    # no collect → features ARE the final hiddens: alias, don't duplicate
    # (a second [N,B,S,H] f32 stack matters on the 16 GB chip this distill
    # already OOM'd at 0.5B scale)
    featss = hiddens if collect is None else jnp.stack(featss)
    top_lps = jnp.stack(top_lps)   # [N, B, S, K]
    top_idxs = jnp.stack(top_idxs)  # [N, B, S, K] int32

    # ---- student: train in float32
    dp = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        init_draft_params(
            cfg, kd, num_feature_layers=len(collect) if collect else 1
        ),
    )
    # draft_apply's output rms_norm pins the prediction's magnitude at the
    # norm gain — initialized at 1, while a TIED-embedding target's hiddens
    # must be large (its head rows stay near unit norm, so logit sharpness
    # lives in |h|; an untied lm_head absorbs the magnitude instead). A
    # unit-gain draft starts with a magnitude floor the optimizer must climb
    # ~|h|x to escape — measured round 3: tied mini accepted 1/732 vs the
    # untied 23/492 purely from this. Initialize the gain at the teacher's
    # hidden RMS so the draft starts on the teacher's scale for ANY head
    # convention.
    teacher_rms = jnp.sqrt(jnp.mean(jnp.square(hiddens)))
    dp["norm"] = dp["norm"] * teacher_rms
    opt = optax.adam(lr)
    opt_state = opt.init(dp)
    cfg32 = cfg  # rms eps etc. unchanged; draft_apply respects input dtype

    def loss_fn(dp, params, tokens, hidden, feats, top_lp, top_idx):
        # inputs at t: (features_t, emb(x_{t+1})) → predict h_{t+1} — the
        # TARGET stays the final-layer hidden (that is what project_logits
        # reads at verify time); only the INPUT widens to multi-layer
        emb_next = llama.embed_tokens(params, tokens[:, 1:], cfg).astype(
            jnp.float32
        )
        pred = draft_apply(cfg32, dp, feats[:, :-1], emb_next)  # [B,S-1,H]
        mse = jnp.mean(jnp.square(pred - hidden[:, 1:]))
        pred_logits = llama.project_logits(cfg, params, pred)
        pred_logp = jax.nn.log_softmax(pred_logits, axis=-1)
        # CE against the teacher's top-k next-step distribution (gathered
        # from the student's full log-softmax at the teacher's indices)
        sel = jnp.take_along_axis(pred_logp, top_idx[:, 1:], axis=-1)
        ce = -jnp.mean(jnp.sum(jnp.exp(top_lp[:, 1:]) * sel, axis=-1))
        return mse + ce_weight * ce

    # single scan = one compile + one device call;
    # params/teacher data as arguments for the same closure-constant reason
    @jax.jit
    def train(dp, opt_state, params, tokens_all, hiddens, featss, top_lps,
              top_idxs):
        def step_fn(carry, step):
            dp, opt_state = carry
            i = step % num_batches
            loss, grads = jax.value_and_grad(loss_fn)(
                dp, params, tokens_all[i], hiddens[i], featss[i],
                top_lps[i], top_idxs[i]
            )
            updates, opt_state = opt.update(grads, opt_state)
            return (optax.apply_updates(dp, updates), opt_state), loss

        (dp, _), losses = jax.lax.scan(
            step_fn, (dp, opt_state), jnp.arange(steps)
        )
        return dp, losses

    dp, _losses = train(dp, opt_state, params, tokens_all, hiddens, featss,
                        top_lps, top_idxs)
    dtype = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda a: a.astype(dtype), dp)


def init_medusa_params(
    cfg: ModelConfig, key: jax.Array, num_heads: int = 4,
    dtype: Optional[jnp.dtype] = None,
) -> Dict[str, jax.Array]:
    """Medusa alternative (reference MedusaHead:474): K residual projections
    of the last hidden, one per lookahead distance; shares the LM head."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    h = cfg.hidden_size
    return {
        "w": (jax.random.normal(key, (num_heads, h, h), jnp.float32) * h**-0.5
              ).astype(dtype),
    }


def medusa_logits(
    cfg: ModelConfig, params: llama.Params, mp: Dict[str, jax.Array],
    hidden: jax.Array,
) -> jax.Array:
    """hidden [B, H] → [B, K, V] logits for +1..+K lookahead."""
    proj = jnp.einsum("bh,khg->bkg", hidden.astype(jnp.float32),
                      mp["w"].astype(jnp.float32))
    proj = proj + hidden.astype(jnp.float32)[:, None, :]
    head = params.get("lm_head", params["embedding"])
    return jnp.einsum("bkh,vh->bkv", proj, head.astype(jnp.float32))


# ---------------------------------------------------------------------------
# The decoder
# ---------------------------------------------------------------------------


@dataclass
class _SpecWave:
    """In-flight speculative wave state (``SpeculativeDecoder.start_wave``).

    Exists so a serving loop can interleave bounded spec dispatches with
    other engine work (adaptive speculation in the batcher, VERDICT r3 #7)
    instead of blocking on a whole generation."""

    requests: List[InferenceRequest]
    seq_ids: List[str]
    start: float
    first_token_time: float
    pendings: np.ndarray
    h_last: Any
    tables: np.ndarray
    prefix_lens: np.ndarray
    cached_counts: List[int]
    emitted: List[List[int]]
    done: List[bool]
    finish: List[Optional[str]]
    stops: List[set]
    stop_pad: np.ndarray
    budgets_full: np.ndarray

    def emit(self, i: int, tok: int) -> None:
        if self.done[i]:
            return
        if tok in self.stops[i]:
            self.done[i] = True
            self.finish[i] = "stop"
            return
        self.emitted[i].append(tok)
        if len(self.emitted[i]) >= self.requests[i].sampling.max_new_tokens:
            self.done[i] = True
            self.finish[i] = "length"

    @property
    def all_done(self) -> bool:
        return all(self.done)


class SpeculativeDecoder:
    """Greedy speculative generation over the paged-KV substrate.

    Batched: every sequence in the batch drafts/verifies the same tree shape
    each step; per-sequence accept lengths differ freely.
    """

    def __init__(
        self,
        model_cfg: ModelConfig | str,
        params: Optional[llama.Params] = None,
        draft_params: Optional[Dict[str, jax.Array]] = None,
        spec_cfg: Optional[SpeculativeConfig] = None,
        max_batch_size: int = 4,
        max_seq_len: int = 1024,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        seed: int = 0,
        eos_token_id: Optional[int] = None,
        prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256, 512, 1024),
        kv_cache_dtype: Optional[str] = None,
    ) -> None:
        """``kv_cache_dtype``: ``"int8"`` stores the decoder's pools
        quantized (per-(page, token) scale pools ride alongside; the tree
        verify pass dequantizes context-sized through the shared
        ``ops.attention.dequantize_kv`` arithmetic, and path compaction
        moves code + scale rows as an atomic pair). Sliding-window models
        speculate at any tree depth since round 8 — the tree-attention
        mask windows within-chunk node visibility by semantic position
        (``ops.attention.paged_tree_attention``)."""
        self.model_cfg = (
            get_model_config(model_cfg) if isinstance(model_cfg, str) else model_cfg
        )
        self.spec_cfg = spec_cfg or SpeculativeConfig()
        if self.model_cfg.latent_kv:
            raise ValueError(
                f"{self.model_cfg.name}: the tree decoder drafts with a "
                "Llama head and moves K/V rows; a latent-attention model's "
                "multi-token-prediction layer is not loaded")
        if kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"SpeculativeDecoder kv_cache_dtype={kv_cache_dtype!r}: "
                "only int8 (or None = model dtype) is wired"
            )
        self.kv_dtype = jnp.int8 if kv_cache_dtype == "int8" else None
        self.block_size = block_size
        self.max_batch_size = max_batch_size
        self.max_seq_len = max_seq_len
        self.max_blocks_per_seq = -(-max_seq_len // block_size)
        self.spec_cfg.validate_blocks(self.max_blocks_per_seq, block_size)
        self.num_blocks = num_blocks or int(
            max_batch_size * self.max_blocks_per_seq * 1.5
        ) + 1
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else llama.init_params(
            self.model_cfg, key
        )
        self._collect = (
            tuple(self.spec_cfg.feature_layers)
            if self.spec_cfg.feature_layers else None
        )
        self.draft_params = (
            draft_params
            if draft_params is not None
            else init_draft_params(
                self.model_cfg, jax.random.PRNGKey(seed + 1),
                num_feature_layers=(
                    len(self._collect) if self._collect else 1
                ),
            )
        )
        self.kv = llama.init_kv_pools(
            self.model_cfg, self.num_blocks, block_size, dtype=self.kv_dtype
        )
        self.manager = PagedKVCacheManager(self.num_blocks, block_size)
        self.eos_token_id = eos_token_id
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        self._step_fns: Dict[Tuple[int, ...], Any] = {}
        self._scan_fns: Dict[Tuple[Any, int], Any] = {}
        self._prefill_fn = self._build_prefill()
        self._widths = tuple(self.spec_cfg.widths)
        self.accept_rate_ema = 0.5
        self.stats: Dict[str, Any] = {
            "steps": 0, "drafted": 0, "accepted": 0, "emitted": 0,
            "depth_changes": 0,
        }

    # ----------------------------------------------------------- jit builders

    def _build_prefill(self):
        cfg, bs = self.model_cfg, self.block_size
        collect = self._collect

        def prefill(params, kv, tokens, positions, block_table, kv_len):
            out = llama.forward_chunk(
                cfg, params, tokens, positions, kv, block_table, kv_len,
                block_size=bs, last_only=True, collect_layers=collect,
            )
            src = out.features if collect else out.hidden
            n_valid = jnp.sum((positions >= 0).astype(jnp.int32), axis=1)
            last_idx = jnp.maximum(n_valid - 1, 0)
            h_last = jnp.take_along_axis(
                src, last_idx[:, None, None].astype(jnp.int32), axis=1
            )[:, 0, :]
            return out.logits[:, 0, :], h_last, out.kv

        return jax.jit(prefill, donate_argnums=(1,))

    def _make_round(self, widths: Tuple[int, ...]):
        """The raw draft→verify→accept→compact round body (un-jitted), shared
        by the single-round step API and the multi-round scan."""
        topo = TreeTopology(widths)
        cfg = self.model_cfg
        bs = self.block_size
        collect = self._collect
        parents = jnp.asarray(topo.parents)
        depths = jnp.asarray(topo.depths)
        tree_mask = jnp.asarray(topo.ancestor_mask)
        n = topo.num_nodes
        dmax = topo.max_depth
        level_slices = topo.level_slices

        def step(params, dp, kv, pending, h_last, prefix_lens, block_tables,
                 active):
            b = pending.shape[0]

            # token embedding must follow the target model's convention
            # (Gemma scales by sqrt(H)) or the draft head sees inputs on a
            # different scale than the hidden states it fuses with
            def emb_of(ids):
                return llama.embed_tokens(params, ids, cfg)

            # ---- draft phase: grow the tree level by level (static shapes)
            tokens = jnp.zeros((b, n), jnp.int32).at[:, 0].set(pending)
            h_root = draft_apply(cfg, dp, h_last, emb_of(pending))
            frontier_h = h_root[:, None, :]           # [B, F, H]
            for li, w in enumerate(widths):
                # draft logits MUST go through project_logits (final_norm +
                # head) — the distillation CE trains the draft against
                # exactly that readout (distill_draft_params loss_fn), and a
                # raw frontier_h @ head readout diverges from it badly
                # enough to zero the accept rate on tied-embedding models
                # (round-3 probe: tied mini accepted 1/732 without the norm,
                # 20x more with it)
                logits = llama.project_logits(cfg, params, frontier_h)
                _, cand = jax.lax.top_k(logits, w)    # [B, F, w]
                start, end = level_slices[li]
                tokens = tokens.at[:, start:end].set(cand.reshape(b, -1))
                # next frontier hiddens: f(parent_h, emb(child_tok))
                child_emb = emb_of(cand)                         # [B, F, w, H]
                parent_h = jnp.broadcast_to(
                    frontier_h[:, :, None, :], child_emb.shape
                )
                frontier_h = draft_apply(cfg, dp, parent_h, child_emb).reshape(
                    b, -1, cfg.hidden_size
                )

            # ---- verify phase: one target forward over the tree.
            # Finished sequences must not write ANY pages (their tables may
            # not even cover the tree range near max_seq_len): position -1
            # drops the writes.
            rope_pos = prefix_lens[:, None] + depths[None, :]
            cache_pos = prefix_lens[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
            cache_pos = jnp.where(active[:, None], cache_pos, -1)
            out = llama.forward_tree_chunk(
                cfg, params, tokens, rope_pos, cache_pos, kv, block_tables,
                prefix_lens, tree_mask, block_size=bs,
                collect_layers=collect,
            )
            target_pred = jnp.argmax(out.logits, axis=-1).astype(jnp.int32)  # [B,N]

            # ---- acceptance: greedy match down the tree
            accept = jnp.zeros((b, n), bool).at[:, 0].set(True)
            for i in range(1, n):
                p = int(topo.parents[i])
                ok = accept[:, p] & (tokens[:, i] == target_pred[:, p])
                accept = accept.at[:, i].set(ok)
            # deepest accepted node, ties → lowest index
            score = jnp.where(
                accept, depths[None, :] * (n + 1) - jnp.arange(n)[None, :], -1
            )
            best = jnp.argmax(score, axis=-1).astype(jnp.int32)   # [B]
            n_accept = jnp.take(depths, best)                      # [B] 0..dmax

            # ---- path extraction (walk parents; static dmax iterations)
            path = jnp.full((b, dmax), n, jnp.int32)  # n = OOB sentinel
            cur = best
            for _ in range(dmax):
                d = jnp.take(depths, cur)
                row = jnp.arange(b)
                write_col = jnp.where(d >= 1, d - 1, dmax)
                path = path.at[row, write_col].set(
                    jnp.where(d >= 1, cur, n), mode="drop"
                )
                cur = jnp.where(d > 1, jnp.take(parents, cur), cur)

            path_valid = path < n                                   # [B, dmax]
            safe_path = jnp.where(path_valid, path, 0)
            accepted_tokens = jnp.where(
                path_valid,
                jnp.take_along_axis(tokens, safe_path, axis=1),
                -1,
            )                                                       # [B, dmax]
            bonus = jnp.take_along_axis(target_pred, best[:, None], axis=1)[:, 0]
            new_h = jnp.take_along_axis(
                out.features if collect else out.hidden,
                best[:, None, None].astype(jnp.int32), axis=1,
            )[:, 0, :]

            # ---- KV compaction: move accepted nodes' pages to depth order
            kv2 = out.kv
            live = path_valid & active[:, None]
            src_pos = jnp.where(live, prefix_lens[:, None] + path, -1)
            dst_pos = prefix_lens[:, None] + 1 + jnp.arange(dmax)[None, :]
            dst_pos = jnp.where(live, dst_pos, -1)
            moved = {
                "k": _move_rows(kv2["k"], block_tables, src_pos, dst_pos, bs),
                "v": _move_rows(kv2["v"], block_tables, src_pos, dst_pos, bs),
            }
            # int8 pools: a code row without its scale is garbage — the
            # compaction moves them as an atomic pair
            for sk in ("k_scale", "v_scale"):
                if sk in kv2:
                    moved[sk] = _move_scale_rows(
                        kv2[sk], block_tables, src_pos, dst_pos, bs
                    )
            return moved, accepted_tokens, n_accept, bonus, new_h

        return step

    def _build_step(self, widths: Tuple[int, ...]):
        return jax.jit(self._make_round(widths), donate_argnums=(2,))

    def _get_step(self, widths: Tuple[int, ...]):
        if widths not in self._step_fns:
            self._step_fns[widths] = self._build_step(widths)
        return self._step_fns[widths]

    def _build_scan(self, widths: Tuple[int, ...], rounds: int):
        """``rounds`` draft→verify→accept rounds in ONE dispatch: a lax.scan
        whose carry keeps KV, pending tokens, draft hiddens, prefix lengths,
        and per-row done/emitted state ON DEVICE — the speculative analogue
        of the engine's ``decode_multi`` scan (``runtime/engine.py``
        decode_multi), so the ~10 ms host RTT is paid once per ``rounds``
        tree rounds instead of once per round (VERDICT r2 weak #2 / next #2).

        Per-round records (pending-in, accepted path, accept counts, bonus,
        active mask) are returned so the host replays cache-manager commits
        and emission bookkeeping EXACTLY as the per-round loop would have —
        device state and host metadata cannot drift.
        """
        round_fn = self._make_round(widths)
        topo = TreeTopology(widths)
        n = topo.num_nodes
        dmax = topo.max_depth
        max_ctx = min(self.max_seq_len, self.max_blocks_per_seq * self.block_size)

        def scan_step(params, dp, kv, pendings, h_last, prefix_lens,
                      block_tables, done0, n_emit0, budgets, stop_ids):
            b = pendings.shape[0]

            def body(carry, _):
                kv, pending, h_last, prefix, done, n_emit = carry
                # a row whose next tree cannot fit below the context capacity
                # freezes here (host labels it "length" after the dispatch)
                fits = prefix + n + 1 <= max_ctx
                active = (~done) & fits
                kv2, acc, n_acc, bonus, new_h = round_fn(
                    params, dp, kv, pending, h_last, prefix, block_tables,
                    active,
                )
                # ---- device emission accounting (gates later rounds only;
                # the authoritative emission replay happens on host from the
                # recorded arrays). Emission order: accepted path then bonus.
                j = jnp.arange(dmax + 1, dtype=jnp.int32)[None, :]
                acc_pad = jnp.concatenate(
                    [acc, jnp.full((b, 1), -1, jnp.int32)], axis=1
                )
                ordered = jnp.where(
                    j < n_acc[:, None], acc_pad,
                    jnp.where(j == n_acc[:, None], bonus[:, None], -1),
                )
                ordered = jnp.where(active[:, None], ordered, -1)
                is_stop = (
                    (ordered[:, :, None] == stop_ids[:, None, :]).any(-1)
                    & (ordered >= 0)
                )
                cum = jnp.cumsum(is_stop.astype(jnp.int32), axis=1)
                pre_stop = (cum - is_stop.astype(jnp.int32)) == 0
                emit_j = (ordered >= 0) & pre_stop & ~is_stop
                rank = jnp.cumsum(emit_j.astype(jnp.int32), axis=1) \
                    - emit_j.astype(jnp.int32)
                emit_mask = emit_j & (n_emit[:, None] + rank < budgets[:, None])
                n_emit2 = n_emit + emit_mask.sum(axis=1)
                stop_hit = (is_stop & pre_stop).any(axis=1)
                done2 = done | (~fits) | (
                    active & (stop_hit | (n_emit2 >= budgets))
                )
                pending2 = jnp.where(active, bonus, pending)
                h2 = jnp.where(active[:, None], new_h, h_last)
                prefix2 = jnp.where(active, prefix + 1 + n_acc, prefix)
                rec = (pending, acc, n_acc, bonus, active)
                return (kv2, pending2, h2, prefix2, done2, n_emit2), rec

            carry, recs = jax.lax.scan(
                body,
                (kv, pendings, h_last, prefix_lens, done0, n_emit0),
                None,
                length=rounds,
            )
            return carry, recs

        return jax.jit(scan_step, donate_argnums=(2,))

    def _get_scan(self, widths: Tuple[int, ...], rounds: int):
        key = (widths, rounds)
        if key not in self._scan_fns:
            self._scan_fns[key] = self._build_scan(widths, rounds)
        return self._scan_fns[key]

    # ------------------------------------------------------------- generation

    def generate(self, requests: Sequence[InferenceRequest]) -> List[InferenceResponse]:
        """Greedy speculative batch generation (waves of ≤ max_batch_size).

        Only greedy sampling is supported (the verify pass is an argmax
        match); non-greedy params are rejected rather than silently ignored
        so behavior can't diverge from TPUEngine under the same request.
        """
        for r in requests:
            if r.sampling.temperature and r.sampling.temperature > 0.0:
                raise ValueError(
                    "SpeculativeDecoder is greedy-only: request "
                    f"{r.request_id} has temperature={r.sampling.temperature}; "
                    "route sampled requests to TPUEngine"
                )
        out: List[InferenceResponse] = []
        for i in range(0, len(requests), self.max_batch_size):
            out.extend(self._generate_wave(requests[i : i + self.max_batch_size]))
        return out

    def _prefill(self, req: InferenceRequest, seq_id: str) -> Tuple[int, jax.Array, int]:
        token_ids = req.prompt_token_ids or []
        if not token_ids:
            raise ValueError("request has no prompt_token_ids")
        blocks, cached = self.manager.allocate_sequence(seq_id, token_ids)
        table = self.manager.block_table_for(seq_id, self.max_blocks_per_seq)
        fresh = token_ids[cached:]
        n = len(fresh)
        # bucket-pad so prefill compiles once per bucket, not per length
        bucket = next((bkt for bkt in self.prefill_buckets if bkt >= n), n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = fresh
        pos = np.full((1, bucket), -1, np.int32)
        pos[0, :n] = np.arange(cached, cached + n)
        logits, h_last, self.kv = self._prefill_fn(
            self.params, self.kv, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(table[None]), jnp.asarray([len(token_ids)], jnp.int32),
        )
        pending = int(jnp.argmax(logits[0]))
        return pending, h_last[0], cached

    def start_wave(self, requests: Sequence[InferenceRequest]) -> "_SpecWave":
        """Prefill a wave (≤ max_batch_size greedy requests) and return its
        state object. Drive with :meth:`advance_wave` (one fused multi-round
        dispatch per call — bounded work, so a serving loop can interleave
        other engine rounds between calls) and collect with
        :meth:`finish_wave`."""
        requests = list(requests)
        if not requests or len(requests) > self.max_batch_size:
            raise ValueError(
                f"wave of {len(requests)} requests (max {self.max_batch_size})"
            )
        b = len(requests)
        seq_ids = [r.session_id or uuid.uuid4().hex for r in requests]
        start = time.time()
        pendings = np.zeros((b,), np.int32)
        h_lasts = []
        cached_counts = []
        tables = np.zeros((b, self.max_blocks_per_seq), np.int32)
        prefix_lens = np.zeros((b,), np.int32)
        try:
            for i, (r, sid) in enumerate(zip(requests, seq_ids)):
                pending, h_last, cached = self._prefill(r, sid)
                pendings[i] = pending
                h_lasts.append(h_last)
                cached_counts.append(cached)
                prefix_lens[i] = len(r.prompt_token_ids or [])
                tables[i] = self.manager.block_table_for(
                    sid, self.max_blocks_per_seq
                )
        except Exception:
            # a failed prefill must not strand the rows already allocated —
            # in a serving loop each leak would shrink the spec pool forever
            for sid in seq_ids:
                if sid in self.manager.seq_blocks:
                    self.manager.free_sequence(sid, cache=False)
            raise
        h_last = jnp.stack(h_lasts)
        first_token_time = time.time()

        stops = [set(r.sampling.stop_token_ids) |
                 ({self.eos_token_id} if self.eos_token_id is not None else set())
                 for r in requests]
        # device stop-id table (pad -1 never matches: ordered tokens are >= 0)
        max_stops = max(1, max(len(s) for s in stops) if stops else 1)
        stop_pad = np.full((b, max_stops), -1, np.int32)
        for i, s in enumerate(stops):
            for si, tok in enumerate(sorted(s)):
                stop_pad[i, si] = tok

        wave = _SpecWave(
            requests=requests, seq_ids=seq_ids, start=start,
            first_token_time=first_token_time,
            pendings=pendings, h_last=h_last, tables=tables,
            prefix_lens=prefix_lens, cached_counts=cached_counts,
            emitted=[[] for _ in range(b)], done=[False] * b,
            finish=[None] * b, stops=stops, stop_pad=stop_pad,
            budgets_full=np.asarray(
                [r.sampling.max_new_tokens for r in requests], np.int32
            ),
        )
        # the prefill-sampled token is the first generated token
        for i in range(b):
            wave.emit(i, int(pendings[i]))
        return wave

    def advance_wave(self, wave: "_SpecWave") -> bool:
        """Run ONE fused multi-round dispatch for the wave; True when every
        sequence finished. Work per call is bounded by
        ``spec_cfg.rounds_per_dispatch`` tree rounds."""
        b = len(wave.requests)
        requests, seq_ids = wave.requests, wave.seq_ids
        emitted, done, finish = wave.emitted, wave.done, wave.finish
        emit = wave.emit
        pendings, h_last = wave.pendings, wave.h_last
        prefix_lens, tables = wave.prefix_lens, wave.tables
        stop_pad, budgets_full = wave.stop_pad, wave.budgets_full
        max_ctx = min(self.max_seq_len, self.max_blocks_per_seq * self.block_size)

        if not all(done):
            widths = self._widths
            topo = TreeTopology(widths)
            topo_n, dmax = topo.num_nodes, topo.max_depth
            # host mirror of the device fits-freeze: rows whose next tree
            # cannot fit finish with "length" (and must not reserve blocks)
            for i in range(b):
                if not done[i] and int(prefix_lens[i]) + topo_n + 1 > max_ctx:
                    done[i] = True
                    finish[i] = "length"
            active_rows = [i for i in range(b) if not done[i]]
            if not active_rows:
                return True
            # rounds per dispatch: capped by the largest remaining budget
            # (each active round emits >= 1 token) and bucketed to a power of
            # two so at most log2(rounds_per_dispatch) graphs compile
            max_remaining = max(
                int(budgets_full[i]) - len(emitted[i]) for i in active_rows
            )
            rounds = max(1, min(self.spec_cfg.rounds_per_dispatch, max_remaining))
            rounds = 1 << (rounds.bit_length() - 1)

            def blocks_needed(n_rounds: int) -> int:
                total = 0
                for i in active_rows:
                    cur = len(self.manager.seq_tokens[seq_ids[i]])
                    have = len(self.manager.seq_blocks[seq_ids[i]])
                    t = min(
                        (n_rounds - 1) * (dmax + 1) + topo_n + 1,
                        max_ctx - int(prefix_lens[i]),
                    )
                    total += max(
                        0,
                        -(-(cur + t) // self.block_size) - have,
                    )
                return total

            # worst-case reservation for `rounds` rounds is ~rounds/2 x the
            # old per-round peak — shrink the dispatch rather than evicting
            # the prefix cache (or aborting the batch) to pre-book blocks
            # most accept rates never use
            while rounds > 1 and \
                    blocks_needed(rounds) > self.manager.num_reclaimable:
                rounds >>= 1
            for i in active_rows:
                sid = seq_ids[i]
                # worst-case growth over the dispatch: (rounds-1) committed
                # paths of dmax+1 plus the final round's tree
                need = (rounds - 1) * (dmax + 1) + topo_n + 1
                need = min(need, max_ctx - int(prefix_lens[i]))
                self.manager.reserve_tokens(sid, need)
                tables[i] = self.manager.block_table_for(
                    sid, self.max_blocks_per_seq
                )
            scan_fn = self._get_scan(widths, rounds)
            done_np = np.asarray(done)
            budgets_rem = np.asarray(
                [int(budgets_full[i]) - len(emitted[i]) for i in range(b)],
                np.int32,
            )
            carry, recs = scan_fn(
                self.params, self.draft_params, self.kv,
                jnp.asarray(pendings), h_last,
                jnp.asarray(prefix_lens, dtype=jnp.int32),
                jnp.asarray(tables),
                jnp.asarray(done_np), jnp.zeros((b,), jnp.int32),
                jnp.asarray(budgets_rem), jnp.asarray(stop_pad),
            )
            self.kv, pend_dev, h_last, prefix_dev, done_dev, _ = carry
            rec_pend, rec_acc, rec_nacc, rec_bonus, rec_active = (
                np.asarray(r) for r in recs
            )
            # ---- host replay: commits + emission EXACTLY as the per-round
            # loop would have done them, from the recorded per-round arrays
            for r in range(rounds):
                act = rec_active[r]
                if not act.any():
                    break
                self.stats["steps"] += 1
                for i in range(b):
                    if not act[i]:
                        continue
                    self.manager.commit_tokens(
                        seq_ids[i], [int(rec_pend[r, i])]
                    )
                    for d in range(int(rec_nacc[r, i])):
                        tok = int(rec_acc[r, i, d])
                        self.manager.commit_tokens(seq_ids[i], [tok])
                        emit(i, tok)
                        if done[i]:
                            break
                    if not done[i]:
                        emit(i, int(rec_bonus[r, i]))
                    self.stats["drafted"] += topo_n - 1
                    self.stats["accepted"] += int(rec_nacc[r, i])
                    self.stats["emitted"] += int(rec_nacc[r, i]) + 1
                    self.stats["row_steps"] = self.stats.get("row_steps", 0) + 1
                # adapt on rows active THIS round (finished rows draft stale
                # state); ema replayed per round, same as the old loop
                live_rate = float(rec_nacc[r][act].mean()) / max(1, dmax)
                self.accept_rate_ema = (
                    self.spec_cfg.ema * self.accept_rate_ema
                    + (1 - self.spec_cfg.ema) * live_rate
                )
            wave.pendings = np.asarray(pend_dev)
            wave.prefix_lens = np.asarray(prefix_dev)
            wave.h_last = h_last
            # rows the device froze for capacity (fits-check) but the host
            # didn't finish otherwise: label them now so the loop terminates
            done_dev_np = np.asarray(done_dev)
            for i in range(b):
                if done_dev_np[i] and not done[i]:
                    done[i] = True
                    finish[i] = "length"
            self._maybe_adapt()
        return all(done)

    def finish_wave(self, wave: "_SpecWave") -> List[InferenceResponse]:
        """Free the wave's sequences (prefix-cached) and build responses."""
        responses = []
        now = time.time()
        for i, (r, sid) in enumerate(zip(wave.requests, wave.seq_ids)):
            self.manager.free_sequence(sid, cache=True)
            responses.append(
                InferenceResponse(
                    request_id=r.request_id,
                    token_ids=wave.emitted[i][: r.sampling.max_new_tokens],
                    finish_reason=wave.finish[i] or "length",
                    prompt_tokens=len(r.prompt_token_ids or []),
                    completion_tokens=len(
                        wave.emitted[i][: r.sampling.max_new_tokens]
                    ),
                    cached_tokens=wave.cached_counts[i],
                    ttft_ms=(wave.first_token_time - wave.start) * 1000.0,
                    e2e_ms=(now - wave.start) * 1000.0,
                )
            )
        return responses

    def abort_wave(self, wave: "_SpecWave") -> None:
        """Release a wave's sequences without caching (serving-loop error
        recovery: the batcher must be able to drop a wedged wave)."""
        for sid in wave.seq_ids:
            if sid in self.manager.seq_blocks:
                self.manager.free_sequence(sid, cache=False)

    def _generate_wave(self, requests: Sequence[InferenceRequest]) -> List[InferenceResponse]:
        wave = self.start_wave(requests)
        while not self.advance_wave(wave):
            pass
        return self.finish_wave(wave)

    def worst_case_tree_nodes(self) -> int:
        """Upper bound on the verify-tree size over adaptive depth growth —
        what an admission policy must budget per round on top of the
        generation itself (the fits-freeze ends a row at
        ``prefix + nodes + 1 > max ctx``)."""
        widths = tuple(self._widths)
        if self.spec_cfg.adaptive:
            widths = widths + (1,) * max(
                0, self.spec_cfg.max_depth - len(widths)
            )
        return TreeTopology(widths).num_nodes

    def _maybe_adapt(self) -> None:
        """Reference _adapt_depth:456-463: shrink when acceptance is poor,
        grow when it is high."""
        if not self.spec_cfg.adaptive:
            return
        depth = len(self._widths)
        if (self.accept_rate_ema < self.spec_cfg.min_accept_rate
                and depth > self.spec_cfg.min_depth):
            self._widths = self._widths[:-1]
            self.stats["depth_changes"] += 1
        elif (self.accept_rate_ema > self.spec_cfg.grow_accept_rate
                and depth < self.spec_cfg.max_depth):
            self._widths = self._widths + (1,)
            self.stats["depth_changes"] += 1

    def get_stats(self) -> Dict[str, Any]:
        out = dict(self.stats)
        # path-level acceptance (the reference's notion, speculative.py:456):
        # accepted tokens per step per sequence over the max draft depth —
        # NOT accepted/drafted nodes, which is structurally low for trees
        # (most sibling branches are always discarded)
        out["accept_rate_ema"] = self.accept_rate_ema
        if out["steps"]:
            # emitted is batch-aggregate; steps counts batch rounds
            out["tokens_per_step_batch"] = out["emitted"] / out["steps"]
            rows = max(self.stats.get("row_steps", 0), 1)
            out["tokens_per_step"] = out["emitted"] / rows
        out["current_widths"] = list(self._widths)
        return out


def _move_rows(
    pool: jax.Array,          # [L, N, Hkv, Bk, D] (head-major pages)
    block_tables: jax.Array,  # [B, M]
    src_pos: jax.Array,       # [B, P] token positions (-1 invalid)
    dst_pos: jax.Array,       # [B, P]
    block_size: int,
) -> jax.Array:
    """Copy KV rows between token positions (all layers), dropping invalid
    entries — the on-device page compaction after tree acceptance."""
    num_blocks = pool.shape[1]
    b, p = src_pos.shape

    def phys_slot(pos):
        valid = pos >= 0
        safe = jnp.maximum(pos, 0)
        logical = safe // block_size
        slot = safe % block_size
        phys = jnp.take_along_axis(block_tables, logical, axis=1)
        return jnp.where(valid, phys, num_blocks), slot, valid

    sphys, sslot, svalid = phys_slot(src_pos)
    dphys, dslot, dvalid = phys_slot(dst_pos)
    # gather first (read everything before any write); advanced indices on
    # dims 1 (page) and 3 (slot) are separated by slices, so the indexed
    # dims move FIRST: rows [B, P, L, Hkv, D]
    rows = pool[
        :, jnp.where(svalid, sphys, 0), :, jnp.where(svalid, sslot, 0)
    ]
    wphys = jnp.where(svalid & dvalid, dphys, num_blocks).reshape(-1)
    wslot = dslot.reshape(-1)
    # scatter values for .at[:, wphys, :, wslot] follow the same rule:
    # [T, L, Hkv, D]
    flat = rows.reshape(b * p, pool.shape[0], pool.shape[2], pool.shape[4])
    return pool.at[:, wphys, :, wslot].set(flat, mode="drop")


def _move_scale_rows(
    pool: jax.Array,          # [L, N, Bk, D] bf16 scale pool (int8 KV)
    block_tables: jax.Array,  # [B, M]
    src_pos: jax.Array,       # [B, P] token positions (-1 invalid)
    dst_pos: jax.Array,       # [B, P]
    block_size: int,
) -> jax.Array:
    """Scale-pool twin of :func:`_move_rows` (no head axis): int8 path
    compaction must move each code row's per-(page, token) scale with it
    or the copied page dequantizes with a stale scale."""
    num_blocks = pool.shape[1]
    b, p = src_pos.shape

    def phys_slot(pos):
        valid = pos >= 0
        safe = jnp.maximum(pos, 0)
        logical = safe // block_size
        slot = safe % block_size
        phys = jnp.take_along_axis(block_tables, logical, axis=1)
        return jnp.where(valid, phys, num_blocks), slot, valid

    sphys, sslot, svalid = phys_slot(src_pos)
    dphys, dslot, dvalid = phys_slot(dst_pos)
    # advanced indices on dims 1 (page) and 2 (slot) are adjacent here, so
    # the indexed dims stay IN PLACE: rows [L, B, P, D]
    rows = pool[
        :, jnp.where(svalid, sphys, 0), jnp.where(svalid, sslot, 0)
    ]
    wphys = jnp.where(svalid & dvalid, dphys, num_blocks).reshape(-1)
    wslot = dslot.reshape(-1)
    flat = rows.reshape(pool.shape[0], b * p, pool.shape[3])
    return pool.at[:, wphys, wslot].set(flat, mode="drop")
