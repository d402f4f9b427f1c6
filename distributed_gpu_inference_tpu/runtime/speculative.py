"""Speculative decoding inside the engine: the chain's configuration
(:class:`SpecDecodeConfig`) and its EAGLE-style feature-level draft head.

``TPUEngine`` drafts and verifies the chain INSIDE its ragged round and its
decode scan, on its own pages (``EngineConfig.speculative``); this module
holds what that path is configured and fed with:

- the draft head predicts the next hidden from ``[hidden ; tok-emb]`` and
  shares the target's embedding and LM head (``init_draft_params`` /
  ``draft_apply``; reference ``worker/engines/speculative.py`` DraftHead:59,
  :94, :98-125);
- ``distill_draft_params`` fits that head on device against the frozen
  target (``TPUEngine.distill_draft``).

**Greedy-equivalence invariant**: with temperature 0 the emitted stream is
bit-identical to vanilla greedy decode regardless of draft quality — the
draft only affects speed. ``tests/test_engine_spec_integrated.py`` enforces
it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import ModelConfig


@dataclass
class SpecDecodeConfig:
    """Engine-integrated speculative decoding (``TPUEngine`` decode mode).

    CHAIN drafts inside the continuous-batching engine: every active
    slot drafts ``num_draft_tokens`` greedily with the EAGLE-style head,
    then ONE multi-query target pass (q_len = K+1 per slot) verifies the
    chain and each slot commits 1..K+1 tokens. Chain positions are
    sequential, so accepted KV is already in place and a rejected suffix is
    simply overwritten by the next step — no compaction, and it
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

    The reference assumes pretrained EAGLE weights exist
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
      these target layers (adds the ``w_feat`` projection). The engine's
      chain feeds the last layer's hidden only, so it serves such a head
      without the projection: leave this ``None`` for a head it will run.
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
