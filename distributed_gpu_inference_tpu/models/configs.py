"""Model geometry registry (Llama-3-class decoder-only transformers).

The reference selects models by HF name and lets vLLM/SGLang introspect the
config (``worker/engines/llm_vllm.py:42``); here geometry is explicit because
the shard planner, KV pool sizing, and mesh sharding rules all consume it
(reference analogue: ``worker/distributed/model_shard.py:273-311``
``analyze_model`` reconstructs exactly these numbers from an HF config).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, NamedTuple, Optional, Tuple


class LatentKind(NamedTuple):
    """What a latent (MLA) layer of one attention kind is made of
    (``ModelConfig.latent_kind``)."""

    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    window: Optional[int]   # keys a query attends, itself among them
    q_scale: float          # on the normed query latent (1.0: none)
    kv_scale: float         # on the normed KV latent, not on the rope key

    @property
    def qk(self) -> int:
        return self.nope + self.rope

    @property
    def cached(self) -> int:
        """Values cached a token a layer: the latent and the rope key."""
        return self.kv_rank + self.rope


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    head_dim: Optional[int] = None           # default hidden_size // num_heads
    max_position_embeddings: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    attention_bias: bool = False             # Qwen2-style QKV biases
    sliding_window: Optional[int] = None     # Mistral-style windowed attention
    # Gemma-family knobs
    activation: str = "silu"                 # silu | gelu (GeGLU MLP)
    scale_embeddings: bool = False           # hidden *= sqrt(hidden_size)
    norm_offset: bool = False                # RMSNorm uses (1 + weight)
    final_logit_softcap: Optional[float] = None  # cap*tanh(logits/cap)
    # MoE (Mixtral-style sparse MLP); 0 experts = dense
    num_experts: int = 0
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True              # renormalise the kept top-k
    # OLMoE-style QK-norm: RMSNorm over the whole projected q / k width
    # (one learned vector per layer each), before the head split and RoPE
    qk_norm: bool = False
    # Qwen3-style QK-norm: RMSNorm over each head's ``head_dim`` values of q
    # and k (one learned ``head_dim`` vector a layer each), before RoPE
    qk_norm_per_head: bool = False
    # learned sparse attention (a lightning indexer on a K/V layer): the
    # indexer's ``index_num_heads`` query heads of ``index_head_dim`` score
    # every cached token against ONE index key a token (kept in a pool
    # beside the K/V pages), and a query attends the ``index_topk`` cached
    # tokens of largest score (every one while it has no more than that).
    # 0 = dense attention
    index_topk: int = 0
    index_num_heads: int = 0
    index_head_dim: int = 0
    # the indexer layer by layer (a latent-attention model's: models/mla.py):
    # ``"full"`` = the layer has an indexer and computes its queries'
    # selection, ``"shared"`` = it has none and attends the selection of the
    # last full layer before it. Empty: every layer full.
    index_types: Tuple[str, ...] = ()
    # what the index queries are projected from: the layer's normed input
    # (``"hidden"``) or its query latent ``c_q`` (``"q_latent"``: needs
    # ``q_lora_rank``); index keys and head weights read the normed input
    index_query_input: str = "hidden"
    # values of an index head (query and key) that are rotated, its first;
    # 0: all ``index_head_dim``
    index_rope_dims: int = 0
    # a latent layer's rope values (and its indexer's) rotate by adjacent
    # pairs (2i, 2i + 1), not by halves (i, i + d/2)
    rope_interleave: bool = False
    # Latent attention (MLA): the cache holds one ``kv_lora_rank`` latent and
    # ``qk_rope_head_dim`` rope values a token a layer instead of per-head K
    # and V (models/mla.py). 0 = the K/V layout. No layer of such a model
    # reads ``head_dim`` (a query/key head is ``qk_head_dim``, nope + rope,
    # its default here): it may carry that, or the value a published config
    # states (``hidden_size // num_heads``, or ``qk_nope_head_dim``), and
    # nothing else.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # per-layer description of the MLP: the first ``first_k_dense`` layers are
    # dense (width ``intermediate_size``), every later one routed experts of
    # width ``moe_intermediate_size`` beside ``n_shared_experts`` every token
    # takes
    first_k_dense: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    # the router of a latent-attention model scores by sigmoid, keeps the
    # top-k of all experts and scales the normalised weights by this
    routed_scaling_factor: float = 1.0
    # the chip's share of an expert-parallel deployment: (first, count) of the
    # ``num_experts`` routed experts this chip holds. The router keeps its
    # published width and top-k; the layer computes its own experts' part.
    held_experts: Optional[Tuple[int, int]] = None
    # RMSNorm after each sub-block as well as before it (four a layer)
    sandwich_norm: bool = False
    # a hybrid of linear and latent attention: the 1-based layers whose
    # attention is latent (MLA), as published; every other layer is a gated
    # delta-rule (KDA) layer of ``kda_num_heads`` heads of ``kda_head_dim``
    # behind a causal depthwise convolution of ``kda_conv_kernel`` taps,
    # whose past is a fixed-size state a sequence (models/kda.py). Empty:
    # every layer is latent.
    full_attn_layers: Tuple[int, ...] = ()
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 0
    # the latent layers rotate nothing: the "rope" dims of q and the shared
    # key are used as they are
    mla_use_nope: bool = False
    # the router picks the top-k of ``score + bias`` (one learned value an
    # expert); the weights are the scores', the bias is not in them
    router_selection_bias: bool = False
    # how the router scores its logits: ``softmax`` over all experts (the
    # K/V recipe's default) or ``sigmoid`` an expert (the latent recipe's)
    router_scoring: Optional[str] = None
    # attention described per layer (the K/V recipe): ``"full"`` or
    # ``"sliding"`` for each layer. A sliding layer attends the last
    # ``sliding_window`` keys; where the two kinds are mixed it has
    # ``sliding_num_heads`` query heads (0: ``num_heads``) over the same
    # ``num_kv_heads`` and rotates a whole head at ``sliding_rope_theta``
    # (0: ``rope_theta``), and its K/V pages lie in a pool of their own
    # (``cache_kinds``). Empty: every layer alike, sliding where
    # ``sliding_window`` is set -- the one-kind case of the same code.
    layer_types: Tuple[str, ...] = ()
    sliding_num_heads: int = 0
    sliding_rope_theta: float = 0.0
    # a full layer (every layer of a model that is not mixed) rotates the
    # first ``partial_rotary_factor`` of a head's values, at frequencies
    # blended by YaRN where ``rope_yarn`` = (factor, original positions,
    # beta_fast, beta_slow, attention_factor) and cos / sin scaled by the
    # last
    partial_rotary_factor: float = 1.0
    rope_yarn: Optional[Tuple[float, int, float, float, float]] = None
    # one learned scalar a head a token, ``sigmoid(x W_g)``, multiplied
    # into the head's attention output before ``W_o``
    head_gate: bool = False
    # latent layers described per kind (``layer_types`` over latent pages:
    # models/mla.py): a sliding layer's own latent ranks and head sizes (0:
    # the full kind's), beside ``sliding_num_heads`` and
    # ``sliding_rope_theta``. Its pages lie in a latent pool of their own,
    # as wide as ITS cached row
    sliding_kv_lora_rank: int = 0
    sliding_q_lora_rank: int = 0
    sliding_qk_nope_head_dim: int = 0
    sliding_qk_rope_head_dim: int = 0
    sliding_v_head_dim: int = 0
    # a constant on a latent layer's two normed latents:
    # ``sqrt(hidden_size / q_lora_rank)`` on ``c_q``, ``sqrt(hidden_size /
    # kv_lora_rank)`` on ``c_kv`` (a layer's own ranks), not on the rope key
    mla_lora_rescale: bool = False
    # a state-space (Mamba-2 / SSD) mixer BESIDE attention in every layer of
    # a K/V model: both read the layer's one normed input and their scaled
    # outputs are summed into the residual (models/ssd.py). ``ssm_num_heads``
    # heads of ``ssm_head_dim`` over a float32 state of ``ssm_head_dim x
    # ssm_state_size`` a head, ``B`` / ``C`` shared by the heads of each of
    # ``ssm_num_groups`` groups, behind a causal depthwise convolution of
    # ``ssm_conv_kernel`` taps; the chunked form cuts a segment every
    # ``ssm_chunk_size`` tokens. What a sequence carries is a row of a state
    # pool beside its K/V pages. 0 heads: no mixer.
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_num_groups: int = 0
    ssm_conv_kernel: int = 0
    ssm_chunk_size: int = 0
    # muP multipliers, as published (the K/V recipe's): on the embedding's
    # rows, the logits, the keys, attention's input and output, the mixer's
    # input and output, the MLP's gate and its output, and the mixer's
    # projected ``z, x, B, C, dt`` in that order
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    ssm_multipliers: Tuple[float, float, float, float, float] = (1.0,) * 5
    dtype: str = "bfloat16"

    def __post_init__(self) -> None:
        if self.kv_lora_rank and self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.qk_nope_head_dim + self.qk_rope_head_dim
            )
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.num_heads)
        if self.num_heads % self.num_kv_heads != 0:
            raise ValueError("num_heads must be divisible by num_kv_heads (GQA)")
        if self.activation not in ("silu", "gelu"):
            raise ValueError(
                f"unknown activation {self.activation!r}; use 'silu' or 'gelu'"
            )
        if self.num_experts and self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")
        if self.qk_norm and self.qk_norm_per_head:
            raise ValueError(
                f"{self.name}: qk_norm (whole width) and qk_norm_per_head "
                "are two conventions; a layer has one")
        index = (self.index_topk, self.index_num_heads, self.index_head_dim)
        if any(index):
            if not all(index) or self.index_head_dim % 2:
                raise ValueError(
                    f"{self.name}: an indexer needs index_topk, "
                    "index_num_heads and an even index_head_dim")
            if self.sliding_window is not None and not (
                    self.kv_lora_rank and self.mixed_attention):
                # (latent pages per kind: the indexer is the FULL layers',
                # whose pages and index keys are never released)
                raise ValueError(
                    f"{self.name}: an indexer with sliding_window is not "
                    "built: pages that left the window are released, the "
                    "selection reads every cached token")
        self._check_index_layers()
        if self.router_scoring is None:
            object.__setattr__(
                self, "router_scoring",
                "sigmoid" if self.kv_lora_rank else "softmax")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"{self.name}: router_scoring {self.router_scoring!r}; use "
                "'softmax' or 'sigmoid'")
        self._check_layer_types()
        self._check_state_space()
        if not self.kv_lora_rank:
            # read by models/mla.py alone: models/llama.py would drop them
            # without a word (two norms a layer, pages in every layer, a
            # router with no bias)
            only_latent = [name for name, default in (
                ("sandwich_norm", False), ("full_attn_layers", ()),
                ("kda_num_heads", 0), ("kda_head_dim", 0),
                ("kda_conv_kernel", 0), ("mla_use_nope", False),
                ("router_selection_bias", False),
            ) if getattr(self, name) != default]
            if only_latent:
                raise ValueError(
                    f"{self.name}: {', '.join(only_latent)} without "
                    "kv_lora_rank: only the latent-attention model "
                    "(models/mla.py) reads them")
            if self.described_per_layer:
                # the per-layer description draws and reads its leaves by
                # ``models/llama.leaf_specs``, which has none of these
                unread = [name for name in (
                    "attention_bias", "qk_norm", "norm_offset",
                    "index_topk") if getattr(self, name)]
                if unread:
                    raise ValueError(
                        f"{self.name}: {', '.join(unread)} on a model "
                        "described per layer (layer_types, first_k_dense, "
                        "n_shared_experts, held_experts, head_gate): its "
                        "leaf specs have no such leaf")
            if (self.first_k_dense or self.n_shared_experts
                    or self.held_experts is not None
                    or self.routed_scaling_factor != 1.0) \
                    and not self.num_experts:
                raise ValueError(
                    f"{self.name}: first_k_dense, n_shared_experts, "
                    "held_experts or routed_scaling_factor without "
                    "num_experts: no expert layer would read them")
        elif self.head_dim not in (self.qk_head_dim,
                                   self.hidden_size // self.num_heads,
                                   self.qk_nope_head_dim):
            raise ValueError(
                f"{self.name}: head_dim {self.head_dim} on a latent-attention "
                f"model, whose query/key head is {self.qk_head_dim} wide "
                "(qk_nope_head_dim + qk_rope_head_dim): no layer reads it, "
                "so it carries that, or the value a published config states "
                "under the key: hidden_size // num_heads, or "
                "qk_nope_head_dim")
        kda = (self.kda_num_heads, self.kda_head_dim, self.kda_conv_kernel)
        if self.full_attn_layers:
            if not all(kda) or self.kda_conv_kernel < 2:
                raise ValueError(
                    f"{self.name}: full_attn_layers leaves linear-attention "
                    "layers, which need kda_num_heads, kda_head_dim and "
                    "kda_conv_kernel (>= 2)")
            at = tuple(self.full_attn_layers)
            if list(at) != sorted(set(at)) or at[0] < 1 \
                    or at[-1] > self.num_layers:
                raise ValueError(
                    f"{self.name}: full_attn_layers {at} is not a rising "
                    f"list of layers 1..{self.num_layers}")
        elif any(kda):
            raise ValueError(
                f"{self.name}: kda_* sizes without full_attn_layers: no "
                "layer would read them")
        if self.router_selection_bias and not self.num_experts:
            raise ValueError(
                f"{self.name}: router_selection_bias without experts")
        if self.sandwich_norm and self.full_attn_layers:
            raise ValueError(
                f"{self.name}: sandwich norms around a linear-attention "
                "layer are not built")
        if self.held_experts is not None:
            first, count = self.held_experts
            if first < 0 or count < 1 or first + count > self.num_experts:
                raise ValueError(
                    f"held_experts {self.held_experts} outside the "
                    f"{self.num_experts} routed experts"
                )

    def _check_index_layers(self) -> None:
        """What the indexer's description must state, and which of its
        fields only the latent-attention model reads: refused here, when
        the configuration is made, each with its reason."""
        latent_only = [name for name, default in (
            ("index_types", ()), ("index_query_input", "hidden"),
            ("index_rope_dims", 0), ("rope_interleave", False),
        ) if getattr(self, name) != default]
        if latent_only and not self.kv_lora_rank:
            raise ValueError(
                f"{self.name}: {', '.join(latent_only)} without "
                "kv_lora_rank: only the latent-attention model "
                "(models/mla.py) reads them")
        if not self.kv_lora_rank:
            return
        kv_only = [name for name in (
            "qk_norm", "qk_norm_per_head", "attention_bias")
            if getattr(self, name)]
        if kv_only:
            raise ValueError(
                f"{self.name}: {', '.join(kv_only)} over latent pages "
                "(kv_lora_rank): the K/V recipe's (models/llama.py), which "
                "no latent layer reads")
        indexed = [name for name in (
            "index_types", "index_rope_dims") if getattr(self, name)] + (
            ["index_query_input"] if self.index_query_input != "hidden"
            else [])
        if indexed and not self.index_topk:
            raise ValueError(
                f"{self.name}: {', '.join(indexed)} without index_topk: no "
                "indexer would read them")
        if not self.index_topk:
            return
        if self.full_attn_layers:
            raise ValueError(
                f"{self.name}: an indexer on a hybrid of linear and latent "
                "attention (full_attn_layers) is not built: the selection "
                "is carried from latent layer to latent layer")
        if self.layer_types and self.index_types:
            raise ValueError(
                f"{self.name}: index_types beside layer_types over latent "
                "pages is not built: every full layer holds the indexer, a "
                "sliding layer none, and no layer borrows a selection")
        if self.index_query_input not in ("hidden", "q_latent") or (
                self.index_query_input == "q_latent"
                and not self.q_lora_rank):
            raise ValueError(
                f"{self.name}: index_query_input "
                f"{self.index_query_input!r}; use 'hidden', or 'q_latent' "
                "with q_lora_rank")
        if self.index_rope_dims % 2 or \
                self.index_rope_dims > self.index_head_dim:
            raise ValueError(
                f"{self.name}: index_rope_dims {self.index_rope_dims} is not "
                f"an even part of index_head_dim {self.index_head_dim}")
        kinds = self.index_types
        if kinds and (len(kinds) != self.num_layers
                      or set(kinds) - {"full", "shared"}
                      or kinds[0] != "full"):
            raise ValueError(
                f"{self.name}: index_types names 'full' or 'shared' for "
                f"each of the {self.num_layers} layers, the first of them "
                "'full' (a shared layer borrows the selection of the full "
                "layer before it)")

    def _check_state_space(self) -> None:
        """What a state-space mixer beside attention must state, what only
        it reads, and what is not built with it: refused here, when the
        configuration is made, each with its reason."""
        ssm = (self.ssm_num_heads, self.ssm_head_dim, self.ssm_state_size,
               self.ssm_num_groups, self.ssm_conv_kernel,
               self.ssm_chunk_size)
        mixer_only = [name for name, default in (
            ("ssm_in_multiplier", 1.0), ("ssm_out_multiplier", 1.0),
            ("ssm_multipliers", (1.0,) * 5),
        ) if getattr(self, name) != default]
        multipliers = mixer_only + [name for name, default in (
            ("embedding_multiplier", 1.0), ("lm_head_multiplier", 1.0),
            ("key_multiplier", 1.0), ("attention_in_multiplier", 1.0),
            ("attention_out_multiplier", 1.0),
            ("mlp_multipliers", (1.0, 1.0)),
        ) if getattr(self, name) != default]
        if len(self.mlp_multipliers) != 2 or len(self.ssm_multipliers) != 5:
            raise ValueError(
                f"{self.name}: mlp_multipliers is (gate, output) and "
                "ssm_multipliers (z, x, B, C, dt)")
        if multipliers and self.kv_lora_rank:
            raise ValueError(
                f"{self.name}: {', '.join(multipliers)} over latent pages "
                "(kv_lora_rank): the K/V recipe's (models/llama.py), which "
                "no latent layer reads")
        if not any(ssm):
            if mixer_only:
                raise ValueError(
                    f"{self.name}: {', '.join(mixer_only)} without a "
                    "mixer (ssm_num_heads): no layer would read them")
            return
        if not all(ssm) or self.ssm_conv_kernel < 2 \
                or self.ssm_num_heads % self.ssm_num_groups:
            raise ValueError(
                f"{self.name}: a state-space mixer needs ssm_num_heads, "
                "ssm_head_dim, ssm_state_size, ssm_num_groups (a divisor "
                "of the heads), ssm_conv_kernel (>= 2) and ssm_chunk_size")
        unbuilt = [name for name, on in (
            ("kv_lora_rank", self.kv_lora_rank),
            ("layer_types", self.layer_types),
            ("sliding_window", self.sliding_window is not None),
            ("num_experts", self.num_experts),
            ("index_topk", self.index_topk),
            ("attention_bias", self.attention_bias),
            ("qk_norm", self.qk_norm or self.qk_norm_per_head),
            ("norm_offset", self.norm_offset),
            ("head_gate", self.head_gate),
        ) if on]
        if unbuilt:
            raise ValueError(
                f"{self.name}: a state-space mixer beside attention with "
                f"{', '.join(unbuilt)} is not built: the layer is two token "
                "mixers on one normed input over plain K/V pages of one "
                "kind and a dense MLP (models/llama.py leaf_specs)")

    def _check_layer_types(self) -> None:
        """What a per-layer description of attention must state, and what a
        model of mixed kinds cannot do yet: refused here, when the
        configuration is made, each with its reason."""
        kinds = self.layer_types
        sliding_only = [name for name, default in (
            ("sliding_num_heads", 0), ("sliding_rope_theta", 0.0),
        ) if getattr(self, name) != default]
        if not kinds:
            if sliding_only:
                raise ValueError(
                    f"{self.name}: {', '.join(sliding_only)} without "
                    "layer_types of two kinds: no layer would read them")
        else:
            if len(kinds) != self.num_layers or \
                    set(kinds) - {"full", "sliding"}:
                raise ValueError(
                    f"{self.name}: layer_types names 'full' or 'sliding' "
                    f"for each of the {self.num_layers} layers")
            if "sliding" in kinds and self.sliding_window is None:
                raise ValueError(
                    f"{self.name}: a sliding layer needs sliding_window")
            if len(set(kinds)) < 2 and sliding_only:
                raise ValueError(
                    f"{self.name}: {', '.join(sliding_only)} on a model of "
                    "one attention kind: no layer would read them")
        self._check_latent_kinds()
        if not self.kv_lora_rank and \
                self.sliding_num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.name}: sliding_num_heads must be divisible by "
                "num_kv_heads (K/V heads that differ by kind are not "
                "built: both pools have num_kv_heads)")
        if self.mixed_attention and self.index_topk \
                and not self.kv_lora_rank:
            raise ValueError(
                f"{self.name}: an indexer on a K/V model of mixed attention "
                "kinds is not built: the index-key pool follows one block "
                "table, and pages that left a window are released (the "
                "latent recipe, models/mla.py, keeps the index keys under "
                "the full kind's table)")
        if not 0.0 < self.partial_rotary_factor <= 1.0 or \
                int(self.head_dim * self.partial_rotary_factor) % 2:
            raise ValueError(
                f"{self.name}: partial_rotary_factor "
                f"{self.partial_rotary_factor} must leave an even, "
                "non-empty rotated width")
        if self.rope_yarn is not None and (
                len(self.rope_yarn) != 5 or self.rope_yarn[0] < 1.0):
            raise ValueError(
                f"{self.name}: rope_yarn is (factor >= 1, original "
                "positions, beta_fast, beta_slow, attention_factor)")
        if self.kv_lora_rank and (
                self.partial_rotary_factor != 1.0 or self.rope_yarn):
            raise ValueError(
                f"{self.name}: partial_rotary_factor and rope_yarn are the "
                "K/V recipe's (models/llama.py); the latent layers would "
                "not read them")

    def _check_latent_kinds(self) -> None:
        """Latent layers described per kind (``layer_types`` over latent
        pages), the latent rescale and the gate on latent attention: what
        only they read, and what is not built with them."""
        swa = [name for name in (
            "sliding_kv_lora_rank", "sliding_q_lora_rank",
            "sliding_qk_nope_head_dim", "sliding_qk_rope_head_dim",
            "sliding_v_head_dim") if getattr(self, name)]
        if swa and not (self.kv_lora_rank and self.mixed_attention):
            raise ValueError(
                f"{self.name}: {', '.join(swa)} without latent layers of "
                "two kinds (kv_lora_rank and layer_types): no layer would "
                "read them")
        if self.mla_lora_rescale and not (
                self.kv_lora_rank and self.q_lora_rank):
            raise ValueError(
                f"{self.name}: mla_lora_rescale scales the two normed "
                "latents: it needs kv_lora_rank and q_lora_rank")
        if not (self.kv_lora_rank and self.layer_types):
            return
        if not self.mixed_attention:
            raise ValueError(
                f"{self.name}: layer_types of one kind over latent pages is "
                "not built: leave it out (every layer full), a model of "
                "sliding latent layers alone has no pool")
        unbuilt = [name for name, on in (
            ("full_attn_layers", self.full_attn_layers),
            ("mla_use_nope", self.mla_use_nope),
            ("sandwich_norm", self.sandwich_norm),
        ) if on]
        if unbuilt:
            raise ValueError(
                f"{self.name}: {', '.join(unbuilt)} beside layer_types over "
                "latent pages is not built: the parameter stacks split by "
                "attention kind, MLP and indexer alone (models/mla.py)")
        if bool(self.q_lora_rank) != bool(
                self.latent_kind("sliding").q_rank):
            raise ValueError(
                f"{self.name}: a query low-rank on one attention kind and "
                "not on the other is not built")

    @property
    def latent_kv(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def attn_kinds(self) -> Tuple[str, ...]:
        """``"full"`` or ``"sliding"`` for each layer of a K/V model."""
        if self.layer_types:
            return tuple(self.layer_types)
        kind = "full" if self.sliding_window is None else "sliding"
        return (kind,) * self.num_layers

    @property
    def mixed_attention(self) -> bool:
        """Layers of both attention kinds: pages per kind."""
        return len(set(self.layer_types)) > 1

    @property
    def cache_kinds(self) -> Tuple[Tuple[str, int, Optional[int]], ...]:
        """(kind, layers, window) of each K/V pool, the full kind first. A
        model of one kind has one pool, with its window if it has one."""
        kinds = self.attn_kinds
        return tuple(
            (kind, kinds.count(kind),
             self.sliding_window if kind == "sliding" else None)
            for kind in ("full", "sliding") if kind in kinds)

    def heads_of(self, kind: str) -> int:
        """Query heads of a layer of ``kind``."""
        if kind == "sliding" and self.mixed_attention \
                and self.sliding_num_heads:
            return self.sliding_num_heads
        return self.num_heads

    def rope_of(self, kind: str) -> Tuple[
            float, int, Optional[Tuple[float, int, float, float, float]]]:
        """(theta, rotated width, YaRN or None) of a layer of ``kind``."""
        if kind == "sliding" and self.mixed_attention:
            return (self.sliding_rope_theta or self.rope_theta,
                    self.head_dim, None)
        return (self.rope_theta,
                int(self.head_dim * self.partial_rotary_factor),
                self.rope_yarn)

    def latent_kind(self, kind: str = "full") -> LatentKind:
        """Sizes, rotation, window and latent rescale of a latent layer
        of ``kind`` (a model of one kind: ``"full"``)."""
        sliding = kind == "sliding" and self.mixed_attention

        def own(name):
            """The sliding kind's own value where it states one."""
            return (getattr(self, "sliding_" + name) if sliding else 0) \
                or getattr(self, name)

        q_rank, kv_rank = own("q_lora_rank"), own("kv_lora_rank")
        rescale = self.mla_lora_rescale
        return LatentKind(
            heads=self.heads_of(kind), q_rank=q_rank, kv_rank=kv_rank,
            nope=own("qk_nope_head_dim"), rope=own("qk_rope_head_dim"),
            v=own("v_head_dim"), theta=own("rope_theta"),
            window=self.sliding_window if kind == "sliding" else None,
            q_scale=(self.hidden_size / q_rank) ** 0.5
            if rescale and q_rank else 1.0,
            kv_scale=(self.hidden_size / kv_rank) ** 0.5 if rescale else 1.0,
        )

    @property
    def described_per_layer(self) -> bool:
        """A K/V model whose layers are described one by one
        (``models/llama.group_of`` / ``leaf_specs``): parameter stacks a
        group, leaves drawn by name."""
        return not self.kv_lora_rank and bool(
            self.layer_types or self.first_k_dense or self.n_shared_experts
            or self.held_experts is not None or self.head_gate
            or self.ssm_num_heads)

    @property
    def index_kinds(self) -> Tuple[str, ...]:
        """``"full"`` or ``"shared"`` for each layer of a model with an
        indexer (empty without one)."""
        if not self.index_topk:
            return ()
        if self.kv_lora_rank and self.layer_types:
            # latent layers per kind: the full layers hold the indexer, a
            # sliding layer none (and borrows none)
            return tuple("full" if kind == "full" else "none"
                         for kind in self.layer_types)
        return tuple(self.index_types) or ("full",) * self.num_layers

    @property
    def num_index_layers(self) -> int:
        """Layers that hold an indexer: the index-key pool's layer axis."""
        return self.index_kinds.count("full")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """``"kda"`` or ``"mla"`` for each layer of a latent-attention
        model, in layer order."""
        if not self.full_attn_layers:
            return ("mla",) * self.num_layers
        full = set(self.full_attn_layers)
        return tuple("mla" if li + 1 in full else "kda"
                     for li in range(self.num_layers))

    @property
    def num_kda_layers(self) -> int:
        """Layers whose past is a state row, not pages."""
        return self.layer_kinds.count("kda") if self.full_attn_layers else 0

    @property
    def num_state_layers(self) -> int:
        """Layers that carry a row of a state pool: the linear-attention
        layers of a hybrid of latent attention, every layer of a model with
        a state-space mixer."""
        return self.num_layers if self.ssm_num_heads else self.num_kda_layers

    @property
    def ssm_inner(self) -> int:
        """The mixer's width ``d_ssm``: heads x head size."""
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels under the mixer's convolution: ``x | B | C``."""
        return self.ssm_inner + 2 * self.ssm_num_groups * self.ssm_state_size

    @property
    def num_cache_layers(self) -> int:
        """Layers that write pages of the (full kind's) latent pool: its
        layer axis."""
        return self.num_layers - self.num_kda_layers \
            - self.num_window_layers

    @property
    def num_window_layers(self) -> int:
        """Sliding latent layers: the window kind's latent pool's layer
        axis (0 for a model of one kind, and for the K/V recipe)."""
        if not (self.kv_lora_rank and self.mixed_attention):
            return 0
        return self.layer_types.count("sliding")

    @property
    def qk_head_dim(self) -> int:
        """Width of a latent layer's query / key head, nope + rope (where
        ``head_dim`` carries a published value no layer reads)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def state_bytes_per_row(self, conv_bytes: int = 2) -> int:
        """Bytes of one sequence's state over all its state layers: a
        float32 matrix a head (``head_dim x head_dim`` of a KDA layer,
        ``head_dim x state_size`` of a mixer) and the convolution's tail."""
        if self.ssm_num_heads:
            return self.num_layers * (
                4 * self.ssm_inner * self.ssm_state_size
                + (self.ssm_conv_kernel - 1) * self.ssm_conv_dim * conv_bytes)
        p = self.kda_num_heads * self.kda_head_dim
        return self.num_kda_layers * (
            4 * p * self.kda_head_dim
            + max(self.kda_conv_kernel - 1, 0) * 3 * p * conv_bytes)

    @property
    def num_held_experts(self) -> int:
        """Routed experts whose weights this chip stores."""
        return self.held_experts[1] if self.held_experts else self.num_experts

    @property
    def mlp_width(self) -> int:
        """Width of the K/V recipe's MLP, or of ONE of its experts: a
        published config that names the experts' width apart
        (``moe_intermediate_size`` beside an ``intermediate_size`` no layer
        of a wholly routed model reads) is taken at its word."""
        if self.num_experts and self.moe_intermediate_size:
            return self.moe_intermediate_size
        return self.intermediate_size

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def num_params(self) -> int:
        """Approximate parameter count (embeddings + layers + head)."""
        if self.latent_kv:
            head = 0 if self.tie_word_embeddings else self.vocab_size
            return (self.vocab_size + head) * self.hidden_size + sum(
                self.layer_params(li) for li in range(self.num_layers)
            ) + self.hidden_size
        if self.described_per_layer:
            head = 0 if self.tie_word_embeddings else self.vocab_size
            return (self.vocab_size + head) * self.hidden_size + sum(
                self.kv_layer_params(li) for li in range(self.num_layers)
            ) + self.hidden_size
        h, i, v = self.hidden_size, self.mlp_width, self.vocab_size
        d = self.head_dim
        attn = h * (self.num_heads * d) + 2 * h * (self.num_kv_heads * d) + (
            self.num_heads * d
        ) * h
        if self.num_experts:
            mlp = self.num_experts * 3 * h * i + h * self.num_experts
        else:
            mlp = 3 * h * i
        norms = 2 * h
        if self.qk_norm:
            norms += (self.num_heads + self.num_kv_heads) * d
        if self.qk_norm_per_head:
            norms += 2 * d
        per_layer = attn + mlp + norms + self.index_params
        emb = v * h
        head = 0 if self.tie_word_embeddings else v * h
        return emb + self.num_layers * per_layer + head + h

    @property
    def index_params(self) -> int:
        """A layer's indexer: query, key and head-weight projections and the
        key's LayerNorm (weight and bias)."""
        if not self.index_topk:
            return 0
        di = self.index_head_dim
        q_in = self.q_lora_rank if self.index_query_input == "q_latent" \
            else self.hidden_size
        return q_in * self.index_num_heads * di + self.hidden_size * (
            di + self.index_num_heads) + 2 * di

    def param_bytes(self, dtype_bytes: int = 2) -> int:
        return self.num_params * dtype_bytes

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        if self.latent_kv:
            # (a sliding latent layer's tokens stay a window long: they are
            # the window pool's, not a token's share of the full pool)
            return (self.num_cache_layers * (
                self.kv_lora_rank + self.qk_rope_head_dim)
                + self.num_index_layers * self.index_head_dim) * dtype_bytes
        return self.num_layers * (
            2 * self.num_kv_heads * self.head_dim
            + (self.index_head_dim if self.index_topk else 0)) * dtype_bytes

    def kv_layer_params(self, layer: int) -> int:
        """Parameters layer ``layer`` of a K/V model described per layer
        stores here (the held experts only)."""
        h, d = self.hidden_size, self.head_dim
        nh = self.heads_of(self.attn_kinds[layer])
        attn = 2 * h * nh * d + 2 * h * self.num_kv_heads * d \
            + (h * nh if self.head_gate else 0)
        norms = 2 * h + (2 * d if self.qk_norm_per_head else 0)
        if self.ssm_num_heads:
            # in and out projections, the convolution and its bias, A_log,
            # D and dt_bias a head, the gated norm
            p, c, sh = self.ssm_inner, self.ssm_conv_dim, self.ssm_num_heads
            attn += h * (p + c + sh) + p * h \
                + c * (self.ssm_conv_kernel + 1) + 3 * sh
            norms += p
        if layer < self.first_k_dense or not self.num_experts:
            mlp = 3 * h * self.intermediate_size
        else:
            mlp = h * self.num_experts + 3 * h * self.mlp_width * (
                self.num_held_experts + self.n_shared_experts)
        return attn + norms + mlp

    def layer_params(self, layer: int) -> int:
        """Parameters layer ``layer`` of a latent-attention model stores
        here (the held experts only)."""
        h, nh = self.hidden_size, self.num_heads
        if self.layer_kinds[layer] == "kda":
            kh, kd = self.kda_num_heads, self.kda_head_dim
            p = kh * kd
            # q, k, v and o; the two low-rank gates; the write strength;
            # the convolution; A_log and dt_bias
            attn = 4 * h * p + 2 * (h * kd + kd * p) + h * kh \
                + 3 * p * self.kda_conv_kernel + kh + p
            norms = 2 * h + kd
        else:
            k = self.latent_kind(
                self.layer_types[layer] if self.layer_types else "full")
            nh = k.heads
            q = (h * k.q_rank + k.q_rank * nh * k.qk
                 if k.q_rank else h * nh * k.qk)
            attn = (
                q + h * k.cached + k.kv_rank * nh * (k.nope + k.v)
                + nh * k.v * h + (h * nh if self.head_gate else 0)
            )
            norms = (4 if self.sandwich_norm else 2) * h \
                + k.q_rank + k.kv_rank
            if self.index_kinds and self.index_kinds[layer] == "full":
                attn += self.index_params
        if layer < self.first_k_dense or not self.num_experts:
            mlp = 3 * h * self.intermediate_size
        else:
            mlp = h * self.num_experts + 3 * h * self.moe_intermediate_size * (
                self.num_held_experts + self.n_shared_experts) + (
                self.num_experts if self.router_selection_bias else 0)
        return attn + norms + mlp

    def layer_param_bytes(self, dtype_bytes: int = 2) -> int:
        """Per-layer weight bytes — the shard planner's unit of placement."""
        if self.latent_kv:
            return self.layer_params(self.num_layers - 1) * dtype_bytes
        if self.described_per_layer:
            return self.kv_layer_params(self.num_layers - 1) * dtype_bytes
        h, i, d = self.hidden_size, self.mlp_width, self.head_dim
        attn = h * (self.num_heads * d) + 2 * h * (self.num_kv_heads * d) + (
            self.num_heads * d
        ) * h
        if self.num_experts:
            mlp = self.num_experts * 3 * h * i + h * self.num_experts
        else:
            mlp = 3 * h * i
        return (attn + mlp + 2 * h + self.index_params) * dtype_bytes


def _llama(name: str, **kw) -> ModelConfig:
    return ModelConfig(name=name, **kw)


MODEL_REGISTRY: Dict[str, ModelConfig] = {
    # test-scale
    "llama3-tiny": _llama(
        "llama3-tiny", vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        max_position_embeddings=1024, rope_theta=10000.0,
    ),
    "llama3-mini": _llama(  # CI-scale but realistic ratios
        "llama3-mini", vocab_size=2048, hidden_size=256, num_layers=4,
        num_heads=8, num_kv_heads=4, intermediate_size=640,
        max_position_embeddings=2048,
    ),
    # Llama 3.2 1B geometry — fits single v5e chip in bf16 with room for KV
    "llama3-1b": _llama(
        "llama3-1b", vocab_size=128256, hidden_size=2048, num_layers=16,
        num_heads=32, num_kv_heads=8, intermediate_size=8192,
        head_dim=64, tie_word_embeddings=True,
        max_position_embeddings=131072,
    ),
    # Llama 3.2 3B geometry
    "llama3-3b": _llama(
        "llama3-3b", vocab_size=128256, hidden_size=3072, num_layers=28,
        num_heads=24, num_kv_heads=8, intermediate_size=8192,
        head_dim=128, tie_word_embeddings=True,
        max_position_embeddings=131072,
    ),
    # Llama 3 8B geometry (BASELINE.json config 1-3)
    "llama3-8b": _llama(
        "llama3-8b", vocab_size=128256, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, intermediate_size=14336,
        max_position_embeddings=8192,
    ),
    # Llama 3 70B geometry (BASELINE.json config 4-5)
    "llama3-70b": _llama(
        "llama3-70b", vocab_size=128256, hidden_size=8192, num_layers=80,
        num_heads=64, num_kv_heads=8, intermediate_size=28672,
        max_position_embeddings=8192,
    ),
    # Qwen2.5 family (the reference's single-worker benchmark default is
    # Qwen2.5-7B, benchmarks/single_worker.py:446) — same decoder recipe
    # with QKV biases and 1e6 rope theta
    "qwen2.5-tiny": _llama(  # test-scale
        "qwen2.5-tiny", vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        max_position_embeddings=1024, rope_theta=10000.0,
        attention_bias=True, tie_word_embeddings=True,
    ),
    "qwen2.5-0.5b": _llama(
        "qwen2.5-0.5b", vocab_size=151936, hidden_size=896, num_layers=24,
        num_heads=14, num_kv_heads=2, intermediate_size=4864,
        max_position_embeddings=32768, rope_theta=1000000.0,
        rms_norm_eps=1e-6, attention_bias=True, tie_word_embeddings=True,
    ),
    "qwen2.5-7b": _llama(
        "qwen2.5-7b", vocab_size=152064, hidden_size=3584, num_layers=28,
        num_heads=28, num_kv_heads=4, intermediate_size=18944,
        max_position_embeddings=32768, rope_theta=1000000.0,
        rms_norm_eps=1e-6, attention_bias=True,
    ),
    # Mistral family — Llama decoder recipe + sliding-window attention.
    # The reference serves Mistral through vLLM/SGLang model auto-detection
    # (worker/engines/llm_vllm.py:42 introspects the HF config); here the
    # window is first-class in the paged attention mask (ops/attention.py).
    "mistral-tiny": _llama(  # test-scale; window smaller than the test
        "mistral-tiny", vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        max_position_embeddings=1024, rope_theta=10000.0,
        rms_norm_eps=1e-5, sliding_window=8,
    ),
    "mistral-7b": _llama(  # v0.1 geometry: 4096-token sliding window
        "mistral-7b", vocab_size=32000, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, intermediate_size=14336,
        max_position_embeddings=32768, rope_theta=10000.0,
        rms_norm_eps=1e-5, sliding_window=4096,
    ),
    # Gemma family — GeGLU MLP, sqrt(H)-scaled embeddings, (1+w) RMSNorm,
    # tied embeddings, 256-dim heads. Served by the reference through
    # vLLM/SGLang auto-detection; first-class decoder variant here.
    "gemma-tiny": _llama(  # test-scale
        "gemma-tiny", vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, intermediate_size=256,
        max_position_embeddings=1024, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=True, activation="gelu", scale_embeddings=True,
        norm_offset=True, final_logit_softcap=30.0,
    ),
    "gemma-2b": _llama(  # MQA: one KV head
        "gemma-2b", vocab_size=256000, hidden_size=2048, num_layers=18,
        num_heads=8, num_kv_heads=1, intermediate_size=16384, head_dim=256,
        max_position_embeddings=8192, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=True, activation="gelu", scale_embeddings=True,
        norm_offset=True,
    ),
    "gemma-7b": _llama(
        "gemma-7b", vocab_size=256000, hidden_size=3072, num_layers=28,
        num_heads=16, num_kv_heads=16, intermediate_size=24576, head_dim=256,
        max_position_embeddings=8192, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=True, activation="gelu", scale_embeddings=True,
        norm_offset=True,
    ),
    # Mixtral family — sparse MoE MLP (top-2 of E experts). The reference's
    # scope lists EP as absent/optional (SURVEY §2.2); on TPU the expert
    # axis shards over the mesh's ``model`` axis, so this is the EP design
    # the reference never had.
    "mixtral-tiny": _llama(  # test-scale: 4 experts, top-2
        "mixtral-tiny", vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        max_position_embeddings=1024, rope_theta=10000.0,
        num_experts=4, num_experts_per_tok=2,
    ),
    "mixtral-8x7b": _llama(
        "mixtral-8x7b", vocab_size=32000, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, intermediate_size=14336,
        max_position_embeddings=32768, rope_theta=1000000.0,
        rms_norm_eps=1e-5, num_experts=8, num_experts_per_tok=2,
    ),
    # OLMoE — many small experts (top-8 of 64, the kept probabilities NOT
    # renormalised) and QK-norm; ``intermediate_size`` is the width of one
    # expert. On one chip the expert layer is routed: it computes and
    # reads only the experts the router chose (models/llama.py _moe_mlp).
    "olmoe-tiny": _llama(  # test-scale: 8 experts, top-4
        "olmoe-tiny", vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, intermediate_size=32,
        max_position_embeddings=1024, rope_theta=10000.0,
        num_experts=8, num_experts_per_tok=4, norm_topk_prob=False,
        qk_norm=True,
    ),
    "olmoe-1b-7b": _llama(  # OLMoE-1B-7B-0125-Instruct: 6.92 B, 1.3 B active
        "olmoe-1b-7b", vocab_size=50304, hidden_size=2048, num_layers=16,
        num_heads=16, num_kv_heads=16, intermediate_size=1024,
        max_position_embeddings=4096, rope_theta=10000.0,
        rms_norm_eps=1e-5, num_experts=64, num_experts_per_tok=8,
        norm_topk_prob=False, qk_norm=True,
    ),
    # openPangu-Ultra-MoE — latent attention (MLA: the cache holds a 512-wide
    # latent and 64 rope values a token a layer), leading dense layers, then
    # routed experts (sigmoid scores, top-8 normalised and scaled) beside a
    # shared expert, four norms a layer (models/mla.py). One multi-token-
    # prediction layer is published and not loaded.
    "keye-vl-tiny": _llama(  # test-scale: contexts of 9+ tokens select
        "keye-vl-tiny", vocab_size=512, hidden_size=64, num_layers=3,
        num_heads=4, num_kv_heads=2, intermediate_size=96,
        moe_intermediate_size=32, head_dim=16,
        max_position_embeddings=1024, rope_theta=10000.0, rms_norm_eps=1e-6,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
        qk_norm_per_head=True, index_topk=8, index_num_heads=2,
        index_head_dim=16,
    ),
    # Keye-VL-2.0-30B-A3B's language model (the tower is not loaded): 8 of
    # its 48 layers, one stage of a six-stage pipeline, each layer whole
    "keye-vl-2.0-30b-a3b-8l": _llama(
        "keye-vl-2.0-30b-a3b-8l", vocab_size=151936, hidden_size=2048,
        num_layers=8, num_heads=32, num_kv_heads=4, intermediate_size=6144,
        moe_intermediate_size=768, head_dim=128, max_position_embeddings=24576, rope_theta=10000000.0,
        rms_norm_eps=1e-6, num_experts=128, num_experts_per_tok=8,
        norm_topk_prob=True, qk_norm_per_head=True, index_topk=2048,
        index_num_heads=16, index_head_dim=64,
    ),
    # Laguna-S-2.1 -- window and full attention mixed layer by layer (period
    # F,S,S,S) with a head count and a rotation per kind (full: the first
    # half of a head under YaRN; sliding: the whole head, plain), a per-head
    # output gate, a dense first layer, then softmax-routed experts scaled
    # by 2.5 beside a shared one; K/V pages per layer kind
    # (models/llama.py, runtime/kv_cache.py)
    "laguna-tiny": _llama(  # test-scale: two periods and an odd end; a
        # window several times shorter than every test context
        "laguna-tiny", vocab_size=512, hidden_size=64, num_layers=10,
        num_heads=4, num_kv_heads=2, intermediate_size=96,
        moe_intermediate_size=32, head_dim=16,
        max_position_embeddings=1024, rope_theta=500000.0,
        rms_norm_eps=1e-6, sliding_window=16,
        layer_types=("full", "sliding", "sliding") * 3 + ("full",),
        sliding_num_heads=6, sliding_rope_theta=10000.0,
        partial_rotary_factor=0.5,
        rope_yarn=(8.0, 64, 32.0, 1.0, 1.2079441541679836),
        head_gate=True, first_k_dense=1, n_shared_experts=1, num_experts=8,
        num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=2.5, held_experts=(0, 2),
    ),
    # one chip's share of the published model as 16 chips serve it: four
    # pipeline stages of 12 layers, each stage's four chips sharing every
    # layer. The first stage: layers 0-11 (three whole periods), 64 of the
    # 256 routed experts, a quarter of the vocabulary; every width as
    # published (benchmark/configs/laguna-s-2.1-ep4-12l-int8.json)
    "laguna-s-2.1-ep4-12l": _llama(
        "laguna-s-2.1-ep4-12l", vocab_size=25088, hidden_size=3072,
        num_layers=12, num_heads=48, num_kv_heads=8,
        intermediate_size=12288, moe_intermediate_size=1024, head_dim=128,
        max_position_embeddings=24576, rope_theta=500000.0,
        rms_norm_eps=1e-6, sliding_window=512,
        layer_types=("full", "sliding", "sliding", "sliding") * 3,
        sliding_num_heads=72, sliding_rope_theta=10000.0,
        partial_rotary_factor=0.5,
        rope_yarn=(128.0, 8192, 32.0, 1.0, 1.4852030263919618),
        head_gate=True, first_k_dense=1, n_shared_experts=1,
        num_experts=256, num_experts_per_tok=10, norm_topk_prob=True,
        routed_scaling_factor=2.5, held_experts=(0, 64),
    ),
    "openpangu-ultra-moe-tiny": _llama(  # test-scale, every mechanism
        "openpangu-ultra-moe-tiny", vocab_size=512, hidden_size=64,
        num_layers=3, num_heads=4, num_kv_heads=4, intermediate_size=96,
        max_position_embeddings=1024, rope_theta=10000.0,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_k_dense=1,
        moe_intermediate_size=32, n_shared_experts=1, num_experts=8,
        num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=2.5,
        sandwich_norm=True,
    ),
    # one chip's share of the published model where 16 chips share each
    # layer: 16 of the 256 routed experts, an eighth of the vocabulary, one
    # leading dense layer and eight expert layers of the 61 (the others lie
    # on further chips); every width as published
    # (benchmark/configs/openpangu-ultra-moe-718b-ep16-int8.json)
    "openpangu-ultra-moe-718b-ep16": _llama(
        "openpangu-ultra-moe-718b-ep16", vocab_size=19200, hidden_size=7680,
        num_layers=9, num_heads=128, num_kv_heads=128,
        intermediate_size=18432, max_position_embeddings=4096,
        rope_theta=25600000.0, rms_norm_eps=1e-5,
        kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, first_k_dense=1,
        moe_intermediate_size=2048, n_shared_experts=1, num_experts=256,
        num_experts_per_tok=8, norm_topk_prob=True,
        routed_scaling_factor=2.5,
        held_experts=(0, 16), sandwich_norm=True,
    ),
    # GLM-5.2 -- latent attention (MLA, rope by adjacent pairs) under a
    # lightning indexer that keeps ``index_topk`` cached tokens a query: a
    # ``full`` layer projects its index queries from the query latent and
    # computes the selection, the ``shared`` layers behind it (three in
    # four) attend the same selection and hold no indexer (IndexShare);
    # the index keys lie in a pool beside the latent pages, a layer a full
    # layer. Leading dense layers, then sigmoid-routed experts with a
    # selection bias beside a shared one (models/mla.py,
    # ops/index_select.py). ``head_dim`` carries the published 192
    # (qk_nope_head_dim), which no layer reads. One multi-token-prediction
    # layer is published and not loaded.
    "glm-5.2-tiny": _llama(  # test-scale: a dense full layer, two periods
        "glm-5.2-tiny", vocab_size=512, hidden_size=64, num_layers=9,
        num_heads=4, num_kv_heads=4, intermediate_size=96, head_dim=16,
        max_position_embeddings=1024, rope_theta=10000.0,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, first_k_dense=1,
        moe_intermediate_size=32, n_shared_experts=1, num_experts=8,
        num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=2.5, held_experts=(0, 2),
        router_selection_bias=True, rope_interleave=True,
        index_topk=8, index_num_heads=2, index_head_dim=16,
        index_types=("full",) + ("shared", "shared", "shared", "full") * 2,
        index_query_input="q_latent", index_rope_dims=8,
    ),
    # one chip's share of the published model where 16 chips share each
    # layer: published layers 2-10 (the last leading dense layer, indexer
    # full, and two whole periods shared, shared, shared, full of expert
    # layers), 16 of the 256 routed experts, an eighth of the vocabulary;
    # every width as published
    # (benchmark/configs/glm-5.2-ep16-9l-int8.json)
    "glm-5.2-ep16-9l": _llama(
        "glm-5.2-ep16-9l", vocab_size=19360, hidden_size=6144,
        num_layers=9, num_heads=64, num_kv_heads=64,
        intermediate_size=12288, head_dim=192,
        max_position_embeddings=24576, rope_theta=8000000.0,
        rms_norm_eps=1e-5, kv_lora_rank=512, q_lora_rank=2048,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        first_k_dense=1, moe_intermediate_size=2048, n_shared_experts=1,
        num_experts=256, num_experts_per_tok=8, norm_topk_prob=True,
        routed_scaling_factor=2.5, held_experts=(0, 16),
        router_selection_bias=True, rope_interleave=True,
        index_topk=2048, index_num_heads=32, index_head_dim=128,
        index_types=("full",) + ("shared", "shared", "shared", "full") * 2,
        index_query_input="q_latent", index_rope_dims=64,
    ),
    # dots3-note-prev -- latent (MLA) layers of two attention kinds: ``full``
    # layers under a lightning indexer (queries from the query latent, rope
    # by adjacent pairs) and ``sliding`` layers with a head count, latent
    # ranks, head sizes and a rotation of their own that attend the last
    # 513 positions, their pages in a second, wider latent pool; a gate a
    # head on every layer's attention, a constant rescale on the two normed
    # latents; a dense first layer, then sigmoid-routed experts with a
    # selection bias beside a shared one (models/mla.py). The vision and
    # audio towers and the multi-token-prediction module are not loaded.
    "dots3-note-tiny": _llama(  # test-scale: a dense full layer, two
        # periods F,S,S,S; window and index_topk far under a test context
        "dots3-note-tiny", vocab_size=512, hidden_size=64, num_layers=9,
        num_heads=4, num_kv_heads=4, intermediate_size=96,
        max_position_embeddings=1024, rope_theta=500000.0,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_k_dense=1,
        moe_intermediate_size=32, n_shared_experts=1, num_experts=8,
        num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=1.0, held_experts=(0, 2),
        router_selection_bias=True, rope_interleave=True,
        index_topk=8, index_num_heads=4, index_head_dim=16,
        index_query_input="q_latent", index_rope_dims=8,
        layer_types=("full",) + ("full", "sliding", "sliding", "sliding") * 2,
        sliding_window=9, sliding_num_heads=2, sliding_rope_theta=10000.0,
        sliding_kv_lora_rank=64, sliding_q_lora_rank=40,
        sliding_qk_nope_head_dim=24, sliding_qk_rope_head_dim=8,
        sliding_v_head_dim=16, head_gate=True, mla_lora_rescale=True,
    ),
    # one chip's share of the published model where 8 chips share each
    # layer: published layers 0-8 (the leading dense layer, full, and two
    # whole periods F,S,S,S of expert layers), 32 of the 256 routed experts,
    # an eighth of the vocabulary; every width as published
    # (benchmark/configs/dots3-note-prev-ep8-9l-int8.json)
    "dots3-note-prev-ep8-9l": _llama(
        "dots3-note-prev-ep8-9l", vocab_size=19008, hidden_size=5120,
        num_layers=9, num_heads=128, num_kv_heads=128,
        intermediate_size=13824, max_position_embeddings=24576,
        rope_theta=80000000.0, rms_norm_eps=1e-5,
        kv_lora_rank=512, q_lora_rank=1024, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, first_k_dense=1,
        moe_intermediate_size=1536, n_shared_experts=1, num_experts=256,
        num_experts_per_tok=8, norm_topk_prob=True,
        routed_scaling_factor=1.0, held_experts=(0, 32),
        router_selection_bias=True, rope_interleave=True,
        index_topk=2048, index_num_heads=64, index_head_dim=128,
        index_query_input="q_latent", index_rope_dims=64,
        layer_types=("full",) + ("full", "sliding", "sliding", "sliding") * 2,
        sliding_window=513, sliding_num_heads=64, sliding_rope_theta=50000.0,
        sliding_kv_lora_rank=1024, sliding_q_lora_rank=1024,
        sliding_qk_nope_head_dim=192, sliding_qk_rope_head_dim=64,
        sliding_v_head_dim=128, head_gate=True, mla_lora_rescale=True,
    ),
    # Falcon-H1 -- in EVERY layer a Mamba-2 (SSD) mixer and GQA attention
    # read the same normed input and their scaled outputs are summed, then
    # a SwiGLU MLP; muP multipliers on embedding, head, keys, both mixers'
    # inputs and outputs and the MLP; a row of a state pool beside the K/V
    # pages (models/llama.py, models/ssd.py)
    "falcon-h1-tiny": _llama(  # test-scale: chunks of 16, so that a
        # segment spans several; every multiplier != 1
        "falcon-h1-tiny", vocab_size=512, hidden_size=256, num_layers=4,
        num_heads=4, num_kv_heads=2, intermediate_size=384, head_dim=64,
        max_position_embeddings=1024, rope_theta=1e11, rms_norm_eps=1e-5,
        ssm_num_heads=4, ssm_head_dim=64, ssm_state_size=32,
        ssm_num_groups=2, ssm_conv_kernel=4, ssm_chunk_size=16,
        embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
        key_multiplier=0.011048543456039804, attention_in_multiplier=0.9,
        attention_out_multiplier=0.0375, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
    ),
    # one chip of the four-chip host that holds Falcon-H1-34B-Instruct as
    # four pipeline stages of 18 whole layers with the embedding and head
    # vocabulary-parallel in four slices: stage 0, layers 0-17, a quarter of
    # the vocabulary; every width, head count, group and state size as
    # published (benchmark/configs/falcon-h1-34b-pp4-18l-int8.json)
    "falcon-h1-34b-pp4-18l": _llama(
        "falcon-h1-34b-pp4-18l", vocab_size=65280, hidden_size=5120,
        num_layers=18, num_heads=20, num_kv_heads=4, intermediate_size=21504,
        head_dim=128, max_position_embeddings=2048, rope_theta=1e11,
        rms_norm_eps=1e-5,
        ssm_num_heads=32, ssm_head_dim=128, ssm_state_size=256,
        ssm_num_groups=2, ssm_conv_kernel=4, ssm_chunk_size=128,
        embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
        key_multiplier=0.011048543456039804, attention_in_multiplier=1.0,
        attention_out_multiplier=0.0375, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
    ),
    # Kimi-Linear — three gated delta-rule (KDA) layers to one latent (MLA)
    # layer that rotates nothing, no query low-rank, a dense first layer,
    # then routed experts picked by sigmoid score plus a selection bias
    # beside a shared expert (models/mla.py, models/kda.py). ``head_dim``
    # carries the published 72 (hidden / heads), which no layer reads: a
    # latent head is 192 / 128 wide, a KDA head 128.
    "kimi-linear-tiny": _llama(  # test-scale: a repeated period and both odd ends
        "kimi-linear-tiny", vocab_size=512, hidden_size=64, num_layers=15,
        num_heads=4, num_kv_heads=4, intermediate_size=96, head_dim=16,
        max_position_embeddings=1024, rope_theta=10000.0,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, first_k_dense=1, moe_intermediate_size=32,
        n_shared_experts=1, num_experts=8, num_experts_per_tok=3,
        norm_topk_prob=True, routed_scaling_factor=2.446,
        held_experts=(0, 2), full_attn_layers=(4, 8, 12, 15), kda_num_heads=4,
        kda_head_dim=16, kda_conv_kernel=4, mla_use_nope=True,
        router_selection_bias=True,
    ),
    # one chip's share of the published model where 8 chips share each
    # layer: 32 of the 256 routed experts, an eighth of the vocabulary, all
    # 27 layers; every width as published
    # (benchmark/configs/kimi-linear-48b-a3b-ep8-int8.json)
    "kimi-linear-48b-a3b-ep8": _llama(
        "kimi-linear-48b-a3b-ep8", vocab_size=20480, hidden_size=2304,
        num_layers=27, num_heads=32, num_kv_heads=32,
        intermediate_size=9216, head_dim=72, max_position_embeddings=4096,
        rope_theta=10000.0, rms_norm_eps=1e-5,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_k_dense=1, moe_intermediate_size=1024,
        n_shared_experts=1, num_experts=256, num_experts_per_tok=8,
        norm_topk_prob=True, routed_scaling_factor=2.446,
        held_experts=(0, 32), full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
        kda_num_heads=32, kda_head_dim=128, kda_conv_kernel=4,
        mla_use_nope=True, router_selection_bias=True,
    ),
}


def get_model_config(name: str, **overrides) -> ModelConfig:
    if name not in MODEL_REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; known: {sorted(MODEL_REGISTRY)}"
        )
    cfg = MODEL_REGISTRY[name]
    return replace(cfg, **overrides) if overrides else cfg
