"""Llama-3-class decoder as pure functional JAX over a params pytree.

TPU-native core replacing the reference's engine-wrapped models
(``worker/engines/llm.py`` — HF Transformers generate; ``llm_vllm.py`` /
``llm_sglang.py`` — CUDA serving engines). Design properties:

- **One generic ``forward_chunk``** serves prefill (S = chunk), chunked/long
  prefill (S = chunk with prefix), and decode (S = 1): static shapes, no
  data-dependent Python control flow, jits once per (B, S) bucket.
- **Paged KV is the only cache layout.** K/V live in HBM pools
  ``[L, num_blocks, n_kv_heads, block_size, head_dim]`` addressed through
  per-sequence block tables — the first-party equivalent of vLLM's
  PagedAttention pools the reference delegates to (SURVEY §2.3), written
  via scatter inside the jitted graph.
- **Stacked layer params** (leading L axis) so layers run under ``lax.scan``
  (fast compiles at 80 layers) and shard/pipeline cleanly over a mesh axis.
- Attention math runs through ``ops.attention`` which picks the Pallas paged
  kernel on TPU and a gather-based XLA fallback elsewhere.

Weight-name parity with HF Llama checkpoints is handled in ``models/loader.py``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_gpu_inference_tpu.models.configs import ModelConfig
from distributed_gpu_inference_tpu.ops.attention import paged_attention
from distributed_gpu_inference_tpu.ops.quantization import (
    matmul as qmm,
    matmul_stacked,
    split_stacked_quant,
)

Params = Dict[str, Any]
KVPools = Dict[str, jax.Array]  # {"k": [L,N,Hkv,Bk,D], "v": [L,N,Hkv,Bk,D]}


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Layers described one by one: attention kind and MLP kind a layer
# ---------------------------------------------------------------------------

_FULL = "full_"         # a full-attention layer's stack in a mixed model
WINDOW_POOLS = "_win"           # the sliding kind's pools in a mixed model's ``kv``


def group_of(cfg: ModelConfig, layer: int) -> str:
    """The parameter stack layer ``layer`` (0-based) lies in: by its MLP
    (``dense_layers`` / ``layers``) and, for a full-attention layer of a
    model of mixed kinds, the prefix ``full_``. A model whose layers are
    all alike has the one stack ``layers``."""
    lead = cfg.first_k_dense if cfg.num_experts else 0
    name = "dense_layers" if layer < lead else "layers"
    if cfg.mixed_attention and cfg.attn_kinds[layer] == "full":
        return _FULL + name
    return name


def group_kind(cfg: ModelConfig, group: str) -> str:
    """The attention kind of a stack's layers."""
    if cfg.mixed_attention:
        return "full" if group.startswith(_FULL) else "sliding"
    return cfg.attn_kinds[0]


def layer_groups(cfg: ModelConfig) -> Tuple[Tuple[str, int], ...]:
    """(params key, layers) of the homogeneous stacks, in a fixed order."""
    names = [group_of(cfg, li) for li in range(cfg.num_layers)]
    order = (_FULL + "dense_layers", _FULL + "layers", "dense_layers",
             "layers")
    return tuple((g, names.count(g)) for g in order if g in names)


def layer_units(cfg: ModelConfig
                ) -> Tuple[Tuple[int, Tuple[Tuple[str, int], ...]], ...]:
    """The forward pass as ``(repeat, runs)`` units in layer order, ``runs``
    the ``(params key, layers)`` of a unit's homogeneous stretches. A model
    whose layers are all alike is one unit of one run; a model of mixed
    kinds is cut before every full layer and equal neighbours merge, so
    that a repeated period is traced once and scanned over its repeats, the
    odd ends once."""
    names = [group_of(cfg, li) for li in range(cfg.num_layers)]
    kinds = cfg.attn_kinds
    cuts = [li for li in range(1, cfg.num_layers)
            if cfg.mixed_attention and kinds[li] == "full"]
    units: list = []
    for lo, hi in zip([0] + cuts, cuts + [cfg.num_layers]):
        runs: list = []
        for name in names[lo:hi]:
            if runs and runs[-1][0] == name:
                runs[-1] = (name, runs[-1][1] + 1)
            else:
                runs.append((name, 1))
        if units and units[-1][1] == tuple(runs):
            units[-1] = (units[-1][0] + 1, units[-1][1])
        else:
            units.append((1, tuple(runs)))
    return tuple(units)


def leaf_specs(cfg: ModelConfig, group: str
               ) -> Dict[str, Tuple[tuple, int, str]]:
    """name -> (shape of ONE layer, fan-in, kind) for the leaves of a stack
    of a model described per layer, kinds as ``models/mla.leaf_specs`` has
    them (``q`` a matmul weight, ``d`` a dense bf16 weight, ``n`` a norm
    vector). ``W_q`` / ``W_o`` and the gate follow the layer kind's head
    count; the gate is one column a head (under the int8 kernel's
    128-column tiles: kept in the activation dtype, like the router)."""
    h, d, nkv = cfg.hidden_size, cfg.head_dim, cfg.num_kv_heads
    nh = cfg.heads_of(group_kind(cfg, group))

    def fan(fan_in: int, *multipliers: float):
        """The fan-in a leaf is drawn at where muP multipliers scale what
        it makes: a standard deviation of ``fan_in ** -0.5`` over their
        product, so that under them each sub-block adds a share of the
        residual that compares with the others' (a dropped branch would
        otherwise hide inside a tolerance). No multiplier: ``fan_in``."""
        m = 1.0
        for x in multipliers:
            m *= x
        return fan_in if m == 1.0 else fan_in * m * m

    a_in, m_gate, m_down = cfg.attention_in_multiplier, *cfg.mlp_multipliers
    spec: Dict[str, Tuple[tuple, int, str]] = {
        "attn_norm": ((h,), 0, "n"),
        "wq": ((h, nh * d), fan(h, a_in), "q"),
        "wk": ((h, nkv * d), fan(h, a_in, cfg.key_multiplier), "q"),
        "wv": ((h, nkv * d), fan(h, a_in), "q"),
        "wo": ((nh * d, h), fan(nh * d, cfg.attention_out_multiplier), "q"),
        "mlp_norm": ((h,), 0, "n"),
    }
    if cfg.ssm_num_heads:
        # the mixer beside attention (models/ssd.py): the in projection as
        # ``z | x | B | C`` (whole 128-column tiles) and the step sizes'
        # columns (one a head, under a tile: kept in the activation dtype,
        # like the router); ``A_log = log(1..H)``, ``D = 1`` and ``dt_bias``
        # as the family draws them
        p, c, sh = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_num_heads
        f_in = fan(h, cfg.ssm_in_multiplier)
        spec.update({
            "w_in": ((h, p + c), f_in, "q"),
            "w_dt": ((h, sh), f_in, "d"),
            "conv": ((cfg.ssm_conv_kernel, c), cfg.ssm_conv_kernel, "d"),
            "conv_bias": ((c,), 0, "z"),
            "dt_bias": ((sh,), 0, "t"),
            "a_log": ((sh,), 0, "r"),
            "d_skip": ((sh,), 0, "o"),
            "ssm_norm": ((p,), 0, "n"),
            "w_out": ((p, h), fan(p, cfg.ssm_out_multiplier), "q"),
        })
    if cfg.head_gate:
        spec["w_hgate"] = ((h, nh), h, "d")
    if cfg.qk_norm_per_head:
        spec["q_norm"] = ((d,), 0, "n")
        spec["k_norm"] = ((d,), 0, "n")
    if group.endswith("dense_layers") or not cfg.num_experts:
        i = cfg.intermediate_size
        spec.update({
            "w_gate": ((h, i), fan(h, m_gate), "q"),
            "w_up": ((h, i), h, "q"),
            "w_down": ((i, h), fan(i, m_down), "q"),
        })
    else:
        mi, held = cfg.mlp_width, cfg.num_held_experts
        spec.update({
            "w_router": ((h, cfg.num_experts), h, "d"),
            "we_gate": ((held, h, mi), h, "q"),
            "we_up": ((held, h, mi), h, "q"),
            "we_down": ((held, mi, h), mi, "q"),
        })
        if cfg.n_shared_experts:
            ms = mi * cfg.n_shared_experts
            spec.update({
                "ws_gate": ((h, ms), h, "q"),
                "ws_up": ((h, ms), h, "q"),
                "ws_down": ((ms, h), ms, "q"),
            })
    return spec


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype: Optional[jnp.dtype] = None,
    mode: Optional[str] = None,
) -> Params:
    """Random-init params with the exact pytree layout the engine shards.
    ``mode``: quantized as drawn, a layer at a time (a model described per
    layer or of latent attention; the others quantize a drawn tree)."""
    if cfg.latent_kv:
        from distributed_gpu_inference_tpu.models import mla

        return mla.init_params(cfg, key, dtype, mode)
    if cfg.described_per_layer:
        from distributed_gpu_inference_tpu.models import mla

        return mla.init_params(cfg, key, dtype, mode,
                               groups=layer_groups(cfg),
                               specs=functools.partial(leaf_specs, cfg))
    if mode is not None:
        from distributed_gpu_inference_tpu.ops.quantization import (
            quantize_params,
        )

        return quantize_params(init_params(cfg, key, dtype), mode)
    dtype = dtype or jnp.dtype(cfg.dtype)
    h, d = cfg.hidden_size, cfg.head_dim
    nh, nkv, i = cfg.num_heads, cfg.num_kv_heads, cfg.mlp_width
    L, v = cfg.num_layers, cfg.vocab_size
    keys = jax.random.split(key, 9)

    def _w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)).astype(
            dtype
        )

    # norm identity depends on the convention: plain RMSNorm scales by w
    # (identity = ones); Gemma's offset form scales by 1+w (identity = zeros)
    norm_init = jnp.zeros if cfg.norm_offset else jnp.ones
    layers: Dict[str, jax.Array] = {
        "attn_norm": norm_init((L, h), dtype),
        "wq": _w(keys[1], (L, h, nh * d), h),
        "wk": _w(keys[2], (L, h, nkv * d), h),
        "wv": _w(keys[3], (L, h, nkv * d), h),
        "wo": _w(keys[4], (L, nh * d, h), nh * d),
        "mlp_norm": norm_init((L, h), dtype),
    }
    if cfg.num_experts:  # Mixtral-style sparse MoE: stacked expert axis E
        E = cfg.num_experts
        ekeys = jax.random.split(keys[5], 3)
        layers["w_router"] = _w(keys[7], (L, h, E), h)
        layers["we_gate"] = _w(ekeys[0], (L, E, h, i), h)
        layers["we_up"] = _w(ekeys[1], (L, E, h, i), h)
        layers["we_down"] = _w(ekeys[2], (L, E, i, h), i)
    else:
        layers["w_gate"] = _w(keys[5], (L, h, i), h)
        layers["w_up"] = _w(keys[6], (L, h, i), h)
        layers["w_down"] = _w(keys[7], (L, i, h), i)
    params: Params = {
        "embedding": _w(keys[0], (v, h), h),
        "layers": layers,
        "final_norm": norm_init((h,), dtype),
    }
    if cfg.qk_norm:  # OLMoE: one norm vector over the whole q / k width
        layers["q_norm"] = norm_init((L, nh * d), dtype)
        layers["k_norm"] = norm_init((L, nkv * d), dtype)
    if cfg.qk_norm_per_head or cfg.index_topk:
        layers.update(init_index_leaves(cfg, key, dtype))
    if cfg.attention_bias:  # Qwen2-style QKV biases (random init ~ small)
        bkeys = jax.random.split(keys[1], 3)
        params["layers"]["bq"] = _w(bkeys[0], (L, nh * d), nh * d)
        params["layers"]["bk"] = _w(bkeys[1], (L, nkv * d), nkv * d)
        params["layers"]["bv"] = _w(bkeys[2], (L, nkv * d), nkv * d)
    if not cfg.tie_word_embeddings:
        # distinct key: an untied head must not be bit-identical to the
        # embedding, or head/embedding swap bugs become invisible to tests
        params["lm_head"] = _w(keys[8], (v, h), h)
    return params


INDEX_KEYS = "ki"      # the index-key pool's name in ``KVPools``
# a scan's index keys in context order, every layer's: beside the pools
# inside a ``decode_multi`` call of several steps and nowhere else
# (``scan_index_keys``)
INDEX_SCAN_KEYS = "ki_scan"
_NORM_SPREAD = 0.25


def init_index_leaves(
    cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype
) -> Dict[str, jax.Array]:
    """The leaves a per-head QK-norm and an indexer add to a layer stack,
    shared by both inits (``init_params`` and the streamed one in
    ``models/loader.py``): one ``head_dim`` norm vector a layer for q and
    for k; the indexer's query projection ``wqi`` (quantized like the other
    matmul weights), its key projection ``wki`` and head weights ``ww``
    (64 and 16 columns: under the int8 kernel's 128-column tiles, kept in
    the activation dtype), and the key's LayerNorm. Norm vectors are drawn
    ``1 + 0.25 x normal`` and the bias ``0.25 x normal`` so that dropping
    one shows."""
    L, h, d = cfg.num_layers, cfg.hidden_size, cfg.head_dim
    keys = jax.random.split(jax.random.fold_in(key, 0x1D8), 7)

    def vec(k, width, centre):
        return (centre + _NORM_SPREAD * jax.random.normal(
            k, (L, width), jnp.float32)).astype(dtype)

    def mat(k, width):
        return (jax.random.normal(k, (L, h, width), jnp.float32)
                * h ** -0.5).astype(dtype)

    out: Dict[str, jax.Array] = {}
    if cfg.qk_norm_per_head:
        out["q_norm"] = vec(keys[0], d, 1.0)
        out["k_norm"] = vec(keys[1], d, 1.0)
    if cfg.index_topk:
        hi, di = cfg.index_num_heads, cfg.index_head_dim
        out.update({
            "wqi": mat(keys[2], hi * di), "wki": mat(keys[3], di),
            "ww": mat(keys[4], hi),
            "ki_norm": vec(keys[5], di, 1.0), "ki_bias": vec(keys[6], di, 0.0),
        })
    return out


def init_kv_pools(
    cfg: ModelConfig,
    num_blocks: int,
    block_size: int = 16,
    dtype: Optional[jnp.dtype] = None,
    state_rows: Optional[int] = None,
    window_blocks: Optional[int] = None,
) -> KVPools:
    """Device-resident paged KV pools. Block 0 is reserved as the garbage/pad
    block — writes for padded tokens land there and reads mask it out.

    A model of mixed attention kinds (``cfg.mixed_attention``) has a pool a
    kind: ``"k"`` / ``"v"`` ``[L_full, N, ...]`` for its full layers and
    ``"k_win"`` / ``"v_win"`` ``[L_sliding, window_blocks, ...]`` for its
    sliding ones, block 0 of each the pad block.

    Layout ``[L, N, Hkv, Bk, D]`` (head-major pages, like vLLM's pools and
    the reference's CacheBlock [max_blocks, heads, block, head_dim],
    kv_cache.py:130-144): a (page, head) slice is a contiguous [Bk, D] tile,
    which the Pallas decode kernel DMAs without breaking TPU tiling.

    ``dtype=int8``: quantized pools — the dict additionally carries
    ``k_scale``/``v_scale`` ([L, N, Bk, D] bf16, lane-replicated): one
    scale per (page, token) shared across KV heads (real = int * scale;
    contract: ``ops.paged_attention_pallas._quantize_token_rows``).

    A model with an indexer (``cfg.index_topk``: learned sparse attention)
    carries a third pool, ``"ki"`` ``[L, N, Bk, lanes]``: the index key of
    every cached token in a row's first ``index_head_dim`` lanes
    (``ops/index_select.py``).

    A latent-attention model (``cfg.latent_kv``) has one pool and no head
    axis instead: ``{"ckv": [L, N, Bk, latent + rope]}`` (models/mla.py),
    and beside it, where some layers are linear attention, the state pool
    of ``state_rows`` sequences (models/kda.py).

    A model with a state-space mixer beside attention (``cfg.ssm_num_heads``)
    carries, beside ``"k"`` / ``"v"``, the state pool of ``state_rows``
    sequences: ``"ssm_state"`` and ``"ssm_conv"`` (models/ssd.py)."""
    if cfg.latent_kv:
        from distributed_gpu_inference_tpu.models import mla

        return mla.init_kv_pools(cfg, num_blocks, block_size, dtype,
                                 state_rows, window_blocks)
    dtype = jnp.dtype(dtype or cfg.dtype)
    if cfg.mixed_attention:
        # pages per layer kind: a pool for the full layers and one for the
        # sliding ones, each under a block table of its own
        if dtype.itemsize == 1:
            raise NotImplementedError(
                f"{cfg.name}: int8 / fp8 pools of a model of mixed "
                "attention kinds are not built")
        if not window_blocks or window_blocks < 2:
            raise ValueError(
                f"{cfg.name}: the sliding kind's pool needs its number of "
                "blocks")
        pools = {}
        for (kind, layers, _), n in zip(cfg.cache_kinds,
                                        (num_blocks, window_blocks)):
            shape = (layers, n, cfg.num_kv_heads, block_size, cfg.head_dim)
            for name in kind_pools(cfg, kind):
                pools[name] = jnp.zeros(shape, dtype)
        return pools
    shape = (cfg.num_layers, num_blocks, cfg.num_kv_heads, block_size, cfg.head_dim)
    pools = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if cfg.ssm_num_heads:
        from distributed_gpu_inference_tpu.models import ssd

        if dtype.itemsize == 1:
            raise NotImplementedError(
                f"{cfg.name}: int8 / fp8 K/V pools beside a state pool are "
                "not built")
        if state_rows is None:
            raise ValueError(
                f"{cfg.name}: the state pool needs its number of rows")
        pools.update(ssd.init_state_pools(cfg, state_rows, conv_dtype=dtype))
    if cfg.index_topk:
        # one index key a token a layer, addressed by the same block table
        # as K/V: a page copy, a prefix hit and a resume bring it
        from distributed_gpu_inference_tpu.ops.index_select import pool_lanes

        pools[INDEX_KEYS] = jnp.zeros(
            (cfg.num_layers, num_blocks, block_size,
             pool_lanes(cfg.index_head_dim)), dtype)
    if dtype == jnp.int8:
        sshape = (cfg.num_layers, num_blocks, block_size, cfg.head_dim)
        pools["k_scale"] = jnp.zeros(sshape, jnp.bfloat16)
        pools["v_scale"] = jnp.zeros(sshape, jnp.bfloat16)
    return pools


def kind_pools(cfg: ModelConfig, kind: str) -> Tuple[str, str]:
    """The names of a layer kind's K and V pools in ``KVPools``."""
    if cfg.mixed_attention and kind == "sliding":
        return "k" + WINDOW_POOLS, "v" + WINDOW_POOLS
    return "k", "v"


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, offset: bool = False
) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if offset:  # Gemma stores zero-centered norm weights; scale is (1 + w)
        w = 1.0 + w
    return (x * w).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dt)


def _rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions [..., S] → (cos, sin) each [..., S, head_dim//2], float32."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def rope_inv_freq(cfg: ModelConfig, kind: str) -> Tuple[jax.Array, float]:
    """(inverse frequencies ``[rotated / 2]``, cos / sin scale) of a layer
    kind's rotation. Under YaRN the frequencies blend interpolation
    (``1 / (factor x f)``) and extrapolation (``1 / f``) by a linear ramp
    over the rotated pairs between the two correction dims, as Hugging
    Face's ``_compute_yarn_parameters`` (truncated) computes them, and cos /
    sin are scaled by the attention factor."""
    theta, rot, yarn = cfg.rope_of(kind)
    half = rot // 2
    freqs = theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    if yarn is None:
        return 1.0 / freqs, 1.0
    import math

    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def correction_dim(rotations: float) -> float:
        return rot * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    inv = (1.0 / (factor * freqs)) * (1.0 - extrapolation) \
        + (1.0 / freqs) * extrapolation
    return inv, float(attention_factor)


def rope_tables(cfg: ModelConfig, kind: str, positions: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin) ``[..., S, rotated / 2]`` float32 of a layer kind, computed
    once a graph: ``apply_rope`` rotates as many of a head's leading values
    as they cover."""
    theta, rot, yarn = cfg.rope_of(kind)
    if yarn is None and rot == cfg.head_dim:
        return _rope_angles(positions, cfg.head_dim, theta)
    inv_freq, scale = rope_inv_freq(cfg, kind)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Half-split RoPE (HF Llama ``rotate_half`` convention).

    x: [B, S, H, D]; cos/sin: [B, S, R/2] broadcast over heads, ``R`` the
    rotated width: the head's first ``R`` values are rotated, the others
    pass as they are (``R = D``: the whole head).
    """
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # [B, S, 1, D/2]
    s = sin[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1
    ).astype(x.dtype)


def _page_scatter_indices(
    num_blocks: int, block_tables: jax.Array, positions: jax.Array,
    block_size: int,
) -> Tuple[jax.Array, jax.Array]:
    """(flat_phys, flat_slot) for scattering per-token rows into a paged
    pool — THE one copy of the OOB-drop index math, shared by the data and
    scale scatters so they can never desynchronize. Pad writes (position <
    0) map to the OUT-OF-RANGE block ``num_blocks`` and are dropped: -1
    would *wrap* to the last block under jax .at[] semantics (negative
    indices stay in-bounds)."""
    valid = positions >= 0
    safe_pos = jnp.where(valid, positions, 0)
    logical = safe_pos // block_size                       # [B, S]
    slot = safe_pos % block_size                           # [B, S]
    phys = jnp.take_along_axis(block_tables, logical, axis=1)  # [B, S]
    phys = jnp.where(valid, phys, num_blocks)
    return phys.reshape(-1), slot.reshape(-1)


def _write_kv_pages(
    pool: jax.Array,          # [N, Hkv, Bk, D] (single layer)
    new: jax.Array,           # [B, S, Hkv, D]
    block_tables: jax.Array,  # [B, M] int32 physical block ids
    positions: jax.Array,     # [B, S] int32 token positions (-1 = pad)
    block_size: int,
) -> jax.Array:
    """Scatter a chunk of new K or V rows into the paged pool.

    Padded slots (position < 0) scatter out-of-bounds and are dropped.
    """
    b, s = positions.shape
    flat_phys, flat_slot = _page_scatter_indices(
        pool.shape[0], block_tables, positions, block_size
    )
    # pool may store a narrower dtype than the activations (fp8 KV cache)
    flat_new = new.astype(pool.dtype).reshape(b * s, *new.shape[2:])  # [T,Hkv,D]
    # advanced indices (dims 0 and 2) separated by the head slice: result
    # dims order as [T, Hkv, D] — exactly flat_new's layout.
    # no unique_indices: padded rows all collapse to the same OOB index, and
    # promising uniqueness there would be undefined behavior
    return pool.at[flat_phys, :, flat_slot].set(flat_new, mode="drop")


def _write_scale_pages(
    pool: jax.Array,          # [N, Bk, D] bf16 scale pool (single layer)
    new: jax.Array,           # [B, S, D] per-token scale rows (lane-replicated)
    block_tables: jax.Array,  # [B, M]
    positions: jax.Array,     # [B, S] (-1 = pad)
    block_size: int,
) -> jax.Array:
    """Scatter int8-KV scale rows — shares :func:`_page_scatter_indices`
    with the data scatter (same OOB-drop semantics by construction)."""
    b, s = positions.shape
    flat_phys, flat_slot = _page_scatter_indices(
        pool.shape[0], block_tables, positions, block_size
    )
    flat_new = new.astype(pool.dtype).reshape(b * s, new.shape[-1])
    return pool.at[flat_phys, flat_slot].set(flat_new, mode="drop")


def _mlp_act(activation: str):
    return jax.nn.silu if activation == "silu" else functools.partial(
        jax.nn.gelu, approximate=True  # Gemma GeGLU (gelu_pytorch_tanh)
    )


def _mlp(x: jax.Array, proj, activation: str = "silu",
         multipliers: Tuple[float, float] = (1.0, 1.0)) -> jax.Array:
    """``down(act(gate(x)) * up(x))``; ``multipliers`` (muP): on the gate's
    pre-activation and on the output."""
    m_gate, m_down = multipliers
    gate = proj(x, "w_gate")
    if m_gate != 1.0:
        gate = gate * jnp.asarray(m_gate, gate.dtype)
    out = proj(_mlp_act(activation)(gate) * proj(x, "w_up"), "w_down")
    if m_down != 1.0:
        out = out * jnp.asarray(m_down, out.dtype)
    return out.astype(x.dtype)


def _moe_mlp(
    x: jax.Array,
    lp: Dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    proj=None,                          # the layer's projections (a shared
                                        # expert's matmuls go through it)
    live: Optional[jax.Array] = None,   # [B, S] bool: tokens that are routed
    pallas: bool = True,
    stacked: Optional[Dict[str, Any]] = None,
    layer_idx: Any = 0,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]], jax.Array]:
    """Sparse MoE MLP: the router (``route_experts``: softmax over all
    logits in float32, the top-k kept, renormalised if
    ``cfg.norm_topk_prob`` -- Mixtral does, OLMoE does not), then
    ``sum_e p_e * down_e(act(gate_e(x)) * up_e(x))`` over the kept experts.
    Two forms of that mathematics, chosen as the other kernels are
    (``pallas`` and the backend, no option):

    - **routed** (``pallas=True``: every one-device path). Compute follows
      the ``T x k`` (token, expert) pairs, no ``[T, E, ...]`` tensor
      exists, tokens that are not ``live`` are routed nowhere, and an
      expert that received no pair is never read
      (``ops/moe_gmm_pallas.py``, ``_routed_sum``). A round with a piece
      (``s > 1``) sorts the pairs by expert into row tiles that each belong
      to one expert and runs gate / up / down as grouped matmuls over them
      (``dgi_moe_gmm``); a scan step (``s == 1``, at most 128 padded rows)
      keeps its rows in ONE resident tile and walks the experts that
      received a pair in one call a layer (``dgi_moe_gmm_step``). On a TPU
      with quantized expert weights (``stacked``: kept whole, addressed by
      ``layer_idx``) both are Pallas kernels that read the int8 weights as
      stored; on the CPU the same plans run through XLA gathers. Returns
      the layer's counters beside the output (``moe_gmm.expert_stats``),
      and the experts each token was routed to (``[T, k]``) last.
    - **dense over the expert axis** (``pallas=False``: a GSPMD mesh, which
      refuses a bare ``pallas_call``). The combine is an einsum over ``E`` with
      top-k-masked weights: where ``we_*`` shard their E axis over
      ``model`` each chip runs its local experts for all tokens and XLA
      inserts the combine all-reduce — expert parallelism without a
      hand-written all-to-all, at E/k times the active-path FLOPs (a
      constant 4x over two local experts for Mixtral on four chips).
      No counters. The routed form under a mesh (``shard_map`` around the
      kernel, as the attention kernels have it since PR 58:
      ``attention_kernels``) is the open upgrade: at a scan step's 8 rows x
      top-2 of 8 experts nearly every expert receives a pair, so it saves
      nothing there (ROADMAP S3).
    """
    if pallas or cfg.held_experts is not None or cfg.n_shared_experts:
        # (a share of the experts and a shared expert have the routed form
        # alone: nothing shards them over a mesh yet)
        return expert_layer(x, lp, cfg, proj, live=live, stacked=stacked,
                            layer_idx=layer_idx)
    act = _mlp_act(cfg.activation)
    b, s, h = x.shape
    t = b * s
    xf = x.reshape(t, h)                                       # [T, H]
    topv, topi = route_experts(cfg, xf, lp["w_router"])        # [T, k]
    # scatter the kept top-k back to a dense [T, E] combine weight
    weights = jnp.zeros((t, cfg.num_experts), jnp.float32).at[
        jnp.arange(t)[:, None], topi
    ].set(topv)                                                # [T, E]
    gate = act(jnp.einsum("th,ehi->tei", xf, _deq(lp["we_gate"], x.dtype)))
    up = jnp.einsum("th,ehi->tei", xf, _deq(lp["we_up"], x.dtype))
    per_expert = jnp.einsum(
        "tei,eih->teh", gate * up, _deq(lp["we_down"], x.dtype)
    )                                                          # [T, E, H]
    out = jnp.einsum(
        "te,teh->th", weights.astype(jnp.float32),
        per_expert.astype(jnp.float32),
    )
    return out.reshape(b, s, h).astype(x.dtype), None, topi


def route_experts(cfg: ModelConfig, x: jax.Array, w_router: jax.Array,
                  bias: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """The router of every expert layer: scores over ALL published experts
    in float32 (``cfg.router_scoring``: softmax over the logits, or a
    sigmoid an expert), the top-k kept (no groups), renormalised where
    ``cfg.norm_topk_prob`` and scaled by ``cfg.routed_scaling_factor`` ->
    (weights [T, k], experts [T, k]). ``bias``
    (``cfg.router_selection_bias``) moves which experts are kept and is not
    in their weights. Top-k selection is precision-sensitive: float32."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    k = cfg.num_experts_per_tok
    if cfg.router_scoring == "softmax":
        topv, topi = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        if cfg.norm_topk_prob:
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    else:
        scores = jax.nn.sigmoid(logits)
        if bias is None:
            topv, topi = lax.top_k(scores, k)
        else:
            _, topi = lax.top_k(scores + bias.astype(jnp.float32), k)
            topv = jnp.take_along_axis(scores, topi, axis=-1)
        if cfg.norm_topk_prob:
            topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    if cfg.routed_scaling_factor != 1.0:
        topv = topv * cfg.routed_scaling_factor
    return topv, topi


def expert_layer(
    x: jax.Array, lp: Dict[str, Any], cfg: ModelConfig, proj, *,
    live: Optional[jax.Array], stacked: Optional[Dict[str, Any]],
    layer_idx: Any,
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """THE routed expert layer, of both recipes (``_moe_mlp`` here,
    ``models/mla._experts``): ``E_shared(m) + sum over the kept experts
    held HERE of w_e E_e(m)``, the counters of the routed part, and every
    token's experts ``[T, k]``. The router scores all published experts
    (``route_experts``); a pair that falls on an expert held elsewhere
    (``cfg.held_experts``: the chip's share of an expert-parallel layer) is
    routed nowhere and reads nothing, and nothing stands in for it; tokens
    that are not ``live`` are routed nowhere either. The routed sum is
    ``_routed_sum`` over the live pairs."""
    from distributed_gpu_inference_tpu.ops import moe_gmm_pallas as moe_gmm

    b, s, h = x.shape
    t, k = b * s, cfg.num_experts_per_tok
    act = _mlp_act(cfg.activation)
    xf = x.reshape(t, h)
    topv, topi = route_experts(
        cfg, xf, lp["w_router"],
        lp["router_bias"] if cfg.router_selection_bias else None)
    live = jnp.ones((t,), bool) if live is None else live.reshape(t)
    if cfg.held_experts is None:
        local, held, count = topi, live, cfg.num_experts
    else:
        first, count = cfg.held_experts
        local = topi - first
        held = live[:, None] & (local >= 0) & (local < count)     # [T, k]
        local = jnp.clip(local, 0, count - 1)
    out, plan = _routed_sum(
        xf, lp, topv, local, held, count,
        max(t * k * count // cfg.num_experts, 1), act,
        stacked=stacked, layer_idx=layer_idx, decode=s == 1)
    if "ws_gate" in lp or (stacked is not None and "ws_gate" in stacked):
        shared = proj(act(proj(x, "ws_gate")) * proj(x, "ws_up"), "ws_down")
        out = out + shared.reshape(t, h).astype(jnp.float32)
    stats = moe_gmm.expert_stats(plan)
    if cfg.held_experts is not None or cfg.latent_kv:
        stats["pairs_routed"] = jnp.sum(live, dtype=jnp.int32) * k
    return out.reshape(b, s, h).astype(x.dtype), stats, topi


def _routed_sum(
    xf: jax.Array,              # [T, H]
    lp: Dict[str, jax.Array],
    topv: jax.Array,            # [T, k] float32 weight of each kept pair
    experts: jax.Array,         # [T, k] its expert, among the STORED ones
    live: jax.Array,            # [T] or [T, k] bool: pairs that are routed
    num_stored: int,
    pairs_hint: int,            # the live pairs expected: sizes the row tiles
    act,
    *,
    stacked: Optional[Dict[str, Any]],
    layer_idx: Any,
    decode: bool,               # one token a row: a scan step
) -> Tuple[jax.Array, Any]:
    """``sum_e w_e * down_e(act(gate_e(x)) * up_e(x))`` over a token's live
    pairs (``ops/moe_gmm_pallas``) → (``[T, H]`` float32, the plan). The
    routed form of ``_moe_mlp`` and the held share of
    ``models/mla._experts`` (whose ``live`` is per pair: a pair on an expert
    held elsewhere is routed nowhere and reads nothing). A scan step whose
    rows fit one tile (``moe_gmm.takes_step_form``: ``decode``, the row
    count and, for the kernel, a hidden width whose thinnest blocks fit its
    budget; nothing else) takes the step form, one call over the resident
    rows; every other call lays the pairs out in sorted tiles and runs
    three grouped matmuls."""
    # imported where a sparse model needs it: a dense model's start does
    # not pay for it
    from distributed_gpu_inference_tpu.ops import moe_gmm_pallas as moe_gmm

    (t, h), k = xf.shape, topv.shape[1]
    if decode and moe_gmm.takes_step_form(t, xf.dtype, stacked):
        plan = moe_gmm.step_plan(experts, topv, live, num_stored,
                                 moe_gmm.step_rows(t, xf.dtype))
        return moe_gmm.routed_step(xf, lp, stacked, layer_idx, plan,
                                   act), plan
    plan = moe_gmm.route_plan(
        experts, live, num_stored,
        moe_gmm.tile_rows(pairs_hint, num_stored, moe_gmm.sublane(xf.dtype)),
    )

    def gmm(rows, name):
        # whole in ``stacked`` exactly where the kernel takes them, else
        # this layer's slice through XLA's gather
        if stacked is not None and name in stacked:
            return moe_gmm.grouped_matmul(
                rows, stacked[name], layer_idx, plan, decode=decode)
        return moe_gmm.grouped_matmul_layer(rows, lp[name], plan)

    rows = jnp.take(xf, plan.row_token, axis=0, mode="fill", fill_value=0)
    mid = act(gmm(rows, "we_gate")) * gmm(rows, "we_up")       # [R, I]
    y = gmm(mid.astype(xf.dtype), "we_down")                   # [R, H]
    out = jnp.zeros((t, h), jnp.float32)
    for j in range(k):      # a token's k rows; dead pairs read nothing
        out = out + topv[:, j, None] * jnp.take(
            y, plan.pair_row[:, j], axis=0, mode="fill", fill_value=0
        ).astype(jnp.float32)
    return out, plan


def _deq(w: Any, dtype) -> jax.Array:
    """Expert weights [E, in, out] (layer axis consumed by scan), possibly
    quantized: convert-on-read, shaped for the einsum contraction."""
    from distributed_gpu_inference_tpu.ops.quantization import (
        dequantize, is_quantized,
    )

    return dequantize(w, dtype) if is_quantized(w) else w


def _split_layers(layers: Dict[str, Any], pallas: bool):
    """``split_stacked_quant`` for a forward pass: the expert weights stay
    whole too where the routed layer's kernel will take them."""
    if not pallas or "we_gate" not in layers:
        return split_stacked_quant(layers)
    from distributed_gpu_inference_tpu.ops import moe_gmm_pallas as moe_gmm

    return split_stacked_quant(layers, experts=moe_gmm.kernel_ok(layers))


# ---------------------------------------------------------------------------
# Transformer forward over paged KV
# ---------------------------------------------------------------------------


def attention_kernels(
    cfg: ModelConfig, quantized_kv: bool, pallas: bool = True, heads=None
) -> bool:
    """Whether a graph's attention MAY take the K/V kernels (the backend,
    the head width and the context then decide, as on one chip:
    ``resolve_impl``). ``pallas``: the caller allows every kernel of the
    graph (one chip). ``heads`` (``parallel/sharding.head_shards``: a mesh
    whose only sharded axis is ``model``): the three attention kernels
    alone, each a shard of heads a chip inside ``jax.shard_map`` -- the
    pools, q and the new K/V rows are sharded on their head axis already
    and pages never cross chips, so a shard's call is the one-chip kernel
    at ``Hkv / tp`` heads. Not over int8 pools: the fused kernel's
    per-token amax would reduce over the local heads only and break the
    scale pools' contract (``kv_scale_sharding``)."""
    return pallas or (
        heads is not None and not quantized_kv
        and cfg.num_kv_heads % heads.size == 0
    )


def decode_attention_path(
    cfg: ModelConfig, padded_ctx: int, quantized_kv: bool,
    pallas: bool = True, heads=None,
) -> str:
    """Which attention a one-token step's graph is built with -- a
    trace-time fact beside ``ragged_kv_path``, from what dispatch can see
    and no knob: ``fused`` (``dgi_paged_decode`` writes the step's rows
    into the stacked pools and attends, in place: where the decode kernel
    is taken, ``ops.attention.resolve_impl``, and the caller allows the
    attention kernels) or ``xla`` (a layer of each pool sliced out,
    scattered into and written back, and the row's whole padded table
    gathered). Both step callers, ``forward_chunk`` and
    ``forward_hidden_chunk``, ask here, and keep ``xla`` for a step that
    brings its own attention (``dense_attn_fn``, ``attn_override``) or is
    packed. A K/V model's: a latent model's steps are ``models/mla.py``'s."""
    from distributed_gpu_inference_tpu.ops.attention import resolve_impl

    fused = attention_kernels(cfg, quantized_kv, pallas, heads) \
        and resolve_impl(
            q_seq=1, head_dim=cfg.head_dim, padded_ctx=padded_ctx
        ) == "pallas"
    return "fused" if fused else "xla"


def ragged_kv_path(
    cfg: ModelConfig, padded_ctx: int, quantized_kv: bool,
    pallas: bool = True, heads=None,
) -> str:
    """Which KV path a multi-token chunk's graph is built with — a
    trace-time fact, from what dispatch can see and no knob:

    - ``in_place``: the page write (``dgi_paged_write``) and the ragged
      kernel address the stacked pools by layer index, so a round moves
      its tokens. Taken where the ragged kernel is taken
      (``ops.attention.resolve_impl``: a TPU backend, ``head_dim % 128 ==
      0``, a padded context of at least 512) and the caller allows the
      attention kernels (``attention_kernels``: one chip, or a mesh that
      shards ``model`` alone, where they run a shard of heads a chip).
    - ``layer_copy``: the layer is sliced out of the stack, scattered into
      and written back — pool-sized copies in every layer. Everything
      else (a mesh with a ``seq`` axis, the CPU, a head width the kernels
      refuse, int8 pools with no in-place scale write), and by these facts
      alone: both chunk callers, ``forward_chunk`` and
      ``forward_hidden_chunk``, ask here."""
    from distributed_gpu_inference_tpu.ops.attention import resolve_impl

    if cfg.latent_kv:
        # the latent pool has no layer slice on any path: the latent kernels
        # address the stacked pool by layer index, and the XLA path scatters
        # into it and gathers its pages with the layer as an index
        # (models/mla.py)
        return "in_place"
    ragged = resolve_impl(
        q_seq=2, head_dim=cfg.head_dim, padded_ctx=padded_ctx
    ) == "ragged"
    return "in_place" if ragged and not quantized_kv and attention_kernels(
        cfg, quantized_kv, pallas, heads) else "layer_copy"


def _fused_decode(block_size: int, window: Optional[int], heads=None,
                  **sel):
    """``_layer_step``'s ``fused_decode`` over bf16 pools: (q, the step's k
    and v rows, k_pool, v_pool, layer_idx, block_tables, positions,
    kv_lens) → (attn, k_pool, v_pool), the pools written in place. Under
    ``heads`` (``attention_kernels``) inside ``jax.shard_map`` over
    ``model``: each chip writes and attends its own heads of the same
    pages."""
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_fused,
    )

    def fused(q, k, v, k_pool, v_pool, layer_idx, tables, positions, lens):
        return paged_decode_attention_fused(
            q, k, v, k_pool, v_pool, layer_idx, tables, positions, lens,
            block_size, window=window, **sel,
        )

    if heads is None:
        return fused
    from distributed_gpu_inference_tpu.parallel.sharding import (
        CHUNK_HEADS, POOL_HEADS,
    )

    return jax.shard_map(
        fused, mesh=heads.mesh,
        in_specs=(CHUNK_HEADS,) * 3 + (POOL_HEADS,) * 2 + (P(),) * 4,
        out_specs=(CHUNK_HEADS, POOL_HEADS, POOL_HEADS),
        check_vma=False,
    )


def _in_place_kv(
    cfg: ModelConfig,
    kv: KVPools,
    block_tables: jax.Array,
    positions: jax.Array,       # [B, S] the rectangle's positions (-1 = pad)
    kv_lens: jax.Array,
    block_size: int,
    token_index: Optional[jax.Array] = None,
    num_tokens: Optional[int] = None,
    kind: Optional[str] = None,
    pallas: bool = True,
    heads=None,                 # ``attention_kernels``: a shard of heads a
                                # chip, or None on one chip
):
    """``_layer_step``'s ``in_place`` for a multi-token chunk on the kernel
    path, or None where ``ragged_kv_path`` says ``layer_copy``: the page
    write into the kind's stacked pools (its plan the same for every layer
    of a kind -- ``block_tables`` is the kind's -- so built here, outside
    the scan) and attention over them. Under ``heads`` both run inside
    ``jax.shard_map`` over ``model``, each chip on its own heads of the
    same pages: the plan is replicated, and its tiles are sized by the
    SHARD's page."""
    if positions.shape[1] == 1 or ragged_kv_path(
        cfg, block_tables.shape[1] * block_size, "k_scale" in kv, pallas,
        heads,
    ) != "in_place":
        return None
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        PageWritePlan, page_write_plan, ragged_paged_attention,
        write_kv_pages_in_place,
    )

    kind = kind or cfg.attn_kinds[0]
    window = cfg.sliding_window if kind == "sliding" else None
    pool = kv[kind_pools(cfg, kind)[0]]
    plan = page_write_plan(
        block_tables, positions, block_size,
        page_bytes=pool.shape[2] // (1 if heads is None else heads.size)
        * pool.shape[3] * pool.shape[4] * pool.dtype.itemsize,
        token_index=token_index, num_tokens=num_tokens,
    )

    def write(k_rows, v_rows, k_pool, v_pool, layer_idx, *where):
        return write_kv_pages_in_place(
            k_rows, v_rows, k_pool, v_pool, layer_idx,
            PageWritePlan(*where, plan.tile))

    def attn(q, k_pool, v_pool, layer_idx, tables, pos, lens, keep=None):
        return ragged_paged_attention(
            q, k_pool, v_pool, tables, pos, lens, block_size, window=window,
            layer_idx=layer_idx, keep=keep,
        )

    where = tuple(plan[:-1])        # the plan's arrays; its tile is static
    if heads is not None:
        from distributed_gpu_inference_tpu.parallel.sharding import (
            CHUNK_HEADS, POOL_HEADS, ROW_HEADS,
        )

        pools = (POOL_HEADS, POOL_HEADS)
        write = jax.shard_map(
            write, mesh=heads.mesh,
            in_specs=(ROW_HEADS, ROW_HEADS, *pools) + (P(),) * (1 + len(where)),
            out_specs=pools, check_vma=False)
        attn = jax.shard_map(
            attn, mesh=heads.mesh,
            in_specs=(CHUNK_HEADS, *pools) + (P(),) * 4,
            out_specs=CHUNK_HEADS, check_vma=False)
    return (lambda *rows_pools_layer: write(*rows_pools_layer, *where),
            lambda *q_pools_layer, **sel: attn(
                *q_pools_layer, block_tables, positions, kv_lens, **sel))


class _IndexPlan(NamedTuple):
    """An indexer's view of a chunk, built once a forward pass: the
    rotation of its ``index_head_dim``-wide heads and where each token's
    index key lands in the pool, on the chunk's flat token axis."""

    cos: jax.Array
    sin: jax.Array
    scatter: Tuple[jax.Array, jax.Array]    # (page, slot) a token


def _index_plan(
    cfg: ModelConfig, num_blocks: int, block_tables: jax.Array,
    positions: jax.Array,        # [B, S] the rectangle's (-1 = nothing)
    rope_positions: jax.Array,   # [B, S], or [1, Tp] of a packed chunk
    packing: Optional["Packing"], block_size: int,
) -> _IndexPlan:
    # (a latent model's indexer may rotate part of a head: models/mla.py)
    cos, sin = _rope_angles(jnp.maximum(rope_positions, 0),
                            cfg.index_rope_dims or cfg.index_head_dim,
                            cfg.rope_theta)
    if packing is None:
        scatter = _page_scatter_indices(
            num_blocks, block_tables, positions, block_size)
    else:
        pos, b = rope_positions[0], block_tables.shape[0]
        valid = (pos >= 0) & (packing.row < b)
        safe = jnp.where(valid, pos, 0)
        phys = block_tables[jnp.minimum(packing.row, b - 1),
                            safe // block_size]
        scatter = (jnp.where(valid, phys, num_blocks), safe % block_size)
    return _IndexPlan(cos, sin, scatter)


def scan_index_keys(
    cfg: ModelConfig, kv: KVPools, block_tables: jax.Array,
    lens: jax.Array,          # [B] cached tokens before the scan
    active: jax.Array,        # [B] bool rows the scan runs
    num_steps: int,
) -> KVPools:
    """``kv`` as a scan of ``num_steps`` decode steps carries it. Where the
    caller handed storage for them (``INDEX_SCAN_KEYS``: a model with an
    indexer, a scan of several steps): with every layer's index keys of the
    rows laid out in context order ONCE (``ops/index_select.
    gather_scan_keys``), for ``forward_chunk`` to append to and score from
    in place of a gather a layer a step. What the entry holds is derived
    from the pool at every call; only its storage outlives the call, in the
    caller's hands and not among the pools. Without the entry (any other
    model, a single step, a table no wider than ``topk``): ``kv`` itself."""
    if INDEX_SCAN_KEYS not in kv:
        return kv
    from distributed_gpu_inference_tpu.ops import index_select

    if cfg.latent_kv and cfg.mixed_attention:
        # a row holds a table a kind; the index keys follow the full kind's
        block_tables = block_tables[:, :block_tables.shape[1] // 2]
    keys = index_select.gather_scan_keys(
        kv[INDEX_KEYS], block_tables,
        jnp.max(jnp.where(active, lens, 0)) + num_steps, cfg.index_topk,
        into=kv[INDEX_SCAN_KEYS],
    )
    return {**kv, INDEX_SCAN_KEYS: keys}


def index_inputs(
    cfg: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array, proj,
    index: _IndexPlan,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """What a layer's indexer makes of the layer's normed input ``x [B, S,
    H]`` (the input q / k / v are projected from): its rotated queries
    ``[B, S, Hi, Di]``, the chunk's rotated index keys ``[B, S, 1, Di]``
    (LayerNorm with bias, then all ``Di`` values rotated) and the heads'
    weights ``[B, S, Hi]`` float32, scaled by ``(Hi * Di) ** -0.5``."""
    b, s, _ = x.shape
    hi, di = cfg.index_num_heads, cfg.index_head_dim
    qi = apply_rope(proj(x, "wqi").reshape(b, s, hi, di), index.cos,
                    index.sin)
    kin = layer_norm(proj(x, "wki"), lp["ki_norm"], lp["ki_bias"],
                     cfg.rms_norm_eps)
    kin = apply_rope(kin[:, :, None, :], index.cos, index.sin)
    wts = proj(x, "ww").astype(jnp.float32) * (hi * di) ** -0.5
    return qi, kin, wts


class ChunkOutput(NamedTuple):
    hidden: jax.Array       # [B, S, H] final-layer hidden states (pre-norm)
    kv: KVPools             # updated pools
    logits: Optional[jax.Array]  # [B, S, V] ([B, 1, V] if last_only; None if
                                 # with_logits=False — intermediate chunks)
    # [B, S, k*H] concat of the requested layers' post-layer hiddens
    # (collect_layers; EAGLE-3-style multi-layer draft features) — None
    # unless asked for: stacking every layer's hidden is layer-count x the
    # activation memory, so only small spec/distill shapes request it
    features: Optional[jax.Array] = None
    # what the routed expert layers did, summed over layers (int32 scalars:
    # ops/moe_gmm_pallas.expert_stats) — None for a dense model and where
    # the expert layer runs dense over the expert axis (a mesh)
    moe: Optional[Dict[str, jax.Array]] = None
    # [L, T, k] the experts every token was routed to in every layer
    # (collect_routing; the benchmark's comparison with its reference)
    routing: Optional[jax.Array] = None
    # a one-token chunk of a model with an indexer: the cached tokens the
    # decode kernel fetches for the rows' selections, summed over layers
    # (int32 scalar: ops/paged_attention_pallas.fetched_tokens; the kernel's
    # rule, whichever form of attention the chunk ran) — None otherwise
    index_fetched: Optional[jax.Array] = None


class Packing(NamedTuple):
    """A ragged round's live tokens on ONE flat axis of ``Tp`` entries, and
    where each sits in the ``[B, S]`` rectangle that the page write and
    the attention kernels take. The dense work of a layer (norms,
    projections, MLP or experts, the residual stream) runs over ``[Tp,
    H]``; q/k/v are laid out into the rectangle for attention and its
    output is read back onto the packed axis. Built by the engine's host
    code (``TPUEngine._build_plain_ragged``); ``S`` is a function of
    ``Tp`` alone, so there is one graph per ``Tp``."""

    row: jax.Array    # [Tp] int32 sequence row of each token; B = padding
    col: jax.Array    # [Tp] int32 column of each token in its row's chunk
    last: jax.Array   # [B] int32 packed index of each row's last token
    width: int        # S, static


def _layer_step(
    cfg: ModelConfig,
    block_size: int,
    carry: Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
    lp: Dict[str, jax.Array],
    *,
    block_tables: jax.Array,
    write_positions: jax.Array,   # where this chunk's KV lands (-1 = drop)
    cos: jax.Array,
    sin: jax.Array,
    attn_fn,                      # (q, layer_k, layer_v) -> attention output
    fused_decode: bool = False,   # S=1 TPU path: one kernel writes + attends
    kv_lens: Optional[jax.Array] = None,  # required when fused_decode
    stacked: Optional[Dict[str, Any]] = None,  # quantized weights kept whole
    dense_attn_fn=None,           # (q, k, v dense chunk) → attn; see below
    emit_hidden: bool = False,    # scan-emit this layer's hidden (features)
    pallas: bool = True,          # False: projections stay on the XLA path
    unpack: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
                                  # packed chunk (forward_chunk ``packing``):
                                  # (rectangle → packed index [B, S], row
                                  # [Tp], col [Tp])
    moe_live: Optional[jax.Array] = None,  # hidden's tokens the experts
                                  # route (None: all of them)
    emit_routing: bool = False,   # scan-emit the experts of every token
    in_place=None,                # ((k rows, v rows, k_pool, v_pool,
                                  # layer_idx) → pools, (q, k_pool, v_pool,
                                  # layer_idx) → attn): a multi-token chunk
                                  # writes and reads the STACKED pools
                                  # (``_in_place_kv``)
    heads=None,                   # ``fused_decode`` a shard of heads a chip
                                  # (``attention_kernels``; None: one chip)
    index=None,                   # an indexer's view of the chunk, the same
                                  # for every layer (``_index_plan``)
    kind: Optional[str] = None,   # the layer's attention kind: its head
                                  # count and window (None: the model's one)
    cache_delta: Any = 0,         # the layer's place in its kind's pools
                                  # less its place in its parameter stack
    mixer=None,                   # a state-space mixer beside attention:
                                  # (its plan of the chunk or None for one
                                  # token a row, kernels) (``models/ssd``)
) -> Tuple[Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
           Tuple[Optional[jax.Array], Optional[Dict[str, jax.Array]],
                 Optional[jax.Array]]]:
    """One transformer layer over paged KV, for every caller: ``attn_fn``
    carries the attention mask and ``write_positions`` where the chunk's
    K/V rows are written.

    The KV path has three forms, chosen at trace time by shape and backend.
    ``fused_decode`` (S = 1 on the kernel path) routes it through the Pallas
    fused write+attention kernel on the STACKED pools
    (ops/paged_attention_pallas). ``in_place`` (S > 1 on the kernel path:
    ``ragged_kv_path``) does the same for a multi-token chunk with two
    kernels: the page write goes into the stacked pools by layer index
    (``dgi_paged_write``) and the ragged kernel reads them there. Under a
    mesh that shards ``model`` alone both forms run a shard of heads a chip
    (``heads``: ``jax.shard_map`` over ``model``, the same pools in place).
    The third form — XLA scatter into a dynamically-indexed layer slice,
    then the write-back — is what a mesh with a ``seq`` axis, the CPU and
    int8 pools take: on a TPU it costs pool-sized HBM copies in every layer
    (scatter-preferred vs kernel-required layout, plus custom-call operand
    materialization;
    round-2 profiling for decode, PERF.md section 5 for the ragged round:
    ~21 ms of a 34 ms Mistral round).

    ``stacked`` holds quantized matmul weights with their layer axis intact
    (``split_stacked_quant``): projections then run through the Pallas
    VMEM-dequant kernel addressed by ``layer_idx``, so no per-layer weight
    slice is ever materialized for the custom call.

    ``dense_attn_fn`` routes attention over this chunk's DENSE K/V instead
    of the paged pools — valid exactly when the chunk IS the whole context
    (a from-scratch prefill with no cached prefix). This is the
    sequence-parallel entry: the engine passes ring/Ulysses attention
    (``parallel/ring_attention.py``) here so a long prompt's attention
    spreads over the ``seq`` mesh axis while KV pages still land in the
    same paged pools decode reads (SURVEY §5.7).

    ``index`` (a model with an indexer): the carry holds the index-key
    pool as a fifth entry. The chunk's index keys are scattered into it
    whatever the K/V path, the selection is computed from it
    (``ops/index_select.select``) and handed to the attention call as
    ``keep``. Inside a scan of several steps a sixth entry holds the scan's
    keys in context order (``scan_index_keys``): the step's keys are
    appended there too and the selection reads them there, not the pool.

    ``mixer`` (a model with a state-space mixer in every layer): the carry
    holds the state pool and the tails' as a fifth and a sixth entry. The
    mixer reads the same normed input as attention, advances its layer of
    both, and the two outputs enter the residual together, each under its
    multiplier.

    ``unpack``: ``hidden`` is ``[1, Tp, H]``, a round's live tokens packed
    on one axis. q/k/v are gathered into the ``[B, S]`` rectangle (empty
    positions zero, their ``write_positions`` -1) for the page write and
    attention exactly as an unpacked chunk has them, and the attention
    output is gathered back; everything else runs over ``Tp`` rows. With
    ``in_place`` only q takes the rectangle: the page write gathers K and V
    from the packed axis straight into page-shaped updates."""
    hidden, k_ent, v_ent, layer_idx, *more = carry
    state_pools = ()
    if mixer is not None:
        state_pools, more = tuple(more), ()
    ki_pool, scan_keys = (*more, None, None)[:2]
    kind = kind or cfg.attn_kinds[0]
    window = cfg.sliding_window if kind == "sliding" else None
    # weights are addressed by ``layer_idx``, pages by ``cache_layer``: the
    # same number where every layer is of one kind
    cache_layer = layer_idx if isinstance(cache_delta, int) \
        and cache_delta == 0 else layer_idx + cache_delta
    # int8-KV pools travel as (pool, scale_pool) tuples through the scan
    # carry; bf16 pools stay bare arrays (static structure, zero overhead)
    quant_kv = isinstance(k_ent, tuple)
    if quant_kv:
        k_pool, k_scale_pool = k_ent
        v_pool, v_scale_pool = v_ent
    else:
        k_pool, v_pool = k_ent, v_ent
        k_scale_pool = v_scale_pool = None
    b, s, _ = hidden.shape
    nh, nkv, d = cfg.heads_of(kind), cfg.num_kv_heads, cfg.head_dim

    def proj(x_, name):
        if stacked is not None and name in stacked:
            return matmul_stacked(x_, stacked[name], layer_idx, pallas)
        return qmm(x_, lp[name], pallas)

    # scopes name the layer's two halves in a device trace's op metadata;
    # the kernels inside carry their own (innermost) names
    with jax.named_scope("dgi_attention"):
        x = rms_norm(hidden, lp["attn_norm"], cfg.rms_norm_eps, cfg.norm_offset)
        mixed = None
        if mixer is not None:
            from distributed_gpu_inference_tpu.models import ssd

            plan, ssd_kernels = mixer
            with jax.named_scope("dgi_ssd"):
                mixed, held = ssd.mixer(
                    cfg, x, lp, proj, dict(zip(ssd.POOLS, state_pools)),
                    cache_layer, plan=plan, positions=write_positions,
                    kernels=ssd_kernels)
            state_pools = tuple(held[name] for name in ssd.POOLS)
            mixed = mixed * jnp.asarray(cfg.ssm_out_multiplier, mixed.dtype)
        if cfg.attention_in_multiplier != 1.0:
            x = x * jnp.asarray(cfg.attention_in_multiplier, x.dtype)
        q = proj(x, "wq")
        k = proj(x, "wk")
        v = proj(x, "wv")
        if cfg.key_multiplier != 1.0:
            k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
        if "bq" in lp:  # Qwen2-style attention biases (static at trace time)
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        if cfg.qk_norm:  # OLMoE QK-norm: over the whole width, pre-RoPE
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, cfg.norm_offset)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, cfg.norm_offset)
        q = q.reshape(b, s, nh, d)
        k = k.reshape(b, s, nkv, d)
        v = v.reshape(b, s, nkv, d)
        if cfg.qk_norm_per_head:  # Qwen3: over each head's values, pre-RoPE
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, cfg.norm_offset)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, cfg.norm_offset)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        keep = None
        if index is not None:
            qi, kin, wts = index_inputs(cfg, lp, x, proj, index)
        if unpack is not None:
            to_rect, tok_row, tok_col = unpack

            def rectangle(t):               # → [B, S, heads, D]
                return jnp.take(t[0], to_rect, axis=0, mode="fill",
                                fill_value=0)

            q = rectangle(q)
            if in_place is None:    # the in-place write reads the packed axis
                k, v = rectangle(k), rectangle(v)
            if index is not None:
                qi, wts = rectangle(qi), rectangle(wts)

        if index is not None:
            from distributed_gpu_inference_tpu.ops import index_select

            with jax.named_scope("dgi_index"):
                ki_pool = index_select.write_index_keys(
                    ki_pool, kin.reshape(-1, cfg.index_head_dim), cache_layer,
                    *index.scatter)
                if scan_keys is not None:
                    scan_keys = index_select.append_scan_keys(
                        scan_keys, kin[:, 0, 0], cache_layer,
                        write_positions[:, 0])
                keep = index_select.select(
                    qi, wts, ki_pool, cache_layer, block_tables,
                    write_positions, kv_lens, cfg.index_topk,
                    kernels=fused_decode or in_place is not None,
                    scan_keys=scan_keys,
                )
        sel = {} if keep is None else {"keep": keep}
        fetched = None
        if keep is not None and unpack is None and s == 1:
            from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
                fetched_tokens,
            )

            fetched = fetched_tokens(keep, block_size)

        if fused_decode:
            from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
                paged_decode_attention_fused,
            )

            if quant_kv:
                # the kernel quantizes the new rows in place (shared contract)
                attn, k_pool, v_pool, k_scale_pool, v_scale_pool = \
                    paged_decode_attention_fused(
                        q, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                        k_pool, v_pool, cache_layer, block_tables,
                        write_positions, kv_lens, block_size,
                        window=window,
                        k_scale=k_scale_pool, v_scale=v_scale_pool,
                    )
            else:
                attn, k_pool, v_pool = _fused_decode(
                    block_size, window, heads, **sel
                )(
                    q, k.astype(k_pool.dtype), v.astype(v_pool.dtype),
                    k_pool, v_pool, cache_layer, block_tables,
                    write_positions, kv_lens,
                )
        elif in_place is not None:
            write, attn_stacked = in_place
            k_pool, v_pool = write(
                k.reshape(-1, nkv, d), v.reshape(-1, nkv, d),
                k_pool, v_pool, cache_layer,
            )
            attn = attn_stacked(q, k_pool, v_pool, cache_layer, **sel)
        else:
            layer_k = lax.dynamic_index_in_dim(k_pool, cache_layer, 0, keepdims=False)
            layer_v = lax.dynamic_index_in_dim(v_pool, cache_layer, 0, keepdims=False)
            layer_ks = layer_vs = None
            if quant_kv:
                from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
                    _quantize_token_rows,
                )

                # per-token quantize over (Hkv, D), scale rows lane-replicated
                k_q, k_s = _quantize_token_rows(k.astype(jnp.float32), (2, 3))
                v_q, v_s = _quantize_token_rows(v.astype(jnp.float32), (2, 3))
                layer_ks = lax.dynamic_index_in_dim(
                    k_scale_pool, cache_layer, 0, keepdims=False)
                layer_vs = lax.dynamic_index_in_dim(
                    v_scale_pool, cache_layer, 0, keepdims=False)
                layer_k = _write_kv_pages(
                    layer_k, k_q, block_tables, write_positions, block_size)
                layer_v = _write_kv_pages(
                    layer_v, v_q, block_tables, write_positions, block_size)
                layer_ks = _write_scale_pages(
                    layer_ks, jnp.broadcast_to(k_s[:, :, 0, :], (*k.shape[:2], d)),
                    block_tables, write_positions, block_size)
                layer_vs = _write_scale_pages(
                    layer_vs, jnp.broadcast_to(v_s[:, :, 0, :], (*v.shape[:2], d)),
                    block_tables, write_positions, block_size)
                k_scale_pool = lax.dynamic_update_index_in_dim(
                    k_scale_pool, layer_ks, cache_layer, 0)
                v_scale_pool = lax.dynamic_update_index_in_dim(
                    v_scale_pool, layer_vs, cache_layer, 0)
            else:
                layer_k = _write_kv_pages(layer_k, k, block_tables, write_positions, block_size)
                layer_v = _write_kv_pages(layer_v, v, block_tables, write_positions, block_size)
            k_pool = lax.dynamic_update_index_in_dim(k_pool, layer_k, cache_layer, 0)
            v_pool = lax.dynamic_update_index_in_dim(v_pool, layer_v, cache_layer, 0)
            if dense_attn_fn is not None:
                # pages written above for decode; attention itself runs over the
                # chunk's dense K/V (== whole context for a from-scratch prefill)
                if quant_kv:
                    # int8 pools: attend over the quantize→dequantize roundtrip
                    # of the chunk's K/V (THE shared dequant arithmetic) so a
                    # dense seq-sharded prefill matches a single-chip engine's
                    # paged-read prefill
                    from distributed_gpu_inference_tpu.ops.attention import (
                        dequantize_kv,
                    )

                    attn = dense_attn_fn(
                        q, dequantize_kv(k_q, k_s), dequantize_kv(v_q, v_s)
                    )
                else:
                    attn = dense_attn_fn(q, k, v)
            elif quant_kv:
                attn = attn_fn(q, layer_k, layer_v, layer_ks, layer_vs)
            else:
                attn = attn_fn(q, layer_k, layer_v, **sel)

        if unpack is not None:
            attn = attn.at[tok_row, tok_col].get(mode="fill", fill_value=0)
        if "w_hgate" in lp:
            # one scalar a head a token, from the layer's normed input
            gate = jax.nn.sigmoid(
                proj(x, "w_hgate").astype(jnp.float32))
            attn = (attn.astype(jnp.float32) * gate[..., None]).astype(
                attn.dtype)
        attn = proj(attn.reshape(b, s, nh * d), "wo").astype(hidden.dtype)
        if cfg.attention_out_multiplier != 1.0:
            attn = attn * jnp.asarray(cfg.attention_out_multiplier,
                                      attn.dtype)
        if mixed is not None:
            attn = attn + mixed
        hidden = hidden + attn
    with jax.named_scope("dgi_experts" if "w_router" in lp else "dgi_mlp"):
        mlp_in = rms_norm(hidden, lp["mlp_norm"], cfg.rms_norm_eps, cfg.norm_offset)
        moe_stats = routing = None
        if "w_router" in lp:
            moe_out, moe_stats, routing = _moe_mlp(
                mlp_in, lp, cfg, proj=proj, live=moe_live, pallas=pallas,
                stacked=stacked, layer_idx=layer_idx,
            )
            hidden = hidden + moe_out
        else:
            hidden = hidden + _mlp(mlp_in, proj, cfg.activation,
                                   cfg.mlp_multipliers)
    k_out = (k_pool, k_scale_pool) if quant_kv else k_pool
    v_out = (v_pool, v_scale_pool) if quant_kv else v_pool
    return (hidden, k_out, v_out, layer_idx + 1, *state_pools,
            *(a for a in (ki_pool, scan_keys) if a is not None)), (
        hidden if emit_hidden else None, moe_stats,
        routing if emit_routing else None, fetched,
    )


def forward_chunk(
    cfg: ModelConfig,
    params: Params,
    token_ids: jax.Array,      # [B, S] int32 (pad = any id at position -1)
    positions: jax.Array,      # [B, S] int32, -1 marks padding
    kv: KVPools,
    block_tables: jax.Array,   # [B, M] int32 physical block ids
    kv_lens: jax.Array,        # [B] int32 total valid context AFTER this chunk
    *,
    block_size: int = 16,
    last_only: bool = True,
    with_logits: bool = True,
    dense_attn_fn=None,
    attn_override=None,   # (q, layer_k, layer_v, tables, positions,
                          # kv_lens, layer_ks, layer_vs) — replaces the
                          # paged-attention read (e.g. the seq-sharded-pool
                          # shard_map op); disables the fused Pallas path.
                          # layer_ks/layer_vs are the layer's scale-pool
                          # slices (int8 pools) or None
    collect_layers: Optional[Tuple[int, ...]] = None,
                          # also return ChunkOutput.features = concat of
                          # these layers' post-layer hiddens (EAGLE-3 draft
                          # features) — costs L x hidden activation memory,
                          # request only on small spec/distill shapes
    collect_routing: bool = False,
                          # also return ChunkOutput.routing (MoE models)
    pallas: bool = True,
                          # gate for every BARE Pallas kernel in the graph
                          # (the int8 matmul, the routed experts, the
                          # attention kernels, a recipe's state, latent and
                          # index kernels): an engine serving over a GSPMD
                          # mesh must pass False — a bare pallas_call has
                          # no partitioning rule (XLA refuses the graph).
                          # Dispatch cannot see the mesh from inside a
                          # trace, so the caller says it.
    heads=None,
                          # with ``pallas=False``, the mesh's head sharding
                          # (``parallel/sharding.head_shards``; None on one
                          # chip and under a ``seq`` axis): the attention
                          # kernels alone run all the same, a shard of
                          # heads a chip inside ``jax.shard_map`` over
                          # ``model``, on the stacked pools in place
                          # (``attention_kernels``: not over int8 pools,
                          # whose per-token amax would reduce over LOCAL
                          # heads only and break the all-reduce-max scale
                          # contract, parallel/sharding.py)
    packing: Optional[Packing] = None,
                          # the chunk's live tokens on one flat axis:
                          # token_ids and positions are [Tp] (padding at
                          # position -1, row B), and the dense work runs
                          # over Tp rows instead of B x S. last_only reads
                          # each row's last token from the packed axis.
) -> ChunkOutput:
    """Run S tokens per sequence through all layers against the paged cache.

    Covers prefill (S = prompt chunk, positions start at the cached prefix
    length) and decode (S = 1) with one traced graph per (B, S).

    ``with_logits=False`` skips the LM-head projection entirely — an
    intermediate chunk of a long prefill only needs its KV side effects, and
    the head matmul reads the full [V, H] embedding from HBM (0.77 GB on
    Llama-3 vocab) for logits nobody consumes.

    ``packing`` (the plain ragged round): same per-token mathematics with
    the padding of the ``[B, S]`` rectangle left out of everything but the
    page write and attention — a token's row through a matmul does not
    depend on which other rows share the call. ``hidden`` comes back
    ``[1, Tp, H]``.
    """
    if cfg.latent_kv:   # latent cache, layers of two kinds: models/mla.py
        from distributed_gpu_inference_tpu.models import mla

        if (dense_attn_fn is not None or attn_override is not None
                or collect_layers is not None):
            raise NotImplementedError(
                "a latent-attention model has no sequence-parallel, "
                "overridden or feature-collecting forward")
        return mla.forward_chunk(
            cfg, params, token_ids, positions, kv, block_tables, kv_lens,
            block_size=block_size, last_only=last_only,
            with_logits=with_logits, collect_routing=collect_routing,
            pallas=pallas, packing=packing,
        )
    if pallas and heads is not None:
        raise ValueError(
            "pallas=True allows every kernel bare, which is one chip's: a "
            "caller under a mesh passes pallas=False beside heads"
        )
    unpack = to_rect = tp = None
    if packing is not None:
        tp = token_ids.shape[0]
        rect = (block_tables.shape[0], packing.width)
        at = (packing.row, packing.col)
        # the rectangle's own view of the round, built once for all layers:
        # where each of its positions reads from on the packed axis (Tp =
        # nothing there) and the position it holds (-1 = nothing to write)
        to_rect = jnp.full(rect, tp, jnp.int32).at[at].set(
            jnp.arange(tp, dtype=jnp.int32), mode="drop")
        unpack = (to_rect, *at)
        token_ids, rope_positions = token_ids[None], positions[None]
        positions = jnp.full(rect, -1, jnp.int32).at[at].set(
            positions, mode="drop")
    else:
        rope_positions = positions
    mixed = cfg.mixed_attention
    if mixed and (dense_attn_fn is not None or attn_override is not None
                  or collect_layers is not None or "k_scale" in kv):
        raise NotImplementedError(
            "a model of mixed attention kinds has no sequence-parallel, "
            "overridden or feature-collecting forward and no int8 pools")
    # a block table a cache kind, side by side in ``block_tables``
    kinds = [kind for kind, _, _ in cfg.cache_kinds]
    m_cols = block_tables.shape[1] // len(kinds)
    tables = {kind: block_tables[:, i * m_cols:(i + 1) * m_cols] if mixed
              else block_tables for i, kind in enumerate(kinds)}
    index = None
    if cfg.index_topk:
        if (dense_attn_fn is not None or attn_override is not None
                or "k_scale" in kv):
            raise NotImplementedError(
                "a model with an indexer has no sequence-parallel or "
                "overridden attention and no int8 pools: the selection is "
                "computed from its own index-key pool")
        index = _index_plan(cfg, kv["k"].shape[1], block_tables, positions,
                            rope_positions, packing, block_size)
    mixer = None
    if cfg.ssm_num_heads:
        from distributed_gpu_inference_tpu.models import ssd

        if (dense_attn_fn is not None or attn_override is not None
                or collect_layers is not None or "k_scale" in kv):
            raise NotImplementedError(
                "a model with a state-space mixer has no sequence-parallel, "
                "overridden or feature-collecting forward and no int8 pools")
        rows = kv[ssd.STATE].shape[1]
        if block_tables.shape[0] != rows:
            raise ValueError(
                f"{cfg.name}: {block_tables.shape[0]} batch rows over a "
                f"state pool of {rows}: a batch row is a state row")
        mixer = (ssd.chunk_plan(cfg, packing, rope_positions[0], positions,
                                rows),
                 ssd.kernels_on(cfg, kv[ssd.STATE].dtype, pallas))
    b, s = token_ids.shape
    hidden = embed_tokens(params, token_ids, cfg)

    safe_pos = jnp.maximum(rope_positions, 0)
    quant_kv = "k_scale" in kv

    def kind_step(kind):
        """``_layer_step`` with what a layer kind fixes, built once a
        graph: its block table, rotation, window and attention calls."""
        kind_tables = tables[kind]
        window = cfg.sliding_window if kind == "sliding" else None
        cos, sin = rope_tables(cfg, kind, safe_pos)
        in_place = None
        if dense_attn_fn is None and attn_override is None:
            in_place = _in_place_kv(
                cfg, kv, kind_tables, positions, kv_lens, block_size,
                token_index=to_rect, num_tokens=tp, kind=kind,
                pallas=pallas, heads=heads,
            )
        if attn_override is not None:
            # int8 pools: the override receives the layer's scale pools too
            # -- the seq-sharded shard_map ops dequantize their local page
            # shards (scales ride the same block axis;
            # parallel/ring_attention.py)
            def attn_fn(q, layer_k, layer_v, layer_ks=None, layer_vs=None):
                return attn_override(
                    q, layer_k, layer_v, kind_tables, positions, kv_lens,
                    layer_ks, layer_vs,
                )
        else:
            def attn_fn(q, layer_k, layer_v, layer_ks=None, layer_vs=None,
                        **sel):
                return paged_attention(
                    q, layer_k, layer_v, kind_tables, positions, kv_lens,
                    block_size, impl="auto" if pallas else "xla",
                    window=window,
                    k_scale=layer_ks, v_scale=layer_vs, **sel,
                )

        return functools.partial(
            _layer_step,
            cfg,
            block_size,
            block_tables=kind_tables,
            write_positions=positions,
            cos=cos,
            sin=sin,
            attn_fn=attn_fn,
            fused_decode=(
                s == 1
                and decode_attention_path(
                    cfg, kind_tables.shape[1] * block_size, quant_kv,
                    pallas, heads) == "fused"
                and dense_attn_fn is None
                and attn_override is None
                and packing is None
            ),
            kv_lens=kv_lens,
            dense_attn_fn=dense_attn_fn,
            emit_hidden=collect_layers is not None,
            pallas=pallas,
            unpack=unpack,
            moe_live=rope_positions >= 0,
            emit_routing=collect_routing,
            in_place=in_place,
            index=index,
            kind=kind,
            mixer=mixer,
            heads=heads,
        )

    steps = {kind: kind_step(kind) for kind in kinds}
    groups = layer_groups(cfg)
    sizes = dict(groups)
    split = {group: _split_layers(params[group], pallas)
             for group, _ in groups}
    pools = {
        kind: tuple((kv[name], kv[name + "_scale"]) if quant_kv else kv[name]
                    for name in kind_pools(cfg, kind))
        for kind in kinds}
    # what rides the layers beside the pages: an indexer's keys, or a
    # mixer's state pool and tails
    extra_names = () if index is None else tuple(
        name for name in (INDEX_KEYS, INDEX_SCAN_KEYS) if name in kv)
    if mixer is not None:
        extra_names = ssd.POOLS
    extra = tuple(kv[name] for name in extra_names)
    # where each stack and each kind's pools stand, in layers
    at_w = {group: 0 for group, _ in groups}
    at_c = {kind: 0 for kind in kinds}
    moe = None
    emitted: Dict[str, list] = {"hs": [], "routing": [], "fetched": []}

    def run(state, group, scanned, n, w0, delta):
        """``n`` layers of one stack (``scanned``: their leaves that ride
        the scan) from layer ``w0`` of the stack, their pages ``delta``
        layers further on in their kind's pools (both scalars, traced
        inside a repeated unit)."""
        hidden_, pools_, extra_ = state
        kind = group_kind(cfg, group)
        step = functools.partial(steps[kind], stacked=split[group][1],
                                 cache_delta=delta)
        (hidden_, k_out, v_out, _, *extra_), outs = lax.scan(
            lambda c, lp: step(c, lp),
            (hidden_, *pools_[kind], w0, *extra_), scanned)
        return (hidden_, {**pools_, kind: (k_out, v_out)},
                tuple(extra_)), outs

    def leaves(group, lo, n, repeat=None):
        """Layers ``lo .. lo + n`` of a stack's scanned leaves (the whole
        stack as it is); with ``repeat`` as ``[repeat, n / repeat, ...]``."""
        if lo == 0 and n == sizes[group] and repeat is None:
            return split[group][0]

        def cut(a):
            a = a[lo:lo + n]
            return a if repeat is None else a.reshape(
                repeat, n // repeat, *a.shape[1:])
        return jax.tree.map(cut, split[group][0])

    def take(outs, lead=0):
        """A run's emissions onto the pass's: the routed experts' counters
        summed over its layers, the rest a layer each (``lead`` leading
        axes of a repeated unit folded into the layer axis)."""
        nonlocal moe
        layer_hs, stats, routing, fetched = outs
        if stats is not None:
            sums = {name: jnp.sum(v) for name, v in stats.items()}
            moe = sums if moe is None else {
                name: moe[name] + v for name, v in sums.items()}
        for name, v in (("hs", layer_hs), ("routing", routing),
                        ("fetched", fetched)):
            if v is not None:
                emitted[name].append(
                    v.reshape(-1, *v.shape[1 + lead:]) if lead else v)

    state = (hidden, pools, extra)
    for repeat, runs in layer_units(cfg):
        if repeat == 1:
            for group, n in runs:
                kind = group_kind(cfg, group)
                state, outs = run(
                    state, group, leaves(group, at_w[group], n), n,
                    jnp.int32(at_w[group]), at_c[kind] - at_w[group])
                at_w[group] += n
                at_c[kind] += n
                take(outs)
            continue
        # a repeated period: one scan over its repeats, its runs inside
        w_lo = {g: at_w[g] for g, _ in runs}
        c_lo = dict(at_c)
        per_c = {kind: sum(n for g, n in runs if group_kind(cfg, g) == kind)
                 for kind in kinds}
        xs = {g: leaves(g, w_lo[g], repeat * n, repeat) for g, n in runs}

        def period(st, px):
            p_, lp_ = px
            outs_ = []
            seen = {kind: 0 for kind in kinds}
            for g, n in runs:
                kind = group_kind(cfg, g)
                w0 = w_lo[g] + p_ * n
                st, out = run(
                    st, g, lp_[g], n, w0,
                    c_lo[kind] + p_ * per_c[kind] + seen[kind] - w0)
                seen[kind] += n
                outs_.append(out)
            return st, outs_

        state, outs = lax.scan(
            period, state, (jnp.arange(repeat, dtype=jnp.int32), xs))
        for (g, n), out in zip(runs, outs):
            at_w[g] += repeat * n
            at_c[group_kind(cfg, g)] += repeat * n
            take(out, lead=1)
    hidden, pools, ki_out = state
    layer_hs, routing, fetched = (
        (v[0] if len(v) == 1 else jnp.concatenate(v, axis=0)) if v else None
        for v in (emitted["hs"], emitted["routing"], emitted["fetched"]))
    if fetched is not None:
        fetched = jnp.sum(fetched)
    new_kv = {}
    for kind in kinds:
        for name, ent in zip(kind_pools(cfg, kind), pools[kind]):
            if quant_kv:
                new_kv[name], new_kv[name + "_scale"] = ent
            else:
                new_kv[name] = ent
    new_kv.update(zip(extra_names, ki_out))
    features = (
        jnp.concatenate([layer_hs[i] for i in collect_layers], axis=-1)
        if collect_layers is not None else None
    )

    if not with_logits:
        return ChunkOutput(
            hidden=hidden, kv=new_kv, logits=None,
            features=features, moe=moe, routing=routing,
            index_fetched=fetched,
        )
    if last_only and packing is not None:
        logits_in = jnp.take(hidden[0], packing.last, axis=0)[:, None]
    elif last_only:
        # last valid token per sequence = kv_lens - 1 mapped into the chunk:
        # chunk covers positions [kv_len - n_valid, kv_len); the last valid
        # chunk index is (number of valid positions in chunk) - 1.
        n_valid = jnp.sum((positions >= 0).astype(jnp.int32), axis=1)  # [B]
        last_idx = jnp.maximum(n_valid - 1, 0)
        logits_in = jnp.take_along_axis(
            hidden, last_idx[:, None, None].astype(jnp.int32), axis=1
        )  # [B, 1, H]
    else:
        logits_in = hidden
    with jax.named_scope("dgi_head"):
        logits = project_logits(cfg, params, logits_in)
    return ChunkOutput(hidden=hidden, kv=new_kv, logits=logits,
                       features=features, moe=moe, routing=routing,
                       index_fetched=fetched)


def forward_hidden_chunk(
    cfg: ModelConfig,
    params: Params,
    hidden: jax.Array,
    positions: jax.Array,
    kv: KVPools,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    *,
    block_size: int = 16,
    layer_offset: int = 0,
) -> Tuple[jax.Array, KVPools]:
    """Forward pre-embedded hidden states through this shard's layers.

    The pipeline-parallel entry point: a stage that owns layers [a, b) calls
    this on activations received from the previous stage (reference analogue:
    ``worker/distributed/model_shard.py:173-228`` ModelShard.forward).
    ``params['layers']`` holds only the owned layers; ``kv`` likewise.
    int8 KV pools are fenced (stage pools are bf16/f32 today; a bare-array
    scan carry would silently truncate rows into the int8 pool).
    """
    if "k_scale" in kv or cfg.index_topk or cfg.described_per_layer:
        raise NotImplementedError(
            "forward_hidden_chunk over int8 KV pools, an index-key pool or "
            "layers described one by one is not wired"
        )
    safe_pos = jnp.maximum(positions, 0)
    cos, sin = _rope_angles(safe_pos, cfg.head_dim, cfg.rope_theta)

    def attn_fn(q, layer_k, layer_v):
        return paged_attention(
            q, layer_k, layer_v, block_tables, positions, kv_lens, block_size,
            window=cfg.sliding_window,
        )

    scanned, stacked = _split_layers(params["layers"], True)
    step = functools.partial(
        _layer_step,
        cfg,
        block_size,
        block_tables=block_tables,
        write_positions=positions,
        cos=cos,
        sin=sin,
        attn_fn=attn_fn,
        fused_decode=hidden.shape[1] == 1 and decode_attention_path(
            cfg, block_tables.shape[1] * block_size, False
        ) == "fused",
        kv_lens=kv_lens,
        stacked=stacked,
        in_place=_in_place_kv(
            cfg, kv, block_tables, positions, kv_lens, block_size
        ),
    )
    (hidden, k_pool, v_pool, _), _ = lax.scan(
        lambda c, lp: step(c, lp),
        (hidden, kv["k"], kv["v"], jnp.int32(0)),
        scanned,
    )
    return hidden, {"k": k_pool, "v": v_pool}


def embed_tokens(
    params: Params, token_ids: jax.Array, cfg: ModelConfig
) -> jax.Array:
    """First pipeline stage: token embedding (reference model_shard.py:163-166).
    Gemma scales embeddings by sqrt(hidden_size) — cfg is REQUIRED so no call
    site can silently skip the scaling convention."""
    hidden = jnp.take(params["embedding"], token_ids, axis=0)
    if cfg.scale_embeddings:
        hidden = hidden * jnp.asarray(
            cfg.hidden_size**0.5, dtype=hidden.dtype
        )
    if cfg.embedding_multiplier != 1.0:
        hidden = hidden * jnp.asarray(cfg.embedding_multiplier, hidden.dtype)
    return hidden


def project_logits(cfg: ModelConfig, params: Params, hidden: jax.Array) -> jax.Array:
    """Last pipeline stage: final norm + LM head (reference model_shard.py:168-171,
    get_logits:230-246)."""
    normed = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps,
                      cfg.norm_offset)
    # NOT dict.get(k, default): the default would be evaluated eagerly and
    # KeyError on a last pipeline stage that carries lm_head but no embedding
    head = params["lm_head"] if "lm_head" in params else params["embedding"]
    logits = jnp.einsum(
        "bsh,vh->bsv", normed.astype(jnp.float32), head.astype(jnp.float32)
    )
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    if cfg.final_logit_softcap is not None:  # Gemma-2 style soft capping
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits
