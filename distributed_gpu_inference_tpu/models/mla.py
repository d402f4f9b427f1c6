"""Latent-attention (MLA) decoder with a shared expert beside routed ones,
sandwich norms and leading dense layers — the openPangu-Ultra-MoE recipe —
as ``forward_chunk`` over a **latent paged cache**.

What differs from ``models/llama.py``, which dispatches here on
``cfg.latent_kv`` (same signatures, same ``ChunkOutput``, so the engine, the
batcher and the cache manager serve this model as they serve the others):

- **The cache** is one pool ``{"ckv": [L, N, Bk, W]}``: a token's normed KV
  latent and its one shared, rotated rope key, no head axis: 576 values,
  ``L x 576 x 2`` bytes a token at the published widths, in rows of ``W`` =
  640 lanes (``pool_width``: whole 128-lane tiles, as the chip stores them
  anyway). It is written and read in place in the stacked pool by layer
  index (``ragged_kv_path`` reads ``in_place``).
- **Attention has two forms of the same numbers.** *Expanded*: the cached
  latents are up-projected through ``W_UK`` / ``W_UV`` into per-head keys
  and values. *Absorbed*: the query is folded into the latent space
  (``q~ = q_n W_UK^T``), all heads share the one 576-wide key, and the
  result is lifted by ``W_UV``. The XLA path (the CPU, ``pallas=False``)
  takes expanded for a multi-token chunk and absorbed for one token a row;
  the kernel path (``ops/mla_attention_pallas.py``) is the absorbed form for
  both, since at the 256-token pieces the engine cuts a prompt into,
  re-expanding a growing prefix for every piece costs what absorbing the
  piece's queries costs and needs ``[ctx, heads, 256]`` temporaries.
- **Layers are described per layer**: ``params["dense_layers"]`` stacks the
  ``cfg.first_k_dense`` leading layers (dense SwiGLU), ``params["layers"]``
  the rest (router over all ``num_experts``, the ``held_experts`` this chip
  stores, a shared expert). Two scans, one layer body.
- **A hybrid model interleaves layers that differ in their cache**
  (``cfg.full_attn_layers``): latent layers as above, without rotation
  where ``cfg.mla_use_nope``, and gated delta-rule layers
  (``models/kda.py``) whose past is a row of a state pool that rides the
  same ``kv`` dict. The latent pool then has a layer for each latent layer
  only, and the scans run over ``layer_units``: one period of the pattern,
  repeated, and the odd ends.
- **A model with an indexer** (``cfg.index_topk``: learned sparse
  attention, ``ops/index_select.py``) attends a selection ``S_t`` of each
  query's cached tokens. A ``full`` layer (``cfg.index_kinds``) holds the
  indexer (stacks ``ix_dense_layers`` / ``ix_layers``), writes its index
  keys into the pool ``"ki"`` (a layer a full layer, addressed by the
  latent pages' block table), computes ``keep`` and hands it on in the
  layer scan's carry; a ``shared`` layer holds none and attends the carry
  (IndexShare). On the kernel path a scan step's carry also holds the
  row's selected pages laid out for the decode kernel, built once a full
  layer and walked again by the shared layers behind it.
- **Latent layers of two attention kinds** (``cfg.layer_types`` over
  latent pages): a ``sliding`` layer has a head count, latent ranks, head
  sizes and a rotation of its own (``cfg.latent_kind``), attends the last
  ``cfg.sliding_window`` keys and writes its rows into a SECOND latent
  pool, ``"ckv_win"``, as wide as its own cached row and a window's worth
  of pages long, under the window kind's block table (a row of
  ``block_tables`` holds both tables side by side, the full kind's first:
  ``runtime/kv_cache._WindowPages``). Its stacks carry the prefix ``sw_``.
  Where such a model has an indexer the ``full`` layers hold it; the index
  keys follow the full kind's table and the selection is carried from full
  layer to full layer, past the sliding layers, which attend their window
  and no selection.
- **A gate a head** (``cfg.head_gate``): ``sigmoid(x W_g)`` of the layer's
  normed input, one value a head, on the head's output before ``W_o``. **The
  latent rescale** (``cfg.mla_lora_rescale``): a constant on the two normed
  latents, by the layer's own ranks.
- **The expert layer computes the chip's share**: sigmoid scores over all
  published experts, top-k kept, normalised and scaled as published; pairs that fall on experts held elsewhere are routed nowhere,
  and nothing stands in for them.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_gpu_inference_tpu.models.configs import (
    LatentKind,
    ModelConfig,
)
from distributed_gpu_inference_tpu.models.llama import (
    INDEX_KEYS,         # the index-key pool's name in the ``kv`` dict
    INDEX_SCAN_KEYS,    # a scan's index keys in context order
)
from distributed_gpu_inference_tpu.ops.quantization import (
    matmul as qmm,
    matmul_stacked,
    split_stacked_quant,
)

Params = Dict[str, Any]
POOL = "ckv"
# the sliding kind's latent pool of a model of two attention kinds (the
# suffix is ``models/llama.WINDOW_POOLS``: no page copy, no prefix of its own)
POOL_WIN = "ckv_win"
_NEG_INF = -1e30
# norm vectors are drawn around one, not set to it: four norms a layer are
# otherwise interchangeable and a misplaced one is invisible
_NORM_SPREAD = 0.25


_LANES = 128
# leaves kept in float32 whatever the model's dtype (``leaf_specs`` kinds)
_F32_KINDS = "atbro"


def latent_width(cfg: ModelConfig, kind: str = "full") -> int:
    """Values cached a token a layer of ``kind``: the latent and the rope
    key."""
    return cfg.latent_kind(kind).cached


def pool_width(cfg: ModelConfig, kind: str = "full") -> int:
    """Lanes of a row of ``kind``'s pool: ``latent_width`` rounded up to
    whole 128-lane tiles, the pad lanes zero. A TPU array is tiled in 128
    lanes (a 576-wide one takes 640 in HBM whatever its shape says), and a
    page DMA must cover whole tiles, so the pool says what it takes."""
    return -(-latent_width(cfg, kind) // _LANES) * _LANES


_KDA = "kda_"
_IX = "ix_"
_SW = "sw_"


def kind_of(group: str) -> str:
    """The attention kind of a latent stack, by its name."""
    return "sliding" if group.startswith(_SW) else "full"


def latent_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """The attention kinds of the model's latent layers, the full kind
    first: each has a pool and a block table of its own."""
    return ("full", "sliding") if cfg.mixed_attention else ("full",)


def pool_of(kind: str) -> str:
    return POOL_WIN if kind == "sliding" else POOL


def group_of(cfg: ModelConfig, layer: int) -> str:
    """The parameter stack layer ``layer`` (0-based) lies in: by its MLP
    (``dense_layers`` / ``layers``), for a gated delta-rule layer the
    prefix ``kda_``, for a sliding latent layer of a model of two attention
    kinds ``sw_`` and for a latent layer that holds an indexer (a ``full``
    one) the prefix ``ix_``."""
    lead = cfg.first_k_dense if cfg.num_experts else 0
    name = "dense_layers" if layer < lead else "layers"
    if cfg.layer_kinds[layer] == "kda":
        return _KDA + name
    if cfg.mixed_attention and cfg.layer_types[layer] == "sliding":
        return _SW + name
    if cfg.index_kinds and cfg.index_kinds[layer] == "full":
        return _IX + name
    return name


def layer_groups(cfg: ModelConfig) -> Tuple[Tuple[str, int], ...]:
    """(params key, layers) of the homogeneous stacks: the latent layers'
    in layer order (those with an indexer ahead of those without), then
    the gated delta-rule layers'."""
    names = [group_of(cfg, li) for li in range(cfg.num_layers)]
    order = (_IX + "dense_layers", "dense_layers", _IX + "layers", "layers",
             _SW + "dense_layers", _SW + "layers",
             _KDA + "dense_layers", _KDA + "layers")
    return tuple((g, names.count(g)) for g in order if g in names)


def layer_units(cfg: ModelConfig
                ) -> Tuple[Tuple[int, Tuple[Tuple[str, int], ...]], ...]:
    """The forward pass as ``(repeat, runs)`` units in layer order, ``runs``
    the ``(params key, layers)`` of a unit's homogeneous stretches. A model
    of one cache is one unit; a hybrid is cut after every latent layer, a
    model whose layers share selections after every layer that computes
    one, a model of two attention kinds before every full layer, and equal
    neighbours merge, so that a repeated period is traced once."""
    names = [group_of(cfg, li) for li in range(cfg.num_layers)]
    cuts = [li for li in cfg.full_attn_layers if li < cfg.num_layers]
    if cfg.mixed_attention:
        cuts = [li for li, kind in enumerate(cfg.layer_types)
                if kind == "full" and li]
    if "shared" in cfg.index_kinds:
        cuts = [li + 1 for li, kind in enumerate(cfg.index_kinds)
                if kind == "full" and li + 1 < cfg.num_layers]
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


def leaf_specs(cfg: ModelConfig, group: str) -> Dict[str, Tuple[tuple, int, str]]:
    """name → (shape of ONE layer, fan-in, kind) for a group's leaves.
    kind: ``q`` a matmul weight (quantized where the engine quantizes),
    ``d`` a dense bf16 weight, ``n`` a norm vector; float32 vectors drawn
    as the family draws them: ``a`` (``A_log``), ``t`` (``dt_bias``), ``b``
    (the router's selection bias), and the state-space mixer's that are not
    drawn at all: ``r`` (``A_log = log(1..H)``) and ``o`` (ones); ``z`` a
    vector drawn around zero (a LayerNorm's or a convolution's bias)."""
    h, nh = cfg.hidden_size, cfg.num_heads
    if group.startswith(_KDA):
        kh, kd, taps = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
        p = kh * kd
        spec = {
            "attn_norm": ((h,), 0, "n"),
            # q, k and v side by side: one matmul, one tail
            "wqkv": ((h, 3 * p), h, "q"),
            "conv": ((taps, 3 * p), taps, "d"),
            "w_fa": ((h, kd), h, "q"),
            "w_fb": ((kd, p), kd, "q"),
            "dt_bias": ((p,), 0, "t"),
            "a_log": ((kh,), 0, "a"),
            "w_b": ((h, kh), h, "d"),
            "w_ga": ((h, kd), h, "q"),
            "w_gb": ((kd, p), kd, "q"),
            "o_norm": ((kd,), 0, "n"),
            "wo": ((p, h), p, "q"),
            "mlp_norm": ((h,), 0, "n"),
        }
    else:
        k = cfg.latent_kind(kind_of(group))
        nh, dn, dr, dv, rq, rkv = k.heads, k.nope, k.rope, k.v, k.q_rank, \
            k.kv_rank
        spec = {"attn_norm": ((h,), 0, "n")}
        # what reads a RESCALED latent is drawn at ``hidden_size ** -0.5``:
        # the rescale makes up for weights initialised at one deviation
        # whatever they read (LongCat-Flash, arXiv:2509.01322, scale
        # correction for MLA: ``a ** 2 x rank = hidden``), which a draw at
        # the fan-in's is not, so that q, k and v come out at the variance
        # the other latent models have them
        fan_q, fan_kv = (h, h) if cfg.mla_lora_rescale else (rq, rkv)
        if rq:
            spec.update({
                "wq_a": ((h, rq), h, "q"),
                "q_a_norm": ((rq,), 0, "n"),
                "wq_b": ((rq, nh * (dn + dr)), fan_q, "q"),
            })
        else:       # no query low-rank: one projection, no norm
            spec["wq"] = ((h, nh * (dn + dr)), h, "q")
        spec.update({
            "wkv_a": ((h, rkv + dr), h, "q"),
            "kv_a_norm": ((rkv,), 0, "n"),
            # W_kvb split a head into W_UK and W_UV; bf16: the absorbed
            # products are batched over heads, not the int8 kernel's shape
            "w_uk": ((nh, rkv, dn), fan_kv, "d"),
            "w_uv": ((nh, rkv, dv), fan_kv, "d"),
            "wo": ((nh * dv, h), nh * dv, "q"),
            "mlp_norm": ((h,), 0, "n"),
        })
        if cfg.head_gate:
            # one column a head: narrow, bf16 (``models/llama.py``'s name)
            spec["w_hgate"] = ((h, nh), h, "d")
        if group.startswith(_IX):
            # the indexer: queries from the query latent or the normed
            # input, ONE key a token and the heads' weights from the normed
            # input (narrow: bf16, as the K/V recipe's), the key's LayerNorm
            hi, di = cfg.index_num_heads, cfg.index_head_dim
            q_in = rq if cfg.index_query_input == "q_latent" else h
            spec.update({
                "wqi": ((q_in, hi * di),
                        fan_q if q_in == rq else q_in, "q"),
                "wki": ((h, di), h, "d"),
                "ww": ((h, hi), h, "d"),
                "ki_norm": ((di,), 0, "n"),
                "ki_bias": ((di,), 0, "z"),
            })
    if cfg.sandwich_norm:
        spec["post_attn_norm"] = ((h,), 0, "n")
        spec["post_mlp_norm"] = ((h,), 0, "n")
    if group.endswith("dense_layers") or not cfg.num_experts:
        i = cfg.intermediate_size
        spec.update({
            "w_gate": ((h, i), h, "q"),
            "w_up": ((h, i), h, "q"),
            "w_down": ((i, h), i, "q"),
        })
    else:
        mi, held = cfg.moe_intermediate_size, cfg.num_held_experts
        spec.update({
            "w_router": ((h, cfg.num_experts), h, "d"),
            "we_gate": ((held, h, mi), h, "q"),
            "we_up": ((held, h, mi), h, "q"),
            "we_down": ((held, mi, h), mi, "q"),
        })
        if cfg.router_selection_bias:
            spec["router_bias"] = ((cfg.num_experts,), 0, "b")
        if cfg.n_shared_experts:
            ms = mi * cfg.n_shared_experts
            spec.update({
                "ws_gate": ((h, ms), h, "q"),
                "ws_up": ((h, ms), h, "q"),
                "ws_down": ((ms, h), ms, "q"),
            })
    return spec


def name_key(root: jax.Array, name: str) -> jax.Array:
    """The key a named leaf is drawn from (stable across processes)."""
    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def draw_leaf(key: jax.Array, shape: tuple, fan_in: int, kind: str
              ) -> jax.Array:
    """ONE layer's float32 draw of a leaf: what both the program's init and
    the benchmark's reference (``harness/reference_mla_moe.py``) round."""
    if kind == "a":     # A_log = log U(1, 16), a head
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, minval=1.0, maxval=16.0))
    if kind == "r":     # A_log = log(1..H), the state-space mixer's ramp
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if kind == "o":     # ones (the mixer's skip D)
        return jnp.ones(shape, jnp.float32)
    if kind == "t":     # dt_bias: the inverse softplus of a log-uniform dt
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, minval=math.log(1e-3),
            maxval=math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "b":     # moves some selections, so a dropped bias shows
        return 0.1 * x
    if kind == "n":
        return 1.0 + _NORM_SPREAD * x
    if kind == "z":
        return _NORM_SPREAD * x
    return x * (fan_in ** -0.5)


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype: Optional[jnp.dtype] = None,
    mode: Optional[str] = None, groups=None, specs=None,
) -> Params:
    """Random weights, a leaf a layer at a time (one float32 layer slice
    live): the same draws whether kept in ``dtype`` or, with ``mode``,
    quantized as the engine quantizes (``ops.quantization.quantize_weight``)
    — so a model whose full-precision tree outgrows the chip starts the way
    a small one does. ``groups`` / ``specs``: the stacks and their leaves
    where they are not this recipe's (``models/llama.py``: a K/V model
    described per layer)."""
    from distributed_gpu_inference_tpu.ops.quantization import quantize_weight

    dtype = jnp.dtype(dtype or cfg.dtype)
    h, v = cfg.hidden_size, cfg.vocab_size

    @functools.lru_cache(maxsize=None)
    def gen(shape, fan_in, kind):
        def body(carry, k):
            w = draw_leaf(k, shape, fan_in, kind)
            if kind == "q" and mode is not None:
                q = quantize_weight(w, mode)
                return carry, (q["qw"], q["scale"])
            return carry, w if kind in _F32_KINDS else w.astype(dtype)

        return jax.jit(lambda keys: lax.scan(body, 0, keys)[1])

    params: Params = {}
    specs = specs or functools.partial(leaf_specs, cfg)
    for group, n in groups or layer_groups(cfg):
        leaves: Dict[str, Any] = {}
        for name, (shape, fan_in, kind) in specs(group).items():
            keys = jax.random.split(name_key(key, f"{group}.{name}"), n)
            out = gen(shape, fan_in, kind)(keys)
            if isinstance(out, tuple):
                out = {"qw": out[0], "scale": out[1]}
            jax.block_until_ready(out)   # bound the float32 slice's life
            leaves[name] = out
        params[group] = leaves
    for name in ("embedding",) + (
            () if cfg.tie_word_embeddings else ("lm_head",)):
        params[name] = draw_leaf(
            name_key(key, name), (v, h), h, "d").astype(dtype)
    params["final_norm"] = draw_leaf(
        name_key(key, "final_norm"), (h,), 0, "n").astype(dtype)
    return params


def init_kv_pools(cfg: ModelConfig, num_blocks: int, block_size: int = 16,
                  dtype: Optional[jnp.dtype] = None,
                  state_rows: Optional[int] = None,
                  window_blocks: Optional[int] = None
                  ) -> Dict[str, jax.Array]:
    """The latent paged pool ``[L, N, Bk, W]``, ``L`` the latent layers
    (of a model of two attention kinds: the full ones, and beside it
    ``"ckv_win"`` ``[L_sliding, window_blocks, Bk, W_sliding]``);
    block 0 is the pad block. A one-byte float dtype (fp8) stores narrower
    rows; int8 with scales is not built. A hybrid model's dict also holds
    the state pool of ``state_rows`` sequences (``models/kda.py``), a
    model with an indexer's the index-key pool ``"ki"`` ``[L_full, N, Bk,
    lanes]`` (``ops/index_select.py``)."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    if dtype == jnp.int8:
        raise NotImplementedError("int8 latent pools (scaled) are not built")
    pools = {POOL: jnp.zeros(
        (cfg.num_cache_layers, num_blocks, block_size, pool_width(cfg)),
        dtype)}
    if cfg.mixed_attention:
        # pages per layer kind: the sliding layers' rows, as wide as THEIR
        # cached row, in a pool a window's worth of blocks long
        if not window_blocks or window_blocks < 2:
            raise ValueError(
                f"{cfg.name}: the sliding kind's pool needs its number of "
                "blocks")
        if dtype.itemsize == 1:
            raise NotImplementedError(
                f"{cfg.name}: one-byte latent pools of a model of two "
                "attention kinds are not built")
        pools[POOL_WIN] = jnp.zeros(
            (cfg.num_window_layers, window_blocks, block_size,
             pool_width(cfg, "sliding")), dtype)
    if cfg.index_topk:
        # one index key a token a FULL layer, addressed by the latent
        # pages' block table: a page copy, a prefix hit and a resume bring it
        from distributed_gpu_inference_tpu.ops.index_select import pool_lanes

        pools[INDEX_KEYS] = jnp.zeros(
            (cfg.num_index_layers, num_blocks, block_size,
             pool_lanes(cfg.index_head_dim)), dtype)
    if cfg.num_kda_layers:
        from distributed_gpu_inference_tpu.models import kda

        if state_rows is None:
            raise ValueError(
                f"{cfg.name}: a state pool needs its number of rows")
        pools.update(kda.init_state_pools(cfg, state_rows, conv_dtype=dtype))
    return pools


# ---------------------------------------------------------------------------
# attention over the latent pool: the XLA forms
# ---------------------------------------------------------------------------


def _visible(positions: jax.Array, kv_lens: jax.Array, ctx: int) -> jax.Array:
    key_pos = jnp.arange(ctx, dtype=jnp.int32)[None, None, :]
    return (positions[:, :, None] >= key_pos) \
        & (key_pos < kv_lens[:, None, None])                    # [B, S, J]


def _softmax_rows(scores: jax.Array, visible: jax.Array) -> jax.Array:
    """Float32 softmax over the last axis; a query that sees nothing (a pad)
    gives zeros. scores [B, H, S, J], visible [B, S, J]."""
    scores = jnp.where(visible[:, None], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(visible[:, None], jnp.exp(scores - m), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    return p / jnp.where(denom > 0, denom, 1.0)


def latent_attention_xla(
    cfg: ModelConfig,
    q_n: jax.Array,            # [B, S, Nh, dn] nope half of the queries
    q_r: jax.Array,            # [B, S, Nh, dr] rotated rope half
    w_uk: jax.Array,           # [Nh, rkv, dn]
    w_uv: jax.Array,           # [Nh, rkv, dv]
    ctx: jax.Array,            # [B, J, W] the rows' cached (latent ; rope ;
                               # pad lanes)
    positions: jax.Array,      # [B, S] int32, -1 = pad
    kv_lens: jax.Array,        # [B]
    form: str,                 # "expanded" | "absorbed"
    keep: Optional[jax.Array] = None,   # [B, S, J] float32 > 0: the cached
                               # tokens each query attends (a selection)
    kind: Optional[LatentKind] = None,  # the layer's kind (None: the
                               # model's one): its widths and its window
) -> jax.Array:
    """Both forms of the one attention, in float32 → [B, S, Nh, dv]."""
    f32 = jnp.float32
    kind = kind or cfg.latent_kind()
    rkv = kind.kv_rank
    c = ctx[..., :rkv].astype(f32)
    k_r = ctx[..., rkv:rkv + kind.rope].astype(f32)
    q_n, q_r = q_n.astype(f32), q_r.astype(f32)
    w_uk, w_uv = w_uk.astype(f32), w_uv.astype(f32)
    scale = kind.qk ** -0.5
    visible = _visible(positions, kv_lens, ctx.shape[1])
    if kind.window is not None:
        # a query at p attends the positions (p - window, p]
        key_pos = jnp.arange(ctx.shape[1], dtype=jnp.int32)[None, None, :]
        visible &= key_pos > positions[:, :, None] - kind.window
    if keep is not None:
        visible &= keep > 0
    rope_scores = jnp.einsum("bshr,bjr->bhsj", q_r, k_r)
    if form == "expanded":
        k_n = jnp.einsum("bjc,hcd->bjhd", c, w_uk)
        v = jnp.einsum("bjc,hcd->bjhd", c, w_uv)
        scores = jnp.einsum("bshd,bjhd->bhsj", q_n, k_n) + rope_scores
        p = _softmax_rows(scores * scale, visible)
        return jnp.einsum("bhsj,bjhd->bshd", p, v)
    q_abs = jnp.einsum("bshd,hcd->bshc", q_n, w_uk)
    scores = jnp.einsum("bshc,bjc->bhsj", q_abs, c) + rope_scores
    p = _softmax_rows(scores * scale, visible)
    u = jnp.einsum("bhsj,bjc->bshc", p, c)
    return jnp.einsum("bshc,hcd->bshd", u, w_uv)


def kernels_on(cfg: ModelConfig, padded_ctx: int, pool_dtype,
               pallas: bool = True) -> bool:
    """Trace-time choice of the latent kernels (``ops/mla_attention_pallas``),
    from what dispatch can see: a TPU backend, the caller's ``pallas`` (no
    mesh), a two-byte pool whose latent is lane-aligned, a padded context of
    at least the crossover the K/V kernels use."""
    from distributed_gpu_inference_tpu.ops import attention as _attention

    return (
        pallas and _attention.pallas_backend()
        and jnp.dtype(pool_dtype).itemsize == 2
        and all(cfg.latent_kind(kind).kv_rank % 128 == 0
                for kind in latent_kinds(cfg))
        and padded_ctx >= _attention._PALLAS_MIN_PADDED_CTX
    )


# ---------------------------------------------------------------------------
# the expert layer: the chip's share
# ---------------------------------------------------------------------------


def route(cfg: ModelConfig, x: jax.Array, w_router: jax.Array,
          bias: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """``models/llama.route_experts``: the one router of both recipes (this
    recipe's ``cfg.router_scoring`` is the sigmoid)."""
    from distributed_gpu_inference_tpu.models.llama import route_experts

    return route_experts(cfg, x, w_router, bias)


def _experts(
    x: jax.Array, lp: Dict[str, Any], cfg: ModelConfig, proj, *,
    live: Optional[jax.Array], stacked: Optional[Dict[str, Any]],
    layer_idx: Any,
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """``models/llama.expert_layer``: the one expert layer of both recipes
    (router, the held share with its per-pair live mask, the shared
    expert)."""
    from distributed_gpu_inference_tpu.models.llama import expert_layer

    return expert_layer(x, lp, cfg, proj, live=live, stacked=stacked,
                        layer_idx=layer_idx)


# ---------------------------------------------------------------------------
# the layer and the forward pass
# ---------------------------------------------------------------------------


def rope(cfg: ModelConfig, x: jax.Array, cos: jax.Array, sin: jax.Array
         ) -> jax.Array:
    """Rotation of the first ``2 x cos.shape[-1]`` values of every head of
    ``x [B, S, H, D]``. By adjacent pairs where ``cfg.rope_interleave``:
    the pairs ``(2i, 2i + 1)`` are brought side by halves and rotated
    there, so what comes out is the pairwise rotation with its values in
    that order -- a permutation both sides of every dot product share
    (queries and cached keys alike), which leaves the products as they
    are."""
    from distributed_gpu_inference_tpu.models.llama import apply_rope

    if cfg.rope_interleave:
        rot = 2 * cos.shape[-1]
        x = jnp.concatenate(
            [x[..., 0:rot:2], x[..., 1:rot:2], x[..., rot:]], axis=-1)
    return apply_rope(x, cos, sin)


def index_inputs(cfg: ModelConfig, lp: Dict[str, Any], x: jax.Array,
                 c_q: Optional[jax.Array], proj, index
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """What a full layer's indexer makes of the layer's normed input ``x``
    and query latent ``c_q``: rotated index queries ``[B, S, Hi, Di]``
    (from ``c_q`` where ``cfg.index_query_input`` says so), the chunk's
    index keys ``[B, S, Di]`` (LayerNorm with bias, then rotated) and the
    heads' weights ``[B, S, Hi]`` float32, scaled by ``(Hi x Di) ** -0.5``.
    The first ``cfg.index_rope_dims`` values of a head are rotated."""
    from distributed_gpu_inference_tpu.models.llama import layer_norm

    b, s, _ = x.shape
    hi, di = cfg.index_num_heads, cfg.index_head_dim
    q_in = c_q if cfg.index_query_input == "q_latent" else x
    qi = rope(cfg, proj(q_in, "wqi").reshape(b, s, hi, di), index.cos,
              index.sin)
    kin = layer_norm(proj(x, "wki"), lp["ki_norm"], lp["ki_bias"],
                     cfg.rms_norm_eps)
    kin = rope(cfg, kin[:, :, None, :], index.cos, index.sin)[:, :, 0]
    wts = proj(x, "ww").astype(jnp.float32) * (hi * di) ** -0.5
    return qi, kin, wts


def selection_state(keep: jax.Array, *, kernels: bool, tiles, unpack,
                    block_tables, positions, kv_lens, block_size: int
                    ) -> Dict[str, Any]:
    """A selection ``keep [B, S, J]`` as the layers that attend it take it
    (the layer that computed it and those that share it): the carry of the
    layer scan. The XLA forms take ``keep`` itself; the packed kernel its
    rows by query tile; a scan step's kernel the rows' selected pages
    (``ops/mla_attention_pallas.selected_walk``: the page list is the part
    of a shared selection a kernel can feel, built once a full layer).
    ``fetched``: for a one-token chunk, the cached tokens of the pages that
    hold a selected token (the kernel's rule, whichever form ran)."""
    from distributed_gpu_inference_tpu.ops import mla_attention_pallas as mla_k
    from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
        fetched_tokens,
    )

    state: Dict[str, Any] = {}
    step = keep.shape[1] == 1 and unpack is None
    if step:
        state["fetched"] = fetched_tokens(keep, block_size)
    if kernels and tiles is not None:
        state["keep_tiles"] = mla_k.keep_for_tiles(keep, tiles, unpack[2])
    elif kernels and step:
        state["walk"] = mla_k.selected_walk(
            keep, block_tables, positions[:, 0], kv_lens, block_size)
    else:
        state["keep"] = keep
    return state


def _latent_attention(
    cfg: ModelConfig, block_size: int, x: jax.Array, lp: Dict[str, Any],
    proj, kv: Dict[str, jax.Array], pool_layer, *, block_tables,
    write_positions, kv_lens, cos, sin, kernels, unpack, tiles, write_plan,
    index=None, index_layer=None, sel=None, scored=False, kind="full",
) -> Tuple[jax.Array, Dict[str, jax.Array], Any]:
    """A latent layer's attention over ``x`` (normed) → (``concat(o)``
    before ``W_o``, the pools with the layer's rows written, the selection
    the layer attended: its own if it holds an indexer, else ``sel`` as it
    came). ``scored``: the layer holds an indexer (its stack's name says
    so; the quantized ``wqi`` may ride ``stacked``, not ``lp``).
    ``kind``: the layer's attention kind; ``block_tables``, ``cos`` /
    ``sin``, ``tiles`` and ``write_plan`` are its kind's (a model of two
    kinds hands a dict a kind), its pool ``pool_of(kind)``. A sliding layer
    attends its window and leaves ``sel`` as it came."""
    from distributed_gpu_inference_tpu.models.llama import rms_norm

    lk = cfg.latent_kind(kind)
    windowed = lk.window is not None
    if cfg.mixed_attention:
        block_tables, cos, sin, tiles, write_plan = (
            None if per is None else per[kind]
            for per in (block_tables, cos, sin, tiles, write_plan))
    pool_name = pool_of(kind)
    pool = kv[pool_name]
    b, s, _ = x.shape
    nh, rkv, dn, dr = lk.heads, lk.kv_rank, lk.nope, lk.rope
    eps = cfg.rms_norm_eps
    c_q = None
    if lk.q_rank:
        c_q = rms_norm(proj(x, "wq_a"), lp["q_a_norm"], eps)
        if lk.q_scale != 1.0:
            c_q = c_q * jnp.asarray(lk.q_scale, c_q.dtype)
        q = proj(c_q, "wq_b").reshape(b, s, nh, dn + dr)
    else:
        q = proj(x, "wq").reshape(b, s, nh, dn + dr)
    ckr = proj(x, "wkv_a")
    c = rms_norm(ckr[..., :rkv], lp["kv_a_norm"], eps)
    if lk.kv_scale != 1.0:
        c = c * jnp.asarray(lk.kv_scale, c.dtype)
    if cfg.mla_use_nope:        # the "rope" dims as they are
        q_n, q_r, k_r = q[..., :dn], q[..., dn:], ckr[..., rkv:]
    else:
        q_n, q_r = q[..., :dn], rope(cfg, q[..., dn:], cos, sin)
        k_r = rope(cfg, ckr[..., None, rkv:], cos, sin)[..., 0, :]
    pad = jnp.zeros((b, s, pool.shape[-1] - rkv - dr), c.dtype)
    new_rows = jnp.concatenate([c, k_r, pad], axis=-1).astype(pool.dtype)
    positions = write_positions

    def rectangle(t_):
        """A packed chunk's ``[1, Tp, ...]`` as the ``[B, S]`` rectangle."""
        if unpack is None:
            return t_
        return jnp.take(t_[0], unpack[0], axis=0, mode="fill", fill_value=0)

    if not kernels:
        # the XLA path attends over the [B, S] rectangle; the kernels
        # take the packed axis as it is (page write) or as query tiles
        q_n, q_r, new_rows = (rectangle(q_n), rectangle(q_r),
                              rectangle(new_rows))
    if scored:
        from distributed_gpu_inference_tpu.ops import index_select

        # the selection is computed over the rectangle on either path; the
        # chunk's keys are written from the axis they are on
        qi, kin, wts = index_inputs(cfg, lp, x, c_q, proj, index)
        qi_r, wts_r = rectangle(qi), rectangle(wts)

        with jax.named_scope("dgi_index"):
            ki_pool = index_select.write_index_keys(
                kv[INDEX_KEYS], kin.reshape(-1, cfg.index_head_dim),
                index_layer, *index.scatter)
            kv = {**kv, INDEX_KEYS: ki_pool}
            scan_keys = kv.get(INDEX_SCAN_KEYS)
            if scan_keys is not None:
                scan_keys = index_select.append_scan_keys(
                    scan_keys, kin[:, 0], index_layer, positions[:, 0])
                kv[INDEX_SCAN_KEYS] = scan_keys
            keep = index_select.select(
                qi_r, wts_r, ki_pool, index_layer, block_tables, positions,
                kv_lens, cfg.index_topk, kernels=kernels,
                scan_keys=scan_keys)
            sel = selection_state(
                keep, kernels=kernels, tiles=tiles, unpack=unpack,
                block_tables=block_tables, positions=positions,
                kv_lens=kv_lens, block_size=block_size)
    if kernels:
        from distributed_gpu_inference_tpu.ops import (
            mla_attention_pallas as mla_k,
        )

        pool = mla_k.write_latent_pages_in_place(
            new_rows.reshape(-1, new_rows.shape[-1]), pool, pool_layer,
            write_plan)
        q_abs = jnp.einsum("bshd,hcd->bshc", q_n, lp["w_uk"],
                           preferred_element_type=jnp.float32)
        q_cat = jnp.concatenate(
            [q_abs.astype(pool.dtype), q_r.astype(pool.dtype),
             jnp.zeros((*q_r.shape[:3], pool.shape[-1] - rkv - dr),
                       pool.dtype)], axis=-1)
        common = dict(scale=lk.qk ** -0.5, latent=rkv)
        if windowed:
            common["window"] = lk.window
        attends = None if windowed else sel
        if tiles is not None:
            picked = {} if attends is None else {
                "keep_tiles": attends["keep_tiles"]}
            u = mla_k.latent_paged_attention_packed(
                q_cat[0], tiles, pool, pool_layer, block_tables,
                kv_lens, block_size, **common, **picked)[None]
        else:
            picked = {} if attends is None else (
                {"walk": attends["walk"]} if "walk" in attends
                else {"keep": attends["keep"]})
            u = mla_k.latent_paged_attention(
                q_cat, pool, pool_layer, block_tables, positions,
                kv_lens, block_size, decode=s == 1, **common, **picked)
        attn = jnp.einsum("bshc,hcd->bshd", u, lp["w_uv"],
                          preferred_element_type=jnp.float32)
    else:
        from distributed_gpu_inference_tpu.models.llama import (
            _page_scatter_indices,
        )

        n_blocks = pool.shape[1]
        phys, slot = _page_scatter_indices(
            n_blocks, block_tables, positions, block_size)
        # straight into the stacked pool: no layer slice, no write-back
        pool = pool.at[pool_layer, phys, slot].set(
            new_rows.reshape(-1, new_rows.shape[-1]), mode="drop")
        ctx = pool[pool_layer, block_tables].reshape(
            block_tables.shape[0], -1, pool.shape[-1])
        attn = latent_attention_xla(
            cfg, q_n, q_r, lp["w_uk"], lp["w_uv"], ctx, positions,
            kv_lens, "absorbed" if positions.shape[1] == 1
            else "expanded",
            keep=None if sel is None or windowed else sel["keep"],
            kind=lk,
        )
        if unpack is not None:
            attn = attn.at[unpack[1], unpack[2]].get(mode="fill",
                                                     fill_value=0)
    if cfg.head_gate:
        # one learned scalar a head a token, from the layer's normed input,
        # on the head's output before W_o
        gate = jax.nn.sigmoid(proj(x, "w_hgate").astype(jnp.float32))
        attn = attn.astype(jnp.float32) * gate[..., None]
    return (attn.astype(x.dtype).reshape(b, s, nh * lk.v),
            {**kv, pool_name: pool}, sel)


def _layer_step(
    cfg: ModelConfig, block_size: int, hidden: jax.Array,
    kv: Dict[str, jax.Array], sel, lp: Dict[str, Any], *, linear: bool,
    layer_idx, cache_layer, index_layer, scored, stacked, pallas, kernels,
    kda_kernels, kda_plan, rope_positions, moe_live, emit_routing,
    kind="full", **latent,
):
    """One layer: ``layer_idx`` its place in its parameter stack,
    ``cache_layer`` its place in its cache (its kind's latent pool, or for
    a ``linear`` layer the state pool), ``index_layer`` its place in the
    index-key pool if it holds an indexer; ``sel`` the selection the last
    such layer computed (``selection_state``), handed on."""
    from distributed_gpu_inference_tpu.models.llama import _mlp, rms_norm

    eps = cfg.rms_norm_eps

    def proj(x_, name):
        if stacked is not None and name in stacked:
            return matmul_stacked(x_, stacked[name], layer_idx, pallas)
        return qmm(x_, lp[name], pallas)

    with jax.named_scope("dgi_attention"):
        x = rms_norm(hidden, lp["attn_norm"], eps)
        if linear:
            from distributed_gpu_inference_tpu.models import kda

            attn, kv = kda.attention(
                cfg, x, lp, proj, kv, cache_layer, plan=kda_plan,
                positions=rope_positions, kernels=kda_kernels)
        else:
            attn, kv, sel = _latent_attention(
                cfg, block_size, x, lp, proj, kv, cache_layer,
                kernels=kernels, index_layer=index_layer, sel=sel,
                scored=scored, kind=kind, **latent)
            attn = proj(attn, "wo").astype(hidden.dtype)
        if "post_attn_norm" in lp:
            attn = rms_norm(attn, lp["post_attn_norm"], eps)
        hidden = hidden + attn
    routed = "w_router" in lp
    with jax.named_scope("dgi_experts" if routed else "dgi_mlp"):
        m = rms_norm(hidden, lp["mlp_norm"], eps)
        stats = routing = None
        if routed:
            out, stats, routing = _experts(
                m, lp, cfg, proj, live=moe_live, stacked=stacked,
                layer_idx=layer_idx)
        else:
            out = _mlp(m, proj, cfg.activation)
        if "post_mlp_norm" in lp:
            out = rms_norm(out, lp["post_mlp_norm"], eps)
        hidden = hidden + out
    return hidden, kv, sel, stats, routing if emit_routing else None


def forward_chunk(
    cfg: ModelConfig, params: Params, token_ids, positions, kv, block_tables,
    kv_lens, *, block_size: int = 16, last_only: bool = True,
    with_logits: bool = True, collect_routing: bool = False,
    pallas: bool = True, packing=None,
):
    """``models/llama.forward_chunk`` for a latent-attention model (its
    docstring holds the contract of every argument; sequence-parallel
    attention, attention overrides and feature collection are not this
    model's). A hybrid model's state rows are the batch rows: row ``b`` of
    ``block_tables`` is row ``b`` of the state pool."""
    from distributed_gpu_inference_tpu.models import llama

    pool = kv[POOL]
    mixed = cfg.mixed_attention
    if mixed:
        # a block table a kind, side by side in a row, the full kind's first
        m_cols = block_tables.shape[1] // 2
        tables = {"full": block_tables[:, :m_cols],
                  "sliding": block_tables[:, m_cols:]}
        block_tables = tables["full"]

    def per_kind(make):
        """What a layer's attention kind fixes, built once a graph: a dict
        a kind for a model of two, else the one kind's (the same operands
        as ever in the same order)."""
        if not mixed:
            return make("full", block_tables, pool)
        return {kind: make(kind, tables[kind], kv[pool_of(kind)])
                for kind in latent_kinds(cfg)}

    unpack = to_rect = tp = None
    if packing is not None:
        tp = token_ids.shape[0]
        rect = (block_tables.shape[0], packing.width)
        at = (packing.row, packing.col)
        to_rect = jnp.full(rect, tp, jnp.int32).at[at].set(
            jnp.arange(tp, dtype=jnp.int32), mode="drop")
        unpack = (to_rect, *at)
        token_ids, rope_positions = token_ids[None], positions[None]
        positions = jnp.full(rect, -1, jnp.int32).at[at].set(
            positions, mode="drop")
    else:
        rope_positions = positions
    kernels = kernels_on(
        cfg, block_tables.shape[1] * block_size, pool.dtype, pallas)
    kda_plan, kda_kernels = None, False
    if cfg.num_kda_layers:
        from distributed_gpu_inference_tpu.models import kda

        rows = kv[kda.STATE].shape[1]
        if block_tables.shape[0] != rows:
            raise ValueError(
                f"{cfg.name}: {block_tables.shape[0]} batch rows over a "
                f"state pool of {rows}: a batch row is a state row")
        kda_kernels = kda.kernels_on(cfg, kv[kda.STATE].dtype, pallas)
        kda_plan = kda.chunk_plan(packing, rope_positions[0], positions,
                                  rows)
    write_plan = tiles = None
    if kernels:
        from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
            page_write_plan,
        )

        if packing is not None:
            from distributed_gpu_inference_tpu.ops.mla_attention_pallas import (
                packed_tiles,
            )

            tiles = per_kind(lambda kind, _t, _p: packed_tiles(
                packing.row, packing.col, rope_positions[0],
                block_tables.shape[0], packing.width,
                cfg.latent_kind(kind).heads))

        write_plan = per_kind(lambda _k, tables_, pool_: page_write_plan(
            tables_, positions, block_size,
            page_bytes=pool_.shape[2] * pool_.shape[3] * pool_.dtype.itemsize,
            token_index=to_rect, num_tokens=tp,
        ))
    index = sel = None
    if cfg.index_topk:
        # the indexer's view of the chunk, the same for every full layer;
        # the carried selection starts empty (the first layer is full)
        index = llama._index_plan(cfg, pool.shape[1], block_tables,
                                  positions, rope_positions, packing,
                                  block_size)
        state = functools.partial(
            selection_state, kernels=kernels,
            tiles=tiles["full"] if mixed and tiles is not None else tiles,
            unpack=unpack, block_tables=block_tables, positions=positions,
            kv_lens=kv_lens,
            block_size=block_size)
        sel = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(state, jax.ShapeDtypeStruct(
                (*positions.shape, block_tables.shape[1] * block_size),
                jnp.float32)))
    hidden = llama.embed_tokens(params, token_ids, cfg)
    cos = sin = None
    if not cfg.mla_use_nope:
        angles = per_kind(lambda kind, _t, _p: llama._rope_angles(
            jnp.maximum(rope_positions, 0), cfg.latent_kind(kind).rope,
            cfg.latent_kind(kind).theta))
        cos, sin = ({kind: a[i] for kind, a in angles.items()}
                    for i in (0, 1)) if mixed else angles

    split = {group: _split_group(params[group], pallas)
             for group, _ in layer_groups(cfg)}
    step = functools.partial(
        _layer_step, cfg, block_size,
        block_tables=tables if mixed else block_tables,
        write_positions=positions,
        kv_lens=kv_lens, cos=cos, sin=sin, pallas=pallas, kernels=kernels,
        kda_kernels=kda_kernels, kda_plan=kda_plan,
        rope_positions=rope_positions, unpack=unpack, tiles=tiles,
        moe_live=rope_positions >= 0, emit_routing=collect_routing,
        write_plan=write_plan, index=index,
    )
    # where each stack, each cache and the index-key pool stand, in layers
    at_w = {group: 0 for group in split}
    at_c = {"full": 0, "sliding": 0, _KDA: 0}
    at_i = 0
    moe = None
    fetched = None
    routes = []

    def run(carry, group, scanned, n, w0, c0, i0):
        """``n`` layers of one stack (``scanned``: their leaves that ride
        the scan): weights from ``w0``, cache layers from ``c0``, index-key
        layers from ``i0`` (scalars, traced inside a repeated unit)."""
        stacked = split[group][1]
        linear = group.startswith(_KDA)

        def body(c, xs):
            j, lp = xs
            hidden_, kv_, sel_, stats, routing = step(
                c[0], c[1], c[2], lp, linear=linear, layer_idx=w0 + j,
                cache_layer=c0 + j, index_layer=i0 + j,
                scored=group.startswith(_IX), stacked=stacked,
                kind=kind_of(group))
            # (a sliding layer walks its window, not the carried selection)
            took = None if sel_ is None or kind_of(group) == "sliding" \
                else sel_.get("fetched")
            return (hidden_, kv_, sel_), (stats, routing, took)

        return lax.scan(body, carry, (jnp.arange(n, dtype=jnp.int32), scanned))

    def leaves(group, lo, n, repeat=None):
        """Layers ``lo .. lo + n`` of a stack's scanned leaves; with
        ``repeat`` as ``[repeat, n / repeat, ...]``."""
        def cut(a):
            a = a[lo:lo + n]
            return a if repeat is None else a.reshape(
                repeat, n // repeat, *a.shape[1:])
        return jax.tree.map(cut, split[group][0])

    def add_stats(stats, took):
        """A run's routed-expert counters, summed over its layers, onto
        the pass's; likewise what its layers' selections fetched."""
        nonlocal moe, fetched
        if stats is not None:
            sums = {name: jnp.sum(v) for name, v in stats.items()}
            moe = sums if moe is None else {
                name: moe[name] + v for name, v in sums.items()}
        if took is not None:
            fetched = jnp.sum(took) + (0 if fetched is None else fetched)

    def indexed(group):
        return int(group.startswith(_IX))

    def cache_of(group):
        """The cache a stack's layers write: the state pool, or their
        attention kind's latent pool."""
        return _KDA if group.startswith(_KDA) else kind_of(group)

    carry = (hidden, kv, sel)
    for repeat, runs in layer_units(cfg):
        if repeat == 1:
            for group, n in runs:
                cache = cache_of(group)
                carry, (stats, routing, took) = run(
                    carry, group, leaves(group, at_w[group], n), n,
                    jnp.int32(at_w[group]), jnp.int32(at_c[cache]),
                    jnp.int32(at_i))
                at_w[group] += n
                at_c[cache] += n
                at_i += n * indexed(group)
                add_stats(stats, took)
                if routing is not None:
                    routes.append(routing)
            continue
        # a repeated period: one scan over its repeats, its runs inside
        w_lo = {g: at_w[g] for g, _ in runs}
        c_lo = dict(at_c)
        i_lo = at_i
        per_c = {cache: sum(n for g, n in runs if cache_of(g) == cache)
                 for cache in at_c}
        per_i = sum(n * indexed(g) for g, n in runs)
        xs = {g: leaves(g, w_lo[g], repeat * n, repeat) for g, n in runs}

        def period(c, px):
            p_, lp_ = px
            outs = []
            seen = dict.fromkeys(at_c, 0)
            seen_i = 0
            for g, n in runs:
                lin = cache_of(g)
                c, out = run(c, g, lp_[g], n, w_lo[g] + p_ * n,
                             c_lo[lin] + p_ * per_c[lin] + seen[lin],
                             i_lo + p_ * per_i + seen_i)
                seen[lin] += n
                seen_i += n * indexed(g)
                outs.append(out)
            return c, outs

        carry, outs = lax.scan(
            period, carry, (jnp.arange(repeat, dtype=jnp.int32), xs))
        period_routes = []
        for (g, n), (stats, routing, took) in zip(runs, outs):
            at_w[g] += repeat * n
            at_c[cache_of(g)] += repeat * n
            at_i += repeat * n * indexed(g)
            add_stats(stats, took)
            if routing is not None:
                period_routes.append(routing)       # [repeat, n, T, k]
        if period_routes:
            r_ = jnp.concatenate(period_routes, axis=1)
            routes.append(r_.reshape(-1, *r_.shape[2:]))
    hidden, new_kv, _ = carry
    routing = jnp.concatenate(routes, axis=0) if routes else None
    if not with_logits:
        return llama.ChunkOutput(hidden=hidden, kv=new_kv, logits=None,
                                 moe=moe, routing=routing,
                                 index_fetched=fetched)
    if last_only and packing is not None:
        logits_in = jnp.take(hidden[0], packing.last, axis=0)[:, None]
    elif last_only:
        n_valid = jnp.sum((positions >= 0).astype(jnp.int32), axis=1)
        logits_in = jnp.take_along_axis(
            hidden, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)
    else:
        logits_in = hidden
    with jax.named_scope("dgi_head"):
        logits = llama.project_logits(cfg, params, logits_in)
    return llama.ChunkOutput(hidden=hidden, kv=new_kv, logits=logits,
                             moe=moe, routing=routing, index_fetched=fetched)


def _split_group(layers: Dict[str, Any], pallas: bool):
    """``split_stacked_quant`` for one group: the expert weights stay whole
    too where the grouped-matmul kernel will take them."""
    if not pallas or "we_gate" not in layers:
        return split_stacked_quant(layers)
    from distributed_gpu_inference_tpu.ops import moe_gmm_pallas as moe_gmm

    return split_stacked_quant(layers, experts=moe_gmm.kernel_ok(layers))
