"""The plain reference of a decoder whose latent-attention (MLA) layers are of
two kinds -- ``full`` layers under a lightning indexer and ``sliding`` layers
with a head count, latent ranks, head sizes and a rotation of their own that
attend a window -- with a gate a head on every layer's attention and a
constant rescale on the two normed latents; a leading dense layer, then
sigmoid-routed experts with a selection bias beside a shared one: the
``dots3_note`` recipe, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

The layer, for ``x`` in R^h, ``a = RMSNorm(x)`` (pre-norm only) and every
RMSNorm with the config's eps; the sizes below are the layer's KIND's (the
``swa_*`` keys for a sliding layer)::

    c_q = a_q * N_qa(a W_qa);   q = c_q W_qb = heads of [q_n (nope) ; q_r (rope)]
    [c ; k_r] = a W_kva;        c = a_kv * N_kva(c)
        a_q = sqrt(h / q_lora_rank), a_kv = sqrt(h / kv_lora_rank) where
        apply_mla_qkv_lora_rescale, else 1; never on k_r
    RoPE by ADJACENT PAIRS (element 2i with 2i + 1), the kind's theta, no
    scaling, on q_r of every head and the one shared k_r
    [k_n,i ; v_i] = c W_kvb     a head's W_UK,i and W_UV,i side by side
    s_ts = (q_n,t . k_n,s + q_r,t . k_r,s) / sqrt(nope + rope)
    softmax over s in A_t ONLY; o_i = sum p v_i
    gate: o_i <- sigmoid(a W_g)_i * o_i  (one scalar a head a token)
    attn = concat(o) W_o

    A_t, a sliding layer: t - window < s <= t (``sliding_window_size`` keys,
        the query itself among them); no indexer
    A_t, a full layer: S_t of its indexer --
        qI = c_q W_qI (Hi heads of Di, from the RESCALED c_q);
        kI = LayerNorm(a W_kI) (weight and bias); the FIRST ``rope`` values
        of every qI head and of kI rotated by adjacent pairs at the full
        kind's theta, the others not; w = a W_w * (Hi * Di) ** -0.5
        I(t, s) = sum_j w[t, j] * relu(qI[t, j] . kI[s])          s <= t
        S_t = every s <= t while t < topk, else {s : I(t, s) >= the topk-th
        largest of I(t, .)}, ties kept

    x1 = x + attn;  m = RMSNorm(x1);  x2 = x1 + F(m)
    F, a dense layer:   W_down(silu(m W_gate) * (m W_up))
    F, a sparse layer:  s = sigmoid(m W_r) over ALL published experts;
                        T = the k largest of s + b (selection bias b);
                        w_e = scale * s_e / (sum_{e in T} s_e + 1e-20);
                        F = E_shared(m) + sum_{e in T and held} w_e E_e(m)

then a final RMSNorm and an untied head over the held slice of the
vocabulary. No absorbed queries, no cache, no paging, no kernels, no
batching: one prompt at a time, a layer at a time, attention a block of heads
and a block of queries at a time so that a 20k-token prompt fits, a loop over
the experts. It shares no code with the program.

**The share**: the configuration states which ``count`` of the published
experts this chip holds, from ``first`` (``expert_share``); ``T`` and the
denominator run over all of them, the sum over the held ones.

Weights come through a provider, layer by layer, already float32:
``SeedStream`` regenerates what the program's init draws from a seed
(``models/mla.py init_params`` with a quantization mode: a leaf a named
stack -- ``ix_dense_layers`` / ``ix_layers`` the full layers, ``sw_layers``
the sliding ones --, normal draws scaled by ``fan_in ** -0.5``, matmul
weights rounded to int8 per output channel and multiplied out, everything
else rounded to bf16, norm vectors ``1 + 0.25 x normal``, the LayerNorm's
bias ``0.25 x normal``, the selection bias ``0.1 x normal`` in float32; a
weight that reads a RESCALED latent -- ``W_qb``, ``W_qI``, ``W_UK``, ``W_UV``
-- drawn at ``hidden_size ** -0.5``, the init the rescale makes up for);
``FromTree`` slices a parameter tree the program built.

``variant`` plants one fault (the tests, and the names
``benchmark/compare_logits_mla_window.py`` plants on the served side):
``no_rescale``, ``no_gate``, ``window_512`` (one key short),
``window_everywhere`` (full layers attend the window, as sliding layers
do, and no selection), ``dense_full_layers`` (no selection), ``sliding_rope_theta_as_full``
and ``fp8_latents`` (both kinds' cached rows and the index keys rounded to
float8_e4m3: the precision below the served bfloat16).
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NORM_SPREAD = 0.25
VARIANTS = ("no_rescale", "no_gate", "window_512", "window_everywhere",
            "dense_full_layers", "sliding_rope_theta_as_full", "fp8_latents")
# queries a block of attention: [heads of a block, BLOCK, n] float32 scores
BLOCK = 256

# the leaves a layer's attention sub-block reads
ATTENTION = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
             "w_uk", "w_uv", "wo", "w_hgate", "wqi", "wki", "ww", "ki_norm",
             "ki_bias")

__all__ = ["SeedStream", "FromTree", "dims", "forward", "last_logits",
           "project", "select", "attend", "expert_layer", "VARIANTS",
           "ATTENTION", "kind_of"]


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    share = cfg.get("expert_share") or {}
    held = int(cfg["n_routed_experts"])
    layers = int(cfg["num_hidden_layers"])
    h = int(cfg["hidden_size"])
    rescale = bool(cfg.get("apply_mla_qkv_lora_rescale"))

    def kind(prefix: str, window: Optional[int]) -> Tuple[Tuple[str, Any], ...]:
        rq, rkv = int(cfg[prefix + "q_lora_rank"]), \
            int(cfg[prefix + "kv_lora_rank"])
        return tuple({
            "nh": int(cfg[prefix + "num_attention_heads"]),
            "rq": rq, "rkv": rkv,
            "dn": int(cfg[prefix + "qk_nope_head_dim"]),
            "dr": int(cfg[prefix + "qk_rope_head_dim"]),
            "dv": int(cfg[prefix + "v_head_dim"]),
            "theta": float(cfg[prefix + "rope_theta"]),
            "window": window,
            "a_q": math.sqrt(h / rq) if rescale else 1.0,
            "a_kv": math.sqrt(h / rkv) if rescale else 1.0,
        }.items())

    return {
        "h": h, "i": int(cfg["intermediate_size"]),
        "mi": int(cfg["moe_intermediate_size"]),
        "L": layers, "V": int(cfg["vocab_size"]),
        "kinds": tuple("full" if t.startswith("full") else "sliding"
                       for t in cfg["layer_types"])[:layers],
        "lead": int(cfg.get("first_k_dense_replace") or 0),
        "full": kind("", None),
        "sliding": kind("swa_", int(cfg["sliding_window_size"])),
        "gate": cfg.get("attention_gate_type") == "headwise",
        "rescale": rescale,
        "E": int(share.get("of", held)),            # the router's width
        "first": int(share.get("first", 0)), "held": held,
        "shared": int(cfg.get("n_shared_experts") or 0),
        "k": int(cfg["num_experts_per_tok"]),
        "renorm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "eps": float(cfg["rms_norm_eps"]),
        "hi": int(cfg["index_n_heads"]), "di": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
    }


def kind_of(s: Dict[str, Any], l: int) -> Dict[str, Any]:
    """The sizes of layer ``l``'s attention kind."""
    return dict(s[s["kinds"][l]])


def group_of(s: Dict[str, Any], l: int) -> Tuple[str, int, int]:
    """(the stack layer ``l`` lies in, its place there, the stack's size):
    by its attention kind (a full layer holds the indexer) and its MLP."""
    def name(i):
        base = "dense_layers" if i < s["lead"] else "layers"
        return ("ix_" if s["kinds"][i] == "full" else "sw_") + base

    names = [name(i) for i in range(s["L"])]
    return names[l], names[:l].count(names[l]), names.count(names[l])


def leaf_shapes(s: Dict[str, Any], group: str
                ) -> Dict[str, Tuple[Tuple[int, ...], int, str]]:
    """name → (shape of one layer, fan-in, kind: q quantized matmul weight,
    d bf16 weight, n norm vector, z a bias around zero, b the selection
    bias)."""
    h = s["h"]
    k = dict(s["full" if group.startswith("ix_") else "sliding"])
    nh = k["nh"]
    # what reads a rescaled latent is drawn at ``h ** -0.5`` (the init the
    # rescale makes up for: ``a ** 2 x rank = h``), else at its fan-in's
    fan_q, fan_kv = (h, h) if s["rescale"] else (k["rq"], k["rkv"])
    out = {
        "attn_norm": ((h,), 0, "n"), "mlp_norm": ((h,), 0, "n"),
        "q_a_norm": ((k["rq"],), 0, "n"), "kv_a_norm": ((k["rkv"],), 0, "n"),
        "wq_a": ((h, k["rq"]), h, "q"),
        "wq_b": ((k["rq"], nh * (k["dn"] + k["dr"])), fan_q, "q"),
        "wkv_a": ((h, k["rkv"] + k["dr"]), h, "q"),
        "w_uk": ((nh, k["rkv"], k["dn"]), fan_kv, "d"),
        "w_uv": ((nh, k["rkv"], k["dv"]), fan_kv, "d"),
        "wo": ((nh * k["dv"], h), nh * k["dv"], "q"),
    }
    if s["gate"]:
        out["w_hgate"] = ((h, nh), h, "d")
    if group.startswith("ix_"):
        out.update({
            "wqi": ((k["rq"], s["hi"] * s["di"]), fan_q, "q"),
            "wki": ((h, s["di"]), h, "d"), "ww": ((h, s["hi"]), h, "d"),
            "ki_norm": ((s["di"],), 0, "n"), "ki_bias": ((s["di"],), 0, "z"),
        })
    if group.endswith("dense_layers"):
        out.update({"w_gate": ((h, s["i"]), h, "q"),
                    "w_up": ((h, s["i"]), h, "q"),
                    "w_down": ((s["i"], h), s["i"], "q")})
    else:
        mi = s["mi"]
        out.update({
            "w_router": ((h, s["E"]), h, "d"),
            "router_bias": ((s["E"],), 0, "b"),
            "we_gate": ((s["held"], h, mi), h, "q"),
            "we_up": ((s["held"], h, mi), h, "q"),
            "we_down": ((s["held"], mi, h), mi, "q"),
        })
        if s["shared"]:
            ms = mi * s["shared"]
            out.update({"ws_gate": ((h, ms), h, "q"),
                        "ws_up": ((h, ms), h, "q"),
                        "ws_down": ((ms, h), ms, "q")})
    return out


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #

def _int8_roundtrip(w: jax.Array) -> jax.Array:
    """Symmetric int8 per output channel (the scale spans the contraction
    axis, -2), then back to float32: the value the served weight stands
    for."""
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q.astype(F32) * scale.astype(F32)


@functools.lru_cache(maxsize=None)
def _draw(shape: Tuple[int, ...], fan_in: int, kind: str, quantized: bool):
    def gen(key):
        x = jax.random.normal(key, shape, F32)
        if kind == "b":
            return 0.1 * x
        if kind == "n":
            return (1.0 + NORM_SPREAD * x).astype(jnp.bfloat16).astype(F32)
        if kind == "z":
            return (NORM_SPREAD * x).astype(jnp.bfloat16).astype(F32)
        w = x * (fan_in ** -0.5)
        if kind == "q" and quantized:
            return _int8_roundtrip(w)
        return w.astype(jnp.bfloat16).astype(F32)
    return jax.jit(gen)


class SeedStream:
    """The program's seeded init, regenerated a layer at a time.
    ``quantized`` False: the weights as a bf16 tree holds them."""

    def __init__(self, cfg: Dict[str, Any], seed: int,
                 quantized: bool = True) -> None:
        self.s = dims(cfg)
        self.root = jax.random.PRNGKey(int(seed))
        self.quantized = quantized

    def _key(self, name: str) -> jax.Array:
        return jax.random.fold_in(
            self.root, zlib.crc32(name.encode()) & 0x7FFFFFFF)

    def _leaf(self, name: str, shape, fan_in: int, kind: str) -> jax.Array:
        return _draw(tuple(shape), fan_in, kind, self.quantized)(
            self._key(name))

    def embedding(self) -> jax.Array:
        return self._leaf("embedding", (self.s["V"], self.s["h"]),
                          self.s["h"], "d")

    def head(self) -> jax.Array:
        return self._leaf("lm_head", (self.s["V"], self.s["h"]),
                          self.s["h"], "d")

    def final_norm(self) -> jax.Array:
        return self._leaf("final_norm", (self.s["h"],), 0, "n")

    def layer(self, l: int, only: Optional[Tuple[str, ...]] = None
              ) -> Dict[str, jax.Array]:
        """Layer ``l``'s leaves (``only``: those named, e.g. ``ATTENTION``
        where the experts' float32 are not read)."""
        group, at, n = group_of(self.s, l)
        return {
            name: _draw(shape, fan_in, kind, self.quantized)(
                jax.random.split(self._key(f"{group}.{name}"), n)[at])
            for name, (shape, fan_in, kind)
            in leaf_shapes(self.s, group).items()
            if only is None or name in only
        }


class FromTree:
    """Weights sliced out of a parameter tree the program built: stacked
    leaves with a leading layer axis under the stacks' names, quantized
    leaves as ``{"qw", "scale"}``. ``s``: :func:`dims` of the configuration
    (which layer lies in which stack)."""

    def __init__(self, params: Dict[str, Any], s: Dict[str, Any]) -> None:
        self.p, self.s = params, s

    @staticmethod
    def _f32(leaf: Any) -> jax.Array:
        if isinstance(leaf, dict):
            return leaf["qw"].astype(F32) * leaf["scale"].astype(F32)
        return jnp.asarray(leaf).astype(F32)

    def embedding(self) -> jax.Array:
        return self._f32(self.p["embedding"])

    def head(self) -> jax.Array:
        return self._f32(self.p["lm_head"])

    def final_norm(self) -> jax.Array:
        return self._f32(self.p["final_norm"])

    def layer(self, l: int, only: Optional[Tuple[str, ...]] = None
              ) -> Dict[str, jax.Array]:
        group, at, _ = group_of(self.s, l)
        return {name: self._f32(jax.tree.map(lambda a: a[at], leaf))
                for name, leaf in self.p[group].items()
                if only is None or name in only}


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #

def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float
                ) -> jax.Array:
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w + b


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [S, heads, d]; position = row index; all ``d`` values rotated by
    adjacent pairs (2i, 2i + 1)."""
    n, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(n, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def _fp8(x: jax.Array) -> jax.Array:
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def project(s: Dict[str, Any], k: Dict[str, Any], w: Dict[str, jax.Array],
            x: jax.Array, variant: Optional[str] = None
            ) -> Dict[str, jax.Array]:
    """Everything a layer's attention reads of a whole prompt ``x [n, h]``
    (``k``: the layer's kind, :func:`kind_of`): ``q_n [n, nh, dn]``, rotated
    ``q_r [n, nh, dr]``, the latent ``c [n, rkv]``, the rotated shared
    ``k_r [n, dr]`` and the heads' gates ``g [n, nh]``; on a layer with an
    indexer also ``qi [n, Hi, Di]``, ``ki [n, Di]``, ``wt [n, Hi]``."""
    n = x.shape[0]
    nh, dn, dr, rkv = k["nh"], k["dn"], k["dr"], k["rkv"]
    a_q, a_kv = (1.0, 1.0) if variant == "no_rescale" \
        else (k["a_q"], k["a_kv"])
    theta = k["theta"]
    if variant == "sliding_rope_theta_as_full" and k["window"] is not None:
        theta = dict(s["full"])["theta"]
    a = _rms_norm(x, w["attn_norm"], s["eps"])
    c_q = a_q * _rms_norm(a @ w["wq_a"], w["q_a_norm"], s["eps"])
    q = (c_q @ w["wq_b"]).reshape(n, nh, dn + dr)
    ckr = a @ w["wkv_a"]
    out = {
        "q_n": q[..., :dn], "q_r": _rope(q[..., dn:], theta),
        "c": a_kv * _rms_norm(ckr[:, :rkv], w["kv_a_norm"], s["eps"]),
        "k_r": _rope(ckr[:, None, rkv:], theta)[:, 0],
    }
    if variant == "fp8_latents":
        out["c"], out["k_r"] = _fp8(out["c"]), _fp8(out["k_r"])
    if s["gate"] and variant != "no_gate":
        out["g"] = jax.nn.sigmoid(a @ w["w_hgate"])
    if "wqi" in w:
        hi, di = s["hi"], s["di"]
        qi = (c_q @ w["wqi"]).reshape(n, hi, di)
        ki = _layer_norm(a @ w["wki"], w["ki_norm"], w["ki_bias"],
                         s["eps"])[:, None, :]
        # the first ``dr`` values of a head rotate, the others do not
        qi = jnp.concatenate([_rope(qi[..., :dr], theta), qi[..., dr:]], -1)
        ki = jnp.concatenate([_rope(ki[..., :dr], theta), ki[..., dr:]], -1)
        if variant == "fp8_latents":
            ki = _fp8(ki)
        out.update({"qi": qi, "ki": ki[:, 0],
                    "wt": (a @ w["ww"]) * (hi * di) ** -0.5})
    return out


def select(s: Dict[str, Any], k: Dict[str, Any], p: Dict[str, jax.Array],
           lo: jax.Array, rows: int, variant: Optional[str] = None
           ) -> jax.Array:
    """``A_t`` of queries ``lo .. lo + rows`` of a projected prompt as a
    mask ``[rows, n]``: a sliding layer's window, a full layer's ``S_t``."""
    n = p["c"].shape[0]
    t = lo + jnp.arange(rows)
    key = jnp.arange(n)[None, :]
    seen = key <= t[:, None]
    window = k["window"]
    if window is None and variant == "window_everywhere":
        window = dict(s["sliding"])["window"]
    elif window is not None and variant == "window_512":
        window -= 1
    if window is not None:
        seen &= key > t[:, None] - window
    if "qi" not in p or n <= s["topk"] or variant in (
            "dense_full_layers", "window_everywhere"):
        return seen
    qi = jax.lax.dynamic_slice_in_dim(p["qi"], lo, rows)
    wt = jax.lax.dynamic_slice_in_dim(p["wt"], lo, rows)
    dots = jnp.einsum("qjd,kd->qjk", qi, p["ki"])
    score = jnp.sum(wt[:, :, None] * jax.nn.relu(dots), axis=1)
    score = jnp.where(key <= t[:, None], score, -jnp.inf)
    kth = jax.lax.top_k(score, s["topk"])[0][:, -1:]
    # a query with fewer than topk tokens before it: kth is -inf and every
    # token it sees is kept; ties at kth are all kept
    return seen & (score >= kth)


def attend(k: Dict[str, Any], w: Dict[str, jax.Array],
           p: Dict[str, jax.Array], keep: jax.Array, lo: jax.Array,
           rows: int) -> jax.Array:
    """Expanded latent attention of queries ``lo .. lo + rows`` over the
    tokens ``keep [rows, n]`` allows, gated a head → ``concat(o)`` ``[rows,
    nh * dv]`` before ``W_o``. A block of heads at a time."""
    nh, dn, dr = k["nh"], k["dn"], k["dr"]
    q_n = jax.lax.dynamic_slice_in_dim(p["q_n"], lo, rows)
    q_r = jax.lax.dynamic_slice_in_dim(p["q_r"], lo, rows)
    c, k_r = p["c"], p["k_r"]

    def heads(block):
        q_n, q_r, w_uk, w_uv = block
        k_n = jnp.einsum("jc,hcd->jhd", c, w_uk)
        v = jnp.einsum("jc,hcd->jhd", c, w_uv)
        scores = (jnp.einsum("qhd,khd->hqk", q_n, k_n)
                  + jnp.einsum("qhr,kr->hqk", q_r, k_r)
                  ) / jnp.sqrt(F32(dn + dr))
        scores = jnp.where(keep[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    g = math.gcd(nh, 8)

    def blocks(x, axis):        # the head axis → [nh / g] blocks of g, first
        x = x.reshape(*x.shape[:axis], nh // g, g, *x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    o = jax.lax.map(heads, (blocks(q_n, 1), blocks(q_r, 1),
                            blocks(w["w_uk"], 0), blocks(w["w_uv"], 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(rows, nh, k["dv"])
    if "g" in p:
        o = o * jax.lax.dynamic_slice_in_dim(p["g"], lo, rows)[:, :, None]
    return o.reshape(rows, nh * k["dv"])


def expert_layer(s: Dict[str, Any], w: Dict[str, jax.Array], m: jax.Array,
                 first: Optional[int] = None, count: Optional[int] = None,
                 shared: bool = True) -> Tuple[jax.Array, jax.Array]:
    """``F`` of a sparse layer for the experts ``first .. first + count``
    (default: the share the configuration states; ``w["we_*"]`` holds
    exactly those, in order), and every token's kept experts ``[S, k]``."""
    first = s["first"] if first is None else first
    count = s["held"] if count is None else count
    scores = jax.nn.sigmoid(m @ w["w_router"])                    # [S, E]
    _, top_i = jax.lax.top_k(scores + w["router_bias"], s["k"])
    top_v = jnp.take_along_axis(scores, top_i, axis=-1)
    if s["renorm"]:
        top_v = top_v / (jnp.sum(top_v, axis=-1, keepdims=True) + 1e-20)
    top_v = top_v * s["route_scale"]

    def expert(e, out):
        weight = jnp.sum(jnp.where(top_i == first + e, top_v, 0.0), axis=-1)
        y = _swiglu(m, w["we_gate"][e], w["we_up"][e], w["we_down"][e])
        return out + weight[:, None] * y

    out = jax.lax.fori_loop(0, count, expert, jnp.zeros_like(m))
    if shared and "ws_gate" in w:
        out = out + _swiglu(m, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out, top_i


@functools.lru_cache(maxsize=None)
def _steps(frozen: Tuple[Tuple[str, Any], ...], kind: str,
           variant: Optional[str]):
    s = dict(frozen)
    k = dict(s[kind])

    def rest(w, x, attn):
        x = x + attn @ w["wo"]
        m = _rms_norm(x, w["mlp_norm"], s["eps"])
        if "w_router" in w:
            out, top_i = expert_layer(s, w, m)
            return x + out, top_i
        return x + _swiglu(m, w["w_gate"], w["w_up"], w["w_down"]), None

    return (
        jax.jit(lambda w, x: project(s, k, w, x, variant)),
        jax.jit(lambda p, lo: select(s, k, p, lo, BLOCK, variant)),
        jax.jit(lambda w, p, keep, lo: attend(k, w, p, keep, lo, BLOCK)),
        jax.jit(rest),
    )


def layer_forward(s: Dict[str, Any], l: int, w: Dict[str, jax.Array],
                  x: jax.Array, variant: Optional[str] = None,
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Layer ``l`` over a whole prompt ``x [n, h]``, ``n`` a multiple of
    ``BLOCK`` (the caller pads at the end; the causal mask keeps padding out
    of what comes before it) → the new ``x`` and a sparse layer's kept
    experts."""
    proj, sel, att, rest = _steps(tuple(sorted(s.items())), s["kinds"][l],
                                  variant)
    p = proj(w, x)
    attn = jnp.concatenate([
        att(w, p, sel(p, jnp.int32(lo)), jnp.int32(lo))
        for lo in range(0, x.shape[0], BLOCK)])
    return rest(w, x, attn)


def forward(cfg: Dict[str, Any], weights: Any, prompts: List[List[int]],
            at: Optional[List[List[int]]] = None,
            variant: Optional[str] = None, tap=None,
            ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Every prompt through the whole model, a layer at a time. Returns,
    per prompt, the logits ``[len(at[i]), V]`` at the positions ``at[i]``
    (default: the last one) and the routing ``[sparse layers, S, k]``.
    ``tap(layer, prompt index, weights of the layer, x [n, h])`` is called
    with every layer's input (the comparison's sub-blocks read it)."""
    s = dims(cfg)
    at = at or [[len(p) - 1] for p in prompts]
    with jax.default_matmul_precision("highest"):
        emb = weights.embedding()
        xs = []
        for p in prompts:
            width = -(-len(p) // BLOCK) * BLOCK
            xs.append(jnp.take(
                emb, jnp.asarray(list(p) + [0] * (width - len(p))), axis=0))
        del emb
        routes: List[List[np.ndarray]] = [[] for _ in prompts]
        for l in range(s["L"]):
            w = weights.layer(l)
            for n, x in enumerate(xs):
                if tap is not None:
                    tap(l, n, w, x[:len(prompts[n])])
                xs[n], top_i = layer_forward(s, l, w, x, variant)
                if top_i is not None:
                    routes[n].append(np.asarray(top_i)[:len(prompts[n])])
            del w
        norm, head = weights.final_norm(), weights.head()
        logits = []
        for x, where in zip(xs, at):
            rows = _rms_norm(x[jnp.asarray(where)], norm, s["eps"])
            logits.append(np.asarray(rows @ head.T, np.float32))
    return logits, [np.stack(r) for r in routes]


def last_logits(cfg: Dict[str, Any], weights: Any,
                prompts: List[List[int]]) -> List[np.ndarray]:
    """The logits at each prompt's last position, ``[V]`` float32 each."""
    return [lg[0] for lg in forward(cfg, weights, prompts)[0]]
