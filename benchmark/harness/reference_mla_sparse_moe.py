"""The plain reference of a latent-attention (MLA) block under a learned
indexer whose selection most layers borrow (DeepSeek-V3.2's sparse
attention with GLM-5.2's IndexShare), leading dense layers, then
sigmoid-routed experts with a selection bias beside a shared one: the
``glm_moe_dsa`` recipe, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

The layer, for ``x`` in R^h, ``a = RMSNorm(x)`` (pre-norm, no sandwich
norms) and every RMSNorm with the config's eps::

    c_q = N_qa(a W_qa);  q = c_q W_qb = heads of [q_n (nope) ; q_r (rope)]
    [c ; k_r] = a W_kva;  c = N_kva(c)
    RoPE by ADJACENT PAIRS (rope_interleave: element 2i with 2i + 1), theta
    as published, no scaling, on q_r of every head and the one shared k_r
    [k_n,i ; v_i] = c W_kvb      a head's W_UK,i and W_UV,i side by side
    s_ts = (q_n,t . k_n,s + q_r,t . k_r,s) / sqrt(nope + rope)
    softmax over s in S_t ONLY; o = sum p v; attn = concat(o) W_o

    indexer, on a layer whose ``indexer_types`` entry is "full":
        qI = c_q W_qI (Hi heads of Di);  kI = LayerNorm(a W_kI) (one key a
        token; weight and bias, eps as above); the FIRST ``rope`` values of
        every qI head and of kI rotated by adjacent pairs, the others not;
        w = a W_w * (Hi * Di) ** -0.5
        I(t, s) = sum_j w[t, j] * relu(qI[t, j] . kI[s])          s <= t
        S_t = every s <= t while t < topk, else {s : I(t, s) >= the topk-th
        largest of I(t, .)}, ties kept
    a "shared" layer has no indexer: S_t is the S_t of the last full layer
    before it

    x1 = x + attn;  m = RMSNorm(x1);  x2 = x1 + F(m)
    F, a "dense" layer:   W_down(silu(m W_gate) * (m W_up))
    F, a "sparse" layer:  s = sigmoid(m W_r) over ALL published experts;
                          T = the k largest of s + b (selection bias b);
                          w_e = scale * s_e / (sum_{e in T} s_e + 1e-20);
                          F = E_shared(m) + sum_{e in T and held} w_e E_e(m)

then a final RMSNorm and an untied head over the held slice of the
vocabulary. No absorbed queries, no cache, no paging, no kernels, no
batching: one prompt at a time, a layer at a time, attention a block of
heads and a block of queries at a time so that a 20k-token prompt fits, a
loop over the experts. It shares no code with the program.

**The share** is ``reference_mla_moe``'s: the configuration states which
``count`` of the published experts this chip holds, from ``first``; ``T``
and the denominator run over all of them, the sum over the held ones.

Assumed, as the configuration file lists under ``assumed``: the Hadamard
rotation and fp8 quantisation of qI / kI in the published inference code
are left out (the rotation leaves qI . kI as it is, the fp8 is a precision);
the LayerNorm on kI; which 64 of an index head's 128 values rotate; the
weights' scale; the tie rule; ``head_dim`` 192 read by no layer; the
softmax scale; the router.

Weights come through a provider, layer by layer, already float32:
``SeedStream`` regenerates what the program's init draws from a seed
(``models/mla.py init_params`` with a quantization mode: a leaf a named
stack, normal draws scaled by ``fan_in ** -0.5``, matmul weights rounded to
int8 per output channel and multiplied out, everything else rounded to
bf16, norm vectors ``1 + 0.25 x normal``, the LayerNorm's bias ``0.25 x
normal``, the selection bias ``0.1 x normal`` in float32); ``FromTree``
slices a parameter tree the program built.

``variant`` plants one fault for the comparison's controls
(``benchmark/compare_logits_mla_sparse.py``) and the tests: ``dense`` (no
selection), ``topk_half``, ``shared_dense`` (shared layers attend
everything), ``shared_stale`` (shared layers read the selection of the full
layer one period earlier), ``no_index_rope``, ``rope_halves``,
``no_renorm``.
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
VARIANTS = ("dense", "topk_half", "shared_dense", "shared_stale",
            "no_index_rope", "rope_halves", "no_renorm")
# queries a block of attention: [heads of a block, BLOCK, n] float32 scores
BLOCK = 256

# the leaves a layer's attention sub-block reads (``project``, ``select``,
# ``attend`` and ``W_o``)
ATTENTION = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
             "w_uk", "w_uv", "wo", "wqi", "wki", "ww", "ki_norm", "ki_bias")

__all__ = ["SeedStream", "FromTree", "dims", "forward", "last_logits",
           "project", "select", "attend", "expert_layer", "VARIANTS",
           "ATTENTION"]


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    share = cfg.get("expert_share") or {}
    held = int(cfg["n_routed_experts"])
    layers = int(cfg["num_hidden_layers"])
    rope = cfg.get("rope_parameters") or {}
    return {
        "h": int(cfg["hidden_size"]), "nh": int(cfg["num_attention_heads"]),
        "rq": int(cfg["q_lora_rank"]), "rkv": int(cfg["kv_lora_rank"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]),
        "i": int(cfg["intermediate_size"]),
        "mi": int(cfg["moe_intermediate_size"]),
        "L": layers, "V": int(cfg["vocab_size"]),
        "mlp": tuple(cfg["mlp_layer_types"])[:layers],
        "ix": tuple(cfg["indexer_types"])[:layers],
        "E": int(share.get("of", held)),            # the router's width
        "first": int(share.get("first", 0)), "held": held,
        "shared": int(cfg.get("n_shared_experts") or 0),
        "k": int(cfg["num_experts_per_tok"]),
        "renorm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "theta": float(rope.get("rope_theta", cfg.get("rope_theta", 1e4))),
        "eps": float(cfg["rms_norm_eps"]),
        "hi": int(cfg["index_n_heads"]), "di": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
        "pairs": bool(cfg.get("rope_interleave")),
        "ipairs": bool(cfg.get("indexer_rope_interleave")),
    }


def group_of(s: Dict[str, Any], l: int) -> Tuple[str, int, int]:
    """(the stack layer ``l`` lies in, its place there, the stack's size):
    by its MLP and by whether it holds an indexer."""
    def name(i):
        base = "dense_layers" if s["mlp"][i] == "dense" else "layers"
        return ("ix_" if s["ix"][i] == "full" else "") + base

    names = [name(i) for i in range(s["L"])]
    return names[l], names[:l].count(names[l]), names.count(names[l])


def leaf_shapes(s: Dict[str, Any], group: str
                ) -> Dict[str, Tuple[Tuple[int, ...], int, str]]:
    """name → (shape of one layer, fan-in, kind: q quantized matmul weight,
    d bf16 weight, n norm vector, z a bias around zero, b the selection
    bias)."""
    h, nh = s["h"], s["nh"]
    out = {
        "attn_norm": ((h,), 0, "n"), "mlp_norm": ((h,), 0, "n"),
        "q_a_norm": ((s["rq"],), 0, "n"), "kv_a_norm": ((s["rkv"],), 0, "n"),
        "wq_a": ((h, s["rq"]), h, "q"),
        "wq_b": ((s["rq"], nh * (s["dn"] + s["dr"])), s["rq"], "q"),
        "wkv_a": ((h, s["rkv"] + s["dr"]), h, "q"),
        "w_uk": ((nh, s["rkv"], s["dn"]), s["rkv"], "d"),
        "w_uv": ((nh, s["rkv"], s["dv"]), s["rkv"], "d"),
        "wo": ((nh * s["dv"], h), nh * s["dv"], "q"),
    }
    if group.startswith("ix_"):
        out.update({
            "wqi": ((s["rq"], s["hi"] * s["di"]), s["rq"], "q"),
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
    ``quantized`` False: the weights as a bf16 tree holds them (the tests'
    tiny model)."""

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
        """Layer ``l``'s leaves (``only``: those named, e.g.
        ``ATTENTION`` where the experts' 2.4 GB of float32 are not read)."""
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

    def layer(self, l: int) -> Dict[str, jax.Array]:
        group, at, _ = group_of(self.s, l)
        return {name: self._f32(jax.tree.map(lambda a: a[at], leaf))
                for name, leaf in self.p[group].items()}


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


def _rope(x: jax.Array, theta: float, pairs: bool) -> jax.Array:
    """x [S, heads, d]; position = row index; all ``d`` values rotated, by
    adjacent pairs (2i, 2i + 1) or by halves (i, i + d / 2)."""
    n, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(n, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if pairs:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def project(s: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array,
            variant: Optional[str] = None) -> Dict[str, jax.Array]:
    """Everything a layer's attention reads of a whole prompt ``x [n, h]``:
    ``q_n [n, nh, dn]``, rotated ``q_r [n, nh, dr]``, the latent ``c [n,
    rkv]`` and the rotated shared ``k_r [n, dr]``; on a layer with an
    indexer also ``qi [n, Hi, Di]``, ``ki [n, Di]``, ``wt [n, Hi]``."""
    n = x.shape[0]
    nh, dn, dr, rkv = s["nh"], s["dn"], s["dr"], s["rkv"]
    pairs = s["pairs"] and variant != "rope_halves"
    a = _rms_norm(x, w["attn_norm"], s["eps"])
    c_q = _rms_norm(a @ w["wq_a"], w["q_a_norm"], s["eps"])
    q = (c_q @ w["wq_b"]).reshape(n, nh, dn + dr)
    ckr = a @ w["wkv_a"]
    out = {
        "q_n": q[..., :dn], "q_r": _rope(q[..., dn:], s["theta"], pairs),
        "c": _rms_norm(ckr[:, :rkv], w["kv_a_norm"], s["eps"]),
        "k_r": _rope(ckr[:, None, rkv:], s["theta"], pairs)[:, 0],
    }
    if "wqi" in w:
        hi, di = s["hi"], s["di"]
        ipairs = s["ipairs"] and variant != "rope_halves"
        qi = (c_q @ w["wqi"]).reshape(n, hi, di)
        ki = _layer_norm(a @ w["wki"], w["ki_norm"], w["ki_bias"],
                         s["eps"])[:, None, :]
        if variant != "no_index_rope":
            # the first ``dr`` values of a head rotate, the others do not
            qi = jnp.concatenate(
                [_rope(qi[..., :dr], s["theta"], ipairs), qi[..., dr:]], -1)
            ki = jnp.concatenate(
                [_rope(ki[..., :dr], s["theta"], ipairs), ki[..., dr:]], -1)
        out.update({"qi": qi, "ki": ki[:, 0],
                    "wt": (a @ w["ww"]) * (hi * di) ** -0.5})
    return out


def select(s: Dict[str, Any], p: Dict[str, jax.Array], lo: jax.Array,
           rows: int, variant: Optional[str] = None) -> jax.Array:
    """``S_t`` of queries ``lo .. lo + rows`` of a projected prompt (a full
    layer's) as a mask ``[rows, n]``."""
    n = p["c"].shape[0]
    topk = s["topk"] // 2 if variant == "topk_half" else s["topk"]
    t = lo + jnp.arange(rows)
    seen = jnp.arange(n)[None, :] <= t[:, None]
    if variant == "dense" or n <= topk:
        return seen
    qi = jax.lax.dynamic_slice_in_dim(p["qi"], lo, rows)
    wt = jax.lax.dynamic_slice_in_dim(p["wt"], lo, rows)
    dots = jnp.einsum("qjd,kd->qjk", qi, p["ki"])
    score = jnp.sum(wt[:, :, None] * jax.nn.relu(dots), axis=1)
    score = jnp.where(seen, score, -jnp.inf)
    kth = jax.lax.top_k(score, topk)[0][:, -1:]
    # a query with fewer than topk tokens before it: kth is -inf and every
    # token it sees is kept; ties at kth are all kept
    return seen & (score >= kth)


def attend(s: Dict[str, Any], w: Dict[str, jax.Array],
           p: Dict[str, jax.Array], keep: jax.Array, lo: jax.Array,
           rows: int) -> jax.Array:
    """Expanded latent attention of queries ``lo .. lo + rows`` over the
    tokens ``keep [rows, n]`` allows → ``concat(o)`` ``[rows, nh * dv]``
    before ``W_o``. A block of heads at a time."""
    nh, dn, dr = s["nh"], s["dn"], s["dr"]
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
    return jnp.moveaxis(o, 0, 1).reshape(rows, nh * s["dv"])


def expert_layer(s: Dict[str, Any], w: Dict[str, jax.Array], m: jax.Array,
                 first: Optional[int] = None, count: Optional[int] = None,
                 shared: bool = True, variant: Optional[str] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """``F`` of a sparse layer for the experts ``first .. first + count``
    (default: the share the configuration states; ``w["we_*"]`` holds
    exactly those, in order), and every token's kept experts ``[S, k]``."""
    first = s["first"] if first is None else first
    count = s["held"] if count is None else count
    scores = jax.nn.sigmoid(m @ w["w_router"])                    # [S, E]
    _, top_i = jax.lax.top_k(scores + w["router_bias"], s["k"])
    top_v = jnp.take_along_axis(scores, top_i, axis=-1)
    if s["renorm"] and variant != "no_renorm":
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
def _steps(frozen: Tuple[Tuple[str, Any], ...], variant: Optional[str]):
    s = dict(frozen)

    def rest(w, x, attn):
        x = x + attn @ w["wo"]
        m = _rms_norm(x, w["mlp_norm"], s["eps"])
        if "w_router" in w:
            out, top_i = expert_layer(s, w, m, variant=variant)
            return x + out, top_i
        return x + _swiglu(m, w["w_gate"], w["w_up"], w["w_down"]), None

    return (
        jax.jit(lambda w, x: project(s, w, x, variant)),
        jax.jit(lambda p, lo: select(s, p, lo, BLOCK, variant)),
        jax.jit(lambda w, p, keep, lo: attend(s, w, p, keep, lo, BLOCK)),
        jax.jit(rest),
    )


def layer_forward(s: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array,
                  keeps: Optional[List[jax.Array]],
                  variant: Optional[str] = None,
                  ) -> Tuple[jax.Array, Optional[jax.Array], List[jax.Array]]:
    """One decoder layer over a whole prompt ``x [n, h]``, ``n`` a multiple
    of ``BLOCK`` (the caller pads at the end; the causal mask keeps padding
    out of what comes before it). ``keeps``: the selection of the last full
    layer, a mask ``[BLOCK, n]`` a block of queries, which a layer with an
    indexer replaces by its own. Returns the new ``x``, a sparse layer's
    kept experts and the selection the layer attended."""
    proj, sel, att, rest = _steps(tuple(sorted(s.items())), variant)
    p = proj(w, x)
    los = range(0, x.shape[0], BLOCK)
    if "wqi" in w:
        keeps = [sel(p, jnp.int32(lo)) for lo in los]
    elif variant == "shared_dense":
        n = x.shape[0]
        keeps = [jnp.arange(n)[None, :] <= (lo + jnp.arange(BLOCK))[:, None]
                 for lo in los]
    attn = jnp.concatenate([att(w, p, keep, jnp.int32(lo))
                            for keep, lo in zip(keeps, los)])
    return (*rest(w, x, attn), keeps)


def forward(cfg: Dict[str, Any], weights: Any, prompts: List[List[int]],
            at: Optional[List[List[int]]] = None,
            variant: Optional[str] = None, tap=None,
            ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Every prompt through the whole model, a layer at a time. Returns,
    per prompt, the logits ``[len(at[i]), V]`` at the positions ``at[i]``
    (default: the last one) and the routing ``[sparse layers, S, k]``.
    ``tap(layer, prompt index, weights of the layer, x [n, h])`` is called
    with every layer's input (the comparison's sub-block reads it)."""
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
        # the selection each prompt carries from full layer to shared layers
        # (``shared_stale``: the one before it)
        keeps: List[Any] = [None] * len(prompts)
        stale: List[Any] = [None] * len(prompts)
        for l in range(s["L"]):
            w = weights.layer(l)
            for n, x in enumerate(xs):
                if tap is not None:
                    tap(l, n, w, x[:len(prompts[n])])
                given = keeps[n]
                if variant == "shared_stale" and "wqi" not in w \
                        and stale[n] is not None:
                    given = stale[n]
                xs[n], top_i, used = layer_forward(s, w, x, given, variant)
                if "wqi" in w:
                    stale[n], keeps[n] = keeps[n], used
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
