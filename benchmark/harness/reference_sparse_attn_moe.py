"""The plain reference of a GQA decoder block with a learned indexer (sparse
attention) and a many-expert MLP: Keye-VL-2.0-30B-A3B's language model, text
path, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

The layer (``x_t`` the residual of token ``t``, ``h = RMSNorm(x)``)::

    q_t = Wq h_t (nh heads of d), k_t = Wk h_t, v_t = Wv h_t (nkv heads)
    q, k: RMSNorm over each head's d values, one learned vector a layer
          each; then half-split RoPE (a text token's three mrope position
          streams are equal, so the rotation is the plain one)
    indexer: qI_t = WqI h_t (Hi heads of Di), kI_t = LayerNorm(WkI h_t)
          (one key head, weight and bias), both rotated over all Di values;
          w_t = Ww h_t * (Hi * Di) ** -0.5
          I(t, s) = sum_j w[t, j] * relu(qI[t, j] . kI[s])        s <= t
    S_t = every s <= t while t < topk, else {s : I(t, s) >= the topk-th
          largest of I(t, .)}
    attention: softmax over s in S_t of q_t . k_s / sqrt(d), GQA; Wo
    m = RMSNorm(x); p = softmax(m Wr) over all experts; the k largest,
          renormalised; x = x + sum_e p_e (silu(m Wg_e) * (m Wu_e)) Wd_e

then a final RMSNorm and an untied head. No kernels, no cache, no paging:
one prompt at a time, a layer at a time, attention in blocks of queries so
that 20k tokens fit, a loop over the experts. It shares no code with the
program.

Assumed, as the configuration file lists under ``assumed``: the per-head
QK-norm (the config has no key for it; the Qwen3-MoE lineage's convention);
the indexer reads ``h`` (DeepSeek-V3.2's reads a query latent this model
has none of); the LayerNorm with bias on ``kI``; rotation of all ``Di``
values; the weights' scale; ``q_chunk_size`` / ``kv_chunk_size`` tile the
computation and do not change ``S_t``.

**The tie rule.** Scores equal to the topk-th largest are ALL kept, so
``S_t`` may hold more than ``topk`` tokens. ``lax.top_k`` would cut ties by
position; the published kernel's tie behaviour is not documented, and a set
defined by a threshold is what both a sort and a bisection give alike.

Weights come through a provider, layer by layer, already float32:

``SeedStream``   regenerates what the program's streamed init makes from a
                 seed (``models/loader.py init_quantized_streamed`` and
                 ``models/llama.py init_index_leaves``): normal draws scaled
                 by ``fan_in ** -0.5``; matmul weights rounded to int8 per
                 output channel and multiplied out; router, embedding, head
                 and the indexer's two narrow projections rounded to bf16;
                 the layer's two RMSNorm vectors ones; the QK-norm and
                 LayerNorm vectors ``1 + 0.25 x normal`` and the LayerNorm's
                 bias ``0.25 x normal``, so that dropping one shows.
``FromTree``     slices a parameter tree the program built.

``variant`` plants one fault in the reference for the comparison's controls
(``benchmark/compare_logits_sparse.py``): ``dense`` (no selection),
``topk_half`` (half the published topk), ``no_qk_norm``, ``no_index_rope``.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .reference_sparse import F32, FromTree, _int8_roundtrip, _rms_norm

NORM_SPREAD = 0.25
INDEX_FOLD = 0x1D8
VARIANTS = ("dense", "topk_half", "no_qk_norm", "no_index_rope")
# queries a block of attention: [heads, block, n] float32 scores
BLOCK = 256

__all__ = ["SeedStream", "FromTree", "dims", "forward", "last_logits",
           "project", "attend", "VARIANTS"]


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    sa = cfg["sa_config"]
    return {
        "h": int(cfg["hidden_size"]), "nh": int(cfg["num_attention_heads"]),
        "nkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "i": int(cfg["moe_intermediate_size"]),   # the width of ONE expert
        "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
        "E": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "renorm": bool(cfg["norm_topk_prob"]),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "hi": int(sa["indexer_num_heads"]), "di": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"]),
    }


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def _draw(shape: Tuple[int, ...], fan_in: int, quantized: bool):
    def gen(key):
        w = jax.random.normal(key, shape, F32) * (fan_in ** -0.5)
        if quantized:
            return _int8_roundtrip(w)
        return w.astype(jnp.bfloat16).astype(F32)
    return jax.jit(gen)


class SeedStream:
    """The streamed init's weights, regenerated a layer at a time."""

    def __init__(self, cfg: Dict[str, Any], seed: int) -> None:
        self.s = dims(cfg)
        self.root = jax.random.PRNGKey(int(seed))
        self._index: Optional[Dict[str, jax.Array]] = None

    def _key(self, name: str) -> jax.Array:
        return jax.random.fold_in(
            self.root, zlib.crc32(name.encode()) & 0x7FFFFFFF)

    def _quantized(self, name: str, layer: int, shape: Sequence[int],
                   fan_in: int) -> jax.Array:
        key = jax.random.split(self._key(name), self.s["L"])[layer]
        return _draw(tuple(shape), fan_in, True)(key)

    def _dense(self, name: str, shape: Sequence[int], fan_in: int
               ) -> jax.Array:
        return _draw(tuple(shape), fan_in, False)(self._key(name))

    def embedding(self) -> jax.Array:
        return self._dense("embedding", (self.s["V"], self.s["h"]),
                           self.s["h"])

    def head(self) -> jax.Array:
        return self._dense("lm_head", (self.s["V"], self.s["h"]), self.s["h"])

    def final_norm(self) -> jax.Array:
        return jnp.ones((self.s["h"],), F32)

    def _index_leaves(self) -> Dict[str, jax.Array]:
        """``init_index_leaves``' draws: whole stacks (they are small)."""
        if self._index is None:
            s = self.s
            L, h, bf = s["L"], s["h"], jnp.bfloat16
            keys = jax.random.split(
                jax.random.fold_in(self.root, INDEX_FOLD), 7)

            def vec(k, width, centre):
                return (centre + NORM_SPREAD * jax.random.normal(
                    k, (L, width), F32)).astype(bf).astype(F32)

            def mat(k, width):
                return (jax.random.normal(k, (L, h, width), F32)
                        * h ** -0.5).astype(bf).astype(F32)

            self._index = {
                "q_norm": vec(keys[0], s["d"], 1.0),
                "k_norm": vec(keys[1], s["d"], 1.0),
                "wqi": _int8_roundtrip(mat(keys[2], s["hi"] * s["di"])),
                "wki": mat(keys[3], s["di"]), "ww": mat(keys[4], s["hi"]),
                "ki_norm": vec(keys[5], s["di"], 1.0),
                "ki_bias": vec(keys[6], s["di"], 0.0),
            }
        return self._index

    def layer(self, l: int) -> Dict[str, jax.Array]:
        s = self.s
        h, d, nh, nkv, i, L, E = (s[x] for x in "h d nh nkv i L E".split())
        w = {
            "attn_norm": jnp.ones((h,), F32), "mlp_norm": jnp.ones((h,), F32),
            "wq": self._quantized("wq", l, (h, nh * d), h),
            "wk": self._quantized("wk", l, (h, nkv * d), h),
            "wv": self._quantized("wv", l, (h, nkv * d), h),
            "wo": self._quantized("wo", l, (nh * d, h), nh * d),
            "w_router": self._dense("w_router", (L, h, E), h)[l],
            "we_gate": self._quantized("we_gate", l, (E, h, i), h),
            "we_up": self._quantized("we_up", l, (E, h, i), h),
            "we_down": self._quantized("we_down", l, (E, i, h), i),
        }
        w.update({k: v[l] for k, v in self._index_leaves().items()})
        return w


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #

def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [S, heads, d]; position = row index; half-split rotation."""
    n, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(n, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float
                ) -> jax.Array:
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w + b


def project(s: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array,
            variant: Optional[str] = None) -> Dict[str, jax.Array]:
    """Everything attention reads of a whole prompt ``x [n, h]``: rotated
    ``q [n, nh, d]``, ``k``, ``v [n, nkv, d]`` and the indexer's ``qi [n,
    Hi, Di]``, ``ki [n, Di]``, ``wt [n, Hi]``."""
    n = x.shape[0]
    nh, nkv, d, hi, di = s["nh"], s["nkv"], s["d"], s["hi"], s["di"]
    a = _rms_norm(x, w["attn_norm"], s["eps"])
    q = (a @ w["wq"]).reshape(n, nh, d)
    k = (a @ w["wk"]).reshape(n, nkv, d)
    if variant != "no_qk_norm":
        q = _rms_norm(q, w["q_norm"], s["eps"])   # over each head's values
        k = _rms_norm(k, w["k_norm"], s["eps"])
    qi = (a @ w["wqi"]).reshape(n, hi, di)
    ki = _layer_norm(a @ w["wki"], w["ki_norm"], w["ki_bias"], s["eps"])
    ki = ki[:, None, :]
    if variant != "no_index_rope":
        qi, ki = _rope(qi, s["theta"]), _rope(ki, s["theta"])
    return {
        "q": _rope(q, s["theta"]), "k": _rope(k, s["theta"]),
        "v": (a @ w["wv"]).reshape(n, nkv, d),
        "qi": qi, "ki": ki[:, 0], "wt": (a @ w["ww"]) * (hi * di) ** -0.5,
    }


def attend(s: Dict[str, Any], p: Dict[str, jax.Array], lo: jax.Array,
           rows: int, variant: Optional[str] = None
           ) -> Tuple[jax.Array, jax.Array]:
    """Queries ``lo .. lo + rows`` of a projected prompt against all of it
    → (attention output ``[rows, nh * d]`` before ``Wo``, ``S_t`` as a mask
    ``[rows, n]``)."""
    nh, nkv, d = s["nh"], s["nkv"], s["d"]
    n = p["k"].shape[0]
    topk = s["topk"] // 2 if variant == "topk_half" else s["topk"]
    t = lo + jnp.arange(rows)
    seen = jnp.arange(n)[None, :] <= t[:, None]                 # [rows, n]
    keep = seen
    if variant != "dense" and n > topk:
        qi = jax.lax.dynamic_slice_in_dim(p["qi"], lo, rows)
        wt = jax.lax.dynamic_slice_in_dim(p["wt"], lo, rows)
        dots = jnp.einsum("qjd,kd->qjk", qi, p["ki"])
        score = jnp.sum(wt[:, :, None] * jax.nn.relu(dots), axis=1)
        score = jnp.where(seen, score, -jnp.inf)
        kth = jax.lax.top_k(score, topk)[0][:, -1:]
        # a query with fewer than topk tokens before it: kth is -inf and
        # every token it sees is kept; ties at kth are all kept
        keep = seen & (score >= kth)
    q = jax.lax.dynamic_slice_in_dim(p["q"], lo, rows)
    k = jnp.repeat(p["k"], nh // nkv, axis=1)
    v = jnp.repeat(p["v"], nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    scores = jnp.where(keep[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(rows, nh * d), keep


def _experts(s: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    m = _rms_norm(x, w["mlp_norm"], s["eps"])
    probs = jax.nn.softmax(m @ w["w_router"], axis=-1)            # [n, E]
    top_v, top_i = jax.lax.top_k(probs, s["k"])
    if s["renorm"]:
        top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)

    def expert(e, out):
        weight = jnp.sum(jnp.where(top_i == e, top_v, 0.0), axis=-1)
        y = (jax.nn.silu(m @ w["we_gate"][e]) * (m @ w["we_up"][e])) \
            @ w["we_down"][e]
        return out + weight[:, None] * y

    out = jax.lax.fori_loop(0, s["E"], expert, jnp.zeros_like(x))
    return x + out, top_i


@functools.lru_cache(maxsize=None)
def _steps(frozen: Tuple[Tuple[str, Any], ...], variant: Optional[str]):
    s = dict(frozen)
    return (
        jax.jit(lambda w, x: project(s, w, x, variant)),
        jax.jit(lambda p, lo: attend(s, p, lo, BLOCK, variant)[0]),
        jax.jit(lambda w, x, attn: _experts(s, w, x + attn @ w["wo"])),
    )


def layer_forward(s: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array,
                  variant: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """One decoder layer over a whole prompt ``x [n, h]``, ``n`` a multiple
    of ``BLOCK`` (the caller pads at the end; the causal mask keeps padding
    out of what comes before it). Returns the new ``x`` and the experts each
    token was routed to."""
    proj, attn_block, rest = _steps(tuple(sorted(s.items())), variant)
    p = proj(w, x)
    attn = jnp.concatenate([attn_block(p, jnp.int32(lo))
                            for lo in range(0, x.shape[0], BLOCK)])
    return rest(w, x, attn)


def forward(cfg: Dict[str, Any], weights: Any, prompts: List[List[int]],
            at: Optional[List[List[int]]] = None,
            variant: Optional[str] = None, tap=None,
            ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Every prompt through the whole model, a layer at a time. Returns,
    per prompt, the logits ``[len(at[i]), V]`` at the positions ``at[i]``
    (default: the last one) and the routing ``[L, S, k]``. ``tap(layer,
    prompt index, weights of the layer, x [n, h])`` is called with every
    layer's input (the comparison's sub-block reads it)."""
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
                xs[n], top_i = layer_forward(s, w, x, variant)
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
