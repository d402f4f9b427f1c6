"""The plain reference of the QK-norm, many-expert decoder block (the OLMoE
recipe), in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

The layer, as the published implementation has it::

    a = RMSNorm(x);  q = a Wq, k = a Wk, v = a Wv
    q = RMSNorm_q(q), k = RMSNorm_k(k)      over the WHOLE projected width
                                            (one learned vector a layer
                                            each), before the split into
                                            heads and before RoPE
    half-split RoPE; causal softmax attention, every head its own K/V
    x = x + attn Wo
    m = RMSNorm(x);  p = softmax(m Wr)      over all experts, float32
    keep the k largest p AS THEY ARE (renormalised only if the config's
    ``norm_topk_prob`` says so)
    x = x + sum_e p_e * (silu(m Wg_e) * (m Wu_e)) Wd_e    over the kept

then a final RMSNorm and an untied head. No kernels, no cache, no paging,
no batching: one prompt at a time through full causal attention, a layer
at a time, a loop over the experts. It shares no code with the program.

Weights come through a provider, layer by layer, already float32:

``SeedStream``   regenerates what the program's streamed init makes from a
                 seed (``models/loader.py init_quantized_streamed``: normal
                 draws scaled by ``fan_in ** -0.5``; matmul weights rounded
                 to int8 per output channel and multiplied out; router,
                 embedding and head rounded to bf16; norm vectors ones).
                 The repository's tests hold it bit-equal to that function
                 on the tiny configuration.
``FromTree``     slices a parameter tree the program built.

Departures from the published model: weights are random, and quantized as
served. Nothing else.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    h, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "h": h, "nh": nh, "nkv": int(cfg["num_key_value_heads"]),
        "d": int(cfg.get("head_dim") or h // nh),
        "i": int(cfg["intermediate_size"]),      # the width of ONE expert
        "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
        "E": int(cfg.get("num_experts") or cfg["num_local_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "renorm": bool(cfg["norm_topk_prob"]),
        "tied": bool(cfg.get("tie_word_embeddings")),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
    }


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

    def _key(self, name: str) -> jax.Array:
        return jax.random.fold_in(
            self.root, zlib.crc32(name.encode()) & 0x7FFFFFFF
        )

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
        if self.s["tied"]:
            return self.embedding()
        return self._dense("lm_head", (self.s["V"], self.s["h"]), self.s["h"])

    def final_norm(self) -> jax.Array:
        return jnp.ones((self.s["h"],), F32)

    def layer(self, l: int) -> Dict[str, jax.Array]:
        s = self.s
        h, d, nh, nkv, i, L, E = (s[x] for x in "h d nh nkv i L E".split())
        return {
            "attn_norm": jnp.ones((h,), F32), "mlp_norm": jnp.ones((h,), F32),
            "q_norm": jnp.ones((nh * d,), F32),
            "k_norm": jnp.ones((nkv * d,), F32),
            "wq": self._quantized("wq", l, (h, nh * d), h),
            "wk": self._quantized("wk", l, (h, nkv * d), h),
            "wv": self._quantized("wv", l, (h, nkv * d), h),
            "wo": self._quantized("wo", l, (nh * d, h), nh * d),
            "w_router": self._dense("w_router", (L, h, E), h)[l],
            "we_gate": self._quantized("we_gate", l, (E, h, i), h),
            "we_up": self._quantized("we_up", l, (E, h, i), h),
            "we_down": self._quantized("we_down", l, (E, i, h), i),
        }


class FromTree:
    """Weights sliced out of a parameter tree the program built: stacked
    leaves with a leading layer axis, quantized leaves as ``{"qw",
    "scale"}``."""

    def __init__(self, params: Dict[str, Any]) -> None:
        self.p = params

    @staticmethod
    def _f32(leaf: Any) -> jax.Array:
        if isinstance(leaf, dict):
            return leaf["qw"].astype(F32) * leaf["scale"].astype(F32)
        return jnp.asarray(leaf).astype(F32)

    def embedding(self) -> jax.Array:
        return self._f32(self.p["embedding"])

    def head(self) -> jax.Array:
        return self._f32(self.p.get("lm_head", self.p["embedding"]))

    def final_norm(self) -> jax.Array:
        return self._f32(self.p["final_norm"])

    def layer(self, l: int) -> Dict[str, jax.Array]:
        return {
            name: self._f32(jax.tree.map(lambda a: a[l], leaf))
            for name, leaf in self.p["layers"].items()
        }


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #

def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [S, heads, d]; position = row index; half-split rotation."""
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_forward(s: Dict[str, Any], w: Dict[str, jax.Array],
                  x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One decoder layer over a whole prompt ``x [S, h]``. Returns the new
    ``x`` and the experts each token was routed to, ``[S, k]``."""
    n = x.shape[0]
    nh, nkv, d = s["nh"], s["nkv"], s["d"]
    a = _rms_norm(x, w["attn_norm"], s["eps"])
    q, k, v = a @ w["wq"], a @ w["wk"], a @ w["wv"]
    q = _rms_norm(q, w["q_norm"], s["eps"])       # over the whole width
    k = _rms_norm(k, w["k_norm"], s["eps"])
    q = _rope(q.reshape(n, nh, d), s["theta"])
    k = _rope(k.reshape(n, nkv, d), s["theta"])
    v = v.reshape(n, nkv, d)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(n, nh * d) @ w["wo"]

    m = _rms_norm(x, w["mlp_norm"], s["eps"])
    probs = jax.nn.softmax(m @ w["w_router"], axis=-1)            # [S, E]
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


def forward(cfg: Dict[str, Any], weights: Any, prompts: List[List[int]],
            at: Optional[List[List[int]]] = None, width: int = 0
            ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Every prompt through the whole model, a layer at a time. Returns,
    per prompt, the logits ``[len(at[i]), V]`` at the positions ``at[i]``
    (default: the last one) and the routing ``[L, S, k]`` of every token in
    every layer. Prompts are padded at the end to one length so that one
    compiled layer serves them all (``width``, if longer: one compiled
    layer over calls whose prompts grow); the causal mask keeps padding out
    of what comes before it."""
    s = dims(cfg)
    width = max(width, max(len(p) for p in prompts))
    at = at or [[len(p) - 1] for p in prompts]
    with jax.default_matmul_precision("highest"):
        emb = weights.embedding()
        xs = [jnp.take(emb, jnp.asarray(p + [0] * (width - len(p))), axis=0)
              for p in prompts]
        del emb
        step = jax.jit(lambda w, x: layer_forward(s, w, x))
        routes: List[List[np.ndarray]] = [[] for _ in prompts]
        for l in range(s["L"]):
            w = weights.layer(l)
            for n, x in enumerate(xs):
                xs[n], top_i = step(w, x)
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
