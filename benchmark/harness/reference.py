"""The plain reference: the Llama-recipe forward pass as published, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.

No kernels, no cache, no paging, no batching: one prompt at a time through
full causal attention, streamed one layer at a time, so a 7B and a 47B model
both fit one chip. It is written from the published equations (RMSNorm,
rotary embeddings in the half-split convention, grouped-query attention with
optional QKV biases and an optional sliding window, SwiGLU, and for sparse
models softmax routing over all experts, top-k, renormalised) and shares no
code with the program.

Weights come through a provider, layer by layer, already dequantized to
float32 (the served weights are int8 with a float32 scale per output
channel; the reference multiplies them out and computes in float32, which
is the mathematics the served model approximates):

``SeedStream``   regenerates the weights the program's streamed init makes
                 from a seed, one layer at a time, without the program. It
                 copies the recipe of ``models/loader.py
                 init_quantized_streamed`` (draws, scaling, int8 rounding);
                 ``tests/test_reference.py`` holds it bit-equal to that
                 function on the tiny configurations.
``FromTree``     slices a parameter tree the program built (the tests, and
                 golden files of the stand-in configurations, whose engines
                 build their weights by the program's other init).

Departures from the published models: weights are random, and quantized as
served. Nothing else.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    h, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "h": h, "nh": nh, "nkv": int(cfg["num_key_value_heads"]),
        "d": int(cfg.get("head_dim") or h // nh),
        "i": int(cfg["intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
        "E": int(cfg.get("num_local_experts") or 0),
        "k": int(cfg.get("num_experts_per_tok") or 0),
        "bias": bool(cfg.get("attention_bias")),
        "tied": bool(cfg.get("tie_word_embeddings")),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "window": cfg.get("sliding_window"),
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
    """One compiled generator per leaf shape: a normal draw scaled by
    ``fan_in ** -0.5``, then stored as the program stores it (int8 per
    output channel, or bf16) and read back as float32."""
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
        h, d, nh, nkv, i, L = s["h"], s["d"], s["nh"], s["nkv"], s["i"], s["L"]
        w = {
            "attn_norm": jnp.ones((h,), F32), "mlp_norm": jnp.ones((h,), F32),
            "wq": self._quantized("wq", l, (h, nh * d), h),
            "wk": self._quantized("wk", l, (h, nkv * d), h),
            "wv": self._quantized("wv", l, (h, nkv * d), h),
            "wo": self._quantized("wo", l, (nh * d, h), nh * d),
        }
        if s["E"]:
            E = s["E"]
            w["w_router"] = self._dense("w_router", (L, h, E), h)[l]
            w["we_gate"] = self._quantized("we_gate", l, (E, h, i), h)
            w["we_up"] = self._quantized("we_up", l, (E, h, i), h)
            w["we_down"] = self._quantized("we_down", l, (E, i, h), i)
        else:
            w["w_gate"] = self._quantized("w_gate", l, (h, i), h)
            w["w_up"] = self._quantized("w_up", l, (h, i), h)
            w["w_down"] = self._quantized("w_down", l, (i, h), i)
        if s["bias"]:
            w["bq"] = self._dense("bq", (L, nh * d), nh * d)[l]
            w["bk"] = self._dense("bk", (L, nkv * d), nkv * d)[l]
            w["bv"] = self._dense("bv", (L, nkv * d), nkv * d)[l]
        return w


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
                  x: jax.Array) -> jax.Array:
    """One decoder layer over a whole prompt ``x [S, h]``."""
    n = x.shape[0]
    nh, nkv, d = s["nh"], s["nkv"], s["d"]
    a = _rms_norm(x, w["attn_norm"], s["eps"])
    q, k, v = a @ w["wq"], a @ w["wk"], a @ w["wv"]
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(n, nh, d), s["theta"])
    k = _rope(k.reshape(n, nkv, d), s["theta"])
    v = v.reshape(n, nkv, d)
    # each group of nh/nkv query heads reads one key/value head
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    qi, ki = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    mask = ki <= qi
    if s["window"]:
        mask = mask & (ki > qi - int(s["window"]))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(n, nh * d) @ w["wo"]
    m = _rms_norm(x, w["mlp_norm"], s["eps"])
    if "w_router" in w:
        probs = jax.nn.softmax(m @ w["w_router"], axis=-1)        # [S, E]
        top_v, top_i = jax.lax.top_k(probs, s["k"])
        top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
        out = jnp.zeros_like(x)
        for e in range(s["E"]):
            weight = jnp.sum(jnp.where(top_i == e, top_v, 0.0), axis=-1)
            y = (jax.nn.silu(m @ w["we_gate"][e]) * (m @ w["we_up"][e])) \
                @ w["we_down"][e]
            out = out + weight[:, None] * y
        return x + out
    return x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


def last_logits(cfg: Dict[str, Any], weights: Any,
                prompts: List[List[int]]) -> List[np.ndarray]:
    """The logits at each prompt's last position, ``[V]`` float32 each.
    Prompts are padded at the end to one length so that one compiled layer
    serves them all; a causal mask keeps padding out of what comes before
    it, and the logits are read at the true last position."""
    s = dims(cfg)
    width = max(len(p) for p in prompts)
    with jax.default_matmul_precision("highest"):
        emb = weights.embedding()
        xs = [jnp.take(emb, jnp.asarray(p + [0] * (width - len(p))), axis=0)
              for p in prompts]
        del emb
        step = jax.jit(lambda w, x: layer_forward(s, w, x))
        for l in range(s["L"]):
            w = weights.layer(l)
            xs = [step(w, x) for x in xs]
            jax.block_until_ready(xs)
            del w
        norm, head = weights.final_norm(), weights.head()
        out = []
        for p, x in zip(prompts, xs):
            last = _rms_norm(x[len(p) - 1], norm, s["eps"])
            out.append(np.asarray(head @ last, np.float32))
    return out


def top(logits: np.ndarray, k: int = 5) -> Dict[str, List[float]]:
    idx = np.argsort(-logits, kind="stable")[:k]
    return {"ids": [int(i) for i in idx],
            "logits": [float(logits[i]) for i in idx]}


def first_token_verdict(token: int, golden: Dict[str, Any],
                        margin: float) -> Dict[str, Any]:
    """The rule of the golden file: the served first token is the
    reference's argmax, or one of its top ids whose logit is within
    ``margin`` of the maximum."""
    ids, logits = golden["ids"], golden["logits"]
    if token not in ids:
        return {"ok": False, "rank": None, "deficit": None}
    rank = ids.index(token)
    deficit = logits[0] - logits[rank]
    return {"ok": rank == 0 or deficit <= margin, "rank": rank,
            "deficit": deficit}
