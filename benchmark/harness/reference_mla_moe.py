"""The plain reference of the latent-attention (MLA) block with a shared
expert beside routed ones, sandwich norms and leading dense layers (the
openPangu-Ultra-MoE recipe), in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

The layer, for ``x`` in R^h and every RMSNorm with the config's eps::

    a = N_in(x)
    c_q = N_qa(a W_qa);  q = c_q W_qb = heads of [q_n (nope) ; q_r (rope)]
    [c ; k_r] = a W_kva;  c = N_kva(c)
    half-split RoPE (theta as published, no scaling) on q_r of every head
    and on the one shared k_r
    [k_n,i ; v_i] = c W_kvb      a head's W_UK,i and W_UV,i side by side
    s_ij = (q_n,i . k_n,j + q_r,i . k_r,j) / sqrt(nope + rope)
    causal softmax; o_i = sum_j p_ij v_j;  attn = concat(o) W_o
    x1 = x + N_post_attn(attn);  m = N_pre_mlp(x1);  x2 = x1 + N_post_mlp(F(m))
    F, leading dense layers:  W_down(silu(m W_gate) * (m W_up))
    F, every later layer:     s = sigmoid(m W_r) over ALL published experts;
                              T = the k largest; w_e = scale * s_e /
                              (sum_{e in T} s_e + 1e-20);
                              F = E_shared(m) + sum_{e in T} w_e E_e(m)

then a final RMSNorm and the head. This is the **expanded** form only: no
absorbed queries, no cache, no paging, no kernels, no batching; one prompt
at a time through full causal attention, a layer at a time, a loop over the
experts. It shares no code with the program.

**The share.** The configuration states which ``count`` of the published
experts this chip holds, from ``first``. The sum above then runs over ``e
in T and held``; ``T`` and the denominator run over all published experts.
What the absent experts would have added is left out, here as in the
program, and that partial result goes on to the next layer. The head is
over the held slice of the vocabulary (a sliced vocabulary is a smaller
vocabulary).

Departures from the published model, each also under ``assumed`` in the
configuration file: weights are random and quantized as served; sigmoid
scoring, group-free top-k with no selection bias (the config has no key for
groups or a bias); ``sandwich_norm: true`` read as one norm after each
sub-block's output before the residual add; RoPE pairs element ``i`` with
``i + rope/2`` (half-split); the multi-token-prediction layer is not loaded
(the published modelling code drops it at inference). ``W_kvb`` is held as
its per-head halves ``W_UK`` / ``W_UV``, which is a relabelling.

Weights come through a provider, layer by layer, already float32:
``SeedStream`` regenerates what the program's init draws from a seed
(``models/mla.py init_params`` with a quantization mode: normal draws
scaled by ``fan_in ** -0.5``, matmul weights rounded to int8 per output
channel and multiplied out, everything else rounded to bf16, norm vectors
``1 + 0.25 * normal``); ``FromTree`` slices a parameter tree the program
built.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the int8 round trip, RMSNorm and half-split RoPE are the sparse reference's
# (one plain reference's arithmetic beside another's; neither is the program's)
from .reference_sparse import F32, _int8_roundtrip, _rms_norm, _rope

NORM_SPREAD = 0.25


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    share = cfg.get("expert_share") or {}
    held = int(cfg["n_routed_experts"])
    return {
        "h": int(cfg["hidden_size"]), "nh": int(cfg["num_attention_heads"]),
        "rq": int(cfg["q_lora_rank"]), "rkv": int(cfg["kv_lora_rank"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]),
        "i": int(cfg["intermediate_size"]),
        "mi": int(cfg["moe_intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]),
        "lead": int(cfg["first_k_dense_replace"]),
        "V": int(cfg["vocab_size"]),
        "E": int(share.get("of", held)),            # the router's width
        "first": int(share.get("first", 0)), "held": held,
        "shared": int(cfg.get("n_shared_experts") or 0),
        "k": int(cfg["num_experts_per_tok"]),
        "renorm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "sandwich": bool(cfg.get("sandwich_norm")),
        "tied": bool(cfg.get("tie_word_embeddings")),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
    }


def groups(s: Dict[str, Any]) -> List[Tuple[str, int]]:
    """(name, layers) of the leading dense layers and of the rest."""
    return [g for g in (("dense_layers", s["lead"]),
                        ("layers", s["L"] - s["lead"])) if g[1]]


def leaf_shapes(s: Dict[str, Any], group: str
                ) -> Dict[str, Tuple[Tuple[int, ...], int, str]]:
    """name → (shape of one layer, fan-in, kind: q quantized matmul weight,
    d bf16 weight, n norm vector)."""
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
    if s["sandwich"]:
        out["post_attn_norm"] = ((h,), 0, "n")
        out["post_mlp_norm"] = ((h,), 0, "n")
    if group == "layers":
        mi = s["mi"]
        out.update({
            "w_router": ((h, s["E"]), h, "d"),
            "we_gate": ((s["held"], h, mi), h, "q"),
            "we_up": ((s["held"], h, mi), h, "q"),
            "we_down": ((s["held"], mi, h), mi, "q"),
        })
        if s["shared"]:
            ms = mi * s["shared"]
            out.update({"ws_gate": ((h, ms), h, "q"),
                        "ws_up": ((h, ms), h, "q"),
                        "ws_down": ((ms, h), ms, "q")})
    else:
        out.update({"w_gate": ((h, s["i"]), h, "q"),
                    "w_up": ((h, s["i"]), h, "q"),
                    "w_down": ((s["i"], h), s["i"], "q")})
    return out


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def _draw(shape: Tuple[int, ...], fan_in: int, kind: str):
    def gen(key):
        x = jax.random.normal(key, shape, F32)
        if kind == "n":
            return (1.0 + NORM_SPREAD * x).astype(jnp.bfloat16).astype(F32)
        w = x * (fan_in ** -0.5)
        if kind == "q":
            return _int8_roundtrip(w)
        return w.astype(jnp.bfloat16).astype(F32)
    return jax.jit(gen)


class SeedStream:
    """The program's seeded init, regenerated a layer at a time."""

    def __init__(self, cfg: Dict[str, Any], seed: int) -> None:
        self.s = dims(cfg)
        self.root = jax.random.PRNGKey(int(seed))

    def _key(self, name: str) -> jax.Array:
        return jax.random.fold_in(
            self.root, zlib.crc32(name.encode()) & 0x7FFFFFFF)

    def embedding(self) -> jax.Array:
        return _draw((self.s["V"], self.s["h"]), self.s["h"], "d")(
            self._key("embedding"))

    def head(self) -> jax.Array:
        if self.s["tied"]:
            return self.embedding()
        return _draw((self.s["V"], self.s["h"]), self.s["h"], "d")(
            self._key("lm_head"))

    def final_norm(self) -> jax.Array:
        return _draw((self.s["h"],), 0, "n")(self._key("final_norm"))

    def layer(self, l: int) -> Dict[str, jax.Array]:
        at = l
        for group, n in groups(self.s):
            if at < n:
                break
            at -= n
        return {
            name: _draw(shape, fan_in, kind)(
                jax.random.split(self._key(f"{group}.{name}"), n)[at])
            for name, (shape, fan_in, kind)
            in leaf_shapes(self.s, group).items()
        }


class FromTree:
    """Weights sliced out of a parameter tree the program built: stacked
    leaves with a leading layer axis under ``dense_layers`` and ``layers``,
    quantized leaves as ``{"qw", "scale"}``."""

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
        lead = self.p.get("dense_layers")
        n_lead = 0 if lead is None else len(lead["attn_norm"])
        tree, at = (lead, l) if l < n_lead else (self.p["layers"], l - n_lead)
        return {name: self._f32(jax.tree.map(lambda a: a[at], leaf))
                for name, leaf in tree.items()}


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #

def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def attention(s: Dict[str, Any], w: Dict[str, jax.Array], a: jax.Array
              ) -> jax.Array:
    """Expanded latent attention over a whole prompt, ``a [S, h]`` the
    normed input → ``concat(o) W_o`` ``[S, h]``."""
    n = a.shape[0]
    nh, dn, dr, rkv = s["nh"], s["dn"], s["dr"], s["rkv"]
    c_q = _rms_norm(a @ w["wq_a"], w["q_a_norm"], s["eps"])
    q = (c_q @ w["wq_b"]).reshape(n, nh, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], s["theta"])
    ckr = a @ w["wkv_a"]
    c = _rms_norm(ckr[:, :rkv], w["kv_a_norm"], s["eps"])
    k_r = _rope(ckr[:, None, rkv:], s["theta"])[:, 0]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def heads(block):
        # a block of heads at a time: the float32 scores of all 128 heads
        # over a 3 k-token prompt are 4.8 GB, beside a layer's weights
        q_n, q_r, w_uk, w_uv = block
        k_n = jnp.einsum("jc,hcd->jhd", c, w_uk)
        v = jnp.einsum("jc,hcd->jhd", c, w_uv)
        scores = (jnp.einsum("qhd,khd->hqk", q_n, k_n)
                  + jnp.einsum("qhr,kr->hqk", q_r, k_r)
                  ) / jnp.sqrt(F32(dn + dr))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    g = math.gcd(nh, 16)

    def blocks(x, axis):        # the head axis → [nh / g] blocks of g, first
        x = x.reshape(*x.shape[:axis], nh // g, g, *x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    o = jax.lax.map(heads, (blocks(q_n, 1), blocks(q_r, 1),
                            blocks(w["w_uk"], 0), blocks(w["w_uv"], 0)))
    o = jnp.moveaxis(o, 0, 1)                         # [n, nh / g, g, dv]
    return o.reshape(n, nh * s["dv"]) @ w["wo"]


def expert_layer(s: Dict[str, Any], w: Dict[str, jax.Array], m: jax.Array,
                 first: Optional[int] = None, count: Optional[int] = None,
                 shared: bool = True) -> Tuple[jax.Array, jax.Array]:
    """``F`` of an expert layer for the experts ``first .. first + count``
    (default: the share the configuration states; ``w["we_*"]`` holds
    exactly those, in order), and every token's kept experts ``[S, k]``."""
    first = s["first"] if first is None else first
    count = s["held"] if count is None else count
    scores = jax.nn.sigmoid(m @ w["w_router"])                    # [S, E]
    top_v, top_i = jax.lax.top_k(scores, s["k"])
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


def layer_forward(s: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One decoder layer over a whole prompt ``x [S, h]``. Returns the new
    ``x`` and, for an expert layer, every token's kept experts."""
    attn = attention(s, w, _rms_norm(x, w["attn_norm"], s["eps"]))
    if "post_attn_norm" in w:
        attn = _rms_norm(attn, w["post_attn_norm"], s["eps"])
    x = x + attn
    m = _rms_norm(x, w["mlp_norm"], s["eps"])
    top_i = None
    if "w_router" in w:
        out, top_i = expert_layer(s, w, m)
    else:
        out = _swiglu(m, w["w_gate"], w["w_up"], w["w_down"])
    if "post_mlp_norm" in w:
        out = _rms_norm(out, w["post_mlp_norm"], s["eps"])
    return x + out, top_i


def forward(cfg: Dict[str, Any], weights: Any, prompts: List[List[int]],
            at: Optional[List[List[int]]] = None, width: int = 0
            ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Every prompt through the whole model, a layer at a time. Returns,
    per prompt, the logits ``[len(at[i]), V]`` at the positions ``at[i]``
    (default: the last one) and the routing ``[expert layers, S, k]`` of
    every token. Prompts are padded at the end to one length so that one
    compiled layer of a kind serves them all; the causal mask keeps padding
    out of what comes before it."""
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
