"""The plain reference of a hybrid decoder: gated delta-rule linear attention
(Kimi Delta Attention, KDA) in most layers, latent attention (MLA) without
rotation in the layers the configuration lists, a dense first MLP and then a
shared expert beside routed ones picked with a selection bias (the
Kimi-Linear recipe), in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

Every layer is ``x1 = x + attn(N_in(x)); x2 = x1 + F(N_mlp(x1))`` with the
config's eps; then a final RMSNorm and an untied head.

*KDA layer* (``H`` heads of ``d``; ``a`` the normed input of one token)::

    [q ; k ; v] = silu(conv(a W_qkv))     causal depthwise convolution over
                                          the sequence, ``taps`` taps a
                                          channel, zeros before position 0
    q = l2norm(q) / sqrt(d);  k = l2norm(k)        per head, over d
                                                   (x / sqrt(sum x^2 + 1e-6))
    g = -exp(A_log[h]) * softplus((a W_fa) W_fb + dt_bias)   per key channel
    beta = sigmoid(a W_b)
    S = 0 at position 0, a d x d matrix a head (keys down, values across):
    S <- diag(exp(g)) S;  u = beta (v - k^T S);  S <- S + k u^T;  o = q^T S
    attn = (rms_d(o) * w_norm * sigmoid((a W_ga) W_gb)) W_o

token by token (a ``lax.scan`` over positions): no chunking, no state pool,
no kernels. No position enters.

*MLA layer*: ``q = a W_q`` as heads of ``[q_n ; q_r]`` (no low-rank, no
norm), ``[c ; k_r] = a W_kva``, ``c = N_kva(c)``, nothing rotated;
``[k_n,i ; v_i] = c W_kvb`` a head; ``s_ij = (q_n,i . k_n,j + q_r,i . k_r,j)
/ sqrt(nope + rope)``; causal softmax; ``concat(o) W_o``. The expanded form,
a block of heads at a time.

*Expert layer* (every layer after the leading dense ones): ``s = sigmoid(m
W_r)`` over ALL published experts; ``T`` = the k largest of ``s + b`` (``b``
the selection bias); ``w_e = scale * s_e / (sum_{e in T} s_e + 1e-20)`` (the
bias is not in the weights); ``F = E_shared(m) + sum_{e in T and held} w_e
E_e(m)``. The configuration states which ``count`` of the published experts
this chip holds, from ``first``; what the absent experts would have added is
left out, here as in the program. The head is over the held slice of the
vocabulary.

Departures from the published model, each under ``assumed`` in the
configuration file: weights are random and quantized as served; ``A_log``,
``dt_bias`` and the selection bias are drawn as the family initialises them
(the config has no key for them); q, k and v projections are one matrix side
by side (a relabelling); no bias on any projection.

Weights come through a provider, a layer at a time, already float32:
``SeedStream`` regenerates what the program's init draws from a seed
(``models/mla.py init_params`` with a quantization mode), ``FromTree``
slices a parameter tree the program built. It shares no code with the
program.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .reference_sparse import F32, _int8_roundtrip, _rms_norm

NORM_SPREAD = 0.25


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    share = cfg.get("expert_share") or {}
    held = int(cfg["num_experts"])
    lin = cfg["linear_attn_config"]
    return {
        "h": int(cfg["hidden_size"]), "nh": int(cfg["num_attention_heads"]),
        "rkv": int(cfg["kv_lora_rank"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]),
        "kh": int(lin["num_heads"]), "kd": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "full": [int(x) for x in lin["full_attn_layers"]],
        "i": int(cfg["intermediate_size"]),
        "mi": int(cfg["moe_intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]),
        "lead": int(cfg["first_k_dense_replace"]),
        "V": int(cfg["vocab_size"]),
        "E": int(share.get("of", held)),            # the router's width
        "first": int(share.get("first", 0)), "held": held,
        "shared": int(cfg.get("num_shared_experts") or 0),
        "k": int(cfg["num_experts_per_token"]),
        "renorm": bool(cfg["moe_renormalize"]),
        "route_scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "bias": bool(cfg.get("router_selection_bias", True)),
        "tied": bool(cfg.get("tie_word_embeddings")),
        "eps": float(cfg["rms_norm_eps"]),
    }


def group_of(s: Dict[str, Any], l: int) -> Tuple[str, int]:
    """(the parameter stack layer ``l`` (0-based) lies in, its place there):
    ``dense_layers`` / ``layers`` by its MLP, ``kda_`` before the name of a
    linear-attention layer's."""
    def name(i):
        mlp = "dense_layers" if i < s["lead"] else "layers"
        return mlp if i + 1 in s["full"] else "kda_" + mlp

    mine = name(l)
    return mine, sum(1 for i in range(l) if name(i) == mine)


def group_size(s: Dict[str, Any], group: str) -> int:
    return sum(1 for l in range(s["L"]) if group_of(s, l)[0] == group)


def leaf_shapes(s: Dict[str, Any], group: str
                ) -> Dict[str, Tuple[Tuple[int, ...], int, str]]:
    """name → (shape of one layer, fan-in, kind: q quantized matmul weight,
    d bf16 weight, n norm vector, a / t / b float32 vectors drawn as
    ``A_log`` / ``dt_bias`` / the selection bias are)."""
    h = s["h"]
    out = {"attn_norm": ((h,), 0, "n"), "mlp_norm": ((h,), 0, "n")}
    if group.startswith("kda_"):
        kh, kd, taps = s["kh"], s["kd"], s["taps"]
        p = kh * kd
        out.update({
            "wqkv": ((h, 3 * p), h, "q"), "conv": ((taps, 3 * p), taps, "d"),
            "w_fa": ((h, kd), h, "q"), "w_fb": ((kd, p), kd, "q"),
            "dt_bias": ((p,), 0, "t"), "a_log": ((kh,), 0, "a"),
            "w_b": ((h, kh), h, "d"),
            "w_ga": ((h, kd), h, "q"), "w_gb": ((kd, p), kd, "q"),
            "o_norm": ((kd,), 0, "n"), "wo": ((p, h), p, "q"),
        })
    else:
        nh = s["nh"]
        out.update({
            "wq": ((h, nh * (s["dn"] + s["dr"])), h, "q"),
            "wkv_a": ((h, s["rkv"] + s["dr"]), h, "q"),
            "kv_a_norm": ((s["rkv"],), 0, "n"),
            "w_uk": ((nh, s["rkv"], s["dn"]), s["rkv"], "d"),
            "w_uv": ((nh, s["rkv"], s["dv"]), s["rkv"], "d"),
            "wo": ((nh * s["dv"], h), nh * s["dv"], "q"),
        })
    if group.endswith("dense_layers"):
        out.update({"w_gate": ((h, s["i"]), h, "q"),
                    "w_up": ((h, s["i"]), h, "q"),
                    "w_down": ((s["i"], h), s["i"], "q")})
    else:
        mi = s["mi"]
        out.update({
            "w_router": ((h, s["E"]), h, "d"),
            "we_gate": ((s["held"], h, mi), h, "q"),
            "we_up": ((s["held"], h, mi), h, "q"),
            "we_down": ((s["held"], mi, h), mi, "q"),
        })
        if s["bias"]:
            out["router_bias"] = ((s["E"],), 0, "b")
        if s["shared"]:
            ms = mi * s["shared"]
            out.update({"ws_gate": ((h, ms), h, "q"),
                        "ws_up": ((h, ms), h, "q"),
                        "ws_down": ((ms, h), ms, "q")})
    return out


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def _draw(shape: Tuple[int, ...], fan_in: int, kind: str):
    def gen(key):
        if kind == "a":
            return jnp.log(jax.random.uniform(key, shape, F32, minval=1.0,
                                              maxval=16.0))
        if kind == "t":
            dt = jnp.exp(jax.random.uniform(
                key, shape, F32, minval=math.log(1e-3), maxval=math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        x = jax.random.normal(key, shape, F32)
        if kind == "b":
            return 0.1 * x
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
        group, at = group_of(self.s, l)
        n = group_size(self.s, group)
        return {
            name: _draw(shape, fan_in, kind)(
                jax.random.split(self._key(f"{group}.{name}"), n)[at])
            for name, (shape, fan_in, kind)
            in leaf_shapes(self.s, group).items()
        }


class FromTree:
    """Weights sliced out of a parameter tree the program built: stacked
    leaves with a leading layer axis under the four stacks' names,
    quantized leaves as ``{"qw", "scale"}``. ``s``: :func:`dims` of the
    configuration (which layer lies in which stack)."""

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
        return self._f32(self.p.get("lm_head", self.p["embedding"]))

    def final_norm(self) -> jax.Array:
        return self._f32(self.p["final_norm"])

    def layer(self, l: int) -> Dict[str, jax.Array]:
        group, at = group_of(self.s, l)
        return {name: self._f32(jax.tree.map(lambda a: a[at], leaf))
                for name, leaf in self.p[group].items()}


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #

def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_recurrence(s: Dict[str, Any], w: Dict[str, jax.Array], a: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """The delta rule over a whole prompt, token by token from a zero
    state: ``a [S, h]`` the normed input → (``o [S, H, d]`` before the
    output norm, the state after the last token ``[H, d, d]``)."""
    n = a.shape[0]
    kh, kd, taps = s["kh"], s["kd"], s["taps"]
    pre = a @ w["wqkv"]                                        # [S, 3P]
    padded = jnp.concatenate([jnp.zeros((taps - 1, pre.shape[1]), F32), pre])
    conv = sum(padded[j:j + n] * w["conv"][j] for j in range(taps))
    qkv = jax.nn.silu(conv).reshape(n, 3, kh, kd)
    q = _l2norm(qkv[:, 0]) / jnp.sqrt(F32(kd))
    k, v = _l2norm(qkv[:, 1]), qkv[:, 2]
    g = -jnp.exp(w["a_log"])[None, :, None] * jax.nn.softplus(
        (a @ w["w_fa"]) @ w["w_fb"] + w["dt_bias"]).reshape(n, kh, kd)
    beta = jax.nn.sigmoid(a @ w["w_b"])                        # [S, H]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        st = state * jnp.exp(g_t)[..., None]                   # [H, dk, dv]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, st))
        st = st + k_t[..., None] * u[:, None, :]
        return st, jnp.einsum("hk,hkv->hv", q_t, st)

    last, o = jax.lax.scan(token, jnp.zeros((kh, kd, kd), F32),
                           (q, k, v, g, beta))
    return o, last


def kda_attention(s: Dict[str, Any], w: Dict[str, jax.Array], a: jax.Array
                  ) -> jax.Array:
    """The gated delta-rule layer over a whole prompt, ``a [S, h]`` the
    normed input."""
    n = a.shape[0]
    kh, kd = s["kh"], s["kd"]
    o, _ = kda_recurrence(s, w, a)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + s["eps"])
    gate = jax.nn.sigmoid((a @ w["w_ga"]) @ w["w_gb"]).reshape(n, kh, kd)
    return (o * w["o_norm"] * gate).reshape(n, kh * kd) @ w["wo"]


def mla_attention(s: Dict[str, Any], w: Dict[str, jax.Array], a: jax.Array
                  ) -> jax.Array:
    """Expanded latent attention over a whole prompt, nothing rotated."""
    n = a.shape[0]
    nh, dn, dr, rkv = s["nh"], s["dn"], s["dr"], s["rkv"]
    q = (a @ w["wq"]).reshape(n, nh, dn + dr)
    q_n, q_r = q[..., :dn], q[..., dn:]
    ckr = a @ w["wkv_a"]
    c = _rms_norm(ckr[:, :rkv], w["kv_a_norm"], s["eps"])
    k_r = ckr[:, rkv:]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def heads(block):
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
    pick = scores + w["router_bias"] if "router_bias" in w else scores
    _, top_i = jax.lax.top_k(pick, s["k"])
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


def layer_forward(s: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One decoder layer over a whole prompt ``x [S, h]``. Returns the new
    ``x`` and, for an expert layer, every token's kept experts."""
    a = _rms_norm(x, w["attn_norm"], s["eps"])
    if "wqkv" in w:
        x = x + kda_attention(s, w, a)
    else:
        x = x + mla_attention(s, w, a)
    m = _rms_norm(x, w["mlp_norm"], s["eps"])
    if "w_router" in w:
        out, top_i = expert_layer(s, w, m)
        return x + out, top_i
    return x + _swiglu(m, w["w_gate"], w["w_up"], w["w_down"]), None


def forward(cfg: Dict[str, Any], weights: Any, prompts: List[List[int]],
            at: Optional[List[List[int]]] = None, width: int = 0,
            tap: Optional[Callable[[int, int, Dict[str, jax.Array],
                                    jax.Array], None]] = None,
            ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Every prompt through the whole model, a layer at a time. Returns,
    per prompt, the logits ``[len(at[i]), V]`` at the positions ``at[i]``
    (default: the last one) and the routing ``[expert layers, S, k]`` of
    every token. Prompts are padded at the end to one length so that one
    compiled layer of a kind serves them all; causality (the mask, the
    convolution, the recurrence) keeps padding out of what comes before.
    ``tap(l, n, w, x)`` is shown layer ``l``'s weights and prompt ``n``'s
    hidden state ``x [width, h]`` as the layer takes it."""
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
                if tap is not None:
                    tap(l, n, w, x)
                xs[n], top_i = step(w, x)
                if top_i is not None:
                    routes[n].append(np.asarray(top_i)[:len(prompts[n])])
            del w
        norm, head = weights.final_norm(), weights.head()
        logits = []
        for x, where in zip(xs, at):
            rows = _rms_norm(x[jnp.asarray(where)], norm, s["eps"])
            logits.append(np.asarray(rows @ head.T, np.float32))
    return logits, [np.stack(r) for r in routes if r]


def last_logits(cfg: Dict[str, Any], weights: Any,
                prompts: List[List[int]]) -> List[np.ndarray]:
    """The logits at each prompt's last position, ``[V]`` float32 each."""
    return [lg[0] for lg in forward(cfg, weights, prompts)[0]]
