"""The plain reference of a decoder whose every layer holds a Mamba-2 (SSD)
mixer and GQA attention side by side under muP multipliers: Falcon-H1, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.

The layer (``h`` the residual of a prompt ``[S, hidden]``, ``u = RMSNorm(h)
* w_in``; every multiplier from the configuration)::

    h = h + ssm_out_multiplier * Mixer(u)
          + attention_out_multiplier * Attn(attention_in_multiplier * u)
    v = RMSNorm(h) * w_ff
    h = h + down(up(v) * silu(gate(v) * mlp_multipliers[0]))
            * mlp_multipliers[1]

    Attn:   q = u Wq (H heads of d), k = (u Wk) * key_multiplier, v = u Wv
            (nkv heads); all d values of a head rotated half-split at
            rope_theta; softmax over the keys s <= t of q . k / sqrt(d), GQA;
            then Wo. No bias, no QK-norm.
    Mixer:  p = ((ssm_in_multiplier * u) W_in) * mup, split as z | x | B | C
            | dt with mup the five ssm_multipliers over them in that order;
            xBC = silu(conv(x | B | C) + b_conv), causal depthwise over
            mamba_d_conv taps; dt = softplus(dt + dt_bias); A = -exp(A_log);
            a head j (P channels, a state S of P x N) reads B, C of group
            j // (heads / groups):
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
                y_t = S_t C_t + D x_t
            y = y * silu(z); RMS over each group's channels, times w_norm
            (mamba_rms_norm true, mamba_norm_before_gate false); then W_out.

The first token embeds as ``embedding[id] * embedding_multiplier``; the
logits are ``head(RMSNorm(h_L) * w_final) * lm_head_multiplier`` over the
held slice of the vocabulary. No kernels, no cache, no paging, no chunks:
the recurrence token by token from a zero state, attention over the whole
prompt. It shares no code with the program.

Weights come through a provider, layer by layer, already float32:

``SeedStream``   regenerates what the program's seeded init makes
                 (``models/mla.py init_params`` over ``models/llama.py
                 leaf_specs``): a leaf a layer, drawn from the key of
                 ``layers.<leaf>``; matmul weights at a standard deviation
                 of ``fan_in ** -0.5`` OVER the product of the multipliers
                 that scale what they make (so that under them mixer,
                 attention and MLP add comparable shares to the residual),
                 rounded to int8 per output channel and multiplied out; the
                 step sizes' columns, the convolution and its bias,
                 embedding and head rounded to bf16; norm vectors ``1 + 0.25
                 x normal``; ``A_log = log(1..H)``, ``D = 1`` and ``dt_bias``
                 the inverse softplus of ``exp(U(log 0.001, log 0.1))`` in
                 float32.
``FromTree``     slices a parameter tree the program built.
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

__all__ = ["SeedStream", "FromTree", "dims", "forward", "last_logits",
           "mixer_recurrence", "mixer", "attention"]


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    mz, mx, mb, mc, mdt = (float(m) for m in cfg["ssm_multipliers"])
    return {
        "h": int(cfg["hidden_size"]), "nh": int(cfg["num_attention_heads"]),
        "nkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "i": int(cfg["intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "sh": int(cfg["mamba_n_heads"]), "sp": int(cfg["mamba_d_head"]),
        "sn": int(cfg["mamba_d_state"]), "sg": int(cfg["mamba_n_groups"]),
        "taps": int(cfg["mamba_d_conv"]), "ds": int(cfg["mamba_d_ssm"]),
        "m_emb": float(cfg["embedding_multiplier"]),
        "m_head": float(cfg["lm_head_multiplier"]),
        "m_key": float(cfg["key_multiplier"]),
        "m_ain": float(cfg["attention_in_multiplier"]),
        "m_aout": float(cfg["attention_out_multiplier"]),
        "m_sin": float(cfg["ssm_in_multiplier"]),
        "m_sout": float(cfg["ssm_out_multiplier"]),
        "m_gate": float(cfg["mlp_multipliers"][0]),
        "m_down": float(cfg["mlp_multipliers"][1]),
        "mup": (mz, mx, mb, mc, mdt),
    }


def _fan(fan_in: int, *multipliers: float):
    m = math.prod(multipliers)
    return fan_in if m == 1.0 else fan_in * m * m


def leaf_shapes(s: Dict[str, Any]
                ) -> Dict[str, Tuple[Tuple[int, ...], Any, str]]:
    """name -> (shape, fan-in, kind) of a layer's leaves. Kinds: ``q`` a
    quantized matmul weight, ``d`` a bf16 weight, ``n`` a norm vector, ``z``
    a bias around zero, ``t`` / ``r`` / ``o`` the float32 vectors ``dt_bias``,
    ``A_log`` and ``D``."""
    h, d, nh, nkv, i = s["h"], s["d"], s["nh"], s["nkv"], s["i"]
    p, conv = s["ds"], s["ds"] + 2 * s["sg"] * s["sn"]
    f_in = _fan(h, s["m_sin"])
    return {
        "attn_norm": ((h,), 0, "n"), "mlp_norm": ((h,), 0, "n"),
        "wq": ((h, nh * d), _fan(h, s["m_ain"]), "q"),
        "wk": ((h, nkv * d), _fan(h, s["m_ain"], s["m_key"]), "q"),
        "wv": ((h, nkv * d), _fan(h, s["m_ain"]), "q"),
        "wo": ((nh * d, h), _fan(nh * d, s["m_aout"]), "q"),
        "w_in": ((h, p + conv), f_in, "q"),
        "w_dt": ((h, s["sh"]), f_in, "d"),
        "conv": ((s["taps"], conv), s["taps"], "d"),
        "conv_bias": ((conv,), 0, "z"),
        "dt_bias": ((s["sh"],), 0, "t"),
        "a_log": ((s["sh"],), 0, "r"),
        "d_skip": ((s["sh"],), 0, "o"),
        "ssm_norm": ((p,), 0, "n"),
        "w_out": ((p, h), _fan(p, s["m_sout"]), "q"),
        "w_gate": ((h, i), _fan(h, s["m_gate"]), "q"),
        "w_up": ((h, i), h, "q"),
        "w_down": ((i, h), _fan(i, s["m_down"]), "q"),
    }


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def _draw(shape: Tuple[int, ...], fan_in: Any, kind: str):
    def gen(key):
        if kind == "r":
            return jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))
        if kind == "o":
            return jnp.ones(shape, F32)
        if kind == "t":
            dt = jnp.exp(jax.random.uniform(
                key, shape, F32, minval=math.log(1e-3), maxval=math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        x = jax.random.normal(key, shape, F32)
        if kind == "n":
            return (1.0 + NORM_SPREAD * x).astype(jnp.bfloat16).astype(F32)
        if kind == "z":
            return (NORM_SPREAD * x).astype(jnp.bfloat16).astype(F32)
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
        return _draw((self.s["V"], self.s["h"]), self.s["h"], "d")(
            self._key("lm_head"))

    def final_norm(self) -> jax.Array:
        return _draw((self.s["h"],), 0, "n")(self._key("final_norm"))

    def layer(self, l: int) -> Dict[str, jax.Array]:
        return {
            name: _draw(shape, fan_in, kind)(
                jax.random.split(self._key(f"layers.{name}"),
                                 self.s["L"])[l])
            for name, (shape, fan_in, kind) in leaf_shapes(self.s).items()
        }


class FromTree:
    """Weights sliced out of a parameter tree the program built: stacked
    leaves with a leading layer axis under ``layers``, quantized leaves as
    ``{"qw", "scale"}``."""

    def __init__(self, cfg: Dict[str, Any], params: Dict[str, Any]) -> None:
        self.s = dims(cfg)
        self.p = params

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
        return {name: self._f32(jax.tree.map(lambda a: a[l], leaf))
                for name, leaf in self.p["layers"].items()}


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #

def _rotate(x: jax.Array, theta: float) -> jax.Array:
    """x [S, heads, d]; position = row index; half-split rotation of the
    whole head."""
    n, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = jnp.arange(n, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(s: Dict[str, Any], w: Dict[str, jax.Array], u: jax.Array
              ) -> jax.Array:
    """Causal GQA over a whole prompt, ``u [S, h]`` the normed input → the
    sub-block's output before ``attention_out_multiplier``."""
    n = u.shape[0]
    nh, nkv, d = s["nh"], s["nkv"], s["d"]
    a = u * s["m_ain"]
    q = _rotate((a @ w["wq"]).reshape(n, nh, d), s["theta"])
    k = _rotate(((a @ w["wk"]) * s["m_key"]).reshape(n, nkv, d), s["theta"])
    v = (a @ w["wv"]).reshape(n, nkv, d)
    q = q.reshape(n, nkv, nh // nkv, d)
    scores = jnp.einsum("qkgd,nkd->kgqn", q, k) / jnp.sqrt(F32(d))
    seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    out = jnp.einsum("kgqn,nkd->qkgd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(n, nh * d) @ w["wo"]


def _projected(s: Dict[str, Any], w: Dict[str, jax.Array], u: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(z ``[S, d_ssm]``, the convolution's input ``x | B | C``, dt before
    its bias ``[S, H]``), each under its entry of ``ssm_multipliers``."""
    ds, gn = s["ds"], s["sg"] * s["sn"]
    mz, mx, mb, mc, mdt = s["mup"]
    a = u * s["m_sin"]
    p = a @ w["w_in"]
    z = p[:, :ds] * mz
    xbc = jnp.concatenate([p[:, ds:2 * ds] * mx,
                           p[:, 2 * ds:2 * ds + gn] * mb,
                           p[:, 2 * ds + gn:] * mc], axis=-1)
    return z, xbc, (a @ w["w_dt"]) * mdt


def mixer_recurrence(s: Dict[str, Any], w: Dict[str, jax.Array],
                     u: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The state-space recurrence over a whole prompt, token by token from
    a zero state: ``u [S, h]`` the normed input → (``y [S, H, P]`` = ``S C +
    D x`` before the gate, ``z [S, d_ssm]``, the state after the last token
    ``[H, P, N]``)."""
    n = u.shape[0]
    sh, sp, sn, sg, taps = s["sh"], s["sp"], s["sn"], s["sg"], s["taps"]
    ds = s["ds"]
    z, pre, dt = _projected(s, w, u)
    padded = jnp.concatenate([jnp.zeros((taps - 1, pre.shape[1]), F32), pre])
    conv = sum(padded[j:j + n] * w["conv"][j] for j in range(taps))
    xbc = jax.nn.silu(conv + w["conv_bias"])
    x = xbc[:, :ds].reshape(n, sh, sp)
    per = sh // sg
    b = jnp.repeat(xbc[:, ds:ds + sg * sn].reshape(n, sg, sn), per, axis=1)
    c = jnp.repeat(xbc[:, ds + sg * sn:].reshape(n, sg, sn), per, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                    # [S, H]
    a = -jnp.exp(w["a_log"])                                   # [H]

    def token(state, t):
        x_t, b_t, c_t, dt_t = t
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    last, y = jax.lax.scan(token, jnp.zeros((sh, sp, sn), F32),
                           (x, b, c, dt))
    return y + w["d_skip"][None, :, None] * x, z, last


def mixer(s: Dict[str, Any], w: Dict[str, jax.Array], u: jax.Array
          ) -> jax.Array:
    """The mixer over a whole prompt → its output before
    ``ssm_out_multiplier``: the recurrence, the gate, THEN the grouped RMS
    norm, ``W_out``."""
    n = u.shape[0]
    y, z, _ = mixer_recurrence(s, w, u)
    y = (y.reshape(n, -1) * jax.nn.silu(z)).reshape(n, s["sg"], -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + s["eps"])
    return (y.reshape(n, -1) * w["ssm_norm"]) @ w["w_out"]


def layer_forward(s: Dict[str, Any], w: Dict[str, jax.Array], x: jax.Array
                  ) -> jax.Array:
    """One decoder layer over a whole prompt ``x [S, h]``."""
    u = _rms_norm(x, w["attn_norm"], s["eps"])
    x = x + s["m_sout"] * mixer(s, w, u) + s["m_aout"] * attention(s, w, u)
    v = _rms_norm(x, w["mlp_norm"], s["eps"])
    gate = jax.nn.silu((v @ w["w_gate"]) * s["m_gate"])
    return x + ((gate * (v @ w["w_up"])) @ w["w_down"]) * s["m_down"]


def forward(cfg: Dict[str, Any], weights: Any, prompts: List[List[int]],
            at: Optional[List[List[int]]] = None, width: int = 0,
            tap: Optional[Callable[[int, int, Dict[str, jax.Array],
                                    jax.Array], None]] = None,
            ) -> List[np.ndarray]:
    """Every prompt through the whole model, a layer at a time. Returns,
    per prompt, the logits ``[len(at[i]), V]`` at the positions ``at[i]``
    (default: the last one). Prompts are padded at the end to one length so
    that one compiled layer serves them all; causality (the mask, the
    convolution, the recurrence) keeps padding out of what comes before.
    ``tap(l, n, w, x)`` is shown layer ``l``'s weights and prompt ``n``'s
    hidden state ``x [width, h]`` as the layer takes it."""
    s = dims(cfg)
    width = max(width, max(len(p) for p in prompts))
    at = at or [[len(p) - 1] for p in prompts]
    with jax.default_matmul_precision("highest"):
        emb = weights.embedding()
        xs = [jnp.take(emb, jnp.asarray(p + [0] * (width - len(p))), axis=0)
              * s["m_emb"] for p in prompts]
        del emb
        step = jax.jit(lambda w, x: layer_forward(s, w, x))
        for l in range(s["L"]):
            w = weights.layer(l)
            for n, x in enumerate(xs):
                if tap is not None:
                    tap(l, n, w, x)
                xs[n] = step(w, x)
            del w
        norm, head = weights.final_norm(), weights.head()
        logits = []
        for x, where in zip(xs, at):
            rows = _rms_norm(x[jnp.asarray(where)], norm, s["eps"])
            logits.append(np.asarray((rows @ head.T) * s["m_head"],
                                     np.float32))
    return logits


def last_logits(cfg: Dict[str, Any], weights: Any,
                prompts: List[List[int]]) -> List[np.ndarray]:
    """The logits at each prompt's last position, ``[V]`` float32 each."""
    return [lg[0] for lg in forward(cfg, weights, prompts)]
