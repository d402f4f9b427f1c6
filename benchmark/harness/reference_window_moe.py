"""The plain reference of a GQA decoder whose layers mix window and full
attention, with a head count and a rotation per kind, a per-head output gate,
a dense first layer and then one chip's share of a many-expert layer beside a
shared expert: Laguna-S-2.1, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

The layer ``l`` (``x_t`` the residual of token ``t``, ``h = RMSNorm(x)``,
``kind = layer_types[l]``, ``H = num_attention_heads_per_layer[l]``)::

    q_t = Wq h_t (H heads of d), k_t = Wk h_t, v_t = Wv h_t (nkv heads)
    full:     the first ``d x partial_rotary_factor`` values of a head are
              rotated (half-split) at YaRN's frequencies, cos / sin scaled
              by ``attention_factor``; the others pass. Keys s <= t.
    sliding:  all d values rotated at ``rope_theta`` of the kind, plain.
              Keys t - window < s <= t.
    o_t      = softmax over the keys of q_t . k_s / sqrt(d), GQA
    g_t      = sigmoid(Wg h_t)            one scalar a head
    x        = x + (g_t * o_t) Wo
    m = RMSNorm(x)
    dense layer:   x = x + (silu(m Wg) * (m Wu)) Wd
    sparse layer:  p = softmax(m Wr) over ALL ``expert_share.of`` experts,
                   float32; the k largest, renormalised, times
                   ``moe_routed_scaling_factor``;
                   x = x + E_shared(m) + sum over the kept experts HELD
                   HERE of p_e E_e(m)

then a final RMSNorm and an untied head over the held slice of the
vocabulary. What the experts held elsewhere would add is left out (guide
section 4): ``share`` = (first, count) computes another chip's part, and
``shared=False`` leaves the shared expert out, so that a test can add the
shares up. No kernels, no cache, no paging: one prompt at a time, a layer at
a time, attention in blocks of queries so that 20k tokens fit, a loop over
the held experts. It shares no code with the program.

YaRN as Hugging Face's ``_compute_yarn_parameters`` (truncate on): over the
``R`` rotated values, ``inv_freq = interpolation x ramp + extrapolation x
(1 - ramp)`` with ``interpolation = 1 / (factor x theta^(2i/R))``,
``extrapolation = 1 / theta^(2i/R)`` and ``ramp`` linear over the pairs
between ``floor(dim(beta_fast))`` and ``ceil(dim(beta_slow))``, ``dim(r) =
R ln(original / (2 pi r)) / (2 ln theta)``.

Weights come through a provider, layer by layer, already float32:

``SeedStream``   regenerates what the program's seeded init makes
                 (``models/mla.py init_params`` over ``models/llama.py
                 leaf_specs``): a leaf a layer, drawn from the key of
                 ``<stack>.<leaf>``; matmul weights rounded to int8 per
                 output channel and multiplied out; router, gate, embedding
                 and head rounded to bf16; norm vectors ``1 + 0.25 x
                 normal``, so that a misplaced one shows.
``FromTree``     slices a parameter tree the program built.

``variant`` plants one fault in the reference for the comparison's controls
(``benchmark/compare_logits_window.py``): ``fp8_qk`` (rotated q / k rounded
to float8_e4m3), ``no_window``, ``window_half``, ``no_yarn``,
``full_rotation`` (a full layer rotates its whole head), ``no_gate``,
``no_scale``.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .reference_sparse import F32, _int8_roundtrip, _rms_norm

NORM_SPREAD = 0.25
VARIANTS = ("fp8_qk", "no_window", "window_half", "no_yarn",
            "full_rotation", "no_gate", "no_scale")
# queries a block of attention: [heads, block, n] float32 scores
BLOCK = 128

__all__ = ["SeedStream", "FromTree", "dims", "forward", "last_logits",
           "attention", "VARIANTS"]


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    share = cfg.get("expert_share") or {}
    held = int(cfg["num_experts"])
    rope = cfg["rope_parameters"]
    L = int(cfg["num_hidden_layers"])
    return {
        "h": int(cfg["hidden_size"]), "nkv": int(cfg["num_key_value_heads"]),
        "d": int(cfg["head_dim"]), "L": L, "V": int(cfg["vocab_size"]),
        "i": int(cfg["intermediate_size"]),
        "mi": int(cfg["moe_intermediate_size"]),
        "ms": int(cfg.get("shared_expert_intermediate_size") or 0),
        "kinds": tuple("full" if t == "full_attention" else "sliding"
                       for t in cfg["layer_types"][:L]),
        "heads": tuple(int(n) for n in
                       cfg["num_attention_heads_per_layer"][:L]),
        "sparse": tuple(t == "sparse" for t in cfg["mlp_layer_types"][:L]),
        "window": int(cfg["sliding_window"]),
        "E": int(share.get("of", held)), "first": int(share.get("first", 0)),
        "held": held, "k": int(cfg["num_experts_per_tok"]),
        "renorm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg.get("moe_routed_scaling_factor", 1.0)),
        "gate": cfg.get("gating") == "per-head",
        "rope_full": tuple(sorted(rope["full_attention"].items())),
        "rope_sliding": tuple(sorted(rope["sliding_attention"].items())),
        "eps": float(cfg["rms_norm_eps"]),
    }


def stack_of(s: Dict[str, Any], l: int) -> Tuple[str, int, int]:
    """(the stack layer ``l`` lies in, its place there, the stack's size):
    by attention kind (``full_`` where the kinds are mixed) and MLP."""
    mixed = len(set(s["kinds"])) > 1

    def name(j: int) -> str:
        mlp = "layers" if s["sparse"][j] else "dense_layers"
        return ("full_" if mixed and s["kinds"][j] == "full" else "") + mlp

    mine = name(l)
    same = [j for j in range(s["L"]) if name(j) == mine]
    return mine, same.index(l), len(same)


def leaf_shapes(s: Dict[str, Any], l: int
                ) -> Dict[str, Tuple[Tuple[int, ...], int, str]]:
    """name -> (shape, fan-in, kind: q quantized matmul weight, d bf16
    weight, n norm vector) of layer ``l``'s leaves."""
    h, d, nkv, nh = s["h"], s["d"], s["nkv"], s["heads"][l]
    out = {
        "attn_norm": ((h,), 0, "n"), "mlp_norm": ((h,), 0, "n"),
        "wq": ((h, nh * d), h, "q"), "wk": ((h, nkv * d), h, "q"),
        "wv": ((h, nkv * d), h, "q"), "wo": ((nh * d, h), nh * d, "q"),
    }
    if s["gate"]:
        out["w_hgate"] = ((h, nh), h, "d")
    if s["sparse"][l]:
        mi = s["mi"]
        out.update({
            "w_router": ((h, s["E"]), h, "d"),
            "we_gate": ((s["held"], h, mi), h, "q"),
            "we_up": ((s["held"], h, mi), h, "q"),
            "we_down": ((s["held"], mi, h), mi, "q"),
        })
        if s["ms"]:
            out.update({"ws_gate": ((h, s["ms"]), h, "q"),
                        "ws_up": ((h, s["ms"]), h, "q"),
                        "ws_down": ((s["ms"], h), s["ms"], "q")})
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
        return _draw((self.s["V"], self.s["h"]), self.s["h"], "d")(
            self._key("lm_head"))

    def final_norm(self) -> jax.Array:
        return _draw((self.s["h"],), 0, "n")(self._key("final_norm"))

    def layer(self, l: int) -> Dict[str, jax.Array]:
        stack, at, n = stack_of(self.s, l)
        return {
            name: _draw(shape, fan_in, kind)(
                jax.random.split(self._key(f"{stack}.{name}"), n)[at])
            for name, (shape, fan_in, kind)
            in leaf_shapes(self.s, l).items()
        }


class FromTree:
    """Weights sliced out of a parameter tree the program built: stacked
    leaves with a leading layer axis under each stack's name, quantized
    leaves as ``{"qw", "scale"}``."""

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
        stack, at, _ = stack_of(self.s, l)
        return {name: self._f32(jax.tree.map(lambda a: a[at], leaf))
                for name, leaf in self.p[stack].items()}


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #

def _inv_freq(rope: Dict[str, Any], d: int, variant: Optional[str]
              ) -> Tuple[np.ndarray, float, int]:
    """(inverse frequencies, cos / sin scale, rotated width) of a kind."""
    rot = int(d * float(rope.get("partial_rotary_factor", 1.0)))
    if variant == "full_rotation":
        rot = d
    theta = float(rope["rope_theta"])
    freqs = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type") != "yarn" or variant == "no_yarn":
        return (1.0 / freqs).astype(np.float32), 1.0, rot
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return rot * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * freqs)) * ramp + (1.0 / freqs) * (1.0 - ramp)
    return inv.astype(np.float32), float(rope["attention_factor"]), rot


def _rotate(x: jax.Array, inv: np.ndarray, scale: float, rot: int
            ) -> jax.Array:
    """x [n, heads, d]; position = row index; the first ``rot`` values of a
    head rotated half-split, the others as they are."""
    n = x.shape[0]
    ang = jnp.arange(n, dtype=F32)[:, None] * jnp.asarray(inv)[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    half = rot // 2
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def layer_type(s: Dict[str, Any], l: int) -> Tuple[str, int, bool]:
    """What of layer ``l`` its equations depend on: (attention kind, query
    heads, routed MLP). Layers of one type share their compiled steps."""
    return s["kinds"][l], s["heads"][l], s["sparse"][l]


def _project(s: Dict[str, Any], lt: Tuple[str, int, bool],
             w: Dict[str, jax.Array], x: jax.Array, variant: Optional[str]
             ) -> Dict[str, jax.Array]:
    n = x.shape[0]
    kind, nh, _ = lt
    nkv, d = s["nkv"], s["d"]
    a = _rms_norm(x, w["attn_norm"], s["eps"])
    rope = dict(s["rope_full"] if kind == "full" else s["rope_sliding"])
    inv, scale, rot = _inv_freq(rope, d, variant if kind == "full" else None)
    q = _rotate((a @ w["wq"]).reshape(n, nh, d), inv, scale, rot)
    k = _rotate((a @ w["wk"]).reshape(n, nkv, d), inv, scale, rot)
    if variant == "fp8_qk":
        q = q.astype(jnp.float8_e4m3fn).astype(F32)
        k = k.astype(jnp.float8_e4m3fn).astype(F32)
    out = {"q": q, "k": k, "v": (a @ w["wv"]).reshape(n, nkv, d)}
    if "w_hgate" in w and variant != "no_gate":
        out["g"] = jax.nn.sigmoid(a @ w["w_hgate"])              # [n, nh]
    return out


def _attend(s: Dict[str, Any], lt: Tuple[str, int, bool],
            p: Dict[str, jax.Array], lo: jax.Array, rows: int,
            variant: Optional[str]) -> jax.Array:
    """Queries ``lo .. lo + rows`` of a projected prompt against all of it
    -> the gated attention output ``[rows, H * d]`` before ``Wo``."""
    kind, nh, _ = lt
    nkv, d = s["nkv"], s["d"]
    n = p["k"].shape[0]
    t = lo + jnp.arange(rows)
    key = jnp.arange(n)[None, :]
    seen = key <= t[:, None]                                    # [rows, n]
    if kind == "sliding" and variant != "no_window":
        window = s["window"] // 2 if variant == "window_half" \
            else s["window"]
        seen = seen & (key > t[:, None] - window)
    q = jax.lax.dynamic_slice_in_dim(p["q"], lo, rows)
    q = q.reshape(rows, nkv, nh // nkv, d)
    scores = jnp.einsum("qkgd,nkd->kgqn", q, p["k"]) / jnp.sqrt(F32(d))
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    attn = jnp.einsum("kgqn,nkd->qkgd", jax.nn.softmax(scores, axis=-1),
                      p["v"]).reshape(rows, nh, d)
    if "g" in p:
        attn = attn * jax.lax.dynamic_slice_in_dim(p["g"], lo, rows)[..., None]
    return attn.reshape(rows, nh * d)


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _mlp(s: Dict[str, Any], lt: Tuple[str, int, bool],
         w: Dict[str, jax.Array], x: jax.Array, variant: Optional[str],
         share: Optional[Tuple[int, int]], shared: bool
         ) -> Tuple[jax.Array, jax.Array]:
    """The layer's MLP half over ``x [n, h]`` -> (the new ``x``, the experts
    each token was routed to ``[n, k]``; zeros for a dense layer)."""
    m = _rms_norm(x, w["mlp_norm"], s["eps"])
    if not lt[2]:
        return x + _swiglu(m, w["w_gate"], w["w_up"], w["w_down"]), \
            jnp.zeros((x.shape[0], s["k"]), jnp.int32)
    probs = jax.nn.softmax(m @ w["w_router"], axis=-1)          # [n, E]
    top_v, top_i = jax.lax.top_k(probs, s["k"])
    if s["renorm"]:
        top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    if variant != "no_scale":
        top_v = top_v * s["route_scale"]
    first, count = share or (s["first"], s["held"])

    def expert(e, out):
        weight = jnp.sum(jnp.where(top_i == first + e, top_v, 0.0), axis=-1)
        y = _swiglu(m, w["we_gate"][e], w["we_up"][e], w["we_down"][e])
        return out + weight[:, None] * y

    out = jax.lax.fori_loop(0, count, expert, jnp.zeros_like(x))
    if shared and "ws_gate" in w:
        out = out + _swiglu(m, w["ws_gate"], w["ws_up"], w["ws_down"])
    return x + out, top_i


@functools.lru_cache(maxsize=None)
def _steps(frozen: Tuple[Tuple[str, Any], ...],
           lt: Tuple[str, int, bool], variant: Optional[str], share,
           shared: bool):
    s = dict(frozen)
    return (
        jax.jit(lambda w, x: _project(s, lt, w, x, variant)),
        jax.jit(lambda p, lo: _attend(s, lt, p, lo, BLOCK, variant)),
        jax.jit(lambda w, x, attn: _mlp(s, lt, w, x + attn @ w["wo"],
                                        variant, share, shared)),
    )


def _blocks(attn_block, p, n: int) -> jax.Array:
    return jnp.concatenate([attn_block(p, jnp.int32(lo))
                            for lo in range(0, n, BLOCK)])


def attention(s: Dict[str, Any], l: int, w: Dict[str, jax.Array],
              x: jax.Array, variant: Optional[str] = None) -> jax.Array:
    """Layer ``l``'s attention sub-block alone over a whole prompt ``x [n,
    h]`` (``n`` a multiple of ``BLOCK``): what it adds to the residual,
    ``(g * o) Wo`` ``[n, h]``."""
    proj, attn_block, _ = _steps(tuple(sorted(s.items())),
                                 layer_type(s, l), variant, None, True)
    with jax.default_matmul_precision("highest"):
        return _blocks(attn_block, proj(w, x), x.shape[0]) @ w["wo"]


def layer_forward(s: Dict[str, Any], l: int, w: Dict[str, jax.Array],
                  x: jax.Array, variant: Optional[str] = None,
                  share: Optional[Tuple[int, int]] = None,
                  shared: bool = True) -> Tuple[jax.Array, jax.Array]:
    """One decoder layer over a whole prompt ``x [n, h]``, ``n`` a multiple
    of ``BLOCK`` (the caller pads at the end; the causal mask keeps padding
    out of what comes before it)."""
    proj, attn_block, rest = _steps(tuple(sorted(s.items())),
                                    layer_type(s, l), variant, share,
                                    shared)
    return rest(w, x, _blocks(attn_block, proj(w, x), x.shape[0]))


def pad_to_block(x: jax.Array) -> jax.Array:
    return jnp.pad(x, ((0, -x.shape[0] % BLOCK), (0, 0)))


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
        xs = [pad_to_block(jnp.take(emb, jnp.asarray(list(p)), axis=0))
              for p in prompts]
        del emb
        routes: List[List[np.ndarray]] = [[] for _ in prompts]
        for l in range(s["L"]):
            w = weights.layer(l)
            for n, x in enumerate(xs):
                if tap is not None:
                    tap(l, n, w, x[:len(prompts[n])])
                xs[n], top_i = layer_forward(s, l, w, x, variant)
                routes[n].append(np.asarray(top_i)[:len(prompts[n])])
            del w
        norm, head = weights.final_norm(), weights.head()
        logits = []
        for x, where in zip(xs, at):
            rows = _rms_norm(x[jnp.asarray(where)], norm, s["eps"])
            logits.append(np.asarray(rows @ head.T, np.float32))
    return logits, [np.stack(r) for r in routes]


def last_logits(cfg: Dict[str, Any], weights: Any,
                prompts: Sequence[List[int]]) -> List[np.ndarray]:
    """The logits at each prompt's last position, ``[V]`` float32 each."""
    return [lg[0] for lg in forward(cfg, weights, list(prompts))[0]]
