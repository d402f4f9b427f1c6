"""Pipeline parallelism: layer-range stages over the ``stage`` mesh axis.

TPU-native re-architecture of the reference's Petals-style pipeline
(``worker/distributed/model_shard.py`` layer-range shards +
``worker/distributed/session.py`` per-hop HTTP tensor shipping). There, every
token crosses N network boundaries as base64 JSON (SURVEY §3.3 calls it the
#1 throughput sin). Here a pipeline "hop" is a ``lax.ppermute`` of activations
over ICI inside ONE jitted graph: no serialization, no host round-trip.

Two layers of the design:

- **In-slice (this module)**: GPipe-style microbatch schedule expressed with
  ``shard_map`` over the ``stage`` axis + ``lax.scan`` over clock ticks; each
  stage owns a contiguous slice of the stacked layer params and its layers'
  paged-KV pools.
- **Cross-host (distributed/)**: the same stage partitioning driven by the
  shard planner below, with activations framed over DCN — the planner mirrors
  the reference's VRAM-proportional ``create_shard_plan``
  (``model_shard.py:313-369``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import ModelConfig
from distributed_gpu_inference_tpu.parallel.mesh import AXIS_STAGE

# ---------------------------------------------------------------------------
# Shard planning (layer ranges per stage)
# ---------------------------------------------------------------------------


def uniform_stages(num_layers: int, num_stages: int) -> List[Tuple[int, int]]:
    """Even split of [0, L) into stages (reference ``model_shard.py:372-394``)."""
    base, rem = divmod(num_layers, num_stages)
    plan, start = [], 0
    for s in range(num_stages):
        n = base + (1 if s < rem else 0)
        plan.append((start, start + n))
        start += n
    return plan


def create_shard_plan(
    cfg: ModelConfig,
    hbm_bytes: Sequence[int],
    kv_reserve_frac: float = 0.3,
) -> List[Tuple[int, int]]:
    """Layer ranges proportional to each stage's HBM minus a KV reserve.

    Mirrors the reference's VRAM-proportional planner
    (``worker/distributed/model_shard.py:313-369``): every stage gets at least
    one layer; capacity shortfalls raise rather than silently overcommit.
    """
    usable = [max(0.0, b * (1.0 - kv_reserve_frac)) for b in hbm_bytes]
    per_layer = cfg.layer_param_bytes(jnp.dtype(cfg.dtype).itemsize)
    cap = [int(u // per_layer) for u in usable]
    L, n = cfg.num_layers, len(hbm_bytes)
    if n > L:
        raise ValueError(f"{n} stages > {L} layers; every stage needs ≥1 layer")
    for s, c in enumerate(cap):
        if c < 1:
            raise ValueError(
                f"stage {s} fits 0 layers "
                f"(per-layer {per_layer / 1e6:.1f} MB > usable HBM)"
            )
    if sum(cap) < L:
        raise ValueError(
            f"stages fit {sum(cap)} layers < model's {L}; "
            f"add stages or HBM (per-layer {per_layer / 1e6:.1f} MB)"
        )
    total = sum(usable)
    raw = [u / total * L for u in usable]
    counts = [1] * n
    while sum(counts) < L:
        cands = [s for s in range(n) if counts[s] < cap[s]]
        s = max(cands, key=lambda j: raw[j] - counts[j])
        counts[s] += 1
    plan, start = [], 0
    for n in counts:
        plan.append((start, start + n))
        start += n
    return plan


def slice_stage_params(
    params: llama.Params, start: int, end: int, *, num_layers: int
) -> llama.Params:
    """Extract one stage's params for the cross-host pipeline: first stage
    keeps the embedding, last keeps final_norm + lm_head (reference
    ``model_shard.py:163-171``)."""
    out: llama.Params = {
        # tree.map: a layer value may be a quantized {"qw","scale"} sub-dict
        # whose leaves both carry the stacked L axis
        "layers": {
            k: jax.tree.map(lambda a: a[start:end], v)
            for k, v in params["layers"].items()
        }
    }
    if start == 0:
        out["embedding"] = params["embedding"]
    if end == num_layers:
        out["final_norm"] = params["final_norm"]
        if "lm_head" in params:
            out["lm_head"] = params["lm_head"]
        elif start != 0:  # tied embeddings: last stage still needs the table
            out["embedding"] = params["embedding"]
    return out


# ---------------------------------------------------------------------------
# In-slice SPMD pipeline (shard_map over the stage axis)
# ---------------------------------------------------------------------------


def stage_param_shardings(mesh: Mesh) -> Dict[str, Any]:
    """Shard the stacked L axis over ``stage``; everything else replicated.
    Composable with TP specs later (stage on L, model on width)."""
    lp = NamedSharding(mesh, P(AXIS_STAGE))

    def _l(*rest):
        return NamedSharding(mesh, P(AXIS_STAGE, *rest))

    return {
        "embedding": NamedSharding(mesh, P()),
        "layers": {
            "attn_norm": _l(None),
            "wq": _l(None, None),
            "wk": _l(None, None),
            "wv": _l(None, None),
            "wo": _l(None, None),
            "bq": _l(None),
            "bk": _l(None),
            "bv": _l(None),
            "q_norm": _l(None),
            "k_norm": _l(None),
            "mlp_norm": _l(None),
            "w_gate": _l(None, None),
            "w_up": _l(None, None),
            "w_down": _l(None, None),
            "w_router": _l(None, None),
            "we_gate": _l(None, None, None),
            "we_up": _l(None, None, None),
            "we_down": _l(None, None, None),
        },
        "final_norm": NamedSharding(mesh, P()),
        "lm_head": NamedSharding(mesh, P()),
    }


def shard_params_stages(params: llama.Params, mesh: Mesh) -> llama.Params:
    from distributed_gpu_inference_tpu.parallel.sharding import prune_rules

    return jax.device_put(
        params, prune_rules(stage_param_shardings(mesh), params)
    )


def stage_kv_sharding(mesh: Mesh) -> NamedSharding:
    """KV pools [L, N, Hkv, Bk, D]: the layer axis follows its stage."""
    return NamedSharding(mesh, P(AXIS_STAGE, None, None, None, None))


def shard_kv_stages(kv: llama.KVPools, mesh: Mesh) -> llama.KVPools:
    s = stage_kv_sharding(mesh)
    return {k: jax.device_put(v, s) for k, v in kv.items()}


def _pipeline_local(
    tokens: jax.Array,        # [n_micro, mb, S] int32
    positions: jax.Array,     # [n_micro, mb, S] int32, -1 = pad
    block_tables: jax.Array,  # [n_micro, mb, M] int32
    kv_lens: jax.Array,       # [n_micro, mb] int32
    params: llama.Params,     # stage-local: layers [L/n, ...], embed/head replicated
    kv: llama.KVPools,        # stage-local: [L/n, N, Hkv, Bk, D]
    *,
    cfg: ModelConfig,
    axis_name: str,
    n_stages: int,
    block_size: int,
) -> Tuple[jax.Array, llama.KVPools]:
    """Per-device pipeline body. Clock tick t: stage s works on microbatch
    t - s (the GPipe diagonal); activations ppermute forward each tick."""
    stage = lax.axis_index(axis_name)
    n_micro, mb, s_len = tokens.shape
    h = cfg.hidden_size
    total_ticks = n_micro + n_stages - 1
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        act, kv_k, kv_v, out_buf = carry
        my_mb = t - stage
        valid = (my_mb >= 0) & (my_mb < n_micro)
        mb_idx = jnp.clip(my_mb, 0, n_micro - 1)

        tok_t = jnp.take(tokens, mb_idx, axis=0)          # [mb, S]
        pos_t = jnp.take(positions, mb_idx, axis=0)
        tab_t = jnp.take(block_tables, mb_idx, axis=0)
        len_t = jnp.take(kv_lens, mb_idx, axis=0)

        # stage 0 ingests fresh embeddings; later stages consume the permuted
        # activations. Padded/invalid ticks write no KV (positions forced -1).
        inject = llama.embed_tokens(params, tok_t, cfg)
        act_in = jnp.where(stage == 0, inject, act)
        write_pos = jnp.where(valid, pos_t, -1)

        hidden, kv_out = llama.forward_hidden_chunk(
            cfg,
            params,
            act_in,
            write_pos,
            {"k": kv_k, "v": kv_v},
            tab_t,
            len_t,
            block_size=block_size,
        )

        # last stage emits last-valid-token logits for its microbatch
        n_valid = jnp.sum((pos_t >= 0).astype(jnp.int32), axis=1)
        last_idx = jnp.maximum(n_valid - 1, 0)
        h_last = jnp.take_along_axis(
            hidden, last_idx[:, None, None].astype(jnp.int32), axis=1
        )                                                  # [mb, 1, H]
        logits = llama.project_logits(cfg, params, h_last)[:, 0, :]
        store = valid & (stage == n_stages - 1)
        out_buf = jnp.where(
            store,
            out_buf.at[mb_idx].set(logits),
            out_buf,
        )

        act_next = lax.ppermute(hidden, axis_name, fwd_perm)
        return (act_next, kv_out["k"], kv_out["v"], out_buf), None

    # activation dtype follows the actual weights (callers may load params
    # in a dtype other than the config default, e.g. float32 on CPU)
    act0 = jnp.zeros((mb, s_len, h), params["embedding"].dtype)
    out0 = jnp.zeros((n_micro, mb, cfg.vocab_size), jnp.float32)
    (_, kv_k, kv_v, out_buf), _ = lax.scan(
        tick,
        (act0, kv["k"], kv["v"], out0),
        jnp.arange(total_ticks, dtype=jnp.int32),
    )
    # out_specs concatenate per-stage buffers on a fresh axis; only the last
    # stage's slice carries real logits — caller reads [-1].
    return out_buf[None], {"k": kv_k, "v": kv_v}


def pipelined_forward(
    cfg: ModelConfig,
    params: llama.Params,      # stage-sharded (shard_params_stages)
    tokens: jax.Array,         # [n_micro, mb, S]
    positions: jax.Array,      # [n_micro, mb, S]
    kv: llama.KVPools,         # stage-sharded on L
    block_tables: jax.Array,   # [n_micro, mb, M]
    kv_lens: jax.Array,        # [n_micro, mb]
    mesh: Mesh,
    *,
    block_size: int = 16,
) -> Tuple[jax.Array, llama.KVPools]:
    """Microbatched pipeline forward. → (logits [n_micro, mb, V], updated kv).

    One jitted graph; hops are ICI ppermutes. Works for prefill (S = chunk)
    and decode (S = 1) alike.
    """
    n_stages = dict(mesh.shape).get(AXIS_STAGE, 1)
    if cfg.num_layers % n_stages:
        raise ValueError(
            f"{cfg.num_layers} layers not divisible by {n_stages} stages; "
            "use the cross-host planner (create_shard_plan) for uneven splits"
        )
    stage_cfg = cfg  # scan runs over whatever L slice the leaves carry

    lspec = {k: P(AXIS_STAGE, *([None] * (v.ndim - 1)))
             for k, v in params["layers"].items()}
    pspec: Dict[str, Any] = {"layers": lspec}
    for name in ("embedding", "final_norm", "lm_head"):
        if name in params:
            pspec[name] = P()
    kv_spec = {"k": P(AXIS_STAGE), "v": P(AXIS_STAGE)}

    fn = jax.shard_map(
        functools.partial(
            _pipeline_local,
            cfg=stage_cfg,
            axis_name=AXIS_STAGE,
            n_stages=n_stages,
            block_size=block_size,
        ),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), pspec, kv_spec),
        out_specs=(P(AXIS_STAGE), kv_spec),
        check_vma=False,
    )
    stacked, kv_out = fn(tokens, positions, block_tables, kv_lens, params, kv)
    return stacked[-1], kv_out
