"""Weight loading: HF Llama safetensors → params pytree; orbax-native
checkpoints; (mesh resharding hooks live in ``parallel/sharding.py``).

Reference analogue: ``worker/engines/llm.py:33-36`` (AutoModelForCausalLM
device_map load) and ``worker/distributed/model_shard.py:61-160``
(layer-range partial loading) — re-designed: weights map straight into the
stacked-layer pytree (leading L axis) that ``lax.scan`` and GSPMD sharding
consume, and a pipeline stage can load only its layer range.
"""

from __future__ import annotations

import functools
import json
import re
import zlib
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from distributed_gpu_inference_tpu.models.configs import ModelConfig
from distributed_gpu_inference_tpu.utils.data_structures import BlockRange

# HF parameter name → (our key, needs_transpose). Layer index is captured by
# the regex; our layout stacks layers on a leading axis.
_HF_LAYER_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    # Qwen2-style attention biases (absent in Llama checkpoints)
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
}

_LAYER_RE = re.compile(r"^model\.layers\.(\d+)\.(.+)$")

# HF Mixtral MoE naming: block_sparse_moe.gate (router) and per-expert
# w1 (gate), w3 (up), w2 (down) projections
_MOE_GATE_KEY = "block_sparse_moe.gate.weight"
_MOE_EXPERT_RE = re.compile(
    r"^block_sparse_moe\.experts\.(\d+)\.(w[123])\.weight$"
)
_MOE_EXPERT_MAP = {"w1": "we_gate", "w3": "we_up", "w2": "we_down"}


def load_hf_llama(
    model_dir: str | Path,
    cfg: ModelConfig,
    dtype: Optional[Any] = None,
    layer_range: Optional[BlockRange] = None,
) -> Dict[str, Any]:
    """Load a HF Llama checkpoint directory (safetensors shards) into the
    stacked params pytree. ``layer_range`` loads only layers [start, end)
    (pipeline stages); embeddings / final norm / head are included only for
    the ranges that own them (first / last stage — reference
    model_shard.py:163-171)."""
    from safetensors import safe_open

    model_dir = Path(model_dir)
    dtype = jnp.dtype(dtype or cfg.dtype)
    rng = layer_range or BlockRange(0, cfg.num_layers)
    first_stage = rng.start == 0
    last_stage = rng.end == cfg.num_layers
    L = rng.num_layers

    files = sorted(model_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_dir}")

    layers: Dict[str, np.ndarray] = {}
    params: Dict[str, Any] = {"layers": {}}

    def _slot(our_key: str, shape: Tuple[int, ...]) -> np.ndarray:
        if our_key not in layers:
            layers[our_key] = np.zeros((L, *shape), dtype=dtype)
        return layers[our_key]

    for f in files:
        with safe_open(str(f), framework="np") as st:
            for name in st.keys():
                m = _LAYER_RE.match(name)
                if m:
                    li = int(m.group(1))
                    if li not in rng:
                        continue
                    sub = m.group(2)
                    em = _MOE_EXPERT_RE.match(sub)
                    if em:  # Mixtral expert: stack on [L, E, in, out]
                        ei = int(em.group(1))
                        our_key = _MOE_EXPERT_MAP[em.group(2)]
                        w = st.get_tensor(name).T  # HF stores [out, in]
                        buf = _slot(
                            our_key, (cfg.num_experts, *w.shape)
                        )
                        buf[li - rng.start, ei] = w.astype(dtype)
                        continue
                    if sub == _MOE_GATE_KEY:  # router [E, H] → [H, E]
                        w = st.get_tensor(name).T
                        _slot("w_router", w.shape)[li - rng.start] = (
                            w.astype(dtype)
                        )
                        continue
                    if sub not in _HF_LAYER_MAP:
                        continue
                    our_key, transpose = _HF_LAYER_MAP[sub]
                    w = st.get_tensor(name)
                    if transpose:
                        w = w.T
                    _slot(our_key, w.shape)[li - rng.start] = w.astype(dtype)
                elif name == "model.embed_tokens.weight" and first_stage:
                    params["embedding"] = jnp.asarray(st.get_tensor(name), dtype)
                elif name == "model.norm.weight" and last_stage:
                    params["final_norm"] = jnp.asarray(st.get_tensor(name), dtype)
                elif name == "lm_head.weight" and last_stage and \
                        not cfg.tie_word_embeddings:
                    params["lm_head"] = jnp.asarray(st.get_tensor(name), dtype)

    params["layers"] = {k: jnp.asarray(v) for k, v in layers.items()}
    if cfg.tie_word_embeddings and last_stage and not first_stage:
        # tied head on a non-first stage still needs the embedding matrix;
        # scan every shard — multi-file checkpoints store it anywhere
        for f in files:
            with safe_open(str(f), framework="np") as st:
                if "model.embed_tokens.weight" in st.keys():
                    params["embedding"] = jnp.asarray(
                        st.get_tensor("model.embed_tokens.weight"), dtype
                    )
                    break
    _validate(params, cfg, rng)
    return params


def _validate(params: Dict[str, Any], cfg: ModelConfig, rng: BlockRange) -> None:
    expected = set(_HF_LAYER_MAP[k][0] for k in _HF_LAYER_MAP)
    if not cfg.attention_bias:  # Llama-family checkpoints carry no biases
        expected -= {"bq", "bk", "bv"}
    if cfg.num_experts:  # Mixtral: sparse expert MLP instead of dense
        expected -= {"w_gate", "w_up", "w_down"}
        expected |= {"w_router", "we_gate", "we_up", "we_down"}
    got = set(params["layers"].keys())
    if got != expected:
        missing, extra = expected - got, got - expected
        parts = []
        if missing:
            parts.append(f"missing {sorted(missing)}")
        if extra:
            hint = (
                " (a biased checkpoint needs a config with "
                "attention_bias=True)"
                if extra <= {"bq", "bk", "bv"} else ""
            )
            parts.append(f"unexpected {sorted(extra)}{hint}")
        raise ValueError("checkpoint layer params: " + "; ".join(parts))
    L = rng.num_layers
    for k, v in params["layers"].items():
        if v.shape[0] != L:
            raise ValueError(f"{k}: expected {L} layers, got {v.shape[0]}")
    if rng.start == 0 and "embedding" not in params:
        raise ValueError("first stage missing embedding")
    if rng.end == cfg.num_layers and "final_norm" not in params:
        raise ValueError("last stage missing final_norm")


def init_quantized_streamed(
    cfg: ModelConfig,
    mode: str,
    dtype: Optional[Any] = None,
    seed: int = 0,
    mesh: Optional[Any] = None,
) -> Dict[str, Any]:
    """Random-init a model DIRECTLY on device in quantized form, one layer
    slice at a time — the cold-start path for models whose full-precision
    tree exceeds device HBM (llama3-8b bf16 = 16.1 GB on a 16 GB v5e).

    Each quantized leaf is produced by ONE jitted ``lax.scan`` over the layer
    axis: the scan body generates a float32 layer slice on device, quantizes
    it (``ops.quantization.quantize_weight``), and the scan stacks the int8/
    fp8 outputs. Peak transient HBM = one f32 layer slice (~0.25 GB for 8B)
    on top of the growing quantized tree — no host-side init and no
    multi-GB host→device upload. Per distinct leaf shape there is one
    compile.

    ``mesh``: every leaf is generated straight into its tensor-parallel
    layout (``parallel/sharding.py`` rules as ``out_shardings``), so no
    device ever holds a whole leaf. The partitionable threefry stream does
    not depend on the layout, so a mesh engine holds exactly the weights a
    one-chip engine draws from the same seed.

    The random stream is deterministic in ``seed`` but differs from
    ``llama.init_params`` (which draws each leaf in one full-shape call);
    random-init weights serve benchmarks/tests, not checkpoints, so only
    determinism matters, not cross-path equality.

    Reference analogue: none — its engines inherit load-time behavior from
    HF/vLLM (``worker/engines/llm.py:33-36``); cold-starting a quantized
    model that doesn't fit in fp16 is delegated to pre-quantized
    checkpoints there.
    """
    import jax
    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.ops.quantization import (
        QUANT_KEYS,
        quantize_weight,
    )

    if cfg.latent_kv:
        # its init draws a layer at a time whatever the mode (models/mla.py)
        from distributed_gpu_inference_tpu.models import mla

        if mesh is not None:
            raise ValueError("a latent-attention model is one-chip")
        return mla.init_params(cfg, jax.random.PRNGKey(seed), dtype, mode)
    if cfg.described_per_layer:
        # likewise, over its own stacks and leaves (models/llama.py)
        if mesh is not None:
            raise ValueError(
                f"{cfg.name}: a model described per layer is one-chip")
        return llama.init_params(cfg, jax.random.PRNGKey(seed), dtype, mode)
    dtype = jnp.dtype(dtype or cfg.dtype)
    h, d = cfg.hidden_size, cfg.head_dim
    nh, nkv, i = cfg.num_heads, cfg.num_kv_heads, cfg.mlp_width
    L, v = cfg.num_layers, cfg.vocab_size

    root = jax.random.PRNGKey(seed)
    rules: Dict[str, Any] = {"layers": {}}
    if mesh is not None:
        from distributed_gpu_inference_tpu.parallel import sharding as _sh

        rules = _sh.param_shardings(mesh)

    def _rule(name: str):
        """The leaf's NamedSharding under ``mesh``; None without one."""
        return rules["layers"].get(name, rules.get(name))

    @functools.lru_cache(maxsize=None)
    def _scan_fn(shape: Tuple[int, ...], fan_in: int, rule):
        def gen(keys):
            def body(carry, k):
                w = jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)
                q = quantize_weight(w, mode)
                return carry, (q["qw"], q["scale"])

            _, (qw, scale) = jax.lax.scan(body, 0, keys)
            return {"qw": qw, "scale": scale}

        if rule is None:
            return jax.jit(gen)
        leaf = jax.eval_shape(gen, jax.random.split(root, L))
        return jax.jit(
            gen, out_shardings=_sh.quantized_leaf_rules(rule, leaf)
        )

    def _name_key(name: str):
        # stable across processes (str hash() is salted per interpreter)
        return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)

    def _q_leaf(name: str, shape: Tuple[int, ...], fan_in: int):
        keys = jax.random.split(_name_key(name), L)
        out = _scan_fn(shape, fan_in, _rule(name))(keys)
        jax.block_until_ready(out["qw"])  # bound transient f32 live range
        return out

    def _dense_leaf(name: str, shape: Tuple[int, ...], fan_in: int):
        k = _name_key(name)
        f = jax.jit(
            lambda k: (
                jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)
            ).astype(dtype),
            out_shardings=_rule(name),
        )
        return f(k)

    def norm_init(shape, name):
        w = (jnp.zeros if cfg.norm_offset else jnp.ones)(shape, dtype)
        return w if mesh is None else jax.device_put(w, _rule(name))

    layers: Dict[str, Any] = {
        "attn_norm": norm_init((L, h), "attn_norm"),
        "mlp_norm": norm_init((L, h), "mlp_norm"),
    }
    leaf_specs = {
        "wq": ((h, nh * d), h),
        "wk": ((h, nkv * d), h),
        "wv": ((h, nkv * d), h),
        "wo": ((nh * d, h), nh * d),
    }
    if cfg.num_experts:
        E = cfg.num_experts
        layers["w_router"] = _dense_leaf("w_router", (L, h, E), h)
        leaf_specs.update({
            "we_gate": ((E, h, i), h),
            "we_up": ((E, h, i), h),
            "we_down": ((E, i, h), i),
        })
    else:
        leaf_specs.update({
            "w_gate": ((h, i), h),
            "w_up": ((h, i), h),
            "w_down": ((i, h), i),
        })
    for name, (shape, fan_in) in leaf_specs.items():
        assert name in QUANT_KEYS
        layers[name] = _q_leaf(name, shape, fan_in)
    if cfg.qk_norm:
        layers["q_norm"] = norm_init((L, nh * d), "q_norm")
        layers["k_norm"] = norm_init((L, nkv * d), "k_norm")
    if cfg.qk_norm_per_head or cfg.index_topk:
        if mesh is not None:
            raise ValueError(
                f"{cfg.name}: a per-head QK-norm or an indexer has no "
                "sharding rule; the model is one-chip")
        extra = llama.init_index_leaves(cfg, root, dtype)
        if "wqi" in extra:
            extra["wqi"] = quantize_weight(extra["wqi"], mode)
        layers.update(extra)
    if cfg.attention_bias:
        layers["bq"] = _dense_leaf("bq", (L, nh * d), nh * d)
        layers["bk"] = _dense_leaf("bk", (L, nkv * d), nkv * d)
        layers["bv"] = _dense_leaf("bv", (L, nkv * d), nkv * d)

    params: Dict[str, Any] = {
        "embedding": _dense_leaf("embedding", (v, h), h),
        "layers": layers,
        "final_norm": norm_init((h,), "final_norm"),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _dense_leaf("lm_head", (v, h), h)
    return params


# ---------------------------------------------------------------------------
# HF ViT-class checkpoints → vit.Params (VERDICT r4 #8: one non-Llama
# family with a real-checkpoint import path)
# ---------------------------------------------------------------------------

# HF ViT encoder-layer name → (our key, needs_transpose). q/k/v weights
# fuse into our wqkv separately below.
_HF_VIT_LAYER_MAP = {
    "layernorm_before.weight": ("norm1", False),
    "layernorm_before.bias": ("norm1_b", False),
    "attention.output.dense.weight": ("wo", True),
    "attention.output.dense.bias": ("bo", False),
    "layernorm_after.weight": ("norm2", False),
    "layernorm_after.bias": ("norm2_b", False),
    "intermediate.dense.weight": ("w1", True),
    "intermediate.dense.bias": ("b1", False),
    "output.dense.weight": ("w2", True),
    "output.dense.bias": ("b2", False),
}
_VIT_LAYER_RE = re.compile(r"^vit\.encoder\.layer\.(\d+)\.(.+)$")
_VIT_QKV_RE = re.compile(
    r"^attention\.attention\.(query|key|value)\.(weight|bias)$"
)


def load_hf_vit(model_dir: str | Path, cfg, dtype: Optional[Any] = None,
                head_seed: int = 0) -> Dict[str, Any]:
    """Load an HF ViT-class safetensors checkpoint (google/vit-* layout)
    into the :mod:`models.vit` params pytree.

    Faithful for everything the architectures share — both are PRE-norm
    encoders, so patch projection (the conv kernel reshaped to our matmul
    layout), position embeddings, every encoder layer incl. all biases,
    and the final layernorm import exactly. What does NOT come from the
    checkpoint, by design: the CLS token (our model pools through learned
    perceiver queries instead — its position-embedding slot is dropped)
    and the ``query_emb``/``out_proj`` resampler head, which is
    fresh-initialized from ``head_seed`` — the LLaVA-style projector that
    is always trained against the paired decoder (reference bar:
    /root/reference/worker/engines/vision.py:57-78 serves a pretrained
    VLM whose projector shipped with the checkpoint; ours is the part a
    deployment fine-tunes).
    """
    import jax

    from safetensors import safe_open

    from distributed_gpu_inference_tpu.models.encoder_common import (
        fan_in_init,
    )

    model_dir = Path(model_dir)
    dtype = jnp.dtype(dtype or "float32")
    L, h = cfg.num_layers, cfg.hidden_size
    files = sorted(model_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_dir}")

    layers: Dict[str, np.ndarray] = {}
    qkv_w = np.zeros((L, 3, h, h), np.float32)
    qkv_b = np.zeros((L, 3, h), np.float32)
    params: Dict[str, Any] = {}
    _QKV_IDX = {"query": 0, "key": 1, "value": 2}
    # every (key, layer) slot must be FILLED from the checkpoint: a missing
    # shard would otherwise leave zero placeholders (zero norms = silent
    # near-no-op blocks) — same contract as the Llama path's _validate
    filled: set = set()

    def _slot(our_key: str, shape: Tuple[int, ...]) -> np.ndarray:
        if our_key not in layers:
            layers[our_key] = np.zeros((L, *shape), dtype=dtype)
        return layers[our_key]

    for f in files:
        with safe_open(str(f), framework="np") as st:
            for name in st.keys():
                m = _VIT_LAYER_RE.match(name)
                if m:
                    li = int(m.group(1))
                    if li >= L:
                        raise ValueError(
                            f"checkpoint layer {li} exceeds config "
                            f"num_layers={L}"
                        )
                    sub = m.group(2)
                    qm = _VIT_QKV_RE.match(sub)
                    if qm:
                        idx = _QKV_IDX[qm.group(1)]
                        w = st.get_tensor(name)
                        if qm.group(2) == "weight":
                            qkv_w[li, idx] = w.T    # HF stores [out, in]
                        else:
                            qkv_b[li, idx] = w
                        filled.add((f"{qm.group(1)}.{qm.group(2)}", li))
                        continue
                    if sub not in _HF_VIT_LAYER_MAP:
                        continue
                    our_key, transpose = _HF_VIT_LAYER_MAP[sub]
                    w = st.get_tensor(name)
                    if transpose:
                        w = w.T
                    _slot(our_key, w.shape)[li] = w.astype(dtype)
                    filled.add((our_key, li))
                elif name == ("vit.embeddings.patch_embeddings."
                              "projection.weight"):
                    # conv kernel [H, C, P, P] → matmul over patchify's
                    # (row, col, channel) flattening → [P*P*C, H]
                    w = st.get_tensor(name).transpose(2, 3, 1, 0)
                    params["patch_proj"] = jnp.asarray(
                        w.reshape(-1, w.shape[-1]), dtype
                    )
                elif name == ("vit.embeddings.patch_embeddings."
                              "projection.bias"):
                    params["patch_bias"] = jnp.asarray(
                        st.get_tensor(name), dtype
                    )
                elif name == "vit.embeddings.position_embeddings":
                    # [1, 1+N, H]: slot 0 is the CLS position — dropped
                    # (we pool through perceiver queries, not CLS)
                    params["pos_emb"] = jnp.asarray(
                        st.get_tensor(name)[0, 1:], dtype
                    )
                elif name == "vit.layernorm.weight":
                    params["out_norm"] = jnp.asarray(
                        st.get_tensor(name), dtype
                    )
                elif name == "vit.layernorm.bias":
                    params["out_norm_b"] = jnp.asarray(
                        st.get_tensor(name), dtype
                    )

    # wqkv columns order (q | k | v) to match the encoder's split:
    # [L, 3, H_in, H_out] → [L, H_in, 3, H_out] → [L, H, 3H]
    layers["wqkv"] = qkv_w.transpose(0, 2, 1, 3).reshape(L, h, 3 * h)
    layers["bqkv"] = qkv_b.reshape(L, 3 * h)
    params["layers"] = {
        k: jnp.asarray(v, dtype) for k, v in layers.items()
    }

    missing = {"patch_proj", "pos_emb", "out_norm"} - set(params)
    if missing:
        raise ValueError(f"checkpoint is missing ViT tensors: {missing}")
    expected_keys = (
        {v[0] for v in _HF_VIT_LAYER_MAP.values()}
        | {f"{q}.{t}" for q in _QKV_IDX for t in ("weight", "bias")}
    )
    gaps = sorted(
        (k, li) for k in expected_keys for li in range(L)
        if (k, li) not in filled
    )
    if gaps:
        raise ValueError(
            f"checkpoint left {len(gaps)} encoder tensors unfilled "
            f"(missing shard / shallower model?): first few {gaps[:4]}"
        )
    if params["pos_emb"].shape[0] != cfg.num_patches:
        raise ValueError(
            f"position embeddings cover {params['pos_emb'].shape[0]} "
            f"patches, config expects {cfg.num_patches} "
            f"(image {cfg.image_size} / patch {cfg.patch_size})"
        )

    # resampler head: fresh init (trained against the paired decoder)
    ks = jax.random.split(jax.random.PRNGKey(head_seed), 2)
    params["query_emb"] = fan_in_init(ks[0], (cfg.num_prefix, h), h, dtype)
    params["out_proj"] = fan_in_init(ks[1], (h, cfg.out_dim), h, dtype)
    return params


# ---------------------------------------------------------------------------
# Native checkpoints (orbax) — serving snapshots / resume (SURVEY §5.4 notes
# the reference has none; we add weight checkpointing as a first-class op)
# ---------------------------------------------------------------------------


def save_checkpoint(path: str | Path, params: Dict[str, Any],
                    cfg: Optional[ModelConfig] = None) -> None:
    import orbax.checkpoint as ocp

    path = Path(path).absolute()
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path / "params", params)
    ckptr.wait_until_finished()
    if cfg is not None:
        from dataclasses import asdict

        # dump EVERY config field: a hand-kept list silently drops new
        # fields (attention_bias once went missing this way)
        (path / "model_config.json").write_text(json.dumps(asdict(cfg)))


def load_checkpoint(path: str | Path, template: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    import orbax.checkpoint as ocp

    path = Path(path).absolute()
    ckptr = ocp.StandardCheckpointer()
    if template is not None:
        return ckptr.restore(path / "params", template)
    return ckptr.restore(path / "params")


def load_or_init_params(
    cfg: ModelConfig,
    checkpoint_path: Optional[str] = None,
    dtype: Optional[Any] = None,
    seed: int = 0,
) -> Dict[str, Any]:
    """One-stop weight source for engines: orbax checkpoint dir, HF
    safetensors dir, or random init (hermetic tests / benchmarks)."""
    import jax

    from distributed_gpu_inference_tpu.models import llama

    if checkpoint_path:
        p = Path(checkpoint_path)
        if (p / "config.json").exists() or list(p.glob("*.safetensors")):
            return load_hf_llama(p, cfg, dtype=dtype)
        return load_checkpoint(p)
    return llama.init_params(
        cfg, jax.random.PRNGKey(seed), jnp.dtype(dtype or cfg.dtype)
    )
