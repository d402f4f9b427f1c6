"""Draft-head distillation: the fit itself, its stream sources, and the
multi-layer features it can read from ``forward_chunk``."""

import pytest

# compile-heavy (jit/scan graphs): excluded from the fast CI gate
pytestmark = pytest.mark.slow
import jax
import jax.numpy as jnp
import numpy as np

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.runtime.speculative import (
    distill_draft_params,
    draft_apply,
    init_draft_params,
)

CFG = get_model_config("llama3-tiny", dtype="float32")


def test_distilled_draft_beats_random():
    """Distillation must cut the draft's next-hidden regression error well
    below a random head's (argmax agreement additionally needs a sharply
    trained target — the TPU benchmark exercises that end to end)."""
    params = llama.init_params(CFG, jax.random.PRNGKey(1), jnp.float32)
    dp = distill_draft_params(
        CFG, params, jax.random.PRNGKey(2), steps=150, batch=4,
        seq_len=32, num_batches=2,
    )

    def feature_mse(dp):
        b, s, bs = 4, 32, 16
        toks = jax.random.randint(jax.random.PRNGKey(77), (b, s), 0,
                                  CFG.vocab_size, jnp.int32)
        m = -(-s // bs)
        kv = llama.init_kv_pools(CFG, 1 + b * m, bs, jnp.float32)
        tables = jnp.asarray(
            np.arange(1, 1 + b * m, dtype=np.int32).reshape(b, m)
        )
        pos = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
        out = llama.forward_chunk(
            CFG, params, toks, pos, kv, tables,
            jnp.full((b,), s, jnp.int32), block_size=bs, last_only=False,
        )
        h = out.hidden
        emb = llama.embed_tokens(params, toks[:, 1:], CFG)
        pred = draft_apply(
            CFG, jax.tree.map(lambda a: a.astype(jnp.float32), dp),
            h[:, :-1], emb,
        )
        return float(jnp.mean(jnp.square(pred - h[:, 1:])))

    rand_dp = init_draft_params(CFG, jax.random.PRNGKey(3), jnp.float32)
    assert feature_mse(dp) < 0.8 * feature_mse(rand_dp)


def test_distill_returns_model_dtype():
    cfg = get_model_config("llama3-tiny")  # bfloat16 default
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    dp = distill_draft_params(cfg, params, jax.random.PRNGKey(1), steps=3,
                              batch=2, seq_len=16, num_batches=1)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(dp))


FL = (1, 2, 3)      # low/mid/high of the 4-layer tiny model


def test_forward_chunk_collect_layers_shapes():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    b, s, bs, m = 2, 16, 16, 2
    kv = llama.init_kv_pools(CFG, 1 + b * m, bs, jnp.float32)
    toks = jnp.zeros((b, s), jnp.int32)
    pos = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
    tables = jnp.asarray(
        np.arange(1, 1 + b * m, dtype=np.int32).reshape(b, m))
    lens = jnp.full((b,), s, jnp.int32)
    out = llama.forward_chunk(CFG, params, toks, pos, kv, tables, lens,
                              block_size=bs, last_only=False,
                              collect_layers=FL)
    assert out.features.shape == (b, s, len(FL) * CFG.hidden_size)
    # the last collected layer IS the final hidden (post-layer == pre-norm)
    np.testing.assert_allclose(
        np.asarray(out.features[..., -CFG.hidden_size:]),
        np.asarray(out.hidden), rtol=1e-5, atol=1e-5,
    )


def test_draft_apply_w_feat_shape_dispatch():
    dp = init_draft_params(CFG, jax.random.PRNGKey(1),
                           num_feature_layers=len(FL))
    assert dp["w_feat"].shape == (len(FL) * CFG.hidden_size, CFG.hidden_size)
    h = CFG.hidden_size
    wide = jnp.ones((2, len(FL) * h), jnp.float32)
    narrow = jnp.ones((2, h), jnp.float32)
    emb = jnp.ones((2, h), jnp.float32)
    # both widths produce H-dim predictions (root vs deeper-level inputs)
    assert draft_apply(CFG, dp, wide, emb).shape == (2, h)
    assert draft_apply(CFG, dp, narrow, emb).shape == (2, h)


@pytest.mark.parametrize("kw", [
    dict(feature_layers=FL),
    dict(feature_layers=FL, on_policy=True),
    dict(on_policy=True),
])
def test_distill_variants(kw):
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    dp = distill_draft_params(CFG, params, jax.random.PRNGKey(2), steps=12,
                              num_batches=2, **kw)
    assert ("w_feat" in dp) == ("feature_layers" in kw)
    assert all(bool(jnp.isfinite(a).all()) for a in jax.tree.leaves(dp))


def test_custom_data_stream():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    calls = []

    def stream(key, b, s):
        calls.append((b, s))
        return jax.random.randint(key, (b, s), 0, CFG.vocab_size, jnp.int32)

    dp = distill_draft_params(CFG, params, jax.random.PRNGKey(3), steps=6,
                              num_batches=2, data_stream=stream)
    assert len(calls) == 2 and "w_fuse" in dp
