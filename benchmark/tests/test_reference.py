"""The plain reference against the program, on the tiny configurations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import reference
from harness.spec import PUBLISHED_KEYS

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import get_model_config
from distributed_gpu_inference_tpu.models.loader import (
    init_quantized_streamed,
)

TINY = ["mistral-tiny", "qwen2.5-tiny", "mixtral-tiny"]


def published(mc):
    return {key: getattr(mc, attr) for key, attr in PUBLISHED_KEYS.items()}


@pytest.mark.parametrize("name", TINY)
def test_seed_stream_is_the_streamed_init_bit_for_bit(name):
    mc = get_model_config(name)
    ours = reference.SeedStream(published(mc), 0)
    theirs = reference.FromTree(init_quantized_streamed(mc, "int8", seed=0))
    for layer in range(mc.num_layers):
        a, b = ours.layer(layer), theirs.layer(layer)
        assert set(a) == set(b)
        for key in a:
            assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key
    assert np.array_equal(np.asarray(ours.embedding()),
                          np.asarray(theirs.embedding()))
    assert np.array_equal(np.asarray(ours.head()), np.asarray(theirs.head()))


@pytest.mark.parametrize("name", TINY)
def test_reference_logits_match_forward_chunk_in_float32(name):
    """Window (mistral-tiny: 8 tokens, prompts longer), GQA with biases
    (qwen2.5-tiny), top-2 of 4 experts (mixtral-tiny). The program runs
    the same int8 weights with float32 activations through its paged
    cache; 1e-4 is float32 rounding over two layers, far under the ~1e-1
    a bf16 run or a dropped term would show."""
    mc = get_model_config(name)
    params = init_quantized_streamed(mc, "int8", seed=0)
    f32 = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(4, 260, n)] for n in (5, 12, 20)]
    ref = reference.last_logits(
        published(mc), reference.SeedStream(published(mc), 0), prompts)
    for p, want in zip(prompts, ref):
        n = len(p)
        out = llama.forward_chunk(
            mc, f32, jnp.asarray([p]), jnp.arange(n)[None],
            llama.init_kv_pools(mc, 8, 16, jnp.float32),
            jnp.asarray([[1, 2, 3, 4]]), jnp.asarray([n]), block_size=16)
        got = np.asarray(out.logits[0, 0])
        assert np.abs(got - want).max() < 1e-4
        assert int(got.argmax()) == int(want.argmax())


def test_first_token_rule():
    g = {"ids": [7, 3, 9], "logits": [4.0, 3.9, 2.0]}
    assert reference.first_token_verdict(7, g, 0.0)["ok"]
    assert reference.first_token_verdict(3, g, 0.2)["ok"]
    assert not reference.first_token_verdict(3, g, 0.05)["ok"]
    assert not reference.first_token_verdict(9, g, 0.2)["ok"]
    assert not reference.first_token_verdict(1, g, 9.9)["ok"]
