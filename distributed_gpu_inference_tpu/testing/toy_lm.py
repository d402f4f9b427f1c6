"""A toy language model with sharp logits, for tests of what needs one."""

from __future__ import annotations

import functools

import numpy as np


def train_toy_lm(cfg, key, steps: int = 600, batch: int = 16,
                 seq_len: int = 64, lr: float = 3e-3, noise: float = 0.05):
    """Train a model on a learnable synthetic task so tests that need a
    PREDICTABLE model (speculative decoding, draft distillation) see real
    behavior.

    Random-init weights have near-uniform, chaotic logits — no draft can
    match them, so an accept-rate measurement on them says nothing (the
    reference dodges this by SIMULATING accept rates). Here the target is
    trained on a noisy Markov chain (x_{t+1} = perm[x_t] w.p. 1-noise): a
    task a tiny transformer learns to near-ceiling in seconds, giving sharp
    logits an EAGLE head can genuinely be distilled against.

    Returns ``(params_in_model_dtype, sample_stream)`` where
    ``sample_stream(key, batch, seq_len)`` draws token streams from the
    chain (use it for prompts so decode continues in-distribution).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from distributed_gpu_inference_tpu.models import llama

    kp, kperm, kdata = jax.random.split(key, 3)
    vocab = cfg.vocab_size
    perm = jax.random.permutation(kperm, vocab)

    def sample_stream(k, b, s):
        ks = jax.random.split(k, s)
        x0 = jax.random.randint(ks[0], (b,), 0, vocab, jnp.int32)

        def step(x, kk):
            k_u, k_r = jax.random.split(kk)
            u = jax.random.uniform(k_u, (b,))
            rnd = jax.random.randint(k_r, (b,), 0, vocab, jnp.int32)
            x2 = jnp.where(u < noise, rnd, perm[x]).astype(jnp.int32)
            return x2, x2

        _, xs = jax.lax.scan(step, x0, ks[1:])
        return jnp.concatenate([x0[:, None], xs.T], axis=1)   # [B, S]

    bs = 16
    m = -(-seq_len // bs)
    positions = jnp.tile(jnp.arange(seq_len, dtype=jnp.int32), (batch, 1))
    lens = jnp.full((batch,), seq_len, jnp.int32)
    tables = jnp.asarray(
        np.arange(1, 1 + batch * m, dtype=np.int32).reshape(batch, m)
    )
    params = llama.init_params(cfg, kp, jnp.float32)
    opt = optax.adam(lr)
    opt_state = opt.init(params)

    def loss_fn(params, toks):
        kv = llama.init_kv_pools(cfg, 1 + batch * m, bs, jnp.float32)
        out = llama.forward_chunk(
            cfg, params, toks, positions, kv, tables, lens,
            block_size=bs, last_only=False,
        )
        logp = jax.nn.log_softmax(out.logits[:, :-1].astype(jnp.float32), -1)
        tgt = toks[:, 1:, None]
        return -jnp.mean(jnp.take_along_axis(logp, tgt, axis=-1))

    # the WHOLE training loop is one lax.scan in one jitted call: a
    # host-driven step loop pays dispatch per step and a compile per shape
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train(params, opt_state):
        def step_fn(carry, step):
            params, opt_state = carry
            toks = sample_stream(
                jax.random.fold_in(kdata, step), batch, seq_len
            )
            loss, grads = jax.value_and_grad(loss_fn)(params, toks)
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            step_fn, (params, opt_state), jnp.arange(steps)
        )
        return params, losses

    params, _losses = train(params, opt_state)
    dtype = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda a: a.astype(dtype), params), sample_stream
