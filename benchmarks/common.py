"""Shared benchmark helpers: percentile stats, request synthesis, JSON out.

Metric definitions mirror the reference's harnesses (SURVEY §6): tokens/s,
TTFT/E2E p50/p95/p99, prefix-cache hit rate, accept rate — so results are
comparable in kind; unlike the reference's distributed/PD/speculative
benchmarks (analytic simulators), every harness here drives REAL compute.
"""

from __future__ import annotations

import functools
import json
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np


def add_platform_arg(ap) -> None:
    """Shared --platform flag (all four harnesses)."""
    ap.add_argument(
        "--platform", default=None,
        help="jax platform to run on (e.g. cpu), set before backend init; "
        "the default is the chip, and a run that finds none fails",
    )


def init_backend(args) -> str:
    """Apply --platform, return the backend. The CPU is used only when
    ``--platform``/``JAX_PLATFORMS`` asked for it: a benchmark that finds
    no chip fails instead of timing the host. Also turns the persistent
    compile cache on (``utils.device.enable_compile_cache``)."""
    import jax

    from distributed_gpu_inference_tpu.utils.device import (
        enable_compile_cache,
        require_backend,
    )

    if getattr(args, "platform", None):
        jax.config.update("jax_platforms", args.platform)
    backend = require_backend()
    enable_compile_cache()
    return backend


def resolve_backend_model(args, tpu_default: str = "llama3-1b",
                          cpu_default: str = "llama3-mini"):
    """``init_backend`` → (backend, model). One implementation so the
    harnesses can't drift on platform/model selection; ``cpu_default`` is
    what a CPU run that was asked for serves."""
    backend = init_backend(args)
    model = args.model or (tpu_default if backend == "tpu" else cpu_default)
    return backend, model


def percentiles(values: Sequence[float],
                ps=(50, 95, 99)) -> Dict[str, Optional[float]]:
    if not values:
        return {f"p{p}": None for p in ps}
    arr = np.asarray(sorted(values))
    return {f"p{p}": round(float(np.percentile(arr, p)), 2) for p in ps}


def synth_prompts(n: int, prompt_len: int, vocab: int, seed: int = 0,
                  shared_prefix_len: int = 0) -> List[List[int]]:
    """Random prompts, optionally sharing a common prefix (prefix-cache and
    PD benchmarks need realistic system-prompt sharing)."""
    rng = np.random.default_rng(seed)
    shared_prefix_len = min(shared_prefix_len, prompt_len)
    prefix = rng.integers(1, vocab, shared_prefix_len).tolist() \
        if shared_prefix_len else []
    out = []
    for _ in range(n):
        rest = rng.integers(1, vocab, prompt_len - len(prefix)).tolist()
        out.append(prefix + rest)
    return out


def make_request(prompt_token_ids: Sequence[int], max_new_tokens: int):
    """One request shape for every harness (greedy, fixed budget) so the
    four benchmarks cannot drift on sampling config."""
    from distributed_gpu_inference_tpu.utils.data_structures import (
        InferenceRequest,
        SamplingParams,
    )

    return InferenceRequest(
        prompt_token_ids=list(prompt_token_ids),
        sampling=SamplingParams(max_new_tokens=max_new_tokens,
                                temperature=0.0),
    )


def make_chain_sampler(perm, noise: float = 0.05):
    """Reconstructable sampler for the noisy Markov chain a trained toy LM
    models — (perm, noise) fully determine the data distribution, so a
    subprocess-trained model's prompts can be drawn in the parent."""
    import jax
    import jax.numpy as jnp

    perm = jnp.asarray(perm)
    tv = int(perm.shape[0])

    def sample_stream(k, b, s):
        ks = jax.random.split(k, s)
        x0 = jax.random.randint(ks[0], (b,), 0, tv, jnp.int32)

        def step(x, kk):
            k_u, k_r = jax.random.split(kk)
            nxt = perm[x]
            u = jax.random.uniform(k_u, (b,))
            rnd = jax.random.randint(k_r, (b,), 0, tv, jnp.int32)
            x2 = jnp.where(u < noise, rnd, nxt).astype(jnp.int32)
            return x2, x2

        _, xs = jax.lax.scan(step, x0, ks[1:])
        return jnp.concatenate([x0[:, None], xs.T], axis=1)   # [B, S]

    sample_stream.perm = perm
    sample_stream.noise = noise
    return sample_stream


def train_toy_lm(cfg, key, steps: int = 600, batch: int = 16,
                 seq_len: int = 64, lr: float = 3e-3, noise: float = 0.05,
                 optimizer: str = "adam", task_vocab: int = 0):
    """Train a model on a learnable synthetic task so benchmarks that need a
    PREDICTABLE model (speculative decoding) measure real behavior.

    Random-init weights have near-uniform, chaotic logits — no draft can
    match them, so an accept-rate measurement on them says nothing (the
    reference dodges this by SIMULATING accept rates,
    ``benchmarks/speculative.py:123-272``). Here the target is trained on a
    noisy Markov chain (x_{t+1} = perm[x_t] w.p. 1-noise): a task a tiny
    transformer learns to near-ceiling in seconds, giving sharp logits an
    EAGLE head can genuinely be distilled against.

    Returns ``(params_in_model_dtype, sample_stream)`` where
    ``sample_stream(key, batch, seq_len)`` draws token streams from the
    chain (use it for prompts so decode continues in-distribution).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from distributed_gpu_inference_tpu.models import llama

    kp, kperm, kdata = jax.random.split(jax.random.PRNGKey(0) if key is None
                                        else key, 3)
    # large-vocab models (Llama-3's 128k) can't memorize a whole-vocab
    # permutation in a few hundred steps — restrict the chain's state space
    # so every transition is seen many times (the MODEL keeps its full
    # vocab; only the data visits a subset)
    tv = min(task_vocab, cfg.vocab_size) if task_vocab else cfg.vocab_size
    perm = jax.random.permutation(kperm, tv)
    sample_stream = make_chain_sampler(perm, noise)

    bs = 16
    m = -(-seq_len // bs)
    positions = jnp.tile(jnp.arange(seq_len, dtype=jnp.int32), (batch, 1))
    lens = jnp.full((batch,), seq_len, jnp.int32)
    tables = jnp.asarray(
        np.arange(1, 1 + batch * m, dtype=np.int32).reshape(batch, m)
    )
    params = llama.init_params(cfg, kp, jnp.float32)
    # adafactor keeps optimizer state ~free (factored second moments) so a
    # 1B-class model trains in f32 within 16 GB HBM — adam's m+v alone adds
    # 2x param bytes and OOMs there
    opt = optax.adam(lr) if optimizer == "adam" else optax.adafactor(lr)
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
    # — this compiles once and runs device-side.
    # Donation lets XLA reuse the input param/opt buffers for the outputs:
    # at 1B-scale f32 that halves peak HBM.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train(params, opt_state):
        def step_fn(carry, step):
            params, opt_state = carry
            toks = sample_stream(
                jax.random.fold_in(kdata, step), batch, seq_len
            )
            loss, grads = jax.value_and_grad(loss_fn)(params, toks)
            # pass params: adafactor's relative scaling requires them
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            step_fn, (params, opt_state), jnp.arange(steps)
        )
        return params, losses

    params, _losses = train(params, opt_state)
    dtype = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda a: a.astype(dtype), params), sample_stream


# hardware constants the projection artifacts (pipeline_70b, mixtral_ep)
# divide by — shared so the two projections can never model different chips
V5E_HBM_GB = 16.0
ICI_GBPS = 45.0          # v5e per-link ICI, one direction (public spec)


def measure_slice(eng, cfg, batch: int, prompt_len: int,
                  decode_tokens: int):
    """THE measured-input slice probe shared by the projection artifacts
    (pipeline_70b, mixtral_ep): warm the engine, then measure prefill wall
    time and the decode_calls-delta-amortized per-step decode time for one
    layer slice. Keeping it in one place keeps the two artifacts'
    numbers method-comparable. → (prefill_s, step_s)."""
    rng = np.random.default_rng(0)

    def reqs():
        return [
            make_request(
                rng.integers(1, cfg.vocab_size, prompt_len).tolist(),
                decode_tokens,
            )
            for _ in range(batch)
        ]

    warm = reqs()
    for r in warm:
        r.sampling.max_new_tokens = 8
    eng.generate(warm, use_multi_step=True)

    t0 = time.perf_counter()
    eng.submit_batch(reqs())
    t_prefill = time.perf_counter() - t0
    calls0 = eng.stats["decode_calls"]
    t1 = time.perf_counter()
    while any(s is not None and s.finish_reason is None for s in eng.slots):
        eng.decode_multi()
    t_decode = time.perf_counter() - t1
    steps = eng.stats["decode_calls"] - calls0
    for i, s in enumerate(list(eng.slots)):
        if s is not None:
            eng.finish_slot(i, cache=False)
    return t_prefill, t_decode / max(steps, 1)


async def open_loop_drive(batcher, prompts, max_tokens: int, rate: float,
                          seed: int = 11):
    """Drive an OPEN-loop Poisson workload through a started batcher:
    arrivals do not slow down when the server falls behind (the only
    regime where sustained-rate TTFT is a valid SLO statement), and each
    request is CONSTRUCTED at its arrival instant so the engine's TTFT
    clock (slot start_time = request.arrival_time) includes queue wait.

    → (results [(response, e2e_ms)], elapsed_s, last_arrival_s). The ONE
    arrival-process implementation for every serving harness
    (single_worker + speculative) so TTFT semantics cannot drift."""
    import asyncio

    gaps = np.random.default_rng(seed).exponential(1.0 / rate, len(prompts))
    arrivals = np.cumsum(gaps)

    async def one(p, at):
        await asyncio.sleep(float(at))
        t0 = time.perf_counter()
        resp = await batcher.submit(make_request(p, max_tokens))
        return resp, (time.perf_counter() - t0) * 1000.0

    t0 = time.perf_counter()
    results = await asyncio.gather(
        *(one(p, a) for p, a in zip(prompts, arrivals))
    )
    return results, time.perf_counter() - t0, float(arrivals[-1])


def emit(result: Dict[str, Any]) -> None:
    print(json.dumps(result))


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.perf_counter() - self.t0
