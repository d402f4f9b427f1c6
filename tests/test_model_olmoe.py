"""OLMoE on the deployed path: QK-norm, top-k of many experts kept as they
are, and the routed expert layer — held to the benchmark's plain reference
(``benchmark/harness/reference_sparse.py``, which shares no code with the
program) on ``olmoe-tiny``, and to the dense einsum form on ``olmoe-tiny``
and ``mixtral-tiny`` (both settings of ``norm_topk_prob``).

Tolerances: float32 activations over the same int8 weights differ from the
reference by float32 rounding over two layers (measured 2e-6; 1e-4 asserted,
where a renormalised or norm-less block is off by 0.3). Greedy tokens are
compared wherever the reference's top-2 margin exceeds 1e-3, ten times
that rounding."""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:      # as benchmark/tests/conftest.py does
    sys.path.insert(0, str(BENCH))

from harness import reference_sparse as reference  # noqa: E402

from distributed_gpu_inference_tpu.models import llama  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    get_model_config,
)
from distributed_gpu_inference_tpu.models.loader import (  # noqa: E402
    init_quantized_streamed,
)
from distributed_gpu_inference_tpu.ops import moe_gmm_pallas as moe_gmm  # noqa: E402
from distributed_gpu_inference_tpu.ops.quantization import (  # noqa: E402
    dequantize,
    quantize_weight,
)
from distributed_gpu_inference_tpu.runtime.engine import (  # noqa: E402
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (  # noqa: E402
    InferenceRequest,
    SamplingParams,
)

MODEL = "olmoe-tiny"
TOL = 1e-4
MARGIN = 1e-3


def published(mc):
    """The configuration as a published ``config.json`` states it."""
    return {
        "hidden_size": mc.hidden_size, "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "intermediate_size": mc.intermediate_size,
        "num_hidden_layers": mc.num_layers, "vocab_size": mc.vocab_size,
        "num_experts": mc.num_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "tie_word_embeddings": mc.tie_word_embeddings,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.rms_norm_eps,
    }


def _f32(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)


@pytest.fixture(scope="module")
def tiny():
    mc = get_model_config(MODEL)
    params = init_quantized_streamed(mc, "int8", seed=0)
    return mc, params, reference.SeedStream(published(mc), 0)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed + n)
    return [int(t) for t in rng.integers(4, 260, n)]


def _logits(mc, params, prompt, **kw):
    n = len(prompt)
    return llama.forward_chunk(
        mc, params, jnp.asarray([prompt]), jnp.arange(n)[None],
        llama.init_kv_pools(mc, 8, 16, jnp.float32),
        jnp.asarray([[1, 2, 3, 4]]), jnp.asarray([n]), block_size=16, **kw)


def test_registry_and_leaves():
    mc = get_model_config("olmoe-1b-7b")
    assert (mc.num_experts, mc.num_experts_per_tok) == (64, 8)
    assert mc.qk_norm and not mc.norm_topk_prob and mc.head_dim == 128
    assert 6.9e9 < mc.num_params < 6.95e9       # 6.92 B as published
    tiny_p = llama.init_params(get_model_config(MODEL), jax.random.PRNGKey(0))
    assert tiny_p["layers"]["q_norm"].shape == (2, 64)
    assert tiny_p["layers"]["k_norm"].shape == (2, 64)
    assert "q_norm" not in llama.init_params(
        get_model_config("mixtral-tiny"), jax.random.PRNGKey(0))["layers"]


def test_seed_stream_is_the_streamed_init_bit_for_bit(tiny):
    mc, params, ours = tiny
    theirs = reference.FromTree(params)
    for layer in range(mc.num_layers):
        a, b = ours.layer(layer), theirs.layer(layer)
        assert set(a) == set(b)
        for key in a:
            assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key
    assert np.array_equal(np.asarray(ours.embedding()),
                          np.asarray(theirs.embedding()))
    assert np.array_equal(np.asarray(ours.head()), np.asarray(theirs.head()))


@pytest.mark.parametrize("n", [5, 12, 20])
def test_forward_chunk_matches_the_reference_in_float32(tiny, n):
    mc, params, ours = tiny
    prompt = _prompt(n)
    (want,), (routes,) = reference.forward(published(mc), ours, [prompt])
    out = _logits(mc, _f32(params), prompt, collect_routing=True)
    got = np.asarray(out.logits[0, 0])
    assert np.abs(got - want[0]).max() < TOL
    # the same k experts for every token in every layer
    assert np.array_equal(np.sort(np.asarray(out.routing), -1),
                          np.sort(routes, -1))


@pytest.mark.parametrize("variant", [{"norm_topk_prob": True},
                                     {"qk_norm": False}],
                         ids=["renormalised", "no-qk-norm"])
def test_a_block_without_either_flag_fails_the_reference(tiny, variant):
    mc, params, ours = tiny
    other = dataclasses.replace(mc, **variant)
    f32 = _f32(params)
    if not other.qk_norm:
        f32 = {**f32, "layers": {k: v for k, v in f32["layers"].items()
                                 if k not in ("q_norm", "k_norm")}}
    prompt = _prompt(12)
    (want,), _ = reference.forward(published(mc), ours, [prompt])
    got = np.asarray(_logits(other, f32, prompt).logits[0, 0])
    assert np.abs(got - want[0]).max() > 100 * TOL


def test_prefill_in_two_chunks_then_decode_through_the_paged_pools(tiny):
    """Positions 0-6 and 7-12 as two chunks, then 4 single-token steps,
    each against the reference's full forward pass over the same tokens
    (its own argmax fed back)."""
    mc, params, ours = tiny
    f32 = _f32(params)
    kv = llama.init_kv_pools(mc, 8, 16, jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4]])
    tokens = _prompt(13)

    def run(piece, start, kv):
        pos = jnp.arange(start, start + len(piece))[None]
        out = llama.forward_chunk(
            mc, f32, jnp.asarray([piece]), pos, kv, tables,
            jnp.asarray([start + len(piece)]), block_size=16)
        return np.asarray(out.logits[0, 0]), out.kv

    _, kv = run(tokens[:7], 0, kv)
    got, kv = run(tokens[7:], 7, kv)
    for _ in range(5):
        (want,), _ = reference.forward(published(mc), ours, [tokens])
        assert np.abs(got - want[0]).max() < TOL, len(tokens)
        tokens = tokens + [int(want[0].argmax())]
        got, kv = run(tokens[-1:], len(tokens) - 1, kv)


def _engine(model, **kw):
    return TPUEngine(model, EngineConfig(
        max_batch_size=4, max_seq_len=128, block_size=16,
        prefill_buckets=(16, 32, 64), ragged_chunk=32, dtype="float32",
        **kw), seed=0)


def _serve(eng, prompts, new):
    """Admit every prompt through packed ragged rounds, then scan. Per
    prompt: the tokens its ragged rounds sampled and those of the scan."""
    flying = [eng.submit_chunked_start(InferenceRequest(
        prompt_token_ids=p, sampling=SamplingParams(
            max_new_tokens=new, temperature=0.0, ignore_eos=True)))
        for p in prompts]
    ragged = {a.slot: [] for a in flying}
    while flying:
        for slot, toks in eng.ragged_round(flying).items():
            ragged[slot] += toks
        flying = [a for a in flying if not a.done]
    scan = eng.decode_multi(new - 1)
    return [(ragged[i], scan[i]) for i in sorted(ragged)]


def test_engine_rounds_follow_the_reference_argmax_chain_and_count():
    """Packed ``ragged_round`` (a 40-token prompt enters in two pieces
    beside a 9-token one, which decodes a token meanwhile) then
    ``decode_multi``: greedy tokens against the reference's argmax chain,
    and the experts' counters against what the reference routed."""
    eng = _engine(MODEL, quantization="int8")
    mc = eng.model_cfg
    cfg, weights = published(mc), reference.FromTree(eng.params)
    prompts, new = [_prompt(40), _prompt(9)], 5
    served = _serve(eng, prompts, new)
    assert [len(r) + len(s) for r, s in served] == [new, new]

    routed = []         # per prompt: the reference's routing [L, S, k]
    for prompt, (first, rest) in zip(prompts, served):
        seq = list(prompt)
        for step, tok in enumerate(first + rest):
            (want,), (routes,) = reference.forward(cfg, weights, [seq])
            top2 = np.sort(want[0])[-2:]
            if top2[1] - top2[0] > MARGIN:
                assert tok == int(want[0].argmax()), (len(prompt), step)
            seq.append(tok)
        routed.append(routes)

    st, layers, k = eng.stats, mc.num_layers, mc.num_experts_per_tok
    # a ragged round routes the prompts' tokens and its decode rows' one
    live_ragged = sum(len(p) + len(first) - 1
                      for p, (first, _) in zip(prompts, served))
    assert st["moe_assignments_ragged"] == live_ragged * k * layers
    assert st["moe_assignments_scan"] == sum(
        len(rest) for _, rest in served) * k * layers
    steps = max(len(rest) for _, rest in served)
    assert st["moe_layer_calls_scan"] == steps * layers
    # a scan step routes each live row's pending token: the experts any of
    # them chose, counted from the reference's routing of that position
    active = 0
    for step in range(steps):
        for layer in range(layers):
            chosen = set()
            for prompt, (first, rest), routes in zip(prompts, served,
                                                     routed):
                if step < len(rest):
                    at = len(prompt) + len(first) - 1 + step
                    chosen |= set(routes[layer, at].tolist())
            active += len(chosen)
    assert st["moe_active_experts_scan"] == active
    assert st["moe_rows_dispatched_scan"] >= st["moe_assignments_scan"]
    assert st["moe_rows_dispatched_scan"] % moe_gmm.sublane(jnp.float32) == 0


def test_a_dense_model_has_no_expert_counters():
    eng = _engine("llama3-tiny")
    _serve(eng, [_prompt(9)], 3)
    assert not [k for k in eng.stats if k.startswith("moe_")]


# --------------------------------------------------------------------- #
# the routed layer against the dense einsum form
# --------------------------------------------------------------------- #

def _layer_weights(model, seed=1):
    cfg = get_model_config(model, dtype="float32")
    p = llama.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    return cfg, jax.tree.map(lambda a: a[0], p["layers"])


@pytest.mark.parametrize("t", [1, 5, 37])
@pytest.mark.parametrize("model", [MODEL, "mixtral-tiny"])
def test_routed_layer_equals_the_dense_form(model, t):
    """Both settings of ``norm_topk_prob``; ``T`` = 1, and ``T k`` = 20 /
    148 / 74, no multiple of the tile; a router biased so that half the
    experts receive no row; dead tokens routed nowhere."""
    cfg, lp = _layer_weights(model)
    e = cfg.num_experts
    # feature 0 is 1 everywhere and votes hard against the upper experts
    lp = dict(lp, w_router=lp["w_router"].at[0, e // 2:].set(-50.0))
    x = jax.random.normal(jax.random.PRNGKey(t), (1, t, 64), jnp.float32)
    x = x.at[..., 0].set(1.0)
    live = jnp.arange(t)[None] != 2
    got, stats, routing = llama._moe_mlp(x, lp, cfg, live=live)
    want, none, _ = llama._moe_mlp(x, lp, cfg, pallas=False)
    assert none is None
    keep = np.asarray(live)[0]
    assert np.abs(np.asarray(got - want))[0, keep].max() < 1e-5
    assert np.all(np.asarray(got)[0, ~keep] == 0)
    assert int(routing.max()) < e // 2
    assert int(stats["assignments"]) == keep.sum() * cfg.num_experts_per_tok
    assert int(stats["active_experts"]) == len(
        set(np.asarray(routing)[keep].ravel().tolist()))
    assert int(stats["rows_dispatched"]) <= moe_gmm.num_tiles(
        t * cfg.num_experts_per_tok, e, 8) * 128


@pytest.mark.parametrize("model", [MODEL, "mixtral-tiny"])
def test_routed_layer_through_the_kernel_in_interpret_mode(model,
                                                           monkeypatch):
    """The layer as one chip runs it: int8 expert weights kept whole, the
    grouped-matmul kernel addressed by layer index (here interpreted),
    against the dense form over the dequantized weights."""
    cfg, lp = _layer_weights(model)
    stacked = {
        name: jax.tree.map(lambda a: jnp.stack([jnp.zeros_like(a), a]),
                           quantize_weight(lp[name], "int8"))
        for name in ("we_gate", "we_up", "we_down")
    }
    deq = dict(lp, **{name: dequantize(
        jax.tree.map(lambda a: a[1], w)) for name, w in stacked.items()})
    monkeypatch.setattr(
        moe_gmm, "grouped_matmul_pallas",
        functools.partial(moe_gmm.grouped_matmul_pallas, interpret=True))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 11, 64), jnp.float32)
    got, _, _ = llama._moe_mlp(x, lp, cfg, stacked=stacked, layer_idx=1)
    want, _, _ = llama._moe_mlp(x, deq, cfg, pallas=False)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_route_plan_is_a_tiling_by_expert():
    """Every live pair has one row, in a tile of its expert; tiles past
    the used count repeat the last expert; the bound on tiles holds."""
    t, k, e, tm = 9, 3, 8, 8
    topi = jax.random.randint(jax.random.PRNGKey(0), (t, k), 0, 5)
    live = jnp.arange(t) != 4
    plan = moe_gmm.route_plan(topi, live, e, tm)
    rows = np.asarray(plan.pair_row)
    tile_e = np.asarray(plan.tile_expert)
    used = int(plan.used_tiles)
    assert len(tile_e) == moe_gmm.num_tiles(t * k, e, tm)
    taken = set()
    for tok in range(t):
        for j in range(k):
            if tok == 4:
                assert rows[tok, j] == len(tile_e) * tm
                continue
            r = rows[tok, j]
            assert r not in taken and r // tm < used
            taken.add(r)
            assert tile_e[r // tm] == int(topi[tok, j])
            assert int(plan.row_token[r]) == tok
    assert np.all(tile_e[used:] == tile_e[used - 1])
    assert int(np.sum(np.asarray(plan.row_token) < t)) == len(taken)


# --------------------------------------------------------------------- #
# the counters on the round spans and on /metrics
# --------------------------------------------------------------------- #

def test_round_spans_carry_the_expert_counters(monkeypatch):
    from distributed_gpu_inference_tpu.runtime import flight

    seen = []

    class Note:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, dict(attrs)
            seen.append(self)

        def set_metadata(self, **attrs):
            self.attrs.update(attrs)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(flight, "_annotation", Note)
    eng = _engine(MODEL)
    _serve(eng, [_prompt(9)], 3)
    for name, kind in (("dgi.engine.ragged_round", "ragged"),
                       ("dgi.engine.decode_multi", "scan")):
        notes = [n for n in seen if n.name == name]
        assert notes
        for what in ("layer_calls", "assignments", "rows_dispatched",
                     "active_experts"):
            assert sum(n.attrs[f"moe_{what}"] for n in notes) \
                == eng.stats[f"moe_{what}_{kind}"] > 0


def test_expert_counters_reach_the_metrics_endpoint():
    from distributed_gpu_inference_tpu.server.observability import (
        MetricsCollector,
    )
    from distributed_gpu_inference_tpu.worker.main import Worker

    class Core:
        def get_stats(self):
            return {"moe_layer_calls_scan": 32, "moe_assignments_scan": 512,
                    "moe_rows_dispatched_scan": 4096,
                    "moe_active_experts_scan": 900,
                    "moe_assignments_ragged": 64}

    class Eng:
        engine = Core()

        def serving_stats(self):
            return {"decode_rounds": 1}

    worker = Worker.__new__(Worker)
    worker.engines = {"llm": Eng()}
    worker.serving_capacity = lambda: 8
    sent = worker._batcher_stats()
    assert sent["moe_active_experts_scan"] == 900
    mc = MetricsCollector()
    mc.record_batcher_engine("w1", sent)
    mc.record_batcher_engine("w1", dict(sent, moe_active_experts_scan=1000))
    text = mc.metrics.render().decode()
    if "worker_moe_active_experts_total" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert ('worker_moe_active_experts_total{round="scan",worker="w1"} '
            '1000.0') in text
    assert ('worker_moe_assignments_total{round="ragged",worker="w1"} 64.0'
            ) in text
    assert ('worker_moe_rows_dispatched_total{round="scan",worker="w1"} '
            '4096.0') in text
