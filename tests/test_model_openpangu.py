"""openPangu-Ultra-MoE on the deployed path: a latent (MLA) paged cache, the
two forms of its attention, leading dense layers, a shared expert beside the
chip's share of the routed ones, sandwich norms — held to the benchmark's
plain reference (``benchmark/harness/reference_mla_moe.py``, which shares no
code with the program) on ``openpangu-ultra-moe-tiny``.

Norm vectors are drawn around one by the model's own init (four norms a
layer are otherwise interchangeable). Tolerances: float32 activations over
the same weights differ from the reference by float32 rounding over three
layers (measured 3e-6; 1e-4 asserted, where a misplaced norm, an
unnormalised router or a missing share is off by 0.05 and more)."""

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

from harness import reference_mla_moe as reference  # noqa: E402

from distributed_gpu_inference_tpu.models import llama, mla  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    get_model_config,
)
from distributed_gpu_inference_tpu.models.loader import (  # noqa: E402
    init_quantized_streamed,
)
from distributed_gpu_inference_tpu.ops import (  # noqa: E402
    mla_attention_pallas as mla_k,
)
from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (  # noqa: E402
    page_write_plan,
)
from distributed_gpu_inference_tpu.runtime.engine import (  # noqa: E402
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (  # noqa: E402
    InferenceRequest,
    SamplingParams,
)

MODEL = "openpangu-ultra-moe-tiny"
SHARE = (2, 4)          # the held subset: experts 2..5 of 8
TOL = 1e-4
MARGIN = 1e-3
BLOCK = 4


def published(mc):
    """The configuration as the benchmark's file states it."""
    first, count = mc.held_experts or (0, mc.num_experts)
    return {
        "hidden_size": mc.hidden_size, "num_attention_heads": mc.num_heads,
        "q_lora_rank": mc.q_lora_rank, "kv_lora_rank": mc.kv_lora_rank,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim, "v_head_dim": mc.v_head_dim,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "num_hidden_layers": mc.num_layers,
        "first_k_dense_replace": mc.first_k_dense,
        "vocab_size": mc.vocab_size, "n_routed_experts": count,
        "expert_share": {"first": first, "count": count,
                         "of": mc.num_experts},
        "n_shared_experts": mc.n_shared_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "sandwich_norm": mc.sandwich_norm,
        "tie_word_embeddings": mc.tie_word_embeddings,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.rms_norm_eps,
    }


def _f32(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)


@pytest.fixture(scope="module")
def tiny():
    """The tiny model holding a SHARE of its experts, int8 as served."""
    mc = get_model_config(MODEL, held_experts=SHARE)
    params = init_quantized_streamed(mc, "int8", seed=0)
    return mc, params, reference.SeedStream(published(mc), 0)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed + n)
    return [int(t) for t in rng.integers(4, 260, n)]


def _tables(rows, pages):
    return jnp.asarray(1 + np.arange(rows * pages).reshape(rows, pages),
                       jnp.int32)


def _run(mc, params, piece, start, kv, tables, **kw):
    pos = jnp.arange(start, start + len(piece))[None]
    return llama.forward_chunk(
        mc, params, jnp.asarray([piece]), pos, kv, tables,
        jnp.asarray([start + len(piece)]), block_size=BLOCK, **kw)


def test_registry_and_the_cut():
    mc = get_model_config("openpangu-ultra-moe-718b-ep16")
    assert mc.latent_kv and mc.head_dim == 192
    assert (mc.num_experts, mc.num_held_experts, mc.num_experts_per_tok) \
        == (256, 16, 8)
    assert (mc.num_layers, mc.first_k_dense) == (9, 1)
    # the issue's bytes: dense layer 0.62 G, expert layer 1.00 G parameters
    assert 0.61e9 < mc.layer_params(0) < 0.63e9
    assert 0.99e9 < mc.layer_params(1) < 1.01e9
    with pytest.raises(ValueError):
        get_model_config(MODEL, held_experts=(6, 4))


@pytest.mark.parametrize("field, value, refused", [
    ("sandwich_norm", True, True), ("mla_use_nope", True, True),
    ("router_selection_bias", True, True),
    ("n_shared_experts", 1, False), ("first_k_dense", 1, False),
    ("routed_scaling_factor", 2.5, False), ("held_experts", (0, 4), False),
])
def test_a_kv_model_refuses_the_fields_only_the_latent_model_reads(
        field, value, refused):
    """``models/llama.py`` would drop them without a word: a K/V model that
    asks for one is refused when its configuration is made. The expert
    layer's per-layer description (a dense lead, a shared expert, the scale,
    a held share) is both recipes' since PR 49: the K/V recipe takes it, and
    refuses it only where no expert layer would read it."""
    if refused:
        with pytest.raises(ValueError, match=field):
            get_model_config("olmoe-tiny", **{field: value})
    else:
        assert getattr(get_model_config("mixtral-tiny", **{field: value}),
                       field) == value
        with pytest.raises(ValueError, match=field):
            get_model_config("llama3-tiny", **{field: value})
    assert getattr(get_model_config(MODEL, **{field: value}), field) == value


def test_f_the_pool_holds_576_values_a_token_a_layer():
    """(f) ``L x 576 x 2`` bytes a token; rows are whole 128-lane tiles."""
    mc = get_model_config("openpangu-ultra-moe-718b-ep16")
    assert mc.kv_bytes_per_token() == mc.num_layers * 576 * 2 == 10368
    assert mla.latent_width(mc) == 576 and mla.pool_width(mc) == 640
    small = get_model_config(MODEL)
    pools = llama.init_kv_pools(small, 5, BLOCK, jnp.bfloat16)
    assert set(pools) == {"ckv"}                 # one pool, no head axis
    assert pools["ckv"].shape == (small.num_layers, 5, BLOCK,
                                  mla.pool_width(small))
    assert small.kv_bytes_per_token() == small.num_layers * (32 + 8) * 2


def test_seed_stream_is_the_programs_init_bit_for_bit(tiny):
    mc, params, ours = tiny
    theirs = reference.FromTree(params)
    for layer in range(mc.num_layers):
        a, b = ours.layer(layer), theirs.layer(layer)
        assert set(a) == set(b)
        for key in a:
            assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key
    for part in ("embedding", "head", "final_norm"):
        assert np.array_equal(np.asarray(getattr(ours, part)()),
                              np.asarray(getattr(theirs, part)()))
    # the norms are drawn, not ones, and no two of a layer are alike
    w = ours.layer(1)
    norms = [np.asarray(w[k]) for k in ("attn_norm", "post_attn_norm",
                                        "mlp_norm", "post_mlp_norm")]
    assert all(np.abs(n - 1).max() > 0.1 for n in norms)
    assert all(np.abs(a - b).max() > 0.1
               for i, a in enumerate(norms) for b in norms[i + 1:])


@pytest.mark.parametrize("n", [5, 13, 22])
def test_forward_chunk_matches_the_reference_in_float32(tiny, n):
    mc, params, ours = tiny
    prompt = _prompt(n)
    (want,), (routes,) = reference.forward(published(mc), ours, [prompt])
    out = _run(mc, _f32(params), prompt, 0,
               llama.init_kv_pools(mc, 9, BLOCK, jnp.float32),
               _tables(1, 8), collect_routing=True)
    assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() < TOL
    assert np.array_equal(np.sort(np.asarray(out.routing), -1),
                          np.sort(routes, -1))


def _swap_norms(params):
    layers = dict(params["layers"])
    layers["post_attn_norm"], layers["mlp_norm"] = \
        layers["mlp_norm"], layers["post_attn_norm"]
    return {**params, "layers": layers}


@pytest.mark.parametrize("broken", [
    lambda mc, p: (dataclasses.replace(mc, norm_topk_prob=False), p),
    lambda mc, p: (dataclasses.replace(mc, routed_scaling_factor=1.0), p),
    lambda mc, p: (dataclasses.replace(mc, held_experts=(0, 4)), p),
    lambda mc, p: (mc, _swap_norms(p)),
], ids=["unnormalised", "unscaled", "another-share", "norms-swapped"])
def test_a_block_that_departs_from_the_description_fails(tiny, broken):
    mc, params, ours = tiny
    prompt = _prompt(13)
    (want,), _ = reference.forward(published(mc), ours, [prompt])
    other, p = broken(mc, _f32(params))
    got = _run(other, p, prompt, 0,
               llama.init_kv_pools(mc, 9, BLOCK, jnp.float32), _tables(1, 8))
    assert np.abs(np.asarray(got.logits[0, 0]) - want[0]).max() > 100 * TOL


def test_a_prefill_in_chunks_then_decode_through_the_latent_cache(tiny):
    """(a) positions 0-6 and 7-12 as two chunks (the expanded form), then 5
    single-token steps (the absorbed form), each against the reference's
    full forward pass over the same tokens (its own argmax fed back)."""
    mc, params, ours = tiny
    f32 = _f32(params)
    kv = llama.init_kv_pools(mc, 9, BLOCK, jnp.float32)
    tables, tokens = _tables(1, 8), _prompt(13)
    out = _run(mc, f32, tokens[:7], 0, kv, tables)
    out = _run(mc, f32, tokens[7:], 7, out.kv, tables)
    for _ in range(5):
        (want,), _ = reference.forward(published(mc), ours, [tokens])
        assert np.abs(np.asarray(out.logits[0, 0]) - want[0]).max() < TOL
        tokens = tokens + [int(want[0].argmax())]
        out = _run(mc, f32, tokens[-1:], len(tokens) - 1, out.kv, tables)


def test_b_absorbed_equals_expanded_on_the_same_cache():
    """(b) both forms over one random cache: mixed lengths, a padded query,
    a row that sees nothing."""
    mc = get_model_config(MODEL)
    rng = np.random.default_rng(1)
    b, s, j = 3, 5, 24
    nh, dn, dr, dv = (mc.num_heads, mc.qk_nope_head_dim,
                      mc.qk_rope_head_dim, mc.v_head_dim)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q_n, q_r = draw(b, s, nh, dn), draw(b, s, nh, dr)
    w_uk, w_uv = draw(nh, mc.kv_lora_rank, dn), draw(nh, mc.kv_lora_rank, dv)
    ctx = draw(b, j, mla.pool_width(mc))
    positions = jnp.asarray([[7, 8, 9, 10, 11], [0, 1, 2, -1, -1],
                             [-1] * 5], jnp.int32)
    kv_lens = jnp.asarray([12, 3, 0], jnp.int32)
    both = [mla.latent_attention_xla(mc, q_n, q_r, w_uk, w_uv, ctx,
                                     positions, kv_lens, form)
            for form in ("expanded", "absorbed")]
    assert np.abs(np.asarray(both[0] - both[1])).max() < 1e-4
    assert np.abs(np.asarray(both[0])[0]).max() > 0.1
    assert np.all(np.asarray(both[0])[2] == 0)          # sees nothing
    assert np.all(np.asarray(both[0])[1, 3:] == 0)      # padded queries


def test_c_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """(c) two chips of four experts each: their routed parts and the
    shared expert, counted once, against the reference's uncut layer."""
    mc = get_model_config(MODEL, dtype="float32")
    params = llama.init_params(mc, jax.random.PRNGKey(3), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    m = jax.random.normal(jax.random.PRNGKey(4), (1, 11, mc.hidden_size),
                          jnp.float32)
    uncut, _ = reference.expert_layer(
        reference.dims(published(mc)), reference.FromTree(params).layer(1),
        m[0])

    def proj(x, name):
        return x @ lp[name]

    total = 0
    for first in (0, 4):
        share = dataclasses.replace(mc, held_experts=(first, 4))
        held = dict(lp, **{k: lp[k][first:first + 4]
                           for k in ("we_gate", "we_up", "we_down")})
        out, stats, topi = mla._experts(
            m, held, share, proj, live=None, stacked=None,
            layer_idx=0)
        assert int(stats["pairs_routed"]) == 11 * mc.num_experts_per_tok
        on_share = (np.asarray(topi) >= first) & (np.asarray(topi) < first + 4)
        assert int(stats["assignments"]) == on_share.sum()
        total = total + out[0]
    shared = proj(jax.nn.silu(proj(m[0], "ws_gate")) * proj(m[0], "ws_up"),
                  "ws_down")
    assert np.abs(np.asarray(total - shared - uncut)).max() < 1e-4
    assert np.abs(np.asarray(uncut)).max() > 0.1


# --------------------------------------------------------------------- #
# through the engine: ragged rounds, scans, the prefix index, refusals
# --------------------------------------------------------------------- #

def _engine(**kw):
    return TPUEngine(
        get_model_config(MODEL, held_experts=SHARE),
        EngineConfig(max_batch_size=4, max_seq_len=128, block_size=16,
                     prefill_buckets=(16, 32, 64), ragged_chunk=32,
                     dtype="float32", **kw), seed=0)


def _serve(eng, prompts, new):
    flying = [eng.submit_chunked_start(InferenceRequest(
        prompt_token_ids=p, sampling=SamplingParams(
            max_new_tokens=new, temperature=0.0, ignore_eos=True)))
        for p in prompts]
    slots = [a.slot for a in flying]
    ragged = {a.slot: [] for a in flying}
    while flying:
        for slot, toks in eng.ragged_round(flying).items():
            ragged[slot] += toks
        flying = [a for a in flying if not a.done]
    scan = eng.decode_multi(new - 1)
    return [(ragged[i], scan[i]) for i in slots]


def test_engine_rounds_follow_the_reference_and_count():
    """Packed ``ragged_round`` (a 40-token prompt enters in two pieces
    beside a 9-token one) then ``decode_multi``: greedy tokens against the
    reference's argmax chain; the share's counters against its routing."""
    eng = _engine(quantization="int8")
    mc = eng.model_cfg
    assert eng.stats["kv_layout"] == "latent"
    assert eng.stats["ragged_kv_path"] == "in_place"
    cfg, weights = published(mc), reference.FromTree(eng.params)
    prompts, new = [_prompt(40), _prompt(9)], 5
    served = _serve(eng, prompts, new)
    on_share = pairs = 0
    first, count = SHARE
    for prompt, (head, rest) in zip(prompts, served):
        seq = list(prompt)
        for step, tok in enumerate(head + rest):
            (want,), (routes,) = reference.forward(cfg, weights, [seq])
            top2 = np.sort(want[0])[-2:]
            if top2[1] - top2[0] > MARGIN:
                assert tok == int(want[0].argmax()), (len(prompt), step)
            seq.append(tok)
        # the scan fed the tokens at positions len(prompt)+len(head)-1 ...
        at = len(prompt) + len(head) - 1
        fed = routes[:, at:at + len(rest)]
        on_share += int(((fed >= first) & (fed < first + count)).sum())
        pairs += fed.size
    st = eng.stats
    assert st["moe_pairs_routed_scan"] == pairs
    assert st["moe_assignments_scan"] == on_share < pairs
    assert st["moe_pairs_routed_ragged"] > st["moe_assignments_ragged"] > 0
    # a scan row starts from the cache its ragged rounds left (the prompt
    # and all but the last token they sampled) and step t attends len + t
    steps = [(len(p) + len(head) - 1, len(rest))
             for p, (head, rest) in zip(prompts, served)]
    assert st["mla_row_steps_scan"] == sum(n for _, n in steps)
    assert st["mla_context_tokens_scan"] == sum(
        n * at + n * (n + 1) // 2 for at, n in steps)
    # the rounds: 32 of the 40 tokens beside the 9-token prompt, then the
    # last 8 behind 32 cached beside the short prompt's first decode token
    assert st["mla_pairs_ragged"] == 32 * 33 // 2 + 9 * 10 // 2 \
        + (8 * 32 + 8 * 9 // 2) + (9 + 1)
    assert st["mla_context_tokens_ragged"] == 32 + 9 + 40 + 10


def test_d_a_prefix_hit_on_latent_pages_serves_what_a_cold_run_serves():
    """(d) the same 40-token prompt twice on one engine: the second run
    takes its first blocks from the radix index (latent pages) and decodes
    the cold run's tokens."""
    eng = _engine()
    prompt, new = _prompt(40), 6
    (cold,) = _serve(eng, [prompt], new)
    for slot, s in enumerate(eng.slots):
        if s is not None:
            eng.finish_slot(slot)
    assert eng.manager.stats.prefix_hit_tokens == 0
    (warm,) = _serve(eng, [prompt], new)
    assert warm[0] + warm[1] == cold[0] + cold[1]
    assert eng.manager.stats.prefix_hit_tokens >= 32    # two 16-token blocks


def test_e_what_carries_kv_pages_refuses_the_model_when_configured():
    """(e) a mesh, the spill tiers, speculative decoding, quantized pools
    and the KV handoff: refused at configuration."""
    from jax.sharding import Mesh

    from distributed_gpu_inference_tpu.runtime import kv_handoff
    from distributed_gpu_inference_tpu.runtime.speculative import (
        SpecDecodeConfig,
    )

    mc = get_model_config(MODEL)
    base = dict(max_batch_size=2, max_seq_len=64, block_size=16,
                prefill_buckets=(16, 32), dtype="float32")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="one chip"):
        TPUEngine(mc, EngineConfig(**base), mesh=mesh)
    with pytest.raises(ValueError, match="spill"):
        TPUEngine(mc, EngineConfig(**base, spill_host_blocks=8))
    with pytest.raises(ValueError, match="multi-token-prediction"):
        TPUEngine(mc, EngineConfig(
            **base, speculative=SpecDecodeConfig(num_draft_tokens=2)))
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        TPUEngine(mc, EngineConfig(**base, kv_cache_dtype="int8"))
    eng = TPUEngine(mc, EngineConfig(**base))
    with pytest.raises(ValueError, match="latent pages"):
        kv_handoff.HandoffReceiver(eng)
    with pytest.raises(ValueError, match="latent pages"):
        kv_handoff.export_slot_kv(eng, 0)


def test_a_worker_with_a_handoff_role_drops_the_model():
    from distributed_gpu_inference_tpu.utils.config import WorkerConfig
    from distributed_gpu_inference_tpu.worker.engines import EngineLoadError
    from distributed_gpu_inference_tpu.worker.main import Worker

    cfg = WorkerConfig.model_validate({
        "name": "w", "task_types": ["llm"], "role": "prefill",
        "engines": {"llm": {"model": MODEL, "dtype": "float32", "extra": {
            "max_seq_len": 64, "max_batch_size": 2,
            "prefill_buckets": [16, 32]}}},
    })
    with pytest.raises(EngineLoadError):
        Worker(cfg).load_engines()


def test_the_new_counters_reach_the_metrics_endpoint():
    from distributed_gpu_inference_tpu.server.observability import (
        MetricsCollector,
    )

    mc = MetricsCollector()
    sent = {"kv_layout": "latent", "ragged_kv_path": "in_place",
            "mla_context_tokens_scan": 5000, "mla_row_steps_scan": 4,
            "mla_pairs_ragged": 70000,
            "moe_pairs_routed_scan": 256, "moe_assignments_scan": 16}
    mc.record_batcher_engine("w1", sent)
    text = mc.metrics.render().decode()
    if "worker_kv_layout" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert 'worker_kv_layout{layout="latent",worker="w1"} 1.0' in text
    assert 'worker_mla_context_tokens_scan_total{worker="w1"} 5000.0' in text
    assert 'worker_mla_pairs_ragged_total{worker="w1"} 70000.0' in text
    assert ('worker_moe_pairs_routed_total{round="scan",worker="w1"} 256.0'
            ) in text


# --------------------------------------------------------------------- #
# the latent kernels, interpreted (tests/test_tpu_lowering.py compiles them)
# --------------------------------------------------------------------- #

def _absorbed_reference(q, pool, layer_idx, block_tables, positions, kv_lens,
                        *, scale, latent):
    """What the absorbed kernel computes, as a gather and two einsums."""
    b, s, nh, w = q.shape
    ctx = pool[layer_idx][block_tables].reshape(b, -1, w).astype(jnp.float32)
    scores = jnp.einsum("bshw,bjw->bhsj", q.astype(jnp.float32), ctx) * scale
    key_pos = jnp.arange(ctx.shape[1], dtype=jnp.int32)[None, None, :]
    visible = (positions[:, :, None] >= key_pos) \
        & (key_pos < kv_lens[:, None, None])
    scores = jnp.where(visible[:, None], scores, -1e30)
    p = jnp.where(visible[:, None], jnp.exp(
        scores - jnp.max(scores, axis=-1, keepdims=True)), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom > 0, denom, 1.0)
    return jnp.einsum("bhsj,bjc->bshc", p, ctx[..., :latent])


@pytest.mark.parametrize("s", [1, 6, 20])
def test_latent_kernels_in_interpret_mode(s):
    """The page write against a scatter, the absorbed kernel against a
    gather and two einsums: decode rows, a chunk beside them, an idle row."""
    rng = np.random.default_rng(s)
    layers, n, bk, w, lat, nh, b, m = 2, 40, 4, 128, 96, 4, 3, 12
    pool = jnp.asarray(rng.normal(size=(layers, n, bk, w)), jnp.float32)
    tables = 1 + np.arange(b * m, dtype=np.int32).reshape(b, m)
    start = [5, 17, 0]
    live = [s, max(s // 2, 1), 0] if s > 1 else [1, 0, 1]
    pos = np.full((b, s), -1, np.int32)
    for r in range(b):
        pos[r, :live[r]] = start[r] + np.arange(live[r])
    lens = np.asarray([start[r] + live[r] for r in range(b)], np.int32)
    rows = jnp.asarray(rng.normal(size=(b * s, w)), jnp.float32)
    plan = page_write_plan(jnp.asarray(tables), jnp.asarray(pos), bk,
                           page_bytes=bk * w * 4)
    new = mla_k.write_latent_pages_in_place(rows, pool, jnp.int32(1), plan,
                                            interpret=True)
    want = np.array(pool)
    for r in range(b):
        for c in range(s):
            if pos[r, c] >= 0:
                want[1, tables[r, pos[r, c] // bk], pos[r, c] % bk] = \
                    np.asarray(rows[r * s + c])
    assert np.array_equal(np.asarray(new), want)
    q = jnp.asarray(rng.normal(size=(b, s, nh, w)), jnp.float32)
    args = (q, new, jnp.int32(1), jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(lens))
    got = mla_k.latent_paged_attention(
        *args, bk, scale=0.2, latent=lat, decode=s == 1, interpret=True)
    ref = _absorbed_reference(*args, scale=0.2, latent=lat)
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() < 1e-5


@pytest.fixture
def small_groups(monkeypatch):
    """Page groups of 8 tokens, so that a 56-token table is seven groups: what
    512-token groups are to the 1.5-3.6 k cached tokens of the cell. The
    group size is read when the kernel is traced."""
    monkeypatch.setattr(mla_k, "_GROUP_TOKENS", 8)
    mla_k.latent_paged_attention.clear_cache()
    yield
    mla_k.latent_paged_attention.clear_cache()


# what each row of the three shapes of a serving call holds: (cached tokens
# before the call, queries in the call). Row 2 is idle; row 1's context ends
# on a group boundary; rows 0 and 3 cross one or more inside their queries.
_GROUP_CASES = {
    "decode": ((36, 1), (7, 1), (0, 0), (55, 1)),
    "chunk": ((20, 12), (3, 5), (0, 0), (44, 12)),
    "packed": ((36, 1), (9, 11), (0, 0), (44, 12)),
}


@pytest.mark.parametrize("shape", sorted(_GROUP_CASES))
def test_the_absorbed_kernel_over_several_page_groups(small_groups, shape):
    """The online-softmax rescale across groups, the prefetch of the next
    group and of the next tile's first group, rows of 0, 1 and 7 live
    groups side by side: the kernel (absorbed, interpreted, lifted through
    W_UV) against ``latent_attention_xla`` in its EXPANDED form over the
    same pool, for a scan step, a rectangle chunk and a packed round."""
    mc = get_model_config(MODEL, dtype="float32")
    rng = np.random.default_rng(len(shape))
    nh, rkv, dr = mc.num_heads, mc.kv_lora_rank, mc.qk_rope_head_dim
    dn, dv, w = mc.qk_nope_head_dim, mc.v_head_dim, mla.pool_width(mc)
    rows = _GROUP_CASES[shape]
    b, m, s = len(rows), 14, max(n for _, n in rows)
    pool = jnp.asarray(rng.normal(size=(2, 1 + b * m, BLOCK, w)), jnp.float32)
    tables = _tables(b, m)
    pos = np.full((b, s), -1, np.int32)
    for r, (start, n) in enumerate(rows):
        pos[r, :n] = start + np.arange(n)
    lens = jnp.asarray([start + n for start, n in rows], jnp.int32)
    q_n = jnp.asarray(rng.normal(size=(b, s, nh, dn)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(b, s, nh, dr)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(size=(nh, rkv, dn)), jnp.float32) / 4
    w_uv = jnp.asarray(rng.normal(size=(nh, rkv, dv)), jnp.float32) / 4
    ctx = pool[1][tables].reshape(b, -1, w)
    want = mla.latent_attention_xla(
        mc, q_n, q_r, w_uk, w_uv, ctx, jnp.asarray(pos), lens, "expanded")
    q_cat = jnp.concatenate([
        jnp.einsum("bshd,hcd->bshc", q_n, w_uk), q_r,
        jnp.zeros((b, s, nh, w - rkv - dr), jnp.float32)], axis=-1)
    common = dict(scale=mc.head_dim ** -0.5, latent=rkv, interpret=True)
    if shape == "packed":
        at = [(r, c) for r in range(b) for c in range(s) if pos[r, c] >= 0]
        tp = 32
        row = np.full((tp,), b, np.int32)
        col, ppos = np.zeros((tp,), np.int32), np.full((tp,), -1, np.int32)
        for i, (r, c) in enumerate(at):
            row[i], col[i], ppos[i] = r, c, pos[r, c]
        q_packed = jnp.zeros((tp, nh, w), jnp.float32).at[:len(at)].set(
            q_cat[tuple(np.asarray(at).T)])
        tiles = mla_k.packed_tiles(jnp.asarray(row), jnp.asarray(col),
                                   jnp.asarray(ppos), b, s, nh)
        u_packed = mla_k.latent_paged_attention_packed(
            q_packed, tiles, pool, jnp.int32(1), tables, lens, BLOCK,
            **common)
        assert np.all(np.asarray(u_packed[len(at):]) == 0)
        u = jnp.zeros((b, s, nh, rkv), jnp.float32).at[
            tuple(np.asarray(at).T)].set(u_packed[:len(at)])
    else:
        u = mla_k.latent_paged_attention(
            q_cat, pool, jnp.int32(1), tables, jnp.asarray(pos), lens, BLOCK,
            decode=s == 1, **common)
    got = jnp.einsum("bshc,hcd->bshd", u, w_uv)
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


def test_forward_chunk_through_the_kernels_matches_the_xla_path(monkeypatch):
    """The kernel path of the layer (in-place page write, absorbed kernel,
    here interpreted) against the XLA path: a rectangle, a decode step, and
    a packed round whose tokens go to the kernel as query tiles (a piece
    beside a decode row and padding)."""
    mc = get_model_config(MODEL, held_experts=SHARE, dtype="float32")
    params = llama.init_params(mc, jax.random.PRNGKey(0), jnp.float32)
    monkeypatch.setattr(mla, "kernels_on",
                        lambda cfg, ctx, dtype, pallas=True: pallas)
    for name in ("write_latent_pages_in_place", "latent_paged_attention",
                 "latent_paged_attention_packed"):
        monkeypatch.setattr(mla_k, name, functools.partial(
            getattr(mla_k, name), interpret=True))
    tables = _tables(2, 8)
    prompts = [_prompt(11), _prompt(6)]
    # the packed round: row 1's 5-token piece at positions 7..11 (its 6
    # prompt tokens and one decoded are cached), row 0's decode token, pads
    tok_at = np.zeros((4, 9), np.int32)
    tok_at[1], tok_at[2] = -1, 2
    tok_at[:, 0] = (5, 12, 0, 0)
    tok_at[0, 1:6], tok_at[1, 1:6] = _prompt(5), np.arange(7, 12)
    tok_at[2, 1:6], tok_at[3, 1:6] = 1, np.arange(5)
    packing = llama.Packing(jnp.asarray(tok_at[2]), jnp.asarray(tok_at[3]),
                            jnp.asarray([0, 5]), 8)
    outs = []
    for pallas in (True, False):
        kv = llama.init_kv_pools(mc, 17, BLOCK, jnp.float32)
        tok = np.zeros((2, 12), np.int32)
        pos = np.full((2, 12), -1, np.int32)
        for r, p in enumerate(prompts):
            tok[r, :len(p)], pos[r, :len(p)] = p, np.arange(len(p))
        lens = jnp.asarray([11, 6])
        out = llama.forward_chunk(mc, params, jnp.asarray(tok),
                                  jnp.asarray(pos), kv, tables, lens,
                                  block_size=BLOCK, pallas=pallas)
        step = llama.forward_chunk(
            mc, params, jnp.asarray([[7], [9]]), jnp.asarray([[11], [6]]),
            out.kv, tables, lens + 1, block_size=BLOCK, pallas=pallas)
        packed = llama.forward_chunk(
            mc, params, jnp.asarray(tok_at[0]), jnp.asarray(tok_at[1]),
            step.kv, tables, jnp.asarray([13, 12]), block_size=BLOCK,
            pallas=pallas, packing=packing)
        outs.append((np.asarray(out.logits), np.asarray(step.logits),
                     np.asarray(packed.logits), np.asarray(packed.kv["ckv"])))
    for a, b in zip(*outs):
        assert np.abs(a - b).max() < 1e-4


def test_packed_tiles_hold_each_sequences_tokens_side_by_side():
    """41 tiles for Tp 264 on 8 sequences where the rectangle has 256; a
    token's slot gives it back; a tile is one sequence's."""
    tp, b, width, heads = 264, 8, 256, 128
    row = np.full((tp,), b, np.int32)
    col = np.zeros((tp,), np.int32)
    pos = np.full((tp,), -1, np.int32)
    row[:3], pos[:3] = [0, 2, 5], [700, 40, 1999]        # decode rows
    row[3:203], col[3:203], pos[3:203] = 6, np.arange(200), 512 + np.arange(200)
    tiles = mla_k.packed_tiles(jnp.asarray(row), jnp.asarray(col),
                               jnp.asarray(pos), b, width, heads)
    token, seq = np.asarray(tiles.token), np.asarray(tiles.seq)
    assert token.shape == (264 // 8 + 8, 8)
    for i in range(203):
        r, c = divmod(int(tiles.slot[i]), 8)
        assert token[r, c] == i and seq[r] == row[i]
        assert int(tiles.pos[r, c]) == pos[i]
    assert np.all(np.asarray(tiles.slot)[203:] == token.size)
    assert (token < tp).sum() == 203
    assert (np.asarray(tiles.pos) >= 0).sum() == 203
