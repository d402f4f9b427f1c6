"""Keye-VL-2.0's language model on the deployed path: a learned indexer that
picks ``topk`` cached tokens a query inside paged GQA attention, an
index-key pool beside the K/V pages, a per-head QK-norm, every layer routed
— held to the benchmark's plain reference
(``benchmark/harness/reference_sparse_attn_moe.py``, which shares no code
with the program) on ``keye-vl-tiny`` (3 layers, 8 experts top-2, 2 indexer
heads of 16, ``topk`` 8: every context past 8 tokens selects).

Tolerances: float32 activations over the same int8 weights differ from the
reference by float32 rounding (measured 3e-6; 1e-3 asserted, where dense
attention instead of the selection is off by 1 and more)."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:      # as benchmark/tests/conftest.py does
    sys.path.insert(0, str(BENCH))

from harness import reference_sparse_attn_moe as reference  # noqa: E402

from distributed_gpu_inference_tpu.models import llama  # noqa: E402
from distributed_gpu_inference_tpu.models.configs import (  # noqa: E402
    get_model_config,
)
from distributed_gpu_inference_tpu.models.loader import (  # noqa: E402
    init_quantized_streamed,
)
from distributed_gpu_inference_tpu.runtime.engine import (  # noqa: E402
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (  # noqa: E402
    InferenceRequest,
    SamplingParams,
)

MODEL = "keye-vl-tiny"
TOL = 1e-3
MARGIN = 1e-2
BLOCK = 16


def published(mc):
    """The configuration as the benchmark's file states it."""
    return {
        "hidden_size": mc.hidden_size, "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim,
        "intermediate_size": mc.intermediate_size,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "num_hidden_layers": mc.num_layers, "vocab_size": mc.vocab_size,
        "num_experts": mc.num_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob, "rope_theta": mc.rope_theta,
        "rms_norm_eps": mc.rms_norm_eps,
        "sa_config": {"indexer_num_heads": mc.index_num_heads,
                      "indexer_head_dim": mc.index_head_dim,
                      "topk": mc.index_topk},
    }


def _f32(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        params)


@pytest.fixture(scope="module")
def tiny():
    mc = get_model_config(MODEL)
    params = _f32(init_quantized_streamed(mc, "int8", seed=3))
    return mc, params, reference.SeedStream(published(mc), 3)


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(4, 260, size=n)]


def _pools(mc, rows, pages=16):
    kv = llama.init_kv_pools(mc, 1 + rows * pages, BLOCK, jnp.float32)
    tables = jnp.asarray(
        1 + np.arange(rows * pages).reshape(rows, pages), jnp.int32)
    return kv, tables


# --------------------------------------------------------------------- #
# the configuration
# --------------------------------------------------------------------- #

def test_registry_and_the_cut():
    mc = get_model_config("keye-vl-2.0-30b-a3b-8l")
    assert (mc.num_layers, mc.num_heads, mc.num_kv_heads, mc.head_dim) \
        == (8, 32, 4, 128)
    assert (mc.num_experts, mc.num_experts_per_tok, mc.mlp_width) \
        == (128, 8, 768)
    assert (mc.index_topk, mc.index_num_heads, mc.index_head_dim) \
        == (2048, 16, 64)
    assert mc.qk_norm_per_head and not mc.qk_norm
    assert mc.max_position_embeddings == 24576
    # a layer: attention 18.87 M + indexer 2.26 M + router 0.26 M + 128
    # experts of 4.72 M; K, V and one index key a token a layer
    assert abs(mc.layer_param_bytes(1) - 625.4e6) < 0.1e6
    assert mc.index_params == 2048 * (1024 + 64 + 16) + 128
    assert mc.kv_bytes_per_token() == 8 * (2 * 4 * 128 + 64) * 2
    pools = jax.eval_shape(lambda: llama.init_kv_pools(mc, 4, 16))
    assert pools[llama.INDEX_KEYS].shape == (8, 4, 16, 128)


@pytest.mark.parametrize("fields,match", [
    (dict(kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=8,
          v_head_dim=16), "latent pages"),
    (dict(sliding_window=8), "sliding_window"),
    (dict(index_num_heads=0), "index_topk, index_num_heads"),
    (dict(qk_norm=True), "two conventions"),
])
def test_a_combination_that_is_not_built_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(get_model_config(MODEL), **fields)


def test_seed_stream_is_the_programs_init_bit_for_bit(tiny):
    mc, params, weights = tiny
    tree = reference.FromTree(params)
    for layer in range(mc.num_layers):
        a, b = weights.layer(layer), tree.layer(layer)
        assert set(a) == set(b)
        for name in a:
            assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), \
                (layer, name)
    assert np.array_equal(np.asarray(weights.head()), np.asarray(tree.head()))
    # vectors a dropped norm would show against
    assert 0.1 < np.asarray(params["layers"]["q_norm"]).std() < 0.4
    assert np.abs(np.asarray(params["layers"]["ki_bias"])).max() > 0.1
    # both inits hold the same leaves
    other = llama.init_params(mc, jax.random.PRNGKey(0))
    assert set(other["layers"]) == set(params["layers"])


# --------------------------------------------------------------------- #
# forward_chunk against the reference
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("n", [5, 24, 64])
def test_forward_chunk_matches_the_reference_in_float32(tiny, n):
    mc, params, weights = tiny
    prompt = _prompt(n)
    at = [list(range(n))]
    (want,), (routes,) = reference.forward(published(mc), weights, [prompt],
                                           at=at)
    kv, tables = _pools(mc, 1)
    out = llama.forward_chunk(
        mc, params, jnp.asarray([prompt]), jnp.arange(n)[None], kv, tables,
        jnp.asarray([n]), block_size=BLOCK, last_only=False,
        collect_routing=True)
    assert np.abs(np.asarray(out.logits[0]) - want).max() < TOL
    got = np.asarray(out.routing)
    assert got.shape == routes.shape == (3, n, 2)
    assert (np.sort(got, -1) == np.sort(routes, -1)).mean() > 0.999
    if n > mc.index_topk:
        # the selection is what is held: dense attention is far off
        (dense,), _ = reference.forward(published(mc), weights, [prompt],
                                        at=at, variant="dense")
        assert np.abs(dense - want).max() > 100 * TOL


def test_a_context_under_topk_is_the_model_without_its_indexer(tiny):
    mc, params, _ = tiny
    prompt = _prompt(mc.index_topk)
    bare = dataclasses.replace(mc, index_topk=0, index_num_heads=0,
                               index_head_dim=0)
    outs = []
    for cfg in (mc, bare):
        kv, tables = _pools(cfg, 1)
        outs.append(np.asarray(llama.forward_chunk(
            cfg, params, jnp.asarray([prompt]),
            jnp.arange(len(prompt))[None], kv, tables,
            jnp.asarray([len(prompt)]), block_size=BLOCK,
            last_only=False).logits))
    assert np.array_equal(outs[0], outs[1])


def test_pieces_then_decode_through_the_three_pools(tiny):
    """Three rows of unequal prompts in 24-token pieces (a piece against a
    cached prefix past ``topk``), then six decode steps: the logits of
    every read position against the reference's full forward passes."""
    mc, params, weights = tiny
    prompts = [_prompt(n, seed=n) for n in (50, 5, 64)]
    fed = [_prompt(6, seed=100 + r) for r in range(3)]
    want, _ = reference.forward(
        published(mc), weights, [p + f for p, f in zip(prompts, fed)],
        at=[list(range(len(p) - 1, len(p) + 6)) for p in prompts])
    rows = len(prompts)
    kv, tables = _pools(mc, rows)
    fwd = jax.jit(lambda t, p, kv, lens: llama.forward_chunk(
        mc, params, t, p, kv, tables, lens, block_size=BLOCK))
    got = [[] for _ in prompts]
    for start in range(0, 64, 24):
        toks = np.zeros((rows, 24), np.int32)
        pos = np.full((rows, 24), -1, np.int32)
        for r, p in enumerate(prompts):
            piece = p[start:start + 24]
            toks[r, :len(piece)] = piece
            pos[r, :len(piece)] = start + np.arange(len(piece))
        out = fwd(toks, pos, kv, (pos.max(1) + 1).clip(min=0))
        kv = out.kv
        for r, p in enumerate(prompts):
            n = len(p[start:start + 24])
            if n and start + n == len(p):
                got[r].append(np.asarray(out.logits[r, 0]))
    for step in range(6):
        toks = np.asarray([[fed[r][step]] for r in range(rows)], np.int32)
        pos = np.asarray([[len(p) + step] for p in prompts], np.int32)
        out = fwd(toks, pos, kv, pos[:, 0] + 1)
        kv = out.kv
        for r in range(rows):
            got[r].append(np.asarray(out.logits[r, 0]))
    for g, w in zip(got, want):
        assert np.abs(np.stack(g) - w).max() < TOL


def test_a_packed_round_of_a_piece_beside_decode_rows(tiny):
    """One packed round holds a decoding row (one token against 39 cached),
    a second piece against its row's cached prefix and a fresh piece: each
    row's logits are the reference's."""
    mc, params, weights = tiny
    prompts = [_prompt(40), _prompt(60, seed=1), _prompt(20, seed=2)]
    (want_a, want_b, want_c), _ = reference.forward(
        published(mc), weights, prompts)
    kv, tables = _pools(mc, 4)

    def rect(tokens, kv):
        width = max(map(len, tokens))
        toks = np.zeros((4, width), np.int32)
        pos = np.full((4, width), -1, np.int32)
        for r, t in enumerate(tokens):
            toks[r, :len(t)] = t
            pos[r, :len(t)] = np.arange(len(t))
        return llama.forward_chunk(
            mc, params, jnp.asarray(toks), jnp.asarray(pos), kv, tables,
            jnp.asarray((pos.max(1) + 1).clip(min=0)), block_size=BLOCK)

    kv = rect([prompts[0][:39], prompts[1][:30], [], []], kv).kv
    segs = [(0, prompts[0][39:], 39), (1, prompts[1][30:], 30),
            (3, prompts[2], 0)]
    tok = np.concatenate([t for _, t, _ in segs] + [np.zeros(3, int)])
    pos = np.concatenate([s + np.arange(len(t)) for _, t, s in segs]
                         + [np.full(3, -1)])
    row = np.concatenate([np.full(len(t), r) for r, t, _ in segs]
                         + [np.full(3, 4)])
    col = np.concatenate([np.arange(len(t)) for _, t, _ in segs]
                         + [np.zeros(3, int)])
    ends = np.cumsum([len(t) for _, t, _ in segs]) - 1
    last = np.zeros(4, np.int32)
    lens = np.zeros(4, np.int32)
    for (r, t, s), e in zip(segs, ends):
        last[r], lens[r] = e, s + len(t)
    out = llama.forward_chunk(
        mc, params, jnp.asarray(tok, jnp.int32), jnp.asarray(pos, jnp.int32),
        kv, tables, jnp.asarray(lens), block_size=BLOCK,
        packing=llama.Packing(jnp.asarray(row, jnp.int32),
                              jnp.asarray(col, jnp.int32),
                              jnp.asarray(last), 32))
    for r, want in ((0, want_a), (1, want_b), (3, want_c)):
        assert np.abs(np.asarray(out.logits[r, 0]) - want[0]).max() < TOL
    # the idle row wrote no index key
    assert not np.asarray(out.kv[llama.INDEX_KEYS][:, tables[2]]).any()


@pytest.mark.parametrize("broken", ["dense", "topk_half", "no_qk_norm",
                                    "no_index_rope"])
def test_a_block_that_departs_from_the_description_fails(tiny, broken):
    """Each of the comparison's controls moves the logits of a 64-token
    prompt far past the tolerance the served model is held to."""
    mc, _, weights = tiny
    prompt = _prompt(64)
    (want,), _ = reference.forward(published(mc), weights, [prompt])
    (got,), _ = reference.forward(published(mc), weights, [prompt],
                                  variant=broken)
    assert np.abs(got - want).max() > 20 * TOL


# --------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------- #

def _engine(**kw):
    base = dict(max_batch_size=4, max_seq_len=256, block_size=BLOCK,
                prefill_buckets=(16, 32, 64), ragged_chunk=32,
                dtype="float32", quantization="int8")
    base.update(kw)
    return TPUEngine(get_model_config(MODEL), EngineConfig(**base), seed=0)


def _req(prompt, new, **kw):
    return InferenceRequest(prompt_token_ids=list(prompt), sampling=SamplingParams(
        max_new_tokens=new, temperature=0.0, ignore_eos=True, **kw))


def _admit(eng, prompts, new):
    """Every prompt through ``ragged_round`` to its first token."""
    flying = [eng.submit_chunked_start(_req(p, n))
              for p, n in zip(prompts, new)]
    slots = [a.slot for a in flying]
    first = {a.slot: [] for a in flying}
    while flying:
        for slot, toks in eng.ragged_round(flying).items():
            first[slot] += toks
        flying = [a for a in flying if not a.done]
    return slots, first


@pytest.fixture(scope="module")
def chain():
    """A reference run: one 40-token prompt alone, 24 new tokens."""
    eng = _engine()
    resp = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    return resp.token_ids


def test_engine_rounds_follow_the_reference_and_count():
    eng = _engine()
    mc = eng.model_cfg
    assert eng.stats["kv_layout"] == "kv+index"
    assert eng.stats["index_pool_bytes"] == \
        mc.num_layers * eng.num_blocks * BLOCK * 128 * 4
    cfg = published(mc)
    weights = reference.FromTree(eng.params)
    prompts, new = [_prompt(40), _prompt(5)], 5
    slots, first = _admit(eng, prompts, [new, new])
    scan = eng.decode_multi(new - 1)
    for prompt, slot in zip(prompts, slots):
        seq = list(prompt)
        for step, tok in enumerate(first[slot] + scan[slot]):
            (want,), _ = reference.forward(cfg, weights, [seq])
            top2 = np.sort(want[0])[-2:]
            if top2[1] - top2[0] > MARGIN:
                assert tok == int(want[0].argmax()), (len(prompt), step)
            seq.append(tok)
    st = eng.get_stats()
    # the rounds: 32 of the 40 tokens beside the 5-token prompt, then the
    # last 8 (against 32 cached) beside the short prompt's first decode
    # token (against 5)
    pairs = (32 * 33 // 2 + 5 * 6 // 2) + (8 * 32 + 8 * 9 // 2 + 6)
    kept = (8 * 9 // 2 + 24 * 8 + 5 * 6 // 2) + (8 * 8 + 6)
    assert st["index_pairs_ragged"] == pairs
    assert st["index_selected_pairs_ragged"] == kept
    # the scans: the long row's steps all select 8 of 41.., the short row's
    # see 7, 8 (dense) and then 9 tokens (one is dropped)
    steps = [len(scan[s]) for s in slots]
    assert st["index_row_steps_scan"] == sum(steps) == 7
    assert st["index_dense_rows_scan"] == 2
    assert st["index_selected_tokens_scan"] == 4 * 8 + 7 + 8 + 8
    assert st["index_context_tokens_scan"] == (41 + 42 + 43 + 44) + (7 + 8 + 9)


def test_a_scan_counts_the_pages_its_selections_keep():
    """``index_fetched_tokens_scan``: what the decode kernel fetches for the
    scans' selections, the pages that hold a selected token, whole, mean
    over the layers: counted on the device from the served ``keep``, here
    from the reference's ``S_t`` of the same queries."""
    eng = _engine()
    mc = eng.model_cfg
    cfg = published(mc)
    prompts = [_prompt(150), _prompt(5)]
    slots, first = _admit(eng, prompts, [6, 16])
    scan = eng.decode_multi(4)
    # step t of a row's scan: the query at the row's last cached position
    queries = [(len(p) + len(first[s]) - 1, len(scan[s]))
               for p, s in zip(prompts, slots)]
    seqs = [(list(p) + first[s] + scan[s])[:-1]
            for p, s in zip(prompts, slots)]
    dims, pages = reference.dims(cfg), []

    def tap(l, n, w, x):
        lo, steps = queries[n]
        _, keep = reference.attend(
            dims, reference.project(dims, w, x), jnp.int32(lo), steps)
        keep = np.asarray(keep)
        keep = np.pad(keep, ((0, 0), (0, -keep.shape[1] % BLOCK)))
        pages.append(int(keep.reshape(steps, -1, BLOCK).any(-1).sum()))

    reference.forward(cfg, reference.FromTree(eng.params), seqs, tap=tap)
    st = eng.get_stats()
    assert st["index_row_steps_scan"] == sum(n for _, n in queries) == 8
    assert st["index_fetched_tokens_scan"] \
        == BLOCK * sum(pages) // mc.num_layers
    rounded = sum(-(-(lo + 1 + t) // BLOCK) * BLOCK
                  for lo, n in queries for t in range(n))
    assert st["index_selected_tokens_scan"] \
        <= st["index_fetched_tokens_scan"] <= rounded
    assert rounded - st["index_context_tokens_scan"] < 8 * BLOCK
    # the long row's 8 tokens of ~150 leave pages out
    assert st["index_fetched_tokens_scan"] < rounded - 8 * BLOCK
    # a routed model without an indexer has no such counter
    assert "index_fetched_tokens_scan" not in TPUEngine(
        get_model_config("mixtral-tiny"),
        EngineConfig(max_batch_size=2, max_seq_len=64, block_size=BLOCK),
    ).stats


def test_a_prefix_hit_and_a_page_copy_bring_the_index_keys(chain):
    """The second request's prompt is a hit on the first one's pages: its
    index keys come with them (the model keeps its prefix cache), and a
    request that forks inside a shared page copies the page with its index
    keys."""
    eng = _engine()
    cold = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    warm = eng.generate([_req(_prompt(40), 24)], use_multi_step=True)[0]
    assert cold.token_ids == warm.token_ids == chain
    assert warm.cached_tokens == 32
    assert eng.manager.stats.prefix_hit_tokens == 32
    # a longer prompt over the same 40 tokens and a fresh engine agree
    longer = _prompt(40) + _prompt(30, seed=9)
    got = eng.generate([_req(longer, 8)], use_multi_step=True)[0]
    want = _engine().generate([_req(longer, 8)], use_multi_step=True)[0]
    assert got.cached_tokens >= 32 and got.token_ids == want.token_ids


def test_a_page_copy_copies_the_index_keys():
    eng = _engine()
    (slot,), _ = _admit(eng, [_prompt(40)], [4])
    src = eng.manager.seq_blocks[eng.slots[slot].seq_id][0]
    dst = eng.num_blocks - 1
    before = np.asarray(eng.kv[llama.INDEX_KEYS][:, src])
    assert np.abs(before).max() > 0
    eng.kv = eng._apply_ops_fn(eng.kv, jnp.asarray([src], jnp.int32),
                               jnp.asarray([dst], jnp.int32))
    for name in ("k", "v", llama.INDEX_KEYS):
        assert np.array_equal(np.asarray(eng.kv[name][:, dst]),
                              np.asarray(eng.kv[name][:, src])), name


def test_preempt_and_resume_continue_token_for_token(chain):
    eng = _engine()
    (slot,), first = _admit(eng, [_prompt(40)], [24])
    got = first[slot] + eng.decode_multi(7)[slot]
    pre = eng.preempt_slot(slot)
    assert eng.slots[slot] is None
    slot = eng.resume(pre)
    # the pages the prefix index still holds are a hit, index keys and all
    assert eng.manager.stats.prefix_hit_tokens >= 32
    while eng.slots[slot].finish_reason is None:
        eng.decode_multi(8)
    assert eng.finish_slot(slot).token_ids == chain
    assert got == chain[:8]


def test_what_cannot_carry_the_index_keys_refuses_the_model():
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
    with pytest.raises(ValueError, match="drafted chain"):
        TPUEngine(mc, EngineConfig(
            **base, speculative=SpecDecodeConfig(num_draft_tokens=2)))
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        TPUEngine(mc, EngineConfig(**base, kv_cache_dtype="int8"))
    eng = TPUEngine(mc, EngineConfig(**base))
    with pytest.raises(ValueError, match="index keys"):
        kv_handoff.HandoffReceiver(eng)
    with pytest.raises(ValueError, match="index keys"):
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


def test_the_index_counters_reach_the_metrics_endpoint():
    from distributed_gpu_inference_tpu.server.observability import (
        MetricsCollector,
    )

    mc = MetricsCollector()
    mc.record_batcher_engine("w1", {
        "kv_layout": "kv+index", "index_pool_bytes": 603979776,
        "index_row_steps_scan": 640, "index_context_tokens_scan": 12800000,
        "index_selected_tokens_scan": 1310720, "index_dense_rows_scan": 3,
        "index_fetched_tokens_scan": 7475200, "index_key_gathers_scan": 1280,
        "index_pairs_ragged": 5000000, "index_selected_pairs_ragged": 524288})
    text = mc.metrics.render().decode()
    if "worker_kv_layout" not in text:
        pytest.skip("prometheus_client is absent: the metrics are no-ops")
    assert 'worker_kv_layout{layout="kv+index",worker="w1"} 1.0' in text
    assert 'worker_index_pool_bytes{worker="w1"} 6.03979776e+08' in text
    assert 'worker_index_row_steps_scan_total{worker="w1"} 640.0' in text
    assert 'worker_index_selected_tokens_scan_total{worker="w1"} ' \
        '1.31072e+06' in text
    assert 'worker_index_dense_rows_scan_total{worker="w1"} 3.0' in text
    assert 'worker_index_fetched_tokens_scan_total{worker="w1"} ' \
        '7.4752e+06' in text
    assert 'worker_index_selected_pairs_ragged_total{worker="w1"} ' \
        '524288.0' in text
    assert 'worker_index_key_gathers_scan_total{worker="w1"} 1280.0' in text


# --------------------------------------------------------------------- #
# a scan of several steps lays its rows' index keys out once
# --------------------------------------------------------------------- #

DOC = _prompt(40, seed=11)
# (what the row is for, its prompt, its new tokens: the first comes from its
# last piece, the rest from the scan)
SCAN_ROWS = [
    ("shares the document's pages", DOC + _prompt(5, seed=12), 6),
    ("crosses a page boundary at 32", _prompt(29, seed=13), 6),
    ("crosses topk at 8", _prompt(5, seed=14), 6),
    ("shares them too, finishes at step 2", DOC + _prompt(7, seed=15), 3),
]


def _tapped_scan(monkeypatch, calls, carried=True):
    """A fresh engine whose every selection hands its layer, its rows'
    positions, its ``keep`` and the scan's keys it read to the host; the
    document cached, SCAN_ROWS admitted to their first token, then
    ``calls`` as (steps, times). ``carried=False``: the scan as it was
    before it carried its keys (a gather a layer a step)."""
    from distributed_gpu_inference_tpu.ops import index_select

    seen, orig = [], index_select.select

    def select(qi, wts, pool, layer_idx, tables, positions, kv_lens, topk,
               kernels, interpret=False, scan_keys=None):
        keep = orig(qi, wts, pool, layer_idx, tables, positions, kv_lens,
                    topk, kernels, interpret, scan_keys)
        if positions.shape[1] == 1:     # a scan's step
            jax.debug.callback(
                lambda *a: seen.append([np.asarray(x) for x in a]),
                layer_idx, positions[:, 0], keep,
                jnp.zeros(()) if scan_keys is None else scan_keys)
        return keep

    monkeypatch.setattr(index_select, "select", select)
    eng = _engine()
    assert eng._scan_keys.shape == (eng.model_cfg.num_layers, 4, 256, 128)
    if not carried:
        eng._scan_keys = None       # no storage: no scan lays its keys out
    eng.generate([_req(DOC, 2)], use_multi_step=True)
    seen.clear()
    slots, first = _admit(eng, [p for _, p, _ in SCAN_ROWS],
                          [n for _, _, n in SCAN_ROWS])
    assert eng.manager.stats.prefix_hit_tokens == 2 * 32
    toks = {s: list(first[s]) for s in slots}
    gathers = eng.stats["index_key_gathers_scan"]
    for steps, times in calls:
        for _ in range(times):
            for slot, more in eng.decode_multi(steps).items():
                toks[slot] += more
    jax.effects_barrier()
    keeps = {}
    for layer, positions, keep, _ in seen:
        for row, p in enumerate(positions):
            if p >= 0:
                keeps[int(layer), row, int(p)] = keep[row, 0]
    return (eng, [toks[s] for s in slots], keeps,
            eng.stats["index_key_gathers_scan"] - gathers, seen)


def test_a_scan_that_carries_its_keys_selects_what_a_gather_a_step_selects(
        monkeypatch):
    """One T=4 scan against four single steps (today's path: a gather a
    layer) and against the T=4 scan as it was (a gather a layer a step):
    the same tokens and, layer by layer and row by row, the same ``keep``
    bit for bit, over rows that cross ``topk``, cross a page boundary,
    finish at step 2 and share their document's pages."""
    layers = get_model_config(MODEL).num_layers
    eng, toks, keeps, gathers, seen = _tapped_scan(monkeypatch, [(4, 1)])
    assert [len(t) for t in toks] == [5, 5, 5, 3]
    assert gathers == layers            # every layer's, once
    # the rows' contexts over the scan: 46-49, 30-33, 6-9, 48-49
    starts, steps = [45, 29, 5, 47], [4, 4, 4, 2]
    assert sorted(keeps) == sorted(
        (l, row, starts[row] + t) for l in range(layers)
        for row in range(4) for t in range(steps[row]))
    assert keeps[0, 2, 7].sum() == 8 and keeps[0, 2, 8].sum() == 8 \
        and keeps[0, 2, 6].sum() == 7
    one, toks1, keeps1, gathers1, _ = _tapped_scan(monkeypatch, [(1, 4)])
    # a single step gathers a layer, where a row is past topk: all four do
    assert gathers1 == 4 * layers
    _, toks0, keeps0, _, seen0 = _tapped_scan(monkeypatch, [(4, 1)],
                                              carried=False)
    assert all(entry[3].ndim == 0 for entry in seen0)
    assert toks == toks1 == toks0
    for other in (keeps1, keeps0):
        assert sorted(other) == sorted(keeps)
        for at, keep in keeps.items():
            assert np.array_equal(keep, other[at]), at
    for name in ("index_row_steps_scan", "index_context_tokens_scan",
                 "index_selected_tokens_scan", "index_dense_rows_scan"):
        assert eng.stats[name] == one.stats[name], name
    # after the scan: every layer's carried keys are the gather of the pool
    # the scan returned (the last layer's call of the last step read them
    # with every append made; the engine holds them as storage for the next
    # scan and not among its pools)
    from distributed_gpu_inference_tpu.ops import index_select

    assert llama.INDEX_SCAN_KEYS not in eng.kv
    assert llama.INDEX_SCAN_KEYS not in one.kv
    last = max((e for e in seen if e[0] == layers - 1),
               key=lambda e: e[1][0])      # row 0 runs all four steps
    carried = last[3]
    assert np.array_equal(carried, np.asarray(eng._scan_keys))
    tables = jnp.asarray(eng._block_tables)
    j = tables.shape[1] * BLOCK
    assert carried.shape[:2] == (layers, 4) and carried.shape[2] >= j
    for l in range(layers):
        want = index_select.gather_index_keys(
            eng.kv[llama.INDEX_KEYS], jnp.int32(l), tables,
            eng.model_cfg.index_head_dim)
        assert np.array_equal(
            carried[l, :, :j, :eng.model_cfg.index_head_dim],
            np.asarray(want)), l


def test_a_scan_whose_rows_stay_under_topk_gathers_nothing(monkeypatch):
    from distributed_gpu_inference_tpu.ops import index_select

    seen, orig = [], index_select.select

    def select(*a, **kw):
        if a[5].shape[1] == 1:
            jax.debug.callback(
                lambda p, k: seen.append((np.asarray(p), np.asarray(k))),
                a[5][:, 0], kw["scan_keys"])
        return orig(*a, **kw)

    monkeypatch.setattr(index_select, "select", select)
    eng = _engine()
    prompts = [_prompt(2, seed=21), _prompt(3, seed=22)]
    slots, first = _admit(eng, prompts, [9, 9])
    scan = eng.decode_multi(4)      # contexts 3-6 and 4-7: at most topk 8
    jax.effects_barrier()
    assert [len(scan[s]) for s in slots] == [4, 4]
    assert eng.stats["index_key_gathers_scan"] == 0
    assert eng.stats["index_dense_rows_scan"] == 8
    # what the steps were handed holds their own appends and nothing else:
    # no page was gathered
    assert seen
    for positions, carried in seen:
        held = np.abs(carried).sum(axis=(0, 3)) > 0      # [B, Jp]
        for row, p in enumerate(positions[:2]):
            assert not held[row, :len(prompts[row])].any()
            assert not held[row, p + 1:].any()
    held = np.abs(np.asarray(eng._scan_keys)).sum(axis=(0, 3)) > 0
    assert held.sum() == 8          # two rows' four appends
    # the next scan crosses topk in its third step and gathers once; the
    # tokens are those of single steps throughout
    more = eng.decode_multi(4)
    assert eng.stats["index_key_gathers_scan"] == eng.model_cfg.num_layers
    ref = _engine()
    slots_r, first_r = _admit(ref, prompts, [9, 9])
    steps = {s: [] for s in slots_r}
    for _ in range(8):
        for s, t in ref.decode_multi(1).items():
            steps[s] += t
    for s, r in zip(slots, slots_r):
        assert first[s] + scan[s] + more[s] == first_r[r] + steps[r]
    # single steps gather where a row is past topk: contexts 8 and 9 on
    assert ref.stats["index_key_gathers_scan"] \
        == 3 * ref.model_cfg.num_layers
