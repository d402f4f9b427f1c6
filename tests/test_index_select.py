"""The selection of learned sparse attention (``ops/index_select.py``) and
the attention kernels that take it: ``S_t`` equal to the benchmark
reference's on float32 inputs (a planted tie included), the kernels in
interpret mode against their XLA forms, and the served layer on the kernel
path (interpret mode) against the reference's full forward pass."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import reference_sparse_attn_moe as reference  # noqa: E402

from distributed_gpu_inference_tpu.ops import index_select as ix  # noqa: E402
from distributed_gpu_inference_tpu.ops import (  # noqa: E402
    paged_attention_pallas as pp,
)
from distributed_gpu_inference_tpu.ops.attention import (  # noqa: E402
    paged_attention_xla,
)

B, S, HI, DI, J, TOPK = 2, 12, 2, 16, 64, 8


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(0)
    qi = jnp.asarray(rng.normal(size=(B, S, HI, DI)), jnp.float32)
    wts = jnp.asarray(rng.normal(size=(B, S, HI)), jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(B, J, DI)), jnp.float32)
    pos = np.full((B, S), -1, np.int32)
    pos[0] = np.arange(30, 42)      # a piece against 30 cached tokens
    pos[1, 0] = 50                  # a decode row beside it
    return qi, wts, ctx, jnp.asarray(pos), jnp.asarray([42, 51])


def _reference_sets(scores, topk):
    """``S_t`` by a sort: the topk-th largest visible score and all at or
    above it."""
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, bool)
    for idx in np.ndindex(scores.shape[:-1]):
        row = scores[idx]
        seen = np.isfinite(row)
        if seen.any():
            kth = np.sort(row[seen])[::-1][min(topk, seen.sum()) - 1]
            out[idx] = seen & (row >= kth)
    return out


def test_scores_are_the_equations(case):
    qi, wts, ctx, pos, lens = case
    got = np.asarray(ix.index_scores_xla(qi, wts, ctx, pos, lens))
    b, s, j = 0, 5, 20
    want = sum(float(wts[b, s, h]) * max(float(qi[b, s, h] @ ctx[b, j]), 0.0)
               for h in range(HI))
    assert got[b, s, j] == pytest.approx(want, rel=1e-5)
    assert np.isneginf(got[0, 5, 36:]).all() and np.isfinite(got[0, 5, :36]).all()
    assert np.isneginf(got[1, 1:]).all()        # padding sees nothing


def test_the_selection_is_the_references_set_ties_kept(case):
    qi, wts, ctx, pos, lens = case
    scores = ix.index_scores_xla(qi, wts, ctx, pos, lens)
    keep = np.asarray(ix.keep_from_scores(scores, TOPK)) > 0
    assert np.array_equal(keep, _reference_sets(scores, TOPK))
    # relu leaves exact zeros, so ties at the 8th score are common: they
    # are all kept, and a row never keeps fewer than topk of what it sees
    counts = keep[0].sum(-1)
    assert (counts >= TOPK).all() and (counts > TOPK).any()
    # a planted tie at the 8th score
    planted = np.asarray(scores).copy()
    row = planted[0, 11]
    kth = np.sort(row[np.isfinite(row)])[::-1][TOPK - 1]
    below = np.flatnonzero(np.isfinite(row) & (row < kth))[:2]
    planted[0, 11, below] = kth
    kept = np.asarray(ix.keep_from_scores(jnp.asarray(planted), TOPK))[0, 11]
    assert kept[below].all() and kept.sum() >= TOPK + 2
    # -0.0 and 0.0 are one score
    zeros = jnp.asarray([[0.0, -0.0, 0.0, -0.0, -1.0, -jnp.inf]])
    assert np.asarray(ix.keep_from_scores(zeros + 0.0, 2))[0].tolist() \
        == [1, 1, 1, 1, 0, 0]


def test_a_row_with_at_most_topk_tokens_keeps_them_all():
    scores = jnp.asarray([[3.0, -1.0, 2.0, -jnp.inf, -jnp.inf, -jnp.inf]])
    assert np.asarray(ix.keep_from_scores(scores, 4))[0].tolist() \
        == [1, 1, 1, 0, 0, 0]
    assert not np.asarray(
        ix.keep_from_scores(jnp.full((1, 6), -jnp.inf), 4)).any()


def test_the_kernels_are_their_xla_forms_in_interpret_mode(case):
    qi, wts, ctx, pos, lens = case
    want = ix.index_scores_xla(qi, wts, ctx, pos, lens)
    got = ix.index_scores_pallas(qi, wts, ctx, pos, lens, interpret=True)
    seen = np.isfinite(np.asarray(want))

    def close(a, b):
        """The same -inf, the rest within float32 rounding."""
        a, b = np.asarray(a), np.asarray(b)
        return np.array_equal(np.isfinite(a), np.isfinite(b)) and np.abs(
            np.where(np.isfinite(a), a, 0) - np.where(np.isfinite(b), b, 0)
        ).max() < 1e-5

    assert close(want, got)
    live = (np.asarray(pos) >= 0).reshape(-1)
    keep = ix.keep_from_scores_pallas(
        want.reshape(B * S, J), jnp.asarray(live), TOPK, interpret=True)
    assert np.array_equal(
        np.asarray(keep).reshape(B, S, J) > 0, _reference_sets(want, TOPK))
    # one-token rows: the step form (the heads as the rows of one matmul)
    one = ix.index_scores_pallas(qi[:, :1], wts[:, :1], ctx, pos[:, :1], lens,
                                 interpret=True)
    assert close(want[:, :1], one) and seen[:, :1].any()
    assert ix._col_tile(24576, 16384) == 12288 and ix._col_tile(24576, 1024) \
        == 1024 and ix._col_tile(64, 1024) == 128


def test_the_pool_round_trip_and_no_work_under_topk(case):
    qi, wts, ctx, pos, lens = case
    bs, m = 4, J // 4
    tables = jnp.asarray(np.stack([np.arange(1, m + 1),
                                   np.arange(m + 1, 2 * m + 1)]), jnp.int32)
    pool = jnp.zeros((3, 2 * m + 1, bs, ix.pool_lanes(DI)), jnp.float32)
    assert pool.shape[3] == 128
    tok = jnp.arange(J)
    for b in range(B):
        pool = ix.write_index_keys(
            pool, ctx[b], jnp.int32(1), tables[b, tok // bs], tok % bs)
    back = ix.gather_index_keys(pool, jnp.int32(1), tables, DI)
    assert np.array_equal(np.asarray(back), np.asarray(ctx))
    assert not np.asarray(pool[0]).any() and not np.asarray(pool[2]).any()
    want = ix.keep_from_scores(
        ix.index_scores_xla(qi, wts, ctx, pos, lens), TOPK)
    for kernels in (False, True):
        got = ix.select(qi, wts, pool, jnp.int32(1), tables, pos, lens, TOPK,
                        kernels=kernels, interpret=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # no row past topk: what each query sees, nothing gathered or scored
    short = jnp.minimum(lens, 8)
    pos8 = jnp.where(pos >= 0, jnp.minimum(pos, 7), -1)
    got = np.asarray(ix.select(qi, wts, pool * jnp.nan, jnp.int32(1), tables,
                               pos8, short, TOPK, kernels=False))
    col = np.arange(J)
    assert np.array_equal(got > 0, (col <= np.asarray(pos8)[..., None])
                          & (col < 8))


def test_the_attention_kernels_take_the_selection(case):
    qi, wts, ctx, pos, lens = case
    rng = np.random.default_rng(1)
    hkv, nh, d, bs = 2, 4, 16, 4
    m = J // bs
    kp = jnp.asarray(rng.normal(size=(2 * m + 1, hkv, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(2 * m + 1, hkv, bs, d)), jnp.float32)
    tables = jnp.asarray(np.stack([np.arange(1, m + 1),
                                   np.arange(m + 1, 2 * m + 1)]), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, S, nh, d)), jnp.float32)
    keep = ix.keep_from_scores(
        ix.index_scores_xla(qi, wts, ctx, pos, lens), TOPK)
    want = paged_attention_xla(q, kp, vp, tables, pos, lens, bs, keep=keep)
    dense = paged_attention_xla(q, kp, vp, tables, pos, lens, bs)
    assert np.abs(np.asarray(want) - np.asarray(dense)).max() > 0.1
    got = pp.ragged_paged_attention(q, kp, vp, tables, pos, lens, bs,
                                    interpret=True, keep=keep)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    # a softmax over S_t alone: row 0's query 5 by hand
    sel = np.flatnonzero(np.asarray(keep)[0, 5] > 0)
    k_ctx = np.asarray(kp)[np.asarray(tables)[0]].transpose(0, 2, 1, 3) \
        .reshape(J, hkv, d)
    v_ctx = np.asarray(vp)[np.asarray(tables)[0]].transpose(0, 2, 1, 3) \
        .reshape(J, hkv, d)
    logits = np.asarray(q)[0, 5, 3] @ k_ctx[sel, 1].T / 4.0
    p = np.exp(logits - logits.max())
    assert np.abs(p / p.sum() @ v_ctx[sel, 1]
                  - np.asarray(want)[0, 5, 3]).max() < 1e-5
    # one-token rows through the decode kernel
    pos1 = jnp.asarray([[41], [50]])
    keep1 = ix.keep_from_scores(ix.index_scores_xla(
        qi[:, :1], wts[:, :1], ctx, pos1, lens), TOPK)
    want1 = paged_attention_xla(q[:, :1], kp, vp, tables, pos1, lens, bs,
                                keep=keep1)
    got1 = pp.paged_attention_pallas(q[:, :1], kp, vp, tables, pos1, lens, bs,
                                     interpret=True, keep=keep1)
    assert np.abs(np.asarray(got1) - np.asarray(want1)).max() < 1e-5


def test_the_references_set_on_float32_inputs():
    """The program's scores and threshold on the reference's own float32
    projections give the reference's ``S_t``, query by query."""
    cfg = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "moe_intermediate_size": 32, "num_hidden_layers": 1,
           "vocab_size": 512, "num_experts": 8, "num_experts_per_tok": 2,
           "norm_topk_prob": True, "rope_theta": 10000.0,
           "rms_norm_eps": 1e-6,
           "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 16,
                         "topk": 8}}
    s = reference.dims(cfg)
    w = reference.SeedStream(cfg, 5).layer(0)
    n = 48
    x = jnp.asarray(np.random.default_rng(2).normal(size=(n, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        p = reference.project(s, w, x)
        _, want = reference.attend(s, p, jnp.int32(0), n)
        pos = jnp.arange(n)[None]
        scores = ix.index_scores_xla(p["qi"][None], p["wt"][None],
                                     p["ki"][None], pos, jnp.asarray([n]))
    got = np.asarray(ix.keep_from_scores(scores, 8))[0] > 0
    assert np.array_equal(got, np.asarray(want))
    assert (got.sum(-1)[8:] >= 8).all() and got.sum(-1)[3] == 4
