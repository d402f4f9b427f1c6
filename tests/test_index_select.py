"""The selection of learned sparse attention (``ops/index_select.py``) and
the attention kernels that take it: ``S_t`` equal to the benchmark
reference's on float32 inputs (a planted tie included), the kernels in
interpret mode against their XLA forms, the served layer on the kernel
path (interpret mode) against the reference's full forward pass, and the
decode kernel's walk under a selection: the pages that hold a selected
token and no other, a dense caller's program as it was."""

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


# --------------------------------------------------------------------------
# the decode kernel under a selection: of a row's table it fetches the pages
# that hold a token the query attends, and no other
# --------------------------------------------------------------------------

BK, HKV, NH, HD, PAGES, ROWS = 16, 2, 4, 16, 6, 3


def _rows(lens, chosen):
    """``keep [ROWS, 1, PAGES * BK]`` with 1 at each row's ``chosen``."""
    keep = np.zeros((ROWS, 1, PAGES * BK), np.float32)
    for r, at in enumerate(chosen):
        keep[r, 0, list(at)] = 1.0
    return np.asarray(lens, np.int32), keep


def _empty_pages():
    # row 0: pages 1, 3 and 4 hold nothing selected; row 1: pages 0 and 2
    # (of its four); row 2: the first and the last token it sees, pages 1
    # to 3 between them empty
    return _rows([90, 50, 70], [(3, 5, 40, 89), (17, 30, 49), (0, 69)])


def _ties_at_the_threshold():
    # eleven scores equal to the 8th largest: all are kept, across pages
    rng = np.random.default_rng(3)
    lens = np.asarray([90, 64, 33], np.int32)
    scores = rng.normal(size=(ROWS, 1, PAGES * BK)).astype(np.float32)
    col = np.arange(PAGES * BK)
    for r in range(ROWS):
        kth = np.sort(scores[r, 0, :lens[r]])[-TOPK]
        scores[r, 0, rng.choice(lens[r], 11, replace=False)] = kth
    scores = np.where(col < lens[:, None, None], scores, -np.inf)
    keep = np.asarray(ix.keep_from_scores(jnp.asarray(scores), TOPK))
    assert (keep.sum(-1) > TOPK).all()
    return lens, keep


def _short_long_inactive():
    # at most topk tokens: the row keeps all it sees; a row past topk; a
    # row that is not decoding (position -1: nothing kept, nothing fetched)
    lens, keep = _rows([7, 90, 0], [range(7), (1, 20, 21, 22, 50, 70, 88, 89),
                                    ()])
    return lens, keep


def _written_token_alone(selected):
    # the step's token lands at position 64, the first slot of page 4,
    # which holds nothing else the query attends
    def case():
        return _rows([65, 41, 9], [
            (2, 30, 60) + ((64,) if selected else ()),
            (0, 40), range(9)])
    return case


DECODE_CASES = {
    # name: (lens and keep, fused write, tokens of a page group or None for
    # the rule's own: the whole table here)
    "empty_pages": (_empty_pages, False, None),
    "ties_at_the_threshold": (_ties_at_the_threshold, False, None),
    "short_long_inactive": (_short_long_inactive, False, None),
    "written_token_alone_selected": (_written_token_alone(True), True, None),
    "written_token_alone_dropped": (_written_token_alone(False), True, None),
    # a table wider than one group, the last group partly past kv_lens
    "three_groups": (_empty_pages, False, 32),
    "two_groups": (_ties_at_the_threshold, True, 64),
}


def _poisoned(pool, tables, keep, fill):
    """``pool [L, N, ...]`` with ``fill`` in every page (of every layer) but
    layer 1's pages that a row's selection keeps a token of."""
    out = np.full_like(pool, fill)
    hit = keep[:, 0].reshape(ROWS, PAGES, BK).any(-1)
    for r in range(ROWS):
        out[1, tables[r, hit[r]]] = pool[1, tables[r, hit[r]]]
    return out


@pytest.mark.parametrize("name", DECODE_CASES)
def test_the_decode_kernel_fetches_only_the_pages_that_hold_a_selected_token(
        name, monkeypatch):
    build, fused, group = DECODE_CASES[name]
    if group is not None:
        monkeypatch.setattr(pp, "_SELECTED_GROUP_TOKENS", group)
    lens, keep = build()
    rng = np.random.default_rng(7)
    n = ROWS * PAGES + 1
    tables = (1 + rng.permutation(ROWS * PAGES)).reshape(ROWS, PAGES) \
        .astype(np.int32)
    kp = rng.normal(size=(2, n, HKV, BK, HD)).astype(np.float32)
    vp = rng.normal(size=(2, n, HKV, BK, HD)).astype(np.float32)
    q = rng.normal(size=(ROWS, 1, NH, HD)).astype(np.float32)
    new_k = rng.normal(size=(ROWS, 1, HKV, HD)).astype(np.float32)
    new_v = rng.normal(size=(ROWS, 1, HKV, HD)).astype(np.float32)
    pos = (lens - 1)[:, None].astype(np.int32)

    def written(pool, new):
        """The pool after the step's tokens are in their slots (layer 1)."""
        out = pool.copy()
        for r in np.flatnonzero(lens):
            page, slot = tables[r, pos[r, 0] // BK], pos[r, 0] % BK
            out[1, page, :, slot] = new[r, 0]
        return out

    def run(kp, vp):
        args = [jnp.asarray(x) for x in (tables, pos, lens)]
        if not fused:
            out = pp.paged_attention_pallas(
                jnp.asarray(q), jnp.asarray(kp[1]), jnp.asarray(vp[1]), *args,
                BK, interpret=True, keep=jnp.asarray(keep))
            return np.asarray(out), kp, vp
        out, k_out, v_out = pp.paged_decode_attention_fused(
            jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v),
            jnp.asarray(kp), jnp.asarray(vp), jnp.int32(1), *args, BK,
            interpret=True, keep=jnp.asarray(keep))
        return np.asarray(out), np.asarray(k_out), np.asarray(v_out)

    got, k_out, v_out = run(kp, vp)
    k_want, v_want = (written(kp, new_k), written(vp, new_v)) if fused \
        else (kp, vp)
    want = paged_attention_xla(
        jnp.asarray(q), jnp.asarray(k_want[1]), jnp.asarray(v_want[1]),
        jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(lens), BK,
        keep=jnp.asarray(keep))
    assert np.abs(got - np.asarray(want)).max() < 1e-5
    np.testing.assert_array_equal(k_out, k_want)
    np.testing.assert_array_equal(v_out, v_want)
    # no page the selection dropped is read: NaN, or any other value, in
    # every page without a selected token leaves the output as it was, bit
    # for bit, and the pool as it was but for the written token
    for fill in (np.nan, -3.0e4):
        k_bad = _poisoned(kp, tables, keep, fill)
        v_bad = _poisoned(vp, tables, keep, fill)
        again, k_out, v_out = run(k_bad, v_bad)
        np.testing.assert_array_equal(again, got)
        if fused:
            np.testing.assert_array_equal(k_out, written(k_bad, new_k))
            np.testing.assert_array_equal(v_out, written(v_bad, new_v))


def _primitives(jaxpr, out=None):
    """Every equation's primitive, sub-jaxprs (the kernel's body, its
    branches) walked in place."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
    return out



# (operands of the kernel's call, equations, digest of their primitives)
DENSE_DIGEST = (12, 417, "bb456541f8f1c49d")


def test_a_call_without_a_selection_keeps_its_group_width_and_its_jaxpr():
    """Everything the selection added to ``dgi_paged_decode`` sits behind
    ``keep``: a dense caller's group is the 512 tokens it was (less where
    the staging budget or the table is smaller), and its program is the one
    the dense walk has: the count and a digest of its primitives in order.
    Taken anew where the dense walk itself changes on purpose (last: the
    write's rows and the walk's page starts traced once and unrolled at
    lowering, 1,007 equations to 417 at these three rows)."""
    import hashlib

    assert pp._pages_per_group(16, 8, 128, 2, 2048) == 32
    assert pp._pages_per_group(16, 4, 128, 2, 1536, staging_pages=16) == 32
    assert pp._pages_per_group(32, 8, 128, 2, 1024) == 16
    assert pp._pages_per_group(16, 8, 128, 2, 20) == 20
    assert pp._pages_per_group(16, 16, 256, 2, 2048, staging_pages=32) == 8
    assert pp._pages_per_group(16, 1, 128, 1, 2048, staging_pages=16,
                               scale_page_bytes=4096) == 32
    # under a selection: 2,048 tokens, cut to whole 128-lane tiles of the
    # selection's block where the budget sets the width
    assert pp._pages_per_group(16, 8, 128, 2, 2048, selected=True) == 64
    assert pp._pages_per_group(16, 4, 128, 2, 1536, staging_pages=16,
                               selected=True) == 120
    assert pp._pages_per_group(16, 2, 16, 4, 6, selected=True) == 6

    b, m, hkv, nh, d, bk = 3, 40, 2, 4, 128, 16
    pool = jnp.zeros((2, b * m + 1, hkv, bk, d), jnp.bfloat16)
    q = jnp.zeros((b, 1, nh, d), jnp.bfloat16)
    new = jnp.zeros((b, 1, hkv, d), jnp.bfloat16)
    tables = jnp.zeros((b, m), jnp.int32)
    pos, lens = jnp.zeros((b, 1), jnp.int32), jnp.ones((b,), jnp.int32)

    def digest(**kw):
        jaxpr = jax.make_jaxpr(lambda: pp.paged_decode_attention_fused(
            q, new, new, pool, pool, jnp.int32(1), tables, pos, lens, bk,
            **kw))().jaxpr
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        names = _primitives(jaxpr)
        return (len(call.invars), len(names),
                hashlib.sha256("\n".join(names).encode()).hexdigest()[:16])

    assert digest() == DENSE_DIGEST
    keep = jnp.ones((b, 1, m * bk), jnp.float32)
    operands, count, _ = digest(keep=keep)
    assert operands == DENSE_DIGEST[0] + 2 and count != DENSE_DIGEST[1]



# --------------------------------------------------------------------- #
# a scan's keys: every layer's laid out once, appended to, read by layer
# --------------------------------------------------------------------- #

def _scan_pool(layers=3, bs=4, seed=5):
    """A pool of ``layers`` layers whose two rows' tables interleave, every
    position of both rows written with its own key."""
    rng = np.random.default_rng(seed)
    m = J // bs
    tables = jnp.asarray(rng.permutation(np.arange(1, 2 * m + 1))
                         .reshape(B, m), jnp.int32)
    pool = jnp.zeros((layers, 2 * m + 1, bs, ix.pool_lanes(DI)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(layers, B, J, DI)), jnp.float32)
    tok = jnp.arange(J)
    for l in range(layers):
        for b in range(B):
            pool = ix.write_index_keys(
                pool, keys[l, b], jnp.int32(l), tables[b, tok // bs],
                tok % bs)
    return pool, tables, keys


def _storage(pool, tables, fill=0.0):
    return jnp.full(ix.scan_keys_shape(pool.shape, *tables.shape, TOPK),
                    fill, pool.dtype)


def test_a_scans_keys_are_every_layers_gather_laid_out_once():
    pool, tables, keys = _scan_pool()
    jp = ix._step_tile(J)[1]
    assert jp == 128 and ix._step_tile(24576) == (12288, 24576)
    assert ix.scan_keys_shape(pool.shape, B, J // 4, TOPK) == (3, B, jp, 128)
    assert ix.scan_keys_shape((8, 12289, 16, 128), 8, 1536, 2048) \
        == (8, 8, 24576, 128)
    # whatever the storage held, what comes back is the gather
    got = ix.gather_scan_keys(pool, tables, jnp.int32(TOPK + 1), TOPK,
                              into=_storage(pool, tables, jnp.nan))
    assert got.shape == (3, B, jp, 128) and got.dtype == pool.dtype
    for l in range(3):
        assert np.array_equal(
            np.asarray(got[l, :, :J, :DI]),
            np.asarray(ix.gather_index_keys(pool, jnp.int32(l), tables, DI)))
    assert np.array_equal(np.asarray(got[:, :, :J, :DI]), np.asarray(keys))
    assert not np.asarray(got[:, :, J:]).any()
    assert not np.asarray(got[..., DI:]).any()
    # no row can pass topk inside the scan: nothing is gathered, the
    # storage comes back as it went in
    got = ix.gather_scan_keys(pool * jnp.nan, tables, jnp.int32(TOPK), TOPK,
                              into=_storage(pool, tables, 7.0))
    assert got.shape == (3, B, jp, 128) and (np.asarray(got) == 7.0).all()
    # a table that cannot hold more than topk: no array at all
    assert ix.scan_keys_shape(pool.shape, B, J // 4, J) is None


def test_an_append_lands_at_its_rows_position_and_a_finished_row_writes_nothing():
    pool, tables, _ = _scan_pool()
    carried = ix.gather_scan_keys(pool, tables, jnp.int32(J), TOPK,
                                  into=_storage(pool, tables))
    new = jnp.asarray(np.random.default_rng(1).normal(size=(B, DI)),
                      jnp.float32)
    got = np.asarray(ix.append_scan_keys(
        carried, new, jnp.int32(1), jnp.asarray([37, -1], jnp.int32)))
    want = np.asarray(carried).copy()
    want[1, 0, 37, :DI] = np.asarray(new[0])
    want[1, 0, 37, DI:] = 0
    assert np.array_equal(got, want)
    # the pool's own write of the same key, gathered again, is the append
    page, slot = tables[:, 37 // 4], jnp.asarray([37 % 4] * B)
    page = jnp.where(jnp.asarray([True, False]), page, pool.shape[1])
    pool2 = ix.write_index_keys(pool, new, jnp.int32(1), page, slot)
    again = ix.gather_scan_keys(pool2, tables, jnp.int32(J), TOPK,
                                into=_storage(pool, tables))
    assert np.array_equal(np.asarray(again), got)


@pytest.mark.parametrize("lens,pos", [
    ([42, 51], [41, 50]),       # both rows past topk
    ([42, 6], [41, 5]),         # a row under topk beside one past it
    ([42, 0], [41, -1]),        # a finished row: position -1, nothing seen
    ([64, 33], [63, 32]),       # the table's last position; a page's first
], ids=["past-topk", "one-under", "finished-row", "edges"])
def test_the_step_kernel_reads_its_layer_of_the_scans_keys_in_place(lens, pos):
    """``keep`` from the carried array, by layer index (the kernel in
    interpret mode and the XLA form), is bit for bit the ``keep`` of the
    gather a layer a step, in every layer."""
    pool, tables, _ = _scan_pool()
    rng = np.random.default_rng(7)
    qi = jnp.asarray(rng.normal(size=(B, 1, HI, DI)), jnp.float32)
    wts = jnp.asarray(rng.normal(size=(B, 1, HI)), jnp.float32)
    lens, pos = jnp.asarray(lens, jnp.int32), jnp.asarray(pos, jnp.int32)
    carried = ix.gather_scan_keys(pool, tables, jnp.max(lens), TOPK,
                                  into=_storage(pool, tables))
    for l in range(3):
        layer = jnp.int32(l)
        for kernels in (False, True):
            args = (qi, wts, pool, layer, tables, pos[:, None], lens, TOPK)
            want = ix.select(*args, kernels=kernels, interpret=True)
            got = ix.select(*args, kernels=kernels, interpret=True,
                            scan_keys=carried)
            assert got.shape == (B, 1, J)
            assert np.array_equal(np.asarray(got), np.asarray(want)), (
                l, kernels)
            assert np.asarray(got).sum() > 0
    # the carried array is what is scored: another key there, another keep
    moved = carried.at[2, 0, :40, :DI].multiply(-1.0)
    other = ix.select(qi, wts, pool, jnp.int32(2), tables, pos[:, None],
                      lens, TOPK, kernels=True, interpret=True,
                      scan_keys=moved)
    assert not np.array_equal(np.asarray(other), np.asarray(want))
