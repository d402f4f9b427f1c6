"""The gated delta-rule layer's two forms and its two kernels
(``models/kda.py``, ``ops/kda_pallas.py``): the chunked form against the
recurrence across chunk, piece and sub-block boundaries, the segment layout
of a packed round, the convolution's tail, and both Pallas kernels in
interpret mode against the XLA forms (``tests/test_tpu_lowering.py``
compiles them for the chip at the published widths)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_gpu_inference_tpu.models import kda
from distributed_gpu_inference_tpu.ops import kda_pallas

R, H, D = 4, 8, 32


def _draws(rng, t, decay=1.0, h=H, d=D):
    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = l2(rng.normal(size=(t, h, d))) / np.sqrt(d)
    k = l2(rng.normal(size=(t, h, d)))
    v = rng.normal(size=(t, h, d))
    g = -np.abs(rng.normal(size=(t, h, d))) * decay
    beta = rng.uniform(size=(t, h))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _recurrence(args, pool, layer, row, fresh=False):
    """One row's tokens through ``step_xla``, one at a time."""
    rows = pool.shape[1]
    live = jnp.arange(rows) == row
    outs = []
    for t in range(args[0].shape[0]):
        lift = [jnp.zeros((rows, *x.shape[1:]), x.dtype).at[row].set(x[t])
                for x in args]
        o, pool = kda.step_xla(*lift, pool, layer, live,
                               live & (fresh and t == 0))
        outs.append(o[row])
    return jnp.stack(outs), pool


def _plan(segs, pad=3, rows=R):
    """``segs``: (row, tokens, first position) in packed order."""
    row = np.concatenate([np.full(n, r) for r, n, _ in segs]
                         + [np.full(pad, rows)])
    col = np.concatenate([np.arange(n) for _, n, _ in segs]
                         + [np.zeros(pad, int)])
    pos = np.concatenate([s + np.arange(n) for _, n, s in segs]
                         + [np.full(pad, -1)])
    as_i = functools.partial(jnp.asarray, dtype=jnp.int32)
    return kda.make_plan(as_i(row), as_i(col), as_i(pos), rows)


def test_the_plan_cuts_segments_into_chunks_in_order():
    plan = _plan([(0, 1, 17), (2, 150, 64), (3, 70, 0)])
    assert plan.count.tolist() == [1, 0, 150, 70]
    assert plan.first.tolist() == [0, 0, 1, 151]
    assert plan.fresh.tolist() == [False, False, False, True]
    # 1 + 3 + 2 chunks of the 4 + 224 // 64 the shape allows
    assert plan.chunk_row.tolist() == [0, 2, 2, 2, 3, 3, 4]
    assert plan.chunk_first.tolist() == [1, 1, 0, 0, 1, 0, 0]
    assert plan.chunk_last.tolist() == [1, 0, 0, 1, 0, 1, 0]
    assert plan.chunk_fresh.tolist() == [0, 0, 0, 0, 1, 0, 0]
    gather = np.asarray(plan.gather)
    assert gather[0, 0] == 0 and (gather[0, 1:] == 224).all()
    assert gather[1].tolist() == list(range(1, 65))
    assert gather[3, :22].tolist() == list(range(129, 151))
    assert (gather[3, 22:] == 224).all() and (gather[6] == 224).all()
    # every live token has its place, the pads none
    place = np.asarray(plan.place)
    assert (gather.reshape(-1)[place[:221]] == np.arange(221)).all()
    assert (place[221:] == 7 * 64).all()


@pytest.mark.parametrize("segs,solved", [
    ([(0, 1, 17), (2, 150, 64), (3, 70, 0)], "every"),
    ([(0, 1, 17), (2, 150, 64), (3, 1, 0)], "picked"),
], ids=["pieces", "a-piece-beside-decode-rows"])
@pytest.mark.parametrize("decay", [1.0, 60.0], ids=["mild", "strong"])
def test_the_chunked_form_gives_the_recurrences_numbers(decay, segs, solved):
    """Segments in one packed round (a decode row from a stored state, a
    second piece across two chunk boundaries, a fresh piece; or one piece
    beside decode rows, one of them fresh, whose one-token chunks take no
    solve), the sub-block boundaries inside every chunk; a decay of 60 a
    token a channel would overflow any factored ``exp(-G)``."""
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(2, R, H, D, D)), jnp.float32)
    live = sum(n for _, n, _ in segs)
    plan = _plan(segs, pad=224 - live)
    # 224 // 64 + 1 chunks are solved where no more hold several tokens
    several = int((np.asarray(plan.gather)[:, 1] < 224).sum())
    assert (several <= 4) == (solved == "picked")
    args = _draws(rng, 224, decay)
    ops = kda.chunk_prepare(*args, plan)
    whole = kda._solved_operands(*args, plan.gather)
    assert all(np.abs(np.asarray(a - b)).max() < 1e-6
               for a, b in zip(ops, whole))
    assert all(bool(jnp.isfinite(x).all()) for x in ops)
    oc, got = kda.chunk_pass_xla(ops, pool, 1, plan)
    o = jnp.take(jnp.moveaxis(oc, 1, 2).reshape(-1, H, D), plan.place,
                 axis=0, mode="fill", fill_value=0)
    want_pool, at = pool, 0
    for row, n, start in segs:
        piece = [x[at:at + n] for x in args]
        want, want_pool = _recurrence(piece, want_pool, 1, row, start == 0)
        assert np.abs(np.asarray(o[at:at + n] - want)).max() < 2e-5
        at += n
    assert np.abs(np.asarray(got - want_pool)).max() < 2e-5
    # the idle row and the other layer: to the bit
    assert np.array_equal(np.asarray(got[1, 1]), np.asarray(pool[1, 1]))
    assert np.array_equal(np.asarray(got[0]), np.asarray(pool[0]))
    assert not np.asarray(o[live:]).any()


def test_the_convolutions_tail_carries_a_piece_boundary():
    """A row's tokens in pieces of 1, 2, 5 and 40 (each shorter than, as
    long as or longer than the tail) give what the whole gives, and a fresh
    segment ignores what the row held."""
    rng = np.random.default_rng(1)
    p3, taps = 3 * H * D, 4
    x = jnp.asarray(rng.normal(size=(48, p3)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, p3)), jnp.float32)
    padded = jnp.concatenate([jnp.zeros((taps - 1, p3)), x])
    want = sum(padded[j:j + 48] * w[j] for j in range(taps))
    pool = jnp.asarray(rng.normal(size=(2, R, taps - 1, p3)), jnp.float32)
    got, at = [], 0
    for n in (1, 2, 5, 40):
        plan = _plan([(2, n, at)], pad=2)
        piece = jnp.concatenate([x[at:at + n], jnp.zeros((2, p3))])
        y, pool = kda.conv_segments(piece, w, pool, 1, plan)
        got.append(y[:n])
        at += n
    assert np.abs(np.asarray(jnp.concatenate(got) - want)).max() < 1e-5
    assert np.array_equal(np.asarray(pool[1, 2]), np.asarray(x[-3:]))
    # one token a row: the step form, a masked row's tail untouched
    live = jnp.asarray([False, False, True, False])
    step = jnp.zeros((R, p3)).at[2].set(x[0])
    before = pool
    y, pool = kda.conv_step(step, w, pool, 1, live, live)
    assert np.abs(np.asarray(y[2] - want[0])).max() < 1e-5
    assert np.array_equal(np.asarray(pool[1, 0]), np.asarray(before[1, 0]))
    assert np.array_equal(np.asarray(pool[0]), np.asarray(before[0]))


@pytest.mark.parametrize("live,fresh", [
    ([1, 1, 1, 1], [0, 0, 0, 0]), ([1, 0, 1, 1], [0, 0, 1, 0]),
    ([0, 0, 0, 1], [0, 0, 0, 1])], ids=["all", "masked-and-fresh", "one"])
def test_the_step_kernel_in_interpret_mode(live, fresh):
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.normal(size=(3, R, H, D, D)), jnp.float32)
    live, fresh = jnp.asarray(live, bool), jnp.asarray(fresh, bool)
    args = _draws(rng, R)
    want_o, want = kda.step_xla(*args, pool, 1, live, fresh)
    got_o, got = kda_pallas.kda_step(*args, pool, 1, live, fresh,
                                     interpret=True)
    rows = np.asarray(live)
    assert np.abs(np.asarray(got_o - want_o))[rows].max() < 1e-5
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    # a masked row and the other layers keep their state to the bit
    assert np.array_equal(np.asarray(got[1])[~rows], np.asarray(pool[1])[~rows])
    assert np.array_equal(np.asarray(got[(0, 2), :]),
                          np.asarray(pool[(0, 2), :]))


def test_the_chunk_kernel_in_interpret_mode():
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(3, R, H, D, D)), jnp.float32)
    plan = _plan([(0, 1, 17), (2, 150, 64), (3, 70, 0)])
    ops = kda.chunk_prepare(*_draws(rng, 224), plan)
    want_o, want = kda.chunk_pass_xla(ops, pool, 2, plan)
    got_o, got = kda_pallas.kda_chunk_pass(
        ops, pool, 2, plan.chunk_row, plan.chunk_first, plan.chunk_last,
        plan.chunk_fresh, interpret=True)
    assert np.abs(np.asarray(got_o - want_o)).max() < 1e-5
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.array_equal(np.asarray(got[2, 1]), np.asarray(pool[2, 1]))
    assert np.array_equal(np.asarray(got[:2]), np.asarray(pool[:2]))


def test_forward_chunk_through_the_kernels_matches_the_xla_path(monkeypatch):
    """The tiny hybrid model's pieces and steps with both KDA kernels on
    (interpreted) against the XLA forms: logits and both pools."""
    from distributed_gpu_inference_tpu.models import llama
    from distributed_gpu_inference_tpu.models.configs import get_model_config

    mc = get_model_config("kimi-linear-tiny")
    params = llama.init_params(mc, jax.random.PRNGKey(0), jnp.float32)
    tables = jnp.asarray(1 + np.arange(2 * 8).reshape(2, 8), jnp.int32)
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(4, 260, (2, 80)), jnp.int32)
    pos = jnp.asarray(np.stack([np.arange(80),
                                np.r_[np.arange(30), np.full(50, -1)]]),
                      jnp.int32)

    def run(kernels):
        if kernels:
            monkeypatch.setattr(kda, "kernels_on", lambda *a, **k: True)
            for name in ("kda_step", "kda_chunk_pass"):
                monkeypatch.setattr(kda_pallas, name, functools.partial(
                    getattr(kda_pallas, name), interpret=True))
        kv = llama.init_kv_pools(mc, 17, 16, jnp.float32, state_rows=2)
        out = llama.forward_chunk(mc, params, toks, pos, kv, tables,
                                  jnp.asarray([80, 30]), block_size=16)
        step = llama.forward_chunk(
            mc, params, toks[:, :1], jnp.asarray([[80], [-1]], jnp.int32),
            out.kv, tables, jnp.asarray([81, 0]), block_size=16)
        return out.logits, step.logits[0], step.kv

    want, got = run(False), run(True)
    assert np.abs(np.asarray(got[0] - want[0])).max() < 1e-4
    assert np.abs(np.asarray(got[1] - want[1])).max() < 1e-4
    for name in (kda.STATE, kda.CONV):
        assert np.abs(np.asarray(got[2][name] - want[2][name])).max() < 1e-4
