"""Spec decoding as a first-class serving path (round 8): spec ragged
rounds (verify rows + prefill chunk rows in ONE dispatch) over int8 and
sliding-window pools, acceptance-adaptive draft depth, and the oracle
draft a ``*.spec`` benchmark cell will drive (ROADMAP R5).

Tier-1 keeps the cheap contracts (config validation, oracle dither,
depth selection, one tiny smoke and the overload stamp on its engines);
the compile-heavy byte-identity matrices ride the ``slow`` marker.
"""

import asyncio

import numpy as np
import pytest

from distributed_gpu_inference_tpu.runtime.engine import (
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.runtime.speculative import (
    SpecDecodeConfig,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)

pytestmark = pytest.mark.spec_serving

MODEL = "llama3-tiny"
PROMPTS = [list(range(10, 30)), list(range(40, 70)), list(range(5, 22))]


def _cfg(**kw):
    # f32 numerics: bit-exact greedy equality across decode paths needs
    # identical arithmetic (same stance as test_engine_spec_integrated)
    base = dict(max_batch_size=4, max_seq_len=128, block_size=32,
                prefill_buckets=(32,), multi_step=8, dtype="float32")
    base.update(kw)
    return EngineConfig(**base)


def _req(prompt, max_new=12, **kw):
    return InferenceRequest(
        prompt_token_ids=prompt,
        sampling=SamplingParams(max_new_tokens=max_new, **kw),
    )


def _serve_ragged(eng, reqs):
    """Drive requests to completion purely through ragged rounds (the
    admission path the batcher uses): chunk rows while prefilling, then
    verify/decode rows, all via ``ragged_round``."""
    adms = [eng.submit_chunked_start(r) for r in reqs]
    while True:
        eng.ragged_round([a for a in adms if not a.done])
        live = any(s is not None and s.finish_reason is None
                   for s in eng.slots)
        if not live and all(a.done for a in adms):
            break
    resps = {}
    for i, s in enumerate(list(eng.slots)):
        if s is not None:
            r = eng.finish_slot(i)
            resps[r.request_id] = r
    return [resps[a.request.request_id] for a in adms]


# ---------------------------------------------------------------- tier-1


def test_spec_config_rejects_kv_seq_sharded():
    """speculative + kv_seq_sharded must fail loudly, naming the fence —
    never silently fall back to split paths."""
    cfg = _cfg(kv_seq_sharded=True)
    with pytest.raises(ValueError, match="kv_seq_sharded"):
        SpecDecodeConfig(num_draft_tokens=4).validate(cfg)


def test_spec_config_oracle_and_adaptive_validation():
    cfg = _cfg()
    with pytest.raises(ValueError, match="oracle_accept_rate"):
        SpecDecodeConfig(oracle_accept_rate=1.5).validate(cfg)
    with pytest.raises(ValueError, match="adaptive_ema"):
        SpecDecodeConfig(adaptive=True, adaptive_ema=1.0).validate(cfg)
    with pytest.raises(ValueError, match="adaptive"):
        SpecDecodeConfig(
            num_draft_tokens=4, adaptive=True, adaptive_k_choices=(2, 8)
        ).validate(cfg)
    with pytest.raises(ValueError, match="adaptive_min_k"):
        # would silently collapse k_choices() to (K,) — reject instead
        SpecDecodeConfig(
            num_draft_tokens=4, adaptive=True, adaptive_min_k=8
        ).validate(cfg)
    with pytest.raises(ValueError, match="end at"):
        # a custom set capped below K would waste K - max(choices)
        # drafted tokens every round (the chain always drafts K)
        SpecDecodeConfig(
            num_draft_tokens=4, adaptive=True, adaptive_k_choices=(1, 2)
        ).validate(cfg)
    # valid configs pass
    SpecDecodeConfig(num_draft_tokens=4, adaptive=True,
                     oracle_accept_rate=0.5).validate(cfg)


def test_spec_k_choices_static_set():
    assert SpecDecodeConfig(num_draft_tokens=4).k_choices() == (1, 2, 4)
    assert SpecDecodeConfig(num_draft_tokens=6).k_choices() == (1, 2, 4, 6)
    assert SpecDecodeConfig(
        num_draft_tokens=8, adaptive_min_k=2
    ).k_choices() == (2, 4, 8)
    assert SpecDecodeConfig(
        num_draft_tokens=4, adaptive_k_choices=(4, 1)
    ).k_choices() == (1, 4)


def test_batcher_accepts_ragged_true_on_spec_engine():
    """A spec-integrated engine is accepted (spec ragged rounds are its
    serving path, and the batcher has no other); an engine that says it
    has no ragged rounds is refused, naming the fence."""
    from distributed_gpu_inference_tpu.runtime.batcher import (
        BatcherConfig,
        ContinuousBatcher,
    )

    class _SpecCfg:
        speculative = SpecDecodeConfig()

    class _SpecEng:
        cfg = _SpecCfg()
        supports_ragged = True

    class _ShardedEng:
        cfg = _SpecCfg()
        supports_ragged = False

    ContinuousBatcher(_SpecEng(), BatcherConfig())
    with pytest.raises(ValueError, match="kv_seq_sharded"):
        ContinuousBatcher(_ShardedEng(), BatcherConfig())


def test_oracle_dither_deterministic():
    """Fractional forced rates dither through the per-slot accumulator:
    exact mean, deterministic schedule."""
    eng = TPUEngine(MODEL, _cfg(speculative=SpecDecodeConfig(
        num_draft_tokens=4, oracle_accept_rate=0.6)), seed=0)
    eng._spec_oracle_acc[:] = 0.0
    ks = np.full((4,), 4, np.int32)
    forced = eng._spec_forced([0], 10, ks)
    seq = [int(forced[r, 0]) for r in range(10)]
    assert abs(sum(seq) / len(seq) - 0.6 * 4) < 1e-9
    eng._spec_oracle_acc[:] = 0.0
    forced2 = eng._spec_forced([0], 10, ks)
    assert [int(forced2[r, 0]) for r in range(10)] == seq
    # inactive rows and rate=None → -1 (real acceptance)
    assert int(forced[0, 1]) == -1
    eng.set_spec_oracle(None)
    assert int(eng._spec_forced([0], 1, ks)[0, 0]) == -1


def test_adaptive_k_selection_tracks_ema():
    eng = TPUEngine(MODEL, _cfg(speculative=SpecDecodeConfig(
        num_draft_tokens=4, adaptive=True)), seed=0)
    eng._spec_k_ema[0] = 0.2
    eng._spec_k_ema[1] = 1.5
    eng._spec_k_ema[2] = 3.9
    eng._spec_k_ema[3] = 4.0
    ks = eng._select_spec_ks([0, 1, 2, 3])
    assert list(ks) == [1, 2, 4, 4]


@pytest.fixture(scope="module")
def smoke_engines():
    """A vanilla engine and a chain engine over the same weights."""
    e1 = TPUEngine(MODEL, _cfg(max_batch_size=2), seed=0)
    e2 = TPUEngine(
        MODEL,
        _cfg(max_batch_size=2,
             speculative=SpecDecodeConfig(num_draft_tokens=2)),
        params=e1.params, seed=0,
    )
    return e1, e2


def test_spec_ragged_smoke(smoke_engines):
    """Cheap tier-1 smoke of the tentpole: one spec engine serves a
    request through ragged rounds (chunk row → verify rows) and the
    greedy stream matches the vanilla engine."""
    e1, e2 = smoke_engines
    want = e1.generate([_req(PROMPTS[0], max_new=5)], use_multi_step=True)
    assert e2.supports_ragged
    got = _serve_ragged(e2, [_req(PROMPTS[0], max_new=5)])
    assert got[0].token_ids == want[0].token_ids
    assert e2.stats["spec_steps"] > 0 and e2.stats["ragged_rounds"] > 0


def test_the_overload_stamp_is_read_by_no_decoder(smoke_engines):
    """The plane's overload ladder stamps ``params["speculative"] = False``
    on a degraded job (``server/admission.py`` ``disable_spec``) and the
    worker copies the stamp onto the request. The chain does not read it:
    the stamped request is drafted for and served byte for byte as the
    unstamped one. ROADMAP D22 changes that knowingly or drops the rung."""
    _, e2 = smoke_engines
    outs, drafted = [], []
    for stamp in (False, True):
        req = _req(PROMPTS[1], max_new=5)
        if stamp:
            req.params["speculative"] = False
        before = e2.stats["spec_drafted"]
        outs.append(_serve_ragged(e2, [req])[0])
        drafted.append(e2.stats["spec_drafted"] - before)
    assert outs[0].token_ids == outs[1].token_ids
    assert outs[0].finish_reason == outs[1].finish_reason
    assert drafted[0] == drafted[1] > 0


# ------------------------------------------------------------------ slow


@pytest.mark.slow
@pytest.mark.parametrize("int8", [False, True])
def test_matrix_spec_x_ragged_x_int8(int8):
    """THE acceptance bar: greedy outputs byte-identical across the
    spec × ragged 4-combo, per KV dtype (8 combos over the parametrize).
    Both fences deleted, not relaxed."""
    kvd = "int8" if int8 else None
    base = TPUEngine(MODEL, _cfg(), seed=0)
    ref = TPUEngine(MODEL, _cfg(kv_cache_dtype=kvd), params=base.params,
                    seed=0)
    want = [r.token_ids for r in ref.generate(
        [_req(p) for p in PROMPTS], use_multi_step=True)]
    assert all(want)
    for spec in (False, True):
        cfg = _cfg(
            kv_cache_dtype=kvd,
            speculative=(SpecDecodeConfig(num_draft_tokens=4)
                         if spec else None),
        )
        for ragged in (False, True):
            e = TPUEngine(MODEL, cfg, params=base.params, seed=0)
            if ragged:
                got = [r.token_ids
                       for r in _serve_ragged(e, [_req(p) for p in PROMPTS])]
            else:
                got = [r.token_ids for r in e.generate(
                    [_req(p) for p in PROMPTS], use_multi_step=True)]
            assert got == want, (int8, spec, ragged)


@pytest.mark.slow
def test_spec_ragged_seeded_sampling_stable():
    """Seeded sampled slots ride spec ragged rounds at one token per
    round with the same key-fold positions as vanilla decode — streams
    must match token for token; greedy neighbors still speculate."""
    e1 = TPUEngine(MODEL, _cfg(), seed=2)
    e2 = TPUEngine(
        MODEL, _cfg(speculative=SpecDecodeConfig(num_draft_tokens=4)),
        params=e1.params, seed=2,
    )
    reqs = lambda: [  # noqa: E731
        _req(PROMPTS[0], temperature=0.8, top_k=40, top_p=0.9, seed=7),
        _req(PROMPTS[1]),
        _req(PROMPTS[2], temperature=0.5, seed=11),
    ]
    want = e1.generate(reqs(), use_multi_step=True)
    got = _serve_ragged(e2, reqs())
    for a, b in zip(want, got):
        assert a.token_ids == b.token_ids


@pytest.mark.slow
def test_adaptive_k_deterministic_schedule_and_identity():
    """Adaptive depth must not change WHAT is emitted (verification is
    the target's own argmax), and the same seed must produce the same K
    schedule run over run."""
    e1 = TPUEngine(MODEL, _cfg(), seed=0)
    want = e1.generate([_req(p) for p in PROMPTS], use_multi_step=True)
    traces = []
    for _ in range(2):
        ea = TPUEngine(MODEL, _cfg(speculative=SpecDecodeConfig(
            num_draft_tokens=4, adaptive=True)), params=e1.params, seed=0)
        ea.spec_k_trace = []
        got = ea.generate([_req(p) for p in PROMPTS], use_multi_step=True)
        for a, b in zip(want, got):
            assert a.token_ids == b.token_ids
        traces.append(ea.spec_k_trace)
    assert traces[0] == traces[1]
    ks_seen = {k for step in traces[0] for (_, k) in step}
    assert ks_seen, "no depths recorded"
    assert ks_seen <= set(SpecDecodeConfig(num_draft_tokens=4).k_choices())


@pytest.mark.slow
def test_adaptive_k_through_ragged_rounds():
    ea = TPUEngine(MODEL, _cfg(speculative=SpecDecodeConfig(
        num_draft_tokens=4, adaptive=True)), seed=0)
    ref = TPUEngine(MODEL, _cfg(), params=ea.params, seed=0)
    want = ref.generate([_req(p) for p in PROMPTS], use_multi_step=True)
    ea.spec_k_trace = []
    got = _serve_ragged(ea, [_req(p) for p in PROMPTS])
    for a, b in zip(want, got):
        assert a.token_ids == b.token_ids
    # a random-init draft accepts ~0, so the EMA must have shrunk depths
    ks_seen = {k for step in ea.spec_k_trace for (_, k) in step}
    assert 1 in ks_seen


@pytest.mark.slow
def test_oracle_forced_acceptance_tokens_per_step():
    """The oracle's forced rate shows up 1:1 in the engine's efficiency
    counters — the contract the --spec bench sweep stands on."""
    base = TPUEngine(MODEL, _cfg(), seed=0)
    for rate, exp in ((1.0, 5.0), (0.5, 3.0), (0.0, 1.0)):
        eo = TPUEngine(MODEL, _cfg(speculative=SpecDecodeConfig(
            num_draft_tokens=4, oracle_accept_rate=rate)),
            params=base.params, seed=0)
        eo.generate(
            [_req(p, max_new=20, ignore_eos=True) for p in PROMPTS],
            use_multi_step=True,
        )
        st = eo.get_stats()
        assert abs(st["spec_tokens_per_step"] - exp) < 0.75, (rate, st)
        assert abs(st["spec_accept_rate"] - rate) < 0.2, (rate, st)


@pytest.mark.slow
def test_ignore_eos_runs_to_budget():
    eng = TPUEngine(MODEL, _cfg(), seed=0, eos_token_id=None)
    free = eng.generate([_req(PROMPTS[0], max_new=16)],
                        use_multi_step=True)[0]
    stop_tok = free.token_ids[3]
    stopped = eng.generate(
        [_req(PROMPTS[0], max_new=16, stop_token_ids=(stop_tok,))],
        use_multi_step=True,
    )[0]
    assert stopped.finish_reason == "stop"
    ignored = eng.generate(
        [_req(PROMPTS[0], max_new=16, stop_token_ids=(stop_tok,),
              ignore_eos=True)],
        use_multi_step=True,
    )[0]
    assert ignored.finish_reason == "length"
    assert len(ignored.token_ids) == 16


@pytest.mark.slow
def test_spec_ragged_sliding_window():
    """Chain verify rows under a Mistral-class sliding window, served
    through ragged rounds: byte-identical to the vanilla SWA engine."""
    e1 = TPUEngine("mistral-tiny", _cfg(), seed=0)
    want = e1.generate([_req(p) for p in PROMPTS], use_multi_step=True)
    e2 = TPUEngine(
        "mistral-tiny",
        _cfg(speculative=SpecDecodeConfig(num_draft_tokens=4)),
        params=e1.params, seed=0,
    )
    got = _serve_ragged(e2, [_req(p) for p in PROMPTS])
    for a, b in zip(want, got):
        assert a.token_ids == b.token_ids


@pytest.mark.slow
def test_batcher_serves_spec_engine_ragged():
    """End to end: a ContinuousBatcher over a spec engine admits through
    ragged rounds and produces the vanilla engine's greedy streams."""
    from distributed_gpu_inference_tpu.runtime.batcher import (
        BatcherConfig,
        ContinuousBatcher,
    )

    e1 = TPUEngine(MODEL, _cfg(), seed=0)
    want = e1.generate([_req(p) for p in PROMPTS], use_multi_step=True)
    eb = TPUEngine(
        MODEL, _cfg(speculative=SpecDecodeConfig(num_draft_tokens=4)),
        params=e1.params, seed=0,
    )

    async def run():
        b = ContinuousBatcher(eb, BatcherConfig())
        b.start()
        rs = await asyncio.gather(*(b.submit(_req(p)) for p in PROMPTS))
        await b.stop()
        return rs, b.get_stats()

    rs, st = asyncio.run(run())
    for w, g in zip(want, rs):
        assert g.error is None
        assert g.token_ids == w.token_ids
    assert st["ragged_admissions"] == len(PROMPTS)
    assert st["ragged_rounds"] > 0
    assert st["spec_integrated"]["steps"] > 0
