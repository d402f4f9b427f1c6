"""The attention kernels a shard of heads a chip (PR 58): under a mesh whose
only sharded axis is ``model``, ``forward_chunk``'s three attention calls --
the scan step's fused write + attention (``dgi_paged_decode``), a round's
page write (``dgi_paged_write``) and its ragged attention
(``dgi_ragged_attention``) -- run inside ``jax.shard_map`` over ``model`` on
the stacked pools in place. Held here on the CPU's virtual devices, the
kernels in interpret mode:

- each wrapped call at ``tp`` 2 and 4 against the one-device kernel on the
  unsharded pools and against the XLA path (the scatter into the sliced
  layer, ``paged_attention_xla``): the written pools equal, attention within
  the kernel tests' tolerance, the pools' sharding kept;
- the page write plan sized by the SHARD's page;
- the predicate: which meshes and pools take the kernels, and what the two
  trace-time facts of ``get_stats()`` then say;
- an engine on a ``model=2`` mesh against the one-device engine: a prompt of
  two pieces, a T=4 scan and a prefix hit, greedy tokens equal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding

from distributed_gpu_inference_tpu.models import llama
from distributed_gpu_inference_tpu.models.configs import ModelConfig
from distributed_gpu_inference_tpu.ops import attention
from distributed_gpu_inference_tpu.ops import paged_attention_pallas as pap
from distributed_gpu_inference_tpu.parallel import sharding as sh
from distributed_gpu_inference_tpu.parallel.mesh import MeshPlan, make_mesh
from distributed_gpu_inference_tpu.runtime.engine import (
    EngineConfig,
    TPUEngine,
)
from distributed_gpu_inference_tpu.utils.data_structures import (
    InferenceRequest,
    SamplingParams,
)

BLOCK, D, LAYERS, LAYER = 16, 128, 3, 1
ROWS, COLS = 3, 32                  # 512 tokens of table a row: the kernels'
NH, HKV = 8, 4                      # crossover (``resolve_impl``)
KERNELS = ("paged_decode_attention_fused", "write_kv_pages_in_place",
           "ragged_paged_attention")
TOL = 2e-5                          # tests/test_ragged_attention.py's, f32


def _cfg(**over):
    base = dict(name="head-shard-probe", vocab_size=256, hidden_size=256,
                num_layers=LAYERS, num_heads=NH, num_kv_heads=HKV,
                intermediate_size=256, head_dim=D, dtype="float32")
    base.update(over)
    return ModelConfig(**base)


@pytest.fixture()
def interpreted(monkeypatch):
    """Dispatch as on a TPU backend, the three kernels in interpret mode;
    yields the names traced."""
    monkeypatch.setattr(attention, "pallas_backend", lambda: True)
    traced = []

    def interpret(name):
        kernel = getattr(pap, name)

        def call(*args, **kwargs):
            traced.append(name)
            return kernel(*args, interpret=True, **kwargs)

        return call

    for name in KERNELS:
        monkeypatch.setattr(pap, name, interpret(name))
    return traced


def _heads(cpu_devices, tp):
    return sh.head_shards(Mesh(np.array(cpu_devices[:tp]), ("model",)))


def _operands(spans, seed=0):
    """Stacked pools of random values, shuffled tables that never name
    block 0, and a rectangle whose row ``i`` holds ``spans[i]`` tokens at
    the tail of its context."""
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(       # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    s = max(max(spans), 1)
    ctx = rng.integers(20, 200, size=ROWS)
    pos = np.full((ROWS, s), -1, np.int32)
    for i, span in enumerate(spans):
        pos[i, :span] = np.arange(ctx[i], ctx[i] + span)
    return dict(
        k_pool=normal(LAYERS, 1 + ROWS * COLS, HKV, BLOCK, D),
        v_pool=normal(LAYERS, 1 + ROWS * COLS, HKV, BLOCK, D),
        q=normal(ROWS, s, NH, D), k=normal(ROWS, s, HKV, D),
        v=normal(ROWS, s, HKV, D),
        tables=jnp.asarray(
            1 + rng.permutation(ROWS * COLS).reshape(ROWS, COLS), jnp.int32),
        pos=jnp.asarray(pos),
        lens=jnp.asarray(ctx + np.asarray(spans), jnp.int32),
    )


def _sharded(o, heads):
    put = lambda a, spec: jax.device_put(      # noqa: E731
        a, NamedSharding(heads.mesh, spec))
    return dict(o, k_pool=put(o["k_pool"], sh.POOL_HEADS),
                v_pool=put(o["v_pool"], sh.POOL_HEADS),
                **{n: put(o[n], sh.CHUNK_HEADS) for n in ("q", "k", "v")})


def _xla(o):
    """The path a mesh took before: the layer sliced out, scattered into,
    written back; XLA's paged attention over the written layer."""
    pools = []
    for pool, new in ((o["k_pool"], o["k"]), (o["v_pool"], o["v"])):
        pools.append(pool.at[LAYER].set(llama._write_kv_pages(
            pool[LAYER], new, o["tables"], o["pos"], BLOCK)))
    attn = attention.paged_attention_xla(
        o["q"], pools[0][LAYER], pools[1][LAYER], o["tables"], o["pos"],
        o["lens"], BLOCK)
    return attn, pools[0], pools[1]


def _step(o, heads):
    return jax.jit(llama._fused_decode(BLOCK, None, heads))(
        o["q"], o["k"], o["v"], o["k_pool"], o["v_pool"], jnp.int32(LAYER),
        o["tables"], o["pos"], o["lens"])


def _round(o, heads):
    @jax.jit
    def run(q, k, v, k_pool, v_pool, tables, pos, lens):
        write, attn = llama._in_place_kv(
            _cfg(), {"k": k_pool, "v": v_pool}, tables, pos, lens, BLOCK,
            pallas=heads is None, heads=heads)
        k_pool, v_pool = write(k.reshape(-1, HKV, D), v.reshape(-1, HKV, D),
                               k_pool, v_pool, jnp.int32(LAYER))
        return attn(q, k_pool, v_pool, jnp.int32(LAYER)), k_pool, v_pool

    return run(o["q"], o["k"], o["v"], o["k_pool"], o["v_pool"],
               o["tables"], o["pos"], o["lens"])


def _same(got, want, heads, attention_too=True, pools_too=True):
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    if pools_too:
        for g, w in zip(got[1:], want[1:]):
            np.testing.assert_array_equal(f32(g), f32(w))
            # heads over ``model`` on the way out as on the way in
            assert g.sharding.is_equivalent_to(
                sh.kv_sharding(heads.mesh), g.ndim)
    if attention_too:
        np.testing.assert_allclose(f32(got[0]), f32(want[0]), rtol=TOL,
                                   atol=TOL)


@pytest.mark.parametrize("tp", [2, 4])
def test_fused_decode_a_shard_of_heads(interpreted, cpu_devices, tp):
    """A scan step: rows at slots 0..15 of their pages, one inactive."""
    heads = _heads(cpu_devices, tp)
    o = _operands([1, 1, 0])
    got = _step(_sharded(o, heads), heads)
    assert interpreted == ["paged_decode_attention_fused"]
    _same(got, _step(o, None), heads)
    _same(got, _xla(o), heads)
    # the write changed the layer it names and no other
    assert np.any(np.asarray(got[1][LAYER]) != np.asarray(o["k_pool"][LAYER]))
    np.testing.assert_array_equal(np.asarray(got[1][0]),
                                  np.asarray(o["k_pool"][0]))


# a round as serving has them: a piece that starts and ends mid-page
# beside a row that decodes and a row with nothing to write
ROUND = [37, 1, 0]


@pytest.mark.parametrize("tp", [2, 4])
def test_page_write_a_shard_of_heads(interpreted, cpu_devices, tp):
    heads = _heads(cpu_devices, tp)
    o = _operands(ROUND, seed=1)
    got = _round(_sharded(o, heads), heads)
    assert "write_kv_pages_in_place" in interpreted
    _same(got, _round(o, None), heads, attention_too=False)
    _same(got, _xla(o), heads, attention_too=False)
    assert np.any(np.asarray(got[2][LAYER]) != np.asarray(o["v_pool"][LAYER]))


@pytest.mark.parametrize("tp", [2, 4])
def test_ragged_attention_a_shard_of_heads(interpreted, cpu_devices, tp):
    heads = _heads(cpu_devices, tp)
    o = _operands(ROUND, seed=2)
    got = _round(_sharded(o, heads), heads)
    assert "ragged_paged_attention" in interpreted
    _same(got, _round(o, None), heads, pools_too=False)
    _same(got, _xla(o), heads, pools_too=False)
    # a shard's output is its own heads': sharded on the head axis
    assert got[0].sharding.is_equivalent_to(
        NamedSharding(heads.mesh, sh.CHUNK_HEADS), 4)


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_the_write_plan_is_sized_by_the_shards_page(interpreted, monkeypatch,
                                                    cpu_devices, tp):
    """``page_bytes`` decides how many cells a grid step of the write
    kernel holds in VMEM: a shard stages ``Hkv / tp`` heads of a page."""
    seen = []
    plan = pap.page_write_plan
    monkeypatch.setattr(
        pap, "page_write_plan",
        lambda *a, **k: seen.append(k["page_bytes"]) or plan(*a, **k))
    heads = None if tp == 1 else _heads(cpu_devices, tp)
    o = _operands(ROUND)
    assert llama._in_place_kv(
        _cfg(), {"k": o["k_pool"], "v": o["v_pool"]}, o["tables"], o["pos"],
        o["lens"], BLOCK, pallas=heads is None, heads=heads) is not None
    assert seen == [HKV // tp * BLOCK * D * 4]


def _meshes(devices):
    return {
        "model2": Mesh(np.array(devices[:2]), ("model",)),
        # as the worker builds it: every axis named, three of them trivial
        "model4_named_axes": make_mesh(MeshPlan(model=4), devices[:4]),
        "seq2_model2": make_mesh(MeshPlan(seq=2, model=2), devices[:4],
                                 keep_trivial_axes=False),
        "stage2_model2": make_mesh(MeshPlan(stage=2, model=2), devices[:4]),
    }


@pytest.mark.parametrize("mesh,over,quantized_kv,want", [
    ("model2", {}, False, ("in_place", "fused")),
    ("model4_named_axes", {}, False, ("in_place", "fused")),
    # int8 pools: the fused kernel's amax would see the local heads only
    ("model2", {}, True, ("layer_copy", "xla")),
    # pools sharded on their block axis too: the partial-softmax ops
    ("seq2_model2", {}, False, ("layer_copy", "xla")),
    ("stage2_model2", {}, False, ("layer_copy", "xla")),
    # two KV heads over four chips
    ("model4_named_axes", {"num_kv_heads": 2}, False, ("layer_copy", "xla")),
    # a head width the kernels refuse on any chip count
    ("model2", {"head_dim": 64}, False, ("layer_copy", "xla")),
    # one chip, as it was: every kernel, the round's not over int8 pools
    (None, {}, False, ("in_place", "fused")),
    (None, {}, True, ("layer_copy", "fused")),
], ids=["model2", "model4_named_axes", "int8_pools", "seq_axis",
        "stage_axis", "kv_heads_not_divisible", "head_dim_64", "one_chip",
        "one_chip_int8_pools"])
def test_which_meshes_take_the_kernels(interpreted, cpu_devices, mesh, over,
                                       quantized_kv, want):
    heads = sh.head_shards(_meshes(cpu_devices)[mesh]) if mesh else None
    cfg, ctx = _cfg(**over), COLS * BLOCK
    args = (cfg, ctx, quantized_kv)
    kw = dict(pallas=mesh is None, heads=heads)
    assert (llama.ragged_kv_path(*args, **kw),
            llama.decode_attention_path(*args, **kw)) == want
    # below the kernels' crossover no chip count takes them
    assert llama.ragged_kv_path(cfg, ctx // 2, quantized_kv, **kw) \
        == "layer_copy"
    assert llama.decode_attention_path(cfg, ctx // 2, quantized_kv, **kw) \
        == "xla"


def _forward(cfg, s, **kw):
    """``forward_chunk`` traced for [ROWS, s] tokens from shapes alone:
    nothing runs."""
    o = _operands((s,) * ROWS)
    shaped = lambda tree: jax.tree.map(         # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    kv = shaped({"k": o["k_pool"], "v": o["v_pool"]})
    return jax.eval_shape(
        lambda params, kv, tables, pos, lens: llama.forward_chunk(
            cfg, params, jnp.maximum(pos, 0), pos, kv, tables, lens,
            block_size=BLOCK, **kw).logits,
        params, kv, *shaped((o["tables"], o["pos"], o["lens"])))


@pytest.mark.parametrize("says,kernels", [
    ("fused", ["paged_decode_attention_fused"]), ("xla", [])])
def test_a_step_holds_what_the_fact_says(interpreted, monkeypatch, says,
                                         kernels):
    """``decode_attention`` cannot drift from the graph: the step asks the
    function the stat reads, as a round asks ``ragged_kv_path``."""
    monkeypatch.setattr(llama, "decode_attention_path", lambda *a, **k: says)
    _forward(_cfg(), 1)
    assert sorted(set(interpreted)) == kernels


def test_every_kernel_bare_beside_a_head_sharding_is_refused(cpu_devices):
    with pytest.raises(ValueError, match="pallas=False beside heads"):
        _forward(_cfg(), 1, pallas=True, heads=_heads(cpu_devices, 2))


def test_a_cpu_mesh_engine_says_layer_copy_and_xla(cpu_devices):
    """No TPU backend: the facts read what the graphs hold, XLA's paths."""
    eng = TPUEngine(_cfg(num_layers=1), _ecfg(),
                    mesh=Mesh(np.array(cpu_devices[:2]), ("model",)))
    stats = eng.get_stats()
    assert (stats["ragged_kv_path"], stats["decode_attention"]) \
        == ("layer_copy", "xla")


# --------------------------------------------------------------------- #
# the engine on a mesh, the kernels taken
# --------------------------------------------------------------------- #

def _ecfg():
    return EngineConfig(max_batch_size=2, max_seq_len=512, block_size=BLOCK,
                        prefill_buckets=(16, 32), ragged_chunk=32,
                        dtype="float32", multi_step=4,
                        enable_prefix_cache=True)


def _req(n, max_new=8):
    return InferenceRequest(
        prompt_token_ids=[(i * 11 + 3) % 250 for i in range(n)],
        sampling=SamplingParams(max_new_tokens=max_new, temperature=0.0),
    )


def _serve(eng):
    """A 40-token prompt (a 32-token piece, then its 8-token second piece),
    a T=4 scan, then the same prompt again: a prefix hit on its first two
    pages, whose piece starts past them."""
    seen = []
    first = eng.submit_chunked_start(_req(40))
    while not first.done:
        seen.append(eng.ragged_round([first]))
    seen.append(eng.decode_multi(num_steps=4))
    eng.finish_slot(first.slot)
    second = eng.submit_chunked_start(_req(40))
    assert second.off == 32 and len(second.fresh) == 8
    seen.append(eng.ragged_round([second]))
    seen.append(eng.decode_multi(num_steps=4))
    return seen


def test_mesh_engine_through_the_kernels_equals_one_device(interpreted,
                                                           monkeypatch,
                                                           cpu_devices):
    cfg = _cfg(num_layers=2, num_heads=4, num_kv_heads=2)
    with monkeypatch.context() as m:
        # the one-device engine on XLA's paths: what the CPU serves
        m.setattr(attention, "pallas_backend", lambda: False)
        single = TPUEngine(cfg, _ecfg(), seed=0)
        want = _serve(single)
    assert not interpreted
    tp = TPUEngine(cfg, _ecfg(), params=jax.device_get(single.params),
                   mesh=make_mesh(MeshPlan(model=2), cpu_devices[:2]))
    stats = tp.get_stats()
    assert (stats["ragged_kv_path"], stats["decode_attention"]) \
        == ("in_place", "fused")
    got = _serve(tp)
    assert set(interpreted) == set(KERNELS)
    assert got == want
    assert len(want) == 5 and len(want[2][0]) == 4      # a T=4 scan
    for name in ("k", "v"):
        assert tp.kv[name].sharding.is_equivalent_to(
            sh.kv_sharding(tp.mesh), 5)
