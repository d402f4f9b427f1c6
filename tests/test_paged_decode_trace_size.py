"""What ``dgi_paged_decode`` costs to TRACE does not grow with the batch or
with the page group: every loop of the kernel's body over rows or over pages
is traced once (``lax.fori_loop(..., unroll=True)``, unrolled where Mosaic
lowers it), so its jaxpr holds a fixed number of copies and branches.

The worker traces the kernel twice a scan graph and four scan graphs a
start. Up to PR 55 a Python loop traced the fused write's five phases once a
row and the dense walk's page starts once a page: 160 ``dma_start`` and 48
``cond`` at 8 rows and 32 pages a group, ~3.9 s a graph on the chip's host,
over a quarter of ``setup_s`` in the K/V cells (PERF.md section 6, PR 56). No
lowering here, only the trace: that the unrolled program is still the one
Mosaic gets is ``tests/test_tpu_lowering.py``'s to say."""

import collections
import functools

import jax
import jax.numpy as jnp
import pytest

from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
    _SELECTED_UNROLL,
    paged_decode_attention_fused,
)
from tests.test_index_select import _primitives

BLOCK, HEADS, KV_HEADS, HEAD_DIM, LAYERS = 16, 32, 8, 128, 2
COUNTED = ("dma_start", "dma_wait", "cond")

# name: (window, under a selection, page starts a pool a ``group_dma`` site)
WALKS = {
    "dense": (None, False, 1),
    "window": (1024, False, 1),
    # a rolled loop of runs of ``_SELECTED_UNROLL``, as PR 50 left it
    "selected": (None, True, _SELECTED_UNROLL),
}


def _traced(batch, pages, window, selected, quantized):
    sds = jax.ShapeDtypeStruct
    n = 1 + batch * pages
    pool = sds((LAYERS, n, KV_HEADS, BLOCK, HEAD_DIM),
               jnp.int8 if quantized else jnp.bfloat16)
    scale = sds((LAYERS, n, BLOCK, HEAD_DIM), jnp.bfloat16)
    new = sds((batch, 1, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    operands = {"q": sds((batch, 1, HEADS, HEAD_DIM), jnp.bfloat16),
                "new_k": new, "new_v": new, "k_pool": pool, "v_pool": pool,
                "layer_idx": sds((), jnp.int32),
                "block_tables": sds((batch, pages), jnp.int32),
                "positions": sds((batch, 1), jnp.int32),
                "kv_lens": sds((batch,), jnp.int32)}
    if quantized:
        operands.update(k_scale=scale, v_scale=scale)
    if selected:
        operands["keep"] = sds((batch, 1, pages * BLOCK), jnp.float32)
    fn = functools.partial(
        paged_decode_attention_fused, block_size=BLOCK, window=window)
    return jax.make_jaxpr(lambda kw: fn(**kw))(operands).jaxpr


@pytest.mark.parametrize("batch,pages", [(4, 128), (8, 128), (4, 1536),
                                         (8, 1536)])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("walk", WALKS)
def test_the_trace_holds_a_copy_a_site_whatever_the_batch_and_the_group(
        walk, quantized, batch, pages):
    window, selected, run = WALKS[walk]
    found = collections.Counter(
        _primitives(_traced(batch, pages, window, selected, quantized)))
    got = {name: found[name] for name in COUNTED}
    pools = 4 if quantized else 2      # K and V, and a scale pool each
    assert got == {
        # the walk starts a group at two sites (the first live group, the
        # next one behind it) and waits for one at one; the write stages a
        # row's page in and back, a start and a wait each way
        "dma_start": pools * (2 * run + 2),
        "dma_wait": pools * (1 + 2),
        # the write's five phases, a row's ``pl.when`` each, and the eight
        # branches of a cell: the write's one cell, an inactive row, a live
        # group, the walk's first group, ``next_chunk``'s choice and the
        # start of what it found, a row's first and its last group
        "cond": 5 + 8,
    }, (walk, quantized, batch, pages)
