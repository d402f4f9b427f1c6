"""The Pallas kernels against their XLA references, at served shapes.

The interpret-mode tests (``tests/test_ragged_attention.py``,
``tests/test_pallas_paged_attention.py``, ``tests/test_qmm_pallas.py``) check
the kernels' arithmetic on the CPU, in f32 at toy geometry. They cannot say
what the Mosaic-compiled kernel computes: that needs a TPU. This module
runs the same row mixes at model geometry (head counts, head_dim, projection
shapes from ``models/configs.py``), in the pool dtypes serving stores,
against ``paged_attention_xla`` / the scatter write / the convert-on-read
matmul (and a chunk's in-place KV path against the scatter into the sliced
layer):

- compiled, on a TPU backend (``chip_smoke.py`` runs the served subset on
  every run; ``python -m distributed_gpu_inference_tpu.testing.kernel_parity``
  runs the whole list);
- ``interpret=True`` on the CPU at a small context, so the list itself is
  debugged before chip time is spent on it.

Tolerance is the one the interpret-mode tests use wherever a bf16 or int8
operand is involved: rtol = atol = 2e-2.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_gpu_inference_tpu.models.configs import (
    ModelConfig,
    get_model_config,
)
from distributed_gpu_inference_tpu.models.llama import _write_kv_pages
from distributed_gpu_inference_tpu.ops.attention import paged_attention_xla
from distributed_gpu_inference_tpu.ops.paged_attention_pallas import (
    page_write_plan,
    paged_decode_attention_fused,
    quantize_kv_pool,
    ragged_paged_attention,
    write_kv_pages_in_place,
)
from distributed_gpu_inference_tpu.ops.qmm_pallas import qmm_stacked_pallas
from distributed_gpu_inference_tpu.ops.quantization import matmul

TOL = 2e-2
BATCH = 8                         # the worker path's max_batch_size
Rows = List[Tuple[int, int]]      # per row: (query span, kv_len); span 0 = inactive


def ragged_row_mixes(ctx: int, chunk: int) -> Dict[str, Rows]:
    """The row mixes of ``tests/test_ragged_attention.py``, scaled to a
    ``ctx``-token table and a ``chunk``-token admission width."""
    c, w = ctx, chunk
    return {
        # no admission in flight: every row one query at its context tail
        "decode_only": [(1, 9), (1, 23), (1, c // 3), (1, c), (1, 1),
                        (1, c // 2 + 5), (1, 77), (1, c - 1)],
        # one full-width chunk row alone, the rest of the batch empty
        "prefill_only": [(w, w + 44)] + [(0, 0)] * (BATCH - 1),
        # decode rows, a spec verify row (q_len 3), chunk rows of several
        # widths incl. one wider than the q tile, an inactive row
        "mixed": [(1, 40), (3, 25), (16, 90), (1, 7), (w, c - 3),
                  (w // 4, w // 4), (0, 0), (1, c)],
        # an admission's non-final chunk: later table pages are garbage the
        # in-length mask must fence off; narrower rows pad with -1 queries
        "mid_prompt_chunk": [(w, 2 * w), (1, 30), (8, 33), (2, 17)],
    }


def _normal(rng: np.random.Generator, shape: Tuple[int, ...]) -> jax.Array:
    """bf16 standard normals, drawn on the host: one upload, no compile."""
    return jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)


def _tables(b: int, m: int) -> jax.Array:
    return jnp.asarray(1 + np.arange(b * m, dtype=np.int32).reshape(b, m))


def _max_err(got: Any, want: Any) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    # allclose's own criterion, as one number: <= 1.0 passes
    return float(np.max(np.abs(got - want) / (TOL + TOL * np.abs(want))))


def check_ragged(geo: ModelConfig, rows: Rows, block: int, ctx: int,
                 quantized: bool, window: Optional[int],
                 interpret: bool) -> float:
    """One ragged batch; each row's queries sit at its context tail (the
    state every producer dispatches). bf16 throughout, or int8 pools with
    their scale pools — what serving holds."""
    rng = np.random.default_rng(0)
    b, m = len(rows), ctx // block
    s = max(max(span for span, _ in rows), 1)
    pool_shape = (1 + b * m, geo.num_kv_heads, block, geo.head_dim)
    k_pool, v_pool = _normal(rng, pool_shape), _normal(rng, pool_shape)
    q = _normal(rng, (b, s, geo.num_heads, geo.head_dim))
    positions = np.full((b, s), -1, np.int32)
    lens = np.zeros((b,), np.int32)
    for i, (span, kv_len) in enumerate(rows):
        lens[i] = kv_len
        if span:
            positions[i, :span] = np.arange(kv_len - span, kv_len)

    @jax.jit
    def both(q, k_pool, v_pool, tables, positions, lens):
        scales: Dict[str, Any] = {}
        if quantized:
            k_pool, k_s = quantize_kv_pool(k_pool)
            v_pool, v_s = quantize_kv_pool(v_pool)
            scales = {"k_scale": k_s, "v_scale": v_s}
        want = paged_attention_xla(
            q, k_pool, v_pool, tables, positions, lens, block,
            window=window, **scales,
        )
        got = ragged_paged_attention(
            q, k_pool, v_pool, tables, positions, lens, block,
            window=window, interpret=interpret, **scales,
        )
        return want, got

    want, got = both(q, k_pool, v_pool, _tables(b, m),
                     jnp.asarray(positions), jnp.asarray(lens))
    # fully padded queries are exact zeros on both paths
    if np.any(np.asarray(got, np.float32)[positions < 0] != 0.0):
        return float("inf")
    return _max_err(got, want)


def check_fused_decode(geo: ModelConfig, lens: Sequence[int], block: int,
                       ctx: int, window: Optional[int],
                       interpret: bool) -> float:
    """Fused write+attend on bf16 pools vs scatter-then-XLA-attention; the
    written layer must also match the scatter and the other layer stay
    untouched."""
    rng = np.random.default_rng(1)
    layers, layer = 2, 1
    b, m = len(lens), ctx // block
    pool_shape = (layers, 1 + b * m, geo.num_kv_heads, block, geo.head_dim)
    k_pool, v_pool = _normal(rng, pool_shape), _normal(rng, pool_shape)
    q = _normal(rng, (b, 1, geo.num_heads, geo.head_dim))
    new_k = _normal(rng, (b, 1, geo.num_kv_heads, geo.head_dim))
    new_v = _normal(rng, (b, 1, geo.num_kv_heads, geo.head_dim))
    lens_a = np.asarray(lens, np.int32)
    positions = (lens_a - 1)[:, None]          # 0-length rows: -1 = inactive

    @jax.jit
    def both(q, new_k, new_v, k_pool, v_pool, tables, positions, lens_a):
        ref_k = _write_kv_pages(k_pool[layer], new_k, tables, positions,
                                block)
        ref_v = _write_kv_pages(v_pool[layer], new_v, tables, positions,
                                block)
        want = paged_attention_xla(
            q, ref_k, ref_v, tables, positions, lens_a, block, window=window
        )
        got, k2, v2 = paged_decode_attention_fused(
            q, new_k, new_v, k_pool, v_pool, jnp.int32(layer), tables,
            positions, lens_a, block, window=window, interpret=interpret,
        )
        wrote = (
            jnp.all(k2[layer] == ref_k) & jnp.all(v2[layer] == ref_v)
            & jnp.all(k2[0] == k_pool[0]) & jnp.all(v2[0] == v_pool[0])
        )
        return want, got, wrote

    want, got, wrote = both(q, new_k, new_v, k_pool, v_pool, _tables(b, m),
                            jnp.asarray(positions), jnp.asarray(lens_a))
    return _max_err(got, want) if bool(wrote) else float("inf")


def check_in_place_chunk(geo: ModelConfig, rows: Rows, block: int, ctx: int,
                         window: Optional[int], interpret: bool) -> float:
    """A multi-token chunk's KV path on the stacked pools (the page write
    by layer index, the ragged kernel reading the layer where it lies)
    against the scatter into the sliced layer and the same kernel on that
    slice: the pools must come out equal byte for byte — the written
    layer, the other layers, block 0 — and so must attention."""
    rng = np.random.default_rng(3)
    layers, layer = 3, 1
    b, m = len(rows), ctx // block
    s = max(max(span for span, _ in rows), 2)
    hkv, d = geo.num_kv_heads, geo.head_dim
    pool_shape = (layers, 1 + b * m, hkv, block, d)
    k_pool, v_pool = _normal(rng, pool_shape), _normal(rng, pool_shape)
    q = _normal(rng, (b, s, geo.num_heads, d))
    new_k, new_v = _normal(rng, (b, s, hkv, d)), _normal(rng, (b, s, hkv, d))
    positions = np.full((b, s), -1, np.int32)
    lens = np.zeros((b,), np.int32)
    for i, (span, kv_len) in enumerate(rows):
        lens[i] = kv_len
        if span:
            positions[i, :span] = np.arange(kv_len - span, kv_len)

    @jax.jit
    def both(q, new_k, new_v, k_pool, v_pool, tables, positions, lens):
        ref_k = k_pool.at[layer].set(_write_kv_pages(
            k_pool[layer], new_k, tables, positions, block))
        ref_v = v_pool.at[layer].set(_write_kv_pages(
            v_pool[layer], new_v, tables, positions, block))
        want = ragged_paged_attention(
            q, ref_k[layer], ref_v[layer], tables, positions, lens, block,
            window=window, interpret=interpret,
        )
        plan = page_write_plan(tables, positions, block,
                               hkv * block * d * k_pool.dtype.itemsize)
        k2, v2 = write_kv_pages_in_place(
            new_k.reshape(-1, hkv, d), new_v.reshape(-1, hkv, d),
            k_pool, v_pool, jnp.int32(layer), plan, interpret=interpret,
        )
        got = ragged_paged_attention(
            q, k2, v2, tables, positions, lens, block, window=window,
            interpret=interpret, layer_idx=jnp.int32(layer),
        )
        return want, got, jnp.all(k2 == ref_k) & jnp.all(v2 == ref_v)

    want, got, wrote = both(q, new_k, new_v, k_pool, v_pool, _tables(b, m),
                            jnp.asarray(positions), jnp.asarray(lens))
    return _max_err(got, want) if bool(wrote) else float("inf")


def check_qmm(k: int, n: int, m: int, interpret: bool) -> float:
    rng = np.random.default_rng(2)
    x = _normal(rng, (m, k))
    qw = jnp.asarray(rng.integers(-127, 128, (2, k, n), np.int8))
    scale = jnp.asarray(
        rng.uniform(0.5, 1.5, (2, 1, n)).astype(np.float32)
        * (k**-0.5 / 64.0)
    )

    @jax.jit
    def both(x, qw, scale):
        want = matmul(x, {"qw": qw[1], "scale": scale[1]}, pallas=False)
        got = qmm_stacked_pallas(x, qw, scale, jnp.int32(1),
                                 interpret=interpret)
        return want, got

    want, got = both(x, qw, scale)
    return _max_err(got, want)


def run(models: Sequence[str], blocks: Sequence[int],
        pools: Sequence[str], ctx: int, chunk: int,
        interpret: bool) -> List[Dict[str, Any]]:
    """Every case of the list; one result row each. A kernel the compiler
    refuses is a failed row carrying the error, so one call reports the
    whole list."""
    results: List[Dict[str, Any]] = []

    def case(name: str, fn, *args) -> None:
        row: Dict[str, Any] = {"case": name}
        try:
            row["err"] = round(fn(*args), 4)
            row["ok"] = bool(row["err"] <= 1.0)
        except Exception as exc:  # noqa: BLE001 — reported as a failed case
            row.update(ok=False, error=f"{type(exc).__name__}: {exc}"[:600])
        results.append(row)

    for model in models:
        geo = get_model_config(model)
        for block in blocks:
            for pool in pools:
                tag = f"{model}/block{block}/{pool}"
                for mix, rows in ragged_row_mixes(ctx, chunk).items():
                    # None: full causal; 24: a window that bites inside
                    # the table (the configured 4096 never does at 2048)
                    for window in (None, 24):
                        case(f"ragged/{tag}/{mix}/window={window}",
                             check_ragged, geo, rows, block, ctx,
                             pool == "int8", window, interpret)
            for mix, rows in ragged_row_mixes(ctx, chunk).items():
                case(f"in_place_chunk/{model}/block{block}/bf16/{mix}",
                     check_in_place_chunk, geo, rows, block, ctx,
                     geo.sliding_window, interpret)
            lens = [33, 5, ctx, 1, 0, ctx // 2, 17, ctx - 1]
            for window in (None, 24):
                case(f"fused_decode/{model}/block{block}/bf16/"
                     f"window={window}",
                     check_fused_decode, geo, lens, block, ctx, window,
                     interpret)
        h, i = geo.hidden_size, geo.intermediate_size
        q_out = geo.num_heads * geo.head_dim
        kv_out = geo.num_kv_heads * geo.head_dim
        for k, n in sorted({(h, q_out), (h, kv_out), (q_out, h), (h, i),
                            (i, h)}):
            # a decode step's rows, and the widest row count dispatch
            # still sends to the kernel
            for m in (BATCH, 256):
                case(f"qmm/{model}/K{k}xN{n}/m={m}",
                     check_qmm, k, n, m, interpret)
    return results


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--models", default="mistral-7b,qwen2.5-7b")
    ap.add_argument("--blocks", default="16,32")
    ap.add_argument("--pools", default="bf16,int8")
    ap.add_argument("--ctx", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernels in interpret mode (CPU debugging)")
    ap.add_argument("--out", default=None, help="write the result rows here")
    args = ap.parse_args(argv)
    if not args.interpret and jax.default_backend() != "tpu":
        print("kernel_parity: the compiled kernels need a TPU backend "
              f"(found {jax.default_backend()!r}); --interpret runs them "
              "interpreted", file=sys.stderr)
        return 2
    results = run(
        args.models.split(","), [int(b) for b in args.blocks.split(",")],
        args.pools.split(","), args.ctx, args.chunk, args.interpret,
    )
    for row in results:
        print(json.dumps(row))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if not r["ok"]]
    print(f"kernel_parity: {len(results) - len(bad)}/{len(results)} agree "
          f"on {jax.devices()[0].device_kind}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
