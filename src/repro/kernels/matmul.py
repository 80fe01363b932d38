"""MXU-tiled matmul kernel — the §4.4 arithmetic-throughput probe and the
block-shape autotuning target.

Grid (M/bm, N/bn, K/bk), K innermost, accumulation in a VMEM scratch (the
MXU-native pattern): fp32 for float inputs, int32 for integer inputs (Mosaic
refuses a float accumulator over an integer lhs).  Block dims should be
multiples of 128 to align with the 128x128 systolic array (cf. the paper's
finding that >=128 threads/block are required to fill a Turing SM — the TPU
analogue is 128-aligned MXU tiles).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: |out| within this factor of finfo.max counts as saturated for float dtypes
_SATURATION_MARGIN = 0.99


def saturation_check(args, out):
    """Guard sentinel: fraction of the matmul output lost to overflow or
    saturation, plus a human-readable detail (see ``repro.kernels.guard``).

    Integer outputs need the bound computed from the *inputs*: a low-precision
    accumulate that overflows int8/int16 range wraps silently on cast, so
    inspecting ``out`` alone has false negatives.  ``|a| @ |b|`` in int64 is a
    triangle-inequality upper bound — every entry it clears is provably safe,
    every entry past the dtype max is counted saturated (conservative, zero
    false negatives).  Float outputs saturate visibly: count non-finite
    entries plus magnitudes within ``_SATURATION_MARGIN`` of ``finfo.max``
    for the narrow dtypes (fp16/bf16); fp32+ counts non-finite only.
    """
    o = np.asarray(out)
    if o.size == 0:
        return 0.0, "empty output"
    if np.issubdtype(o.dtype, np.integer):
        a = np.abs(np.asarray(args[0]).astype(np.int64))
        b = np.abs(np.asarray(args[1]).astype(np.int64))
        bound = a @ b
        limit = np.iinfo(o.dtype).max
        frac = float(np.mean(bound > limit))
        return frac, (
            f"|a|@|b| accumulation bound exceeds {o.dtype} max ({limit}) on "
            f"{frac:.1%} of entries"
        )
    of = o.astype(np.float64)
    bad = ~np.isfinite(of)
    detail = "non-finite entries"
    if o.dtype in (np.dtype(np.float16), np.dtype(jnp.bfloat16)):
        limit = _SATURATION_MARGIN * float(jnp.finfo(o.dtype).max)
        bad |= np.abs(of) >= limit
        detail = f"non-finite or |out| >= {_SATURATION_MARGIN:g}*finfo.max"
    return float(np.mean(bad)), detail


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=acc_ref.dtype
    )

    @pl.when(k == nk - 1)
    def _():
        acc = acc_ref[...]
        if jnp.issubdtype(o_ref.dtype, jnp.integer):
            # saturate like the oracle's float->int convert; an int32
            # accumulator would otherwise wrap on the narrowing cast
            info = jnp.iinfo(o_ref.dtype)
            acc = jnp.clip(acc, info.min, info.max)
        o_ref[...] = acc.astype(o_ref.dtype)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = True,
) -> jax.Array:
    """a (M,K) @ b (K,N); dims must divide by the block sizes."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, ((m, k, n), (bm, bk, bn))
    out_dtype = out_dtype or a.dtype
    grid = (m // bm, n // bn, k // bk)
    acc_dtype = jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer) else jnp.float32
    return pl.pallas_call(
        _matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(a, b)
