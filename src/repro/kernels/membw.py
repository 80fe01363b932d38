"""Streaming-bandwidth probe kernels (paper §3.1/3.2/3.7 analogue).

``stream_copy``: HBM->VMEM->HBM round trip per block (write-allocate path).
``stream_reduce``: read-only scan accumulating a checksum — the TPU analogue
of the paper's l1_bw/l2_bw read benchmarks (the accumulate into ``sink``
plays the same side-effect role as the paper's ``dsink``).

Block shape is the probe variable: footprint-per-step = block bytes, so
sweeping block shape vs. array footprint maps the memory-hierarchy transfer
efficiency exactly like the paper's working-set sweeps.  The checksum is an
SMEM scalar: Mosaic cannot store a scalar to VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def stream_copy(
    x: jax.Array, *, block_rows: int = 8, block_cols: int = 512, interpret: bool = True
) -> jax.Array:
    r, c = x.shape
    assert r % block_rows == 0 and c % block_cols == 0
    grid = (r // block_rows, c // block_cols)
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        interpret=interpret,
    )(x)


def _reduce_kernel(x_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        o_ref[0, 0] = 0.0

    o_ref[0, 0] += jnp.sum(x_ref[...].astype(jnp.float32))


def _reduce_call(x: jax.Array, block: tuple, grid: tuple, interpret: bool) -> jax.Array:
    return pl.pallas_call(
        _reduce_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(x)


def stream_reduce(
    x: jax.Array, *, block_rows: int = 8, block_cols: int = 512, interpret: bool = True
) -> jax.Array:
    """Read-bandwidth probe: returns the (1,1) fp32 checksum."""
    r, c = x.shape
    assert r % block_rows == 0 and c % block_cols == 0
    return _reduce_call(
        x, (block_rows, block_cols), (r // block_rows, c // block_cols), interpret
    )


def strided_reduce(
    x: jax.Array, *, stride: int, block_rows: int = 64, interpret: bool = True
) -> jax.Array:
    """Checksum of one row out of every ``stride`` (paper Tab 3.1 "load
    granularity"): sparse access probing the transfer unit.

    Row ``t * stride`` of ``x`` is the first ``c`` columns of row ``t`` of the
    (r/stride, stride*c) view, so each block fetches only the rows it sums
    (Mosaic has no strided VMEM load over a last dim other than 128).
    """
    r, c = x.shape
    assert r % block_rows == 0 and block_rows % stride == 0
    view = x.reshape(r // stride, stride * c)
    return _reduce_call(view, (block_rows // stride, c), (r // block_rows, 1), interpret)
