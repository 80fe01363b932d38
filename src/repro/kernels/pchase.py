"""Pointer-chase kernel — the paper's core §3 methodology (Mei & Chu [9]).

A permutation array is walked with fully dependent loads: ``idx = perm[idx]``.
Wall-clock / steps = dependent-load latency at the hierarchy level holding the
array.  On TPU the interesting transition is VMEM-resident vs. HBM-streamed;
on the CPU host (measure mode) the same kernel traces out L1/L2/L3/DRAM —
which is how we validate the methodology end-to-end (core/dissect.py).

The walked array stays VMEM-resident and the final index is written to an
SMEM scalar (Mosaic cannot store a scalar to VMEM) — the TPU analogue of the
paper's §3.5.2 "uniform datapath" observation: index math stays off the
vector path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pchase_kernel(perm_ref, o_ref, *, steps: int):
    def body(_, idx):
        return perm_ref[idx, 0]

    idx = jax.lax.fori_loop(0, steps, body, jnp.int32(0))
    o_ref[0, 0] = idx


def pchase_pallas(perm: jax.Array, steps: int, *, interpret: bool = True) -> jax.Array:
    """perm: (N,) int32 permutation of range(N).  Returns final index (1,1)."""
    n = perm.shape[0]
    perm2 = perm.reshape(n, 1)
    return pl.pallas_call(
        partial(_pchase_kernel, steps=steps),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid=(1,),
        in_specs=[pl.BlockSpec((n, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(perm2)
