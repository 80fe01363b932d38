"""Chunked SSD (Mamba2) scan kernel.

One grid cell = one (batch*head); the chunk axis is innermost with the SSM
state (P, N) persisted in VMEM scratch across chunk steps — the Pallas
mirror of ``repro.models.mamba.ssd_chunked``.

``a_log`` enters as a (BH, 1, S) row so its (1, chunk) block meets the TPU
(8, 128) tiling rule (the full array's unit sublane dim is exempt), and the
in-chunk prefix sum is a masked lane reduction because Mosaic has no
``cumsum`` lowering.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(u_ref, a_ref, b_ref, c_ref, o_ref, h_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    u = u_ref[0].astype(jnp.float32)  # (L, P)
    a = a_ref[0].astype(jnp.float32)  # (1, L)
    bmat = b_ref[0].astype(jnp.float32)  # (L, N)
    cmat = c_ref[0].astype(jnp.float32)  # (L, N)

    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (chunk, chunk), 1
    )
    acum = jnp.sum(jnp.where(tri, a, 0.0), axis=1, keepdims=True)  # (L, 1) inclusive
    acum_row = acum.T  # (1, L)
    atot = acum_row[:, chunk - 1:]  # (1, 1)
    h = h_ref[...]  # (P, N)

    # intra-chunk: decay-masked (C.B^T) score matrix
    cb = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    dd = acum - acum_row
    w = cb * jnp.exp(jnp.clip(dd, -60.0, 0.0)) * tri.astype(jnp.float32)
    y_intra = jax.lax.dot_general(
        w, u, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, P)

    # inter-chunk: contribution of the carried state
    y_inter = jax.lax.dot_general(
        cmat, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(acum)  # (L, P)

    o_ref[0] = (y_intra + y_inter).astype(o_ref.dtype)

    # state update: h' = exp(atot) h + sum_s exp(atot - A_s) u_s B_s^T
    sdecay = jnp.exp(jnp.clip(atot - acum, -60.0, 0.0))  # (L, 1)
    us = u * sdecay  # (L, P)
    # exp(atot) as a (1, N) row sliced off a lane broadcast of acum: Mosaic
    # cannot broadcast (1, 1) across sublanes and lanes at once
    decay = jnp.exp(jnp.broadcast_to(acum, (chunk, h.shape[1]))[chunk - 1:])
    h_ref[...] = h * decay + jax.lax.dot_general(
        us, bmat, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)


def ssm_scan_pallas(
    u: jax.Array,
    a_log: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """u (BH, S, P); a_log (BH, S); b/c (BH, S, N); S % chunk == 0.

    Returns y (BH, S, P).  (State starts at zero; the framework's cross-chunk
    carry uses the model-level scan — this kernel is the per-sequence core.)
    """
    bh, s, p = u.shape
    n = b.shape[-1]
    assert s % chunk == 0
    grid = (bh, s // chunk)
    return pl.pallas_call(
        partial(_ssd_kernel, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(u, a_log.reshape(bh, 1, s), b, c)
