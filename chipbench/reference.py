"""Plain float32 reference of a served decoder, and its fp8 control: the
parts every family shares.

A family (``chipbench/families/<name>.py``) brings its own decoder layer,
``layer(x, w, arch, fp8)``, written from its published architecture and
not from the program, and its ``arch(cfg)``: a hashable description with at
least ``n_layers``, ``vocab`` and ``eps``.  Shared here: the token
embedding, the layer loop, the final RMSNorm and the untied head; the
building blocks a layer uses (``linear``, ``rmsnorm``, ``rope``, causal GQA
``attention``, ``fp8_round``); and the gaps the check reads.  All in
float32 under ``jax.default_matmul_precision("highest")``.  A new family
adds its layer as a new file and edits nothing here.

It imports nothing of ``src/`` and takes nothing the program made: every
layer's weights are drawn again from the seed by ``weights.py`` with the
family's rule, one layer at a time, so the reference fits beside nothing
else on the chip once the served model is freed.

The control is the same forward with every linear layer computed in fp8
(e4m3, weights scaled per tensor, activations per token): the precision a
later change would be tempted to serve in.  It has to fail the comparison.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

Q_BLOCK = 512  # queries per attention block (bounds the score matrix)
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def fp8_round(x, axis):
    """Round to e4m3 with an amax scale over ``axis`` (None: whole tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def linear(x, w, fp8: bool):
    """x (..., d_in) @ w (d_in, ...)."""
    if fp8:
        x, w = fp8_round(x, -1), fp8_round(w, None)
    return jnp.tensordot(x, w, axes=1)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (T, heads, hd) at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv  # (T, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal GQA attention.  q (T, H, hd), k/v (T, K, hd) -> (T, H, hd)."""
    t, h, hd = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    outs = []
    for s in range(0, t, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        mask = jnp.arange(t)[None, :] <= (s + jnp.arange(qb.shape[0]))[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    return jnp.concatenate(outs, 0)


@partial(jax.jit, static_argnames=("arch",))
def _final(x, scale, arch):
    return rmsnorm(x, scale, arch.eps)


@partial(jax.jit, static_argnames=("vocab", "fp8"))
def _logits(h, head, vocab: int, fp8: bool):
    with jax.default_matmul_precision("highest"):
        return linear(h, head, fp8)[:, :vocab]


@partial(jax.jit, static_argnames=("vocab",))
def _served_gap_rows(h, head, nxt, vocab: int):
    logits = _logits(h, head, vocab, False)
    return logits.max(-1) - jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]


@partial(jax.jit, static_argnames=("vocab",))
def _choice_gap_rows(h, h_ctl, head, vocab: int):
    ref = _logits(h, head, vocab, False)
    pick = _logits(h_ctl, head, vocab, True).argmax(-1)
    return ref.max(-1) - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _padded(toks, length: int) -> np.ndarray:
    out = np.zeros(length, np.int32)
    out[: len(toks)] = toks
    return out


def hidden(shapes, seed: int, family, arch, seqs: list, length: int,
           fp8: bool = False) -> list:
    """Final normalised hidden states, float32 on the device, of each token
    list in ``seqs`` padded to ``length`` (causal: the pads change nothing
    before them; one length, so one program per cell), through ``family``'s
    layer.  Layer-major: each layer's weights are drawn once and applied to
    every sequence."""
    rule = family.leaf_std
    embed = weights.top(shapes, seed, "embed", rule)
    xs = [embed[jnp.asarray(_padded(t, length))].astype(jnp.float32) for t in seqs]
    del embed
    layer_fn = jax.jit(lambda k, i: _f32(weights.layer_from_key(shapes, k, i, rule)))
    key = weights.key_for(seed)
    for i in range(arch.n_layers):
        w = layer_fn(key, i)
        xs = [family.layer(x, w, arch, fp8) for x in xs]
        del w
    scale = weights.top(shapes, seed, "final_norm", rule).astype(jnp.float32)
    return [_final(x, scale, arch) for x in xs]


def head_weights(shapes, seed: int, family):
    return weights.top(shapes, seed, "lm_head", family.leaf_std).astype(jnp.float32)


def served_gaps(h, head, prompt: list, out: list, vocab: int) -> np.ndarray:
    """At each served position, how far the served token's reference logit
    lies below the reference's best (0 where they agree).  ``h`` holds the
    hidden states of ``prompt + out[:-1]``."""
    nxt = _padded((prompt + out)[1:], h.shape[0])
    rows = np.asarray(_served_gap_rows(h, head, jnp.asarray(nxt), vocab))
    return rows[len(prompt) - 1: len(prompt) - 1 + len(out)]


def choice_gaps(h, h_ctl, head, prompt: list, out: list, vocab: int) -> np.ndarray:
    """The same gap for the token the control puts first at each position."""
    rows = np.asarray(_choice_gap_rows(h, h_ctl, head, vocab))
    return rows[len(prompt) - 1: len(prompt) - 1 + len(out)]
