"""Weights from the seed, made by the benchmark and never by the program.

The program's ``model.init`` is read only for the layout: the names, shapes
and dtypes of its parameter tree (``jax.eval_shape``, nothing computed).
Each leaf is then drawn here, one layer at a time, by an integer-exact rule:
16 random bits, read as a signed integer, times a power of two, rounded once
to the served dtype.  The same (seed, leaf, layer) gives the same bits on any
device and in any program, so ``reference.py`` regenerates one layer at a time
what the served model holds, without taking anything from the program.

Shared by every family: the keys, the draw and the layer-by-layer build.
The scale of each leaf is the family's: its ``leaf_std(path, shape)``
(``chipbench/families/<name>.py``) gives the leaf's kind ('matrix', 'scale':
drawn about 1, or 'bias') and standard deviation, and is passed here as
``rule``.  A family with other leaves brings its rule as a new file.
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# uniform int16 has standard deviation 2**15 / sqrt(3)
_INT16_STD = 2.0 ** 15 / math.sqrt(3.0)


def key_for(seed: int):
    """A JAX key from any non-negative seed (JAX keeps 32 bits of an int)."""
    k = jax.random.key(seed % 2**32)
    return jax.random.fold_in(k, (seed >> 32) % 2**32)


def _scale_exp(std: float) -> int:
    """Power-of-two exponent that brings uniform int16 to about ``std``."""
    return round(math.log2(std / _INT16_STD))


def draw(key, path: tuple, shape: tuple, dtype, rule):
    """One per-layer leaf from ``key`` (already folded with leaf and layer),
    scaled by the family's ``rule``."""
    kind, std = rule(path, shape)
    bits = jax.random.bits(key, shape, jnp.uint16)
    ints = jax.lax.bitcast_convert_type(bits, jnp.int16).astype(jnp.float32)
    x = ints * np.float32(2.0 ** _scale_exp(std))  # exact: a power of two
    if kind == "scale":
        x = x + np.float32(1.0)  # exact at these magnitudes
    return x.astype(dtype)


def _leaf_key(key, path: tuple, layer: int):
    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
    return jax.random.fold_in(k, layer)


def _paths(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(tuple(p.key for p in path), leaf) for path, leaf in flat]


def _build(shapes, rule, key):
    leaves = []
    for path, s in _paths(shapes):
        if path[0] == "layers":  # the layer axis first
            leaves.append(jnp.stack([draw(_leaf_key(key, path, i), path, s.shape[1:], s.dtype,
                                          rule) for i in range(s.shape[0])]))
        else:
            leaves.append(draw(_leaf_key(key, path, 0), path, s.shape, s.dtype, rule))
    return jax.tree.unflatten(jax.tree.structure(shapes), leaves)


def make(shapes, seed: int, rule):
    """The whole parameter tree for ``shapes`` (``jax.eval_shape`` of the
    program's init; leaves under ``layers`` carry the layer axis first), as
    one compiled call on the default device.  The key is an argument, so
    every seed reuses the one program."""
    return jax.jit(partial(_build, shapes, rule))(key_for(seed))


def layer_from_key(shapes, key, index, rule) -> dict:
    """One layer's leaves (no layer axis), as the served model holds them;
    ``index`` may be traced."""
    out = {}
    for path, s in _paths(shapes):
        if path[0] != "layers":
            continue
        node = out
        for p in path[1:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = draw(_leaf_key(key, path, index), path, s.shape[1:], s.dtype, rule)
    return out


def top(shapes, seed: int, name: str, rule):
    """A leaf outside the layer stack, by its name at the top of the tree."""
    for path, s in _paths(shapes):
        if path[0] == name:  # jitted: no full-size float32 temporaries
            return jax.jit(lambda k: draw(_leaf_key(k, path, 0), path, s.shape, s.dtype,
                                          rule))(key_for(seed))
    raise KeyError(name)
