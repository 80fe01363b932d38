"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16) — ``model`` maps to the 16
ICI-adjacent chips of a v5e torus row (TP wants the fastest links); ``data``
carries gradient reduction.  Multi-pod: a leading ``pod`` axis (DCI links;
gradient-only traffic, compressible via dist.compress).

Functions, not module constants: importing this module must never touch jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(*, multi_pod: bool = False) -> Mesh:
    """Small mesh for CI-size integration tests (needs 8 fake devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh_for(devices: int, model_parallel: int, pods: int = 1) -> Mesh:
    """Elastic-scaling helper: any (pods, data, model) factorization."""
    per_pod = devices // pods
    data = per_pod // model_parallel
    assert pods * data * model_parallel == devices, (devices, model_parallel, pods)
    if pods > 1:
        return _mk((pods, data, model_parallel), ("pod", "data", "model"))
    return _mk((data, model_parallel), ("data", "model"))
