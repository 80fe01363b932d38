"""Sharding rules: logical activation axes and name-based parameter specs.

Two layers of API:

- **Activation pinning** (used inside model code): ``shard_act(x, "dp", "sp",
  None)`` constrains an activation with logical axes — ``dp`` (batch, maps to
  the ``pod``+``data`` mesh axes), ``sp`` (sequence parallel, maps to
  ``model``), ``tp`` (tensor parallel, maps to ``model``).  Outside an
  ``activation_sharding(mesh)`` context (single-device tests, examples) both
  ``shard_act`` and ``shard_params`` are identity functions, so models run
  unmodified without a mesh.

- **Parameter specs** (used by the dry-run/launch layer): ``param_specs``
  walks a parameter tree and assigns Megatron-style tensor-parallel specs by
  leaf path: vocab-sharded embedding/lm_head, head-sharded wq/wk/wv,
  row-parallel attention/MLP ``wo``, column-parallel ``wi*``, expert- or
  ffn-sharded MoE weights (``cfg.moe_shard``).  Scanned layer stacks (extra
  leading layer dim) are handled by right-aligning the core spec.

Every axis assignment is divisibility-guarded: a dim that doesn't divide the
mesh axis stays replicated, so reduced CI configs compile on small meshes.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from math import prod

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


def current_mesh() -> Mesh | None:
    return getattr(_ctx, "mesh", None)


@contextmanager
def activation_sharding(mesh: Mesh):
    """Enable ``shard_act``/``shard_params`` constraints while tracing."""
    prev = getattr(_ctx, "mesh", None)
    _ctx.mesh = mesh
    try:
        yield mesh
    finally:
        _ctx.mesh = prev


# ---------------------------------------------------------------------------
# logical -> mesh axis resolution
# ---------------------------------------------------------------------------
def _dp_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def _resolve(mesh: Mesh, logical: str | None):
    if logical is None:
        return None
    if logical == "dp":
        return _dp_axes(mesh)
    if logical in ("tp", "sp", "ep"):
        return "model" if "model" in mesh.shape else None
    raise ValueError(f"unknown logical axis {logical!r}")


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return prod(mesh.shape[a] for a in axis)
    return mesh.shape.get(axis, 1)


def _guarded_spec(mesh: Mesh, shape, axes) -> P:
    """Drop any axis assignment whose mesh size doesn't divide the dim."""
    spec = []
    for dim, ax in zip(shape, axes):
        size = _axis_size(mesh, ax)
        spec.append(ax if ax is not None and size > 1 and dim % size == 0 else None)
    return P(*spec)


# ---------------------------------------------------------------------------
# activation pinning
# ---------------------------------------------------------------------------
def shard_act(x: jax.Array, *logical: str | None) -> jax.Array:
    """Constrain ``x`` with logical axes; identity outside a mesh context."""
    mesh = current_mesh()
    if mesh is None:
        return x
    axes = tuple(_resolve(mesh, l) for l in logical) + (None,) * (x.ndim - len(logical))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _guarded_spec(mesh, x.shape, axes))
    )


def shard_params(tree, cfg):
    """Pin a (layer) parameter subtree to its rule-derived specs.

    Used inside scanned layer bodies so the sliced layer params — and hence
    their gradients — keep the tensor-parallel layout. Identity without mesh.
    """
    mesh = current_mesh()
    if mesh is None:
        return tree

    def pin(path, leaf):
        axes = _param_axes(_path_str(path), leaf.ndim, cfg)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, _guarded_spec(mesh, leaf.shape, axes))
        )

    return jax.tree_util.tree_map_with_path(pin, tree)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------
def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _core_spec(path: str, cfg) -> tuple:
    """Tensor-parallel spec for a leaf's trailing 'core' dims, by name."""
    if "router" in path:
        return (None, None)  # (d, E): routing probs need the full expert set
    if "moe" in path:  # expert weights (E, d, f) / (E, f, d)
        if getattr(cfg, "moe_shard", "expert") == "expert":
            return ("model", None, None)  # expert parallel
        if "wo" in path:
            return (None, "model", None)  # TP inside each expert, row-parallel
        return (None, None, "model")
    if "embed" in path:
        return ("model", None)  # (V, d) vocab-sharded
    if "lm_head" in path or "unembed" in path:
        return (None, "model")  # (d, V) vocab-sharded logits
    if "attn" in path:
        if "wo" in path:
            return ("model", None, None)  # (h, hd, d) row-parallel on heads
        if any(w in path for w in ("wq", "wk", "wv")):
            return (None, "model", None)  # (d, h|k, hd) head-sharded
        return ()
    if any(w in path for w in ("wi_gate", "wi_up", "in_proj", "w_in")):
        return (None, "model")  # (d, f) column-parallel
    if "mlp" in path and "wi" in path:
        return (None, "model")
    if ("mlp" in path and "wo" in path) or "out_proj" in path or "w_out" in path:
        return ("model", None)  # (f, d) row-parallel
    return ()  # norms, biases, scalars: replicated


def _param_axes(path: str, ndim: int, cfg) -> tuple:
    core = _core_spec(path, cfg)
    if len(core) > ndim:  # e.g. a bias that matched a weight-name substring
        core = core[-ndim:]
    return (None,) * (ndim - len(core)) + tuple(core)


def param_specs(shapes, cfg, mesh: Mesh):
    """Tree of ``NamedSharding`` for a parameter tree of ShapeDtypeStructs."""

    def one(path, leaf):
        axes = _param_axes(_path_str(path), leaf.ndim, cfg)
        return NamedSharding(mesh, _guarded_spec(mesh, leaf.shape, axes))

    return jax.tree_util.tree_map_with_path(one, shapes)


# ---------------------------------------------------------------------------
# input / output shardings for the launch layer
# ---------------------------------------------------------------------------
def batch_shardings(batch, mesh: Mesh):
    """Batch-dim data-parallel sharding for every input leaf."""
    dp = _dp_axes(mesh)

    def one(leaf):
        axes = (dp,) + (None,) * (leaf.ndim - 1)
        return NamedSharding(mesh, _guarded_spec(mesh, leaf.shape, axes))

    return jax.tree.map(one, batch)


def cache_shardings(cache, cfg, mesh: Mesh):
    """Decode-cache sharding: batch on dp; KV heads on model when divisible.

    Stacked KV caches are (L, B, Smax, K, hd); recurrent-state caches keep
    batch at dim 1 as well — everything else stays replicated.
    """
    dp = _dp_axes(mesh)

    def one(leaf):
        axes = [None] * leaf.ndim
        if leaf.ndim >= 2:
            axes[1] = dp
        if leaf.ndim == 5 and leaf.shape[3] == getattr(cfg, "n_kv_heads", -1):
            axes[3] = "model" if "model" in mesh.shape else None
        return NamedSharding(mesh, _guarded_spec(mesh, leaf.shape, tuple(axes)))

    return jax.tree.map(one, cache)


def paged_cache_shardings(cache, cfg, mesh: Mesh):
    """Paged KV-pool sharding: KV heads on ``model`` when divisible.

    Pool leaves are (L, N_pages, page, K, hd).  The page dim stays
    replicated on purpose — block tables address pages randomly, so sharding
    pages would turn every ``gather_pages`` into a cross-device gather; the
    tensor-parallel axis for decode is the KV-head dim, matching the
    head-sharded wk/wv that produce the entries.
    """

    def one(leaf):
        axes = [None] * leaf.ndim
        if leaf.ndim == 5 and leaf.shape[3] == getattr(cfg, "n_kv_heads", -1):
            axes[3] = "model" if "model" in mesh.shape else None
        return NamedSharding(mesh, _guarded_spec(mesh, leaf.shape, tuple(axes)))

    return jax.tree.map(one, cache)


def logits_sharding(global_batch: int, vocab_size: int, mesh: Mesh) -> NamedSharding:
    """Output-logits sharding: batch-dim dp, vocab gathered for sampling.

    Rank-agnostic (covers (B, S, V) prefill and (B, V) decode): only dim 0 is
    named, trailing dims are replicated.
    """
    dp = _dp_axes(mesh)
    if dp is not None and global_batch % _axis_size(mesh, dp) != 0:
        dp = None
    return NamedSharding(mesh, P(dp))
