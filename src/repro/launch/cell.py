"""Dry-run cell construction: (arch x shape x mesh) -> a lowerable jit'd
step function with full input/output shardings and donation.

This is the single source of truth used by the dry-run, the roofline
report (``benchmarks/roofline.py``), and the §Perf hillclimb
(``benchmarks/hillclimb.py``, which imports this module to re-lower cells
under modified configs — the dependency runs from that entry point into
here, never the reverse).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.sharding import (
    activation_sharding,
    batch_shardings,
    cache_shardings,
    logits_sharding,
    param_specs,
)
from repro.dist.zero import zero1_state_specs
from repro.models import build_model
from repro.models.api import input_specs
from repro.optim import AdamW
from repro.optim.schedule import cosine_with_warmup
from repro.train.step import TrainState, make_train_step, state_shapes


@dataclass
class Cell:
    cfg: ModelConfig
    shape: ShapeConfig
    mesh: Mesh
    fn: Callable
    args: tuple
    in_shardings: Any
    out_shardings: Any
    donate: tuple
    n_params: float
    n_params_active: float

    @property
    def name(self) -> str:
        pods = self.mesh.shape.get("pod", 1)
        return f"{self.cfg.name}__{self.shape.name}__{'multi' if pods > 1 else 'single'}"

    def lower(self):
        with activation_sharding(self.mesh):
            jfn = jax.jit(
                self.fn,
                in_shardings=self.in_shardings,
                out_shardings=self.out_shardings,
                donate_argnums=self.donate,
            )
            return jfn.lower(*self.args)


# ---------------------------------------------------------------------------
def count_params_shapes(tree) -> float:
    return float(sum(int(l.size) for l in jax.tree.leaves(tree)))


def count_active_params(cfg: ModelConfig, tree) -> float:
    """MoE: experts count at k/E weight; everything else fully."""
    total = count_params_shapes(tree)
    if cfg.family != "moe" or not cfg.n_experts:
        return total
    expert = 0.0

    def walk(path, leaf):
        nonlocal expert
        pstr = "/".join(str(getattr(p, "key", p)) for p in path)
        if "moe/wi_gate" in pstr or "moe/wi_up" in pstr or "moe/wo" in pstr:
            expert += float(leaf.size)
        return leaf

    jax.tree_util.tree_map_with_path(walk, tree)
    frac = cfg.experts_per_token / cfg.n_experts
    return total - expert * (1.0 - frac)


def _rep(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# cost reference: XLA's cost_analysis does NOT multiply while-loop bodies by
# trip count, so scanned programs under-report FLOPs.  The reference lowers a
# fully-unrolled, scan-free variant (layers unrolled, naive attention, whole-
# sequence SSD chunk, no microbatching) WITHOUT sharding or compilation and
# reads global FLOPs off the lowered module.  Remat is kept, so backward
# recompute is counted (that is real work the TPU performs).
# ---------------------------------------------------------------------------
def cost_reference(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    ref_cfg = cfg.replace(
        scan_layers=False,
        attn_impl="naive",
        ssm_chunk=max(shape.seq_len, cfg.ssm_chunk),
        train_microbatches=1,
    )
    model = build_model(ref_cfg)
    if shape.kind == "train":
        opt = AdamW()
        lr_fn = cosine_with_warmup(3e-4, warmup=2000, total=100_000)
        step = make_train_step(model.loss_fn, opt, lr_fn, microbatches=1)
        state_sh = state_shapes(model.init, opt)
        batch = input_specs(ref_cfg, shape)
        lowered = jax.jit(step).lower(state_sh, batch)
    elif shape.kind == "prefill":
        batch = input_specs(ref_cfg, shape)
        params_sh = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        lowered = jax.jit(lambda p, b: model.prefill(p, b, shape.seq_len)).lower(
            params_sh, batch
        )
    else:
        specs = input_specs(ref_cfg, shape)
        params_sh = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        lowered = jax.jit(model.decode_step).lower(
            params_sh, specs["cache"], specs["tokens"], specs["pos"]
        )
    ca = lowered.cost_analysis() or {}
    return {
        "global_flops": float(ca.get("flops", 0.0)),
        "global_bytes_prefusion": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


# ---------------------------------------------------------------------------
def _local_bytes(tree_shapes, tree_shardings) -> int:
    """Exact per-device bytes of a sharded tree."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(tree_shapes), jax.tree.leaves(tree_shardings)):
        div = 1
        if isinstance(sh, NamedSharding):
            mesh = sh.mesh
            for ax in sh.spec:
                if ax is None:
                    continue
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    div *= mesh.shape[a]
        total += leaf.size * leaf.dtype.itemsize // div
    return int(total)


def analytic_memory(cell: "Cell") -> dict:
    """TPU-expectation HBM footprint (the CPU-compiled memory_analysis keeps
    f32 copies of bf16 buffers — see perfmodel.costs).  Exact for state/cache
    bytes (from the actual shardings); formulaic for live activations."""
    cfg, shape, mesh = cell.cfg, cell.shape, cell.mesh
    amap_dp = 1
    for n in ("pod", "data"):
        if n in mesh.shape:
            amap_dp *= mesh.shape[n]
    tp = mesh.shape.get("model", 1)

    out = {}
    if shape.kind == "train":
        state_sh, batch = cell.args
        state_bytes = _local_bytes(state_sh, cell.in_shardings[0])
        grads = _local_bytes(state_sh.params, cell.in_shardings[0].params)
        b_local = max(shape.global_batch // (amap_dp * cfg.train_microbatches), 1)
        s_local = max(shape.seq_len // tp, 1)  # sp-sharded saves
        layers = cfg.n_layers
        saves = layers * b_local * shape.seq_len // tp * cfg.d_model * 2
        logits = b_local * shape.seq_len * max(cfg.padded_vocab // tp, 1) * 6
        act_live = int(2.5 * b_local * shape.seq_len * cfg.d_model * 4)  # one-layer bwd
        out = {
            "state_bytes": state_bytes,
            "grad_bytes": grads,
            "saves_bytes": saves,
            "logits_bytes": logits,
            "act_live_bytes": act_live,
            "analytic_peak_bytes": state_bytes + grads + saves + logits + act_live,
        }
    elif shape.kind == "prefill":
        params_sh, batch = cell.args
        pbytes = _local_bytes(params_sh, cell.in_shardings[0])
        cache_sd = jax.eval_shape(cell.fn, *cell.args)[1]
        cbytes = _local_bytes(cache_sd, cell.out_shardings[1])
        b_local = max(shape.global_batch // amap_dp, 1)
        act = int(3 * b_local * shape.seq_len // tp * cfg.d_model * 2 * 4)
        out = {
            "param_bytes": pbytes,
            "cache_bytes": cbytes,
            "act_live_bytes": act,
            "analytic_peak_bytes": pbytes + 2 * cbytes + act,
        }
    else:  # decode
        params_sh = cell.args[0]
        pbytes = _local_bytes(params_sh, cell.in_shardings[0])
        cbytes = _local_bytes(cell.args[1], cell.in_shardings[1])
        out = {
            "param_bytes": pbytes,
            "cache_bytes": cbytes,
            "analytic_peak_bytes": pbytes + cbytes + (cbytes // 4),
        }
    return out


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> Cell:
    model = build_model(cfg)
    if shape.kind == "train":
        return _build_train(cfg, shape, mesh, model)
    if shape.kind == "prefill":
        return _build_prefill(cfg, shape, mesh, model)
    return _build_decode(cfg, shape, mesh, model)


def _build_train(cfg, shape, mesh, model) -> Cell:
    opt = AdamW()
    lr_fn = cosine_with_warmup(3e-4, warmup=2000, total=100_000)

    state_sh = state_shapes(model.init, opt)
    pspecs = param_specs(state_sh.params, cfg, mesh)
    zspecs = zero1_state_specs(state_sh.params, pspecs, mesh)
    # ZeRO staging: 1 = optimizer state sharded over data; 2 = +grad
    # accumulation sharded; 3 = +fp32 master params sharded (FSDP storage;
    # XLA all-gathers per-layer slices inside the scan for compute)
    mspecs = zspecs if cfg.zero_stage >= 1 else pspecs
    gspecs = zspecs if cfg.zero_stage >= 2 else None
    pstore = zspecs if cfg.zero_stage >= 3 else pspecs
    opt_sh = type(state_sh.opt)(step=_rep(mesh), mu=mspecs, nu=mspecs)
    state_shardings = TrainState(params=pstore, opt=opt_sh)

    step = make_train_step(
        model.loss_fn,
        opt,
        lr_fn,
        microbatches=cfg.train_microbatches,
        grad_shardings=gspecs,
    )

    batch = input_specs(cfg, shape)
    bshard = batch_shardings(batch, mesh)
    metrics_sh = {"loss": _rep(mesh), "grad_norm": _rep(mesh), "lr": _rep(mesh)}

    return Cell(
        cfg=cfg,
        shape=shape,
        mesh=mesh,
        fn=step,
        args=(state_sh, batch),
        in_shardings=(state_shardings, bshard),
        out_shardings=(state_shardings, metrics_sh),
        donate=(0,),
        n_params=count_params_shapes(state_sh.params),
        n_params_active=count_active_params(cfg, state_sh.params),
    )


def _serving_params(model):
    """Serving holds bf16 weights (the training fp32 master stays on the
    trainer); float leaves are served in bf16."""
    sd = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape, jnp.bfloat16 if jnp.issubdtype(l.dtype, jnp.floating) else l.dtype
        ),
        sd,
    )


def _serving_pspecs(params_sh, cfg, mesh):
    pspecs = param_specs(params_sh, cfg, mesh)
    if cfg.serve_param_fsdp:
        pspecs = zero1_state_specs(params_sh, pspecs, mesh)
    return pspecs


def _build_prefill(cfg, shape, mesh, model) -> Cell:
    batch = input_specs(cfg, shape)
    params_sh = _serving_params(model)
    pspecs = _serving_pspecs(params_sh, cfg, mesh)
    bshard = batch_shardings(batch, mesh)

    def fn(params, batch):
        return model.prefill(params, batch, shape.seq_len)

    out_sd = jax.eval_shape(fn, params_sh, batch)  # (logits, cache)
    lsh = logits_sharding(shape.global_batch, cfg.vocab_size, mesh)
    cshard = cache_shardings(out_sd[1], cfg, mesh)

    return Cell(
        cfg=cfg,
        shape=shape,
        mesh=mesh,
        fn=fn,
        args=(params_sh, batch),
        in_shardings=(pspecs, bshard),
        out_shardings=(lsh, cshard),
        donate=(),
        n_params=count_params_shapes(params_sh),
        n_params_active=count_active_params(cfg, params_sh),
    )


def _build_decode(cfg, shape, mesh, model) -> Cell:
    specs = input_specs(cfg, shape)
    params_sh = _serving_params(model)
    pspecs = _serving_pspecs(params_sh, cfg, mesh)
    cshard = cache_shardings(specs["cache"], cfg, mesh)
    tp_sh = batch_shardings(
        {"tokens": specs["tokens"], "pos": specs["pos"]}, mesh
    )
    lsh = logits_sharding(shape.global_batch, cfg.vocab_size, mesh)

    def fn(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return Cell(
        cfg=cfg,
        shape=shape,
        mesh=mesh,
        fn=fn,
        args=(params_sh, specs["cache"], specs["tokens"], specs["pos"]),
        in_shardings=(pspecs, cshard, tp_sh["tokens"], tp_sh["pos"]),
        out_shardings=(lsh, cshard),
        donate=(1,),
        n_params=count_params_shapes(params_sh),
        n_params_active=count_active_params(cfg, params_sh),
    )
