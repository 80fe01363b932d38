"""The dense GQA decoder family (Qwen2, Llama, Yi): what the benchmark knows
of this architecture, and nothing any other family shares.

- ``program_config``: the program's ``ModelConfig`` for a configuration file;
- ``leaf_std``: the weight rule of every leaf of the program's parameter tree;
- ``arch`` and ``layer``: one float32 reference decoder layer, written from
  the published architecture and not from the program: RMSNorm -> q, k, v
  projections (with bias where the configuration has it) -> rotary embedding
  -> causal softmax attention where query head h reads KV head h // (H/K) ->
  output projection -> residual -> RMSNorm -> SwiGLU (silu(x Wg) * (x Wu)) Wd
  -> residual; with ``fp8`` every linear layer runs in e4m3 (the control);
- ``Shape``: parameters, KV bytes, and the FLOPs and bytes of a call.

Counted (``Shape``) by the rules of ``chipbench/counts.py``: 2 FLOPs per
multiply-add of every projection (q, k, v, o, the three SwiGLU matrices) for
each real token, 4 * H * hd per key a token attends to (scores and values),
and the head, 2 * d * V, only for tokens whose logits are used.  Norms, RoPE,
biases and the softmax are left out (under 0.1% here).  A call reads the
layer weights and the head once, the embedding rows of its tokens and the KV
of every live lane up to its furthest position, and writes its new tokens'
KV.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import numpy as np

from chipbench.reference import attention, linear, rmsnorm, rope


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file: its architecture
    from the registry, every size from the file."""
    from repro.configs import get_config

    return get_config(conf["arch"]).replace(
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        qkv_bias=bool(conf["attention_bias"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"],
        param_dtype=conf["torch_dtype"],
    )


# -- weights ------------------------------------------------------------------
# a matrix draws with standard deviation 1/sqrt(fan_in), the embedding with
# 1; norm scales are 1 +- 0.25; biases have standard deviation about 0.1

# leaf name -> axis (of the per-layer shape) whose size is the fan-in
_FAN_IN = {"wq": 0, "wk": 0, "wv": 0, "wi_gate": 0, "wi_up": 0, "lm_head": 0}
_FAN_IN_PRODUCT = {"wo"}  # attention (H, hd, d) or mlp (f, d): all but the last axis


def leaf_std(path: tuple, shape: tuple) -> tuple:
    """(kind, std) of one per-layer leaf; kind is 'matrix', 'scale' or 'bias'."""
    name = path[-1]
    if name == "scale":
        return "scale", 0.25 / math.sqrt(3.0)
    if name in ("bq", "bk", "bv"):
        return "bias", 0.1
    if name == "embed":
        return "matrix", 1.0
    if name in _FAN_IN:
        return "matrix", 1.0 / math.sqrt(shape[_FAN_IN[name]])
    if name in _FAN_IN_PRODUCT:
        return "matrix", 1.0 / math.sqrt(int(np.prod(shape[:-1])))
    raise KeyError(f"no weight rule for parameter {'/'.join(path)}")


# -- reference layer ------------------------------------------------------------
@dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    eps: float


def arch(cfg) -> Arch:
    return Arch(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.head_dim, cfg.vocab_size, cfg.rope_theta, cfg.norm_eps)


@partial(jax.jit, static_argnames=("arch", "fp8"))
def layer(x, w, arch: Arch, fp8: bool):
    """One decoder layer on x (T, d) float32; ``w`` holds float32 leaves."""
    with jax.default_matmul_precision("highest"):
        a = w["attn"]
        h = rmsnorm(x, w["attn_norm"]["scale"], arch.eps)
        q = linear(h, a["wq"], fp8)
        k = linear(h, a["wk"], fp8)
        v = linear(h, a["wv"], fp8)
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        o = attention(rope(q, arch.rope_theta), rope(k, arch.rope_theta), v)
        x = x + linear(o.reshape(o.shape[0], -1), a["wo"].reshape(-1, arch.d_model), fp8)
        m = w["mlp"]
        h = rmsnorm(x, w["mlp_norm"]["scale"], arch.eps)
        g = jax.nn.silu(linear(h, m["wi_gate"], fp8)) * linear(h, m["wi_up"], fp8)
        return x + linear(g, m["wo"], fp8)


# -- counts -------------------------------------------------------------------------
@dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    dtype_bytes: int = 2

    @classmethod
    def of(cls, cfg) -> "Shape":
        """From a ``repro.configs`` ModelConfig (attributes only)."""
        return cls(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                   cfg.d_ff, cfg.vocab_size, cfg.qkv_bias)

    # -- parameters -------------------------------------------------------
    @property
    def layer_matrix_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        return attn + 3 * d * self.d_ff

    @property
    def layer_params(self) -> int:
        bias = (self.n_heads + 2 * self.n_kv_heads) * self.head_dim if self.qkv_bias else 0
        return self.layer_matrix_params + bias + 2 * self.d_model

    @property
    def params(self) -> int:
        """Layers, embedding, untied head and final norm."""
        return (self.n_layers * self.layer_params + 2 * self.vocab * self.d_model
                + self.d_model)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim * self.dtype_bytes

    # -- FLOPs --------------------------------------------------------------
    def token_flops(self, context: int, logits: bool) -> int:
        """One token that attends to ``context`` keys (itself included)."""
        f = self.n_layers * (2 * self.layer_matrix_params
                             + 4 * self.n_heads * self.head_dim * context)
        if logits:
            f += 2 * self.d_model * self.vocab
        return f

    def call_flops(self, tokens) -> int:
        """``tokens``: (position, logits used) of every real token of a call."""
        return sum(self.token_flops(p + 1, used) for p, used in tokens)

    # -- bytes ----------------------------------------------------------------
    @property
    def weight_bytes_per_call(self) -> int:
        """Layer weights, final norm and head: read once by any call."""
        return self.dtype_bytes * (self.n_layers * self.layer_params
                                   + self.vocab * self.d_model + self.d_model)

    def call_bytes(self, tokens, lane_reach) -> int:
        """``tokens`` as for :meth:`call_flops`; ``lane_reach``: for every
        live lane, the number of its positions the call reads (its furthest
        position + 1)."""
        n = len(tokens)
        return (self.weight_bytes_per_call
                + n * self.d_model * self.dtype_bytes  # embedding rows
                + sum(lane_reach) * self.kv_bytes_per_token  # KV read
                + n * self.kv_bytes_per_token)  # KV written
