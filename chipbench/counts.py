"""The algorithm's work from shapes: FLOPs and bytes of a dense GQA decoder.

Counted from the configuration alone, the same whatever implements it:

- FLOPs: 2 per multiply-add of every projection (q, k, v, o, the three SwiGLU
  matrices) for each real token, 4 * H * hd per key a token attends to
  (scores and values), and the head, 2 * d * V, only for tokens whose logits
  are used (every decoded token, and the last prompt token of a prefill).
  Norms, RoPE, biases and the softmax are left out (under 0.1% here).
- Bytes of one call of a compiled step: the layer weights and the head read
  once, the embedding rows of its tokens, the KV of every live lane read
  once up to its furthest position, and the KV of its new tokens written.

Pad lanes, pad positions and positions whose logits nobody samples are not
work.  Tokens whose KV comes from shared prefix pages are not fed, so they
cost nothing here.
"""
from __future__ import annotations

from dataclasses import dataclass


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


def roofline_s(flops: float, nbytes: float, peak_flops: float, bandwidth: float) -> tuple:
    """(least seconds, bound) of a call: the larger of compute and memory time."""
    t_c, t_m = flops / peak_flops, nbytes / bandwidth
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
