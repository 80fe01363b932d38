"""The algorithm's work from shapes: the rules every family counts by, and
the roofline that reads the counts.

Each family (``chipbench/families/<name>.py``) brings its own ``Shape``,
built by ``Shape.of(cfg)`` from the program's ModelConfig, with
``params``, ``kv_bytes_per_token``, ``call_flops(tokens)`` and
``call_bytes(tokens, lane_reach)``.  It counts from the configuration alone,
the same whatever implements it, by these rules:

- FLOPs: 2 per multiply-add of the work a token needs, and the head only for
  tokens whose logits are used (every decoded token, and the last prompt
  token of a prefill);
- bytes of one call of a compiled step: the weights the call needs read
  once, the embedding rows of its tokens, the KV of every live lane read once
  up to its furthest position, and the KV of its new tokens written.

Pad lanes, pad positions and positions whose logits nobody samples are not
work.  Tokens whose KV comes from shared prefix pages are not fed, so they
cost nothing here.  A new family adds its ``Shape`` as a new file and edits
nothing here.
"""
from __future__ import annotations


def roofline_s(flops: float, nbytes: float, peak_flops: float, bandwidth: float) -> tuple:
    """(least seconds, bound) of a call: the larger of compute and memory time."""
    t_c, t_m = flops / peak_flops, nbytes / bandwidth
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
