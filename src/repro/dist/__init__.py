"""repro.dist — mesh/sharding/ZeRO/compression/pipeline distribution layer.

- ``sharding``  logical activation axes + name-based parameter specs
- ``zero``      ZeRO-1/2/3 state partitioning over the data axis
- ``compress``  int8 gradient compression for cross-pod links
- ``pipeline``  GPipe microbatch pipelining over a mesh axis
"""
from .compress import dequantize_int8, psum_compressed, quantize_int8
from .pipeline import gpipe_apply
from .sharding import (
    activation_sharding,
    batch_shardings,
    cache_shardings,
    logits_sharding,
    param_specs,
    shard_act,
    shard_params,
)
from .zero import zero1_state_specs

__all__ = [
    "activation_sharding",
    "batch_shardings",
    "cache_shardings",
    "dequantize_int8",
    "gpipe_apply",
    "logits_sharding",
    "param_specs",
    "psum_compressed",
    "quantize_int8",
    "shard_act",
    "shard_params",
    "zero1_state_specs",
]
