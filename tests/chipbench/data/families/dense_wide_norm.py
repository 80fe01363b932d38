"""A family added by this file alone: the dense GQA decoder with norm scales
drawn 1 +- 0.5 instead of 1 +- 0.25.  Everything else is ``dense_gqa``'s."""
import math
from pathlib import Path

from chipbench import spec

_dense = spec.family(Path(__file__).resolve().parents[2], {"family": "dense_gqa"}, __file__)
program_config, arch, layer, Shape = _dense.program_config, _dense.arch, _dense.layer, _dense.Shape


def leaf_std(path: tuple, shape: tuple) -> tuple:
    if path[-1] == "scale":
        return "scale", 0.5 / math.sqrt(3.0)
    return _dense.leaf_std(path, shape)
