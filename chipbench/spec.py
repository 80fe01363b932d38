"""What a cell is, read from ``BENCHMARK.json`` and the files it names.

Nothing here knows a cell, a configuration, a mix or a metric by name:

- a configuration is the file its ``configs`` entry names, with every size,
  the engine's settings (``EngineConfig``: lanes, ``max_len``, chunk, KV
  layout) and its ``family``;
- a family is ``chipbench/families/<family>.py``, which brings what the
  benchmark knows of one architecture: ``program_config(conf)`` (the
  program's ModelConfig), ``leaf_std(path, shape)`` (its weight rule, for
  ``weights.py``), ``arch(cfg)`` and ``layer(x, w, arch, fp8)`` (its float32
  reference layer and fp8 control, for ``reference.py``), and ``Shape``
  (its counts, for ``counts.py``); the rest of the harness is shared;
- a traffic mix is ``chipbench/traffic/<traffic>.json``;
- a metric is ``chipbench/metrics/<name>.py``, whose ``read(run)`` returns
  the number or None when the run holds nothing to read.

A later change adds a cell, a configuration, a family, a mix or a metric by
adding files and entries, and edits no file that is here.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from functools import lru_cache
from pathlib import Path
from types import ModuleType
from typing import Callable


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    family: ModuleType  # chipbench/families/<config's family>.py
    traffic: dict
    end_to_end: tuple  # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _applies(entry: dict, cell: str, reported=None) -> bool:
    """A metric with ``workloads`` is read in those cells; a per-layer metric
    without it in every cell that reports the end-to-end metric it moves."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return reported is None or entry["moves"] in reported


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _applies(m, name))
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"] if _applies(m, name, names))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        family=family(root, config, conf["file"]),
        traffic=json.loads((root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # where dataclasses look up the module's names
    spec.loader.exec_module(mod)
    return mod


def reader(root: Path, metric: str) -> Callable:
    """``read`` of ``chipbench/metrics/<metric>.py``."""
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    return _load(path, f"chipbench_metric_{metric}").read


def family(root: Path, config: dict, where: str) -> ModuleType:
    """The family module that the configuration ``config`` (read from
    ``where``) names.  Never a default: a configuration without ``family``,
    or one whose family has no file, is refused with the file to add."""
    if "family" not in config:
        raise KeyError(f"{where} names no family: add \"family\": \"<name>\" and "
                       "chipbench/families/<name>.py")
    path = root / "chipbench" / "families" / f"{config['family']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{where} names family {config['family']!r}: add {path}")
    return _family(path.resolve())


@lru_cache(maxsize=None)
def _family(path: Path) -> ModuleType:
    """Each file loaded once a process, so its jitted layer compiles once."""
    return _load(path, f"chipbench_family_{path.stem}")


def peaks(root: Path, device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error, not a default."""
    table = json.loads((root / "chipbench" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} not in chipbench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
