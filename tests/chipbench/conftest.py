"""A tiny benchmark tree for CPU tests: the real metrics, families, peaks and
schema, with cells of a two-layer model small enough to serve on the CPU,
and the test-only families of ``data/families``."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# each tiny cell stands for the real cell whose metric entries it takes
TINY = {"tiny.chat": "qwen14b.chat", "tiny.docs": "yi34b.docs", "tiny.prefix": "qwen14b.prefix"}
# the tiny configuration each serves on: dense KV, or paged as its real cell
PAGED = {"tiny.docs", "tiny.prefix"}


def tiny_bench() -> dict:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": n, "source": "test", "file": f"chipbench/configs/{n}.json",
                         "reduced": [], "why": "CPU tests"} for n in ("tiny", "tiny-paged")]
    bench["workloads"] = [{"name": n, "config": "tiny-paged" if n in PAGED else "tiny",
                           "traffic": n.split(".")[1], "chips": 1, "why": "CPU tests"}
                          for n in TINY]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for t, real in TINY.items() if real in m["workloads"]]
    return bench


def build_root(path: Path) -> Path:
    """A checkout-like tree under ``path`` for the tiny cells."""
    cb = path / "chipbench"
    skip = shutil.ignore_patterns("__pycache__")
    for sub in ("metrics", "families"):
        shutil.copytree(REPO / "chipbench" / sub, cb / sub, ignore=skip)
    shutil.copytree(DATA / "families", cb / "families", ignore=skip, dirs_exist_ok=True)
    shutil.copy(REPO / "chipbench" / "peaks.json", cb / "peaks.json")
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(DATA / sub, cb / sub)
    (path / "BENCHMARK.json").write_text(json.dumps(tiny_bench()))
    return path


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return build_root(tmp_path_factory.mktemp("bench"))


def cpu_chip(cell, root):
    """Stands in for the chip check: the CPU, with made-up peaks."""
    import jax

    return jax.devices(), {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
