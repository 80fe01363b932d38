"""The harness finds cells, configurations, mixes and metrics by name, and
refuses to run without a chip."""
import json
import shutil
import subprocess
import sys

import pytest
from conftest import REPO, build_root

from chipbench import run, spec


def test_real_benchmark_cells_load():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(REPO, m["name"]))
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= {m["name"] for m in cell.end_to_end}
        assert (REPO / "chipbench" / "limits" / f"{w['name']}.json").is_file()


def test_added_files_are_found_by_name(tmp_path):
    root = build_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*") if p.is_file()}
    cb = root / "chipbench"
    conf = json.loads((cb / "configs" / "tiny.json").read_text())
    (cb / "configs" / "tiny2.json").write_text(json.dumps(dict(conf, num_hidden_layers=1)))
    (cb / "traffic" / "burst.json").write_text(json.dumps(
        {"kind": "poisson", "rate_per_s": 3.0, "prompt": {"median": 8, "sigma": 0.1,
         "min": 4, "max": 16}, "output": {"median": 4, "sigma": 0.1, "min": 2, "max": 8}}))
    (cb / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny2", "source": "test",
                             "file": "chipbench/configs/tiny2.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "tiny2.burst", "config": "tiny2", "traffic": "burst",
                               "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "requests_seen", "unit": "requests", "better": "higher",
                               "source": "host_clock", "layer": "scheduler",
                               "moves": "setup_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(root, "tiny2.burst")
    assert cell.config["num_hidden_layers"] == 1 and cell.traffic["rate_per_s"] == 3.0
    assert "requests_seen" in {m["name"] for m in cell.per_layer}  # moves setup_s: every cell
    assert spec.reader(root, "requests_seen")(type("R", (), {"requests": [1, 2]})) == 2
    assert all(p.read_bytes() == b for p, b in before.items())  # nothing edited
    with pytest.raises(KeyError):
        spec.load_cell(root, "tiny2.missing")
    with pytest.raises(FileNotFoundError):
        spec.reader(root, "no_such_metric")


def test_unknown_device_kind_is_refused():
    assert spec.peaks(REPO, "TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks(REPO, "TPU v9 imaginary")


def test_chip_check_refuses_the_cpu():
    cell = spec.load_cell(REPO, "qwen14b.chat")
    with pytest.raises(SystemExit) as e:
        run.chip(cell, REPO)
    assert e.value.code != 0


def _run(cwd, *extra):
    return subprocess.run([sys.executable, "chipbench/run.py", "--workload", "qwen14b.chat",
                           "--seed", "1", "--seconds", "1", *extra],
                          cwd=cwd, capture_output=True, text=True, timeout=120,
                          env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                               "HOME": str(cwd)})


def test_run_on_a_cpu_exits_nonzero_without_a_result(tmp_path):
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(REPO / "src")
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
    (tmp_path / "src").unlink()  # only BENCHMARK.json and the benchmark's files
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
