"""Rehearsal of whole cell runs at a tiny size on the CPU: the loop, the
result line, and ``correct`` from the reference comparison, which the fp8
control fails."""
import json

import pytest
from conftest import cpu_chip

from chipbench import run


def measure(root, cell, seed, trace=0, control=False):
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                      "--trace", str(trace)])
    return run.measure(args, root=root, chip_fn=cpu_chip, control=control)


@pytest.mark.parametrize("cell,trace", [("tiny.chat", 0), ("tiny.docs", 1), ("tiny.prefix", 0),
                                        ("tiny.prefix", 1)])
def test_cell_runs_correct_with_its_metrics(tiny_root, cell, trace):
    res = measure(tiny_root, cell, 2**31 + 17, trace=trace)
    json.dumps(res)  # one JSON object
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[kind] if cell in m.get("workloads", [cell])}
    # device-trace readers find nothing on the CPU and leave their metric out
    host = {m for m in want if not m.startswith(("idle_share", "decode_roof", "prefill_roof"))}
    assert host <= set(res["metrics"]) <= want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"


def test_control_fails_where_the_program_passes(tiny_root):
    res = measure(tiny_root, "tiny.chat", 5, control=True)
    limit = res["checks"]["max_logit_gap"]["limit"]
    assert res["program_gap"] <= limit
    assert res["checks"]["max_logit_gap"]["value"] > limit
    assert not res["correct"]
