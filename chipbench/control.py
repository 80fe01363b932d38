"""Readings that a cell's correctness limit is set from, on the chip.

    python3 chipbench/control.py --workload qwen14b.chat --seeds 1-12 --seconds 20

For each seed, in one process (one compilation): a run of the cell at its own
load for ``--seconds``, then the sample of what it served compared with the
float32 reference (the program's reading), and the same sample's tokens read
by the fp8 control (the control's reading), which takes the program's place
in the run's checks: every seed's ``correct`` has to come out false.  The
limit in ``chipbench/limits/<cell>.json`` lies between the largest program
reading and the smallest control reading.  The benchmark's own runs never run
this.  Exits non-zero where the control passed on any seed.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seeds(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True, help="e.g. 1-12")
    ap.add_argument("--seconds", type=float, default=20.0)
    a = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import run

    run.place_cache(ROOT)
    rows = []
    for s in a.seeds:
        args = run.parse(["--workload", a.workload, "--seed", str(s),
                          "--seconds", str(a.seconds)])
        res = run.measure(args, control=True)
        rows.append({"seed": s, "program": res["program_gap"],
                     "control": res["checks"]["max_logit_gap"]["value"],
                     "control_correct": res["correct"],
                     "sampled_tokens": res["checks"]["sampled_tokens"]["value"]})
        print(json.dumps(rows[-1]), flush=True)
    prog = [r["program"] for r in rows]
    ctl = [r["control"] for r in rows]
    passed = [r["seed"] for r in rows if r["control_correct"]]
    print(json.dumps({"workload": a.workload, "seeds": len(rows), "program_max": max(prog),
                      "control_min": min(ctl), "ratio": min(ctl) / max(prog),
                      "control_passed_on": passed}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
