"""Find a poisson cell's knee once, on the chip: the highest arrival rate it
holds without a growing backlog.

    python3 chipbench/sweep.py --workload qwen14b.chat --rates 1,1.5,2,2.5 --seconds 60 \
        --trace-seeds 1,2

One process: the cell is set up once; then, for each trace seed (the order of
the mix's sizes and gaps), its mix runs at each rate, in rising order, for
``--seconds``, the engine drained between rates, until the first rate whose
backlog grows (the queue is not empty at the close and the last third's
median TTFT is over twice the first third's).  Per rate it prints the
requests due, time to first token (median and 90th percentile, requests not
yet served counted as misses), the median TTFT of the window's first and
last thirds (a backlog that grows makes the last far longer), and the queue
left at the close.  The cell's rate is then fixed in its traffic file at
about four fifths of the knee; the benchmark's own runs never search.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace-seeds", default=None, help="comma-separated; default: the mix's")
    a = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import run

    run.place_cache(ROOT)
    from chipbench import harness, spec, traffic
    from chipbench.stats import percentile

    cell = spec.load_cell(ROOT, a.workload)
    devices, peaks = run.chip(cell, ROOT)
    h = harness.Harness(cell, peaks, a.seed, run.log)
    h.setup(a.seconds)
    orders = a.trace_seeds.split(",") if a.trace_seeds else [cell.traffic["trace_seed"]]
    for order in (int(x) for x in orders):
        for k, r in enumerate(sorted(float(x) for x in a.rates.split(","))):
            h.mix = dict(cell.traffic, rate_per_s=r, trace_seed=order)
            h.todo = list(traffic.generate(h.mix, a.seed + k, h.cfg.vocab_size, a.seconds))
            h.run = harness.Run(cell, h.run.shape, peaks, h.run.n_slots)
            h.window(a.seconds)
            queued = h.engine.scheduler.pending()
            run_ = h.run
            due = run_.due_in_window()
            ttft = [q.ttft_s if q.first_token <= run_.t_end else float("inf")
                    for q in due]
            third = a.seconds / 3
            early = [t for q, t in zip(due, ttft) if q.due < run_.t0 + third]
            late = [t for q, t in zip(due, ttft) if q.due >= run_.t0 + 2 * third]
            growing = queued > 0 and percentile(late, 50) > 2 * percentile(early, 50)
            print(json.dumps({
                "trace_seed": order, "rate": r, "due": len(due), "queued_at_close": queued,
                "ttft_p50_ms": percentile(ttft, 50) * 1e3,
                "ttft_p90_ms": percentile(ttft, 90) * 1e3,
                "ttft_p50_first_third_ms": percentile(early, 50) * 1e3,
                "ttft_p50_last_third_ms": percentile(late, 50) * 1e3,
                "tokens_out": sum(len(run_.token_times(q)) for q in run_.requests),
                "growing": growing}), flush=True)
            h.engine.run()
            h.engine.reset_metrics()
            if growing:
                break
    return 0


if __name__ == "__main__":
    sys.exit(main())
