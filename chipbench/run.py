"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload qwen14b.chat --seed 7 --seconds 51 --trace 0

From the root of a checkout that holds ``BENCHMARK.json``, ``chipbench/``
and the program (``src/``).  Set-up (weights from the seed, the engine, every
shape compiled or read from the persistent cache in ``<checkout>/.jax_cache``)
counts as ``setup_s``; then the cell's traffic runs for ``--seconds``; then a
sample of what was served is compared with the float32 reference.  With
``--trace 1`` the window is profiled and the cell's per-layer metrics are
printed instead of its end-to-end ones.

The last line of stdout is one JSON object.  The run exits non-zero and
prints no result when JAX finds no TPU, fewer chips than the cell asks for,
or a device kind that ``chipbench/peaks.json`` does not list.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the engine's two compiled steps, by the function names their modules carry
PROGRAMS = {"decode": "decode_step", "prefill": "decode_chunk"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Refused(SystemExit):
    """The run cannot measure what the cell asks for: no result is printed."""

    def __init__(self, why: str):
        super().__init__(f"chipbench: {why}")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def place_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the machine sets, so later runs of a cell there find it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def chip(cell, root: Path):
    """(devices, peaks) of the chip this run measures, or refuse."""
    import jax

    from chipbench import spec

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU found (JAX platform {devices[0].platform!r}); "
                      "the benchmark measures the chip and does not fall back")
    if len(devices) < cell.chips:
        raise Refused(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
    try:
        return devices, spec.peaks(root, devices[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None


def measure(args, root: Path = ROOT, chip_fn=chip, control: bool = False) -> dict:
    """One run of a cell; returns the result object.  ``chip_fn`` finds the
    chip and its peaks (tests pass one that accepts the CPU).  With
    ``control`` the fp8 control's choices are what the checks read, and the
    program's own widest gap goes under ``program_gap``."""
    import jax

    from chipbench import check, harness, spec, trace

    cell = spec.load_cell(root, args.workload)
    devices, peaks = chip_fn(cell, root)
    dev = devices[0]
    compiles = harness.CompileCounter()
    h = harness.Harness(cell, peaks, args.seed, log)
    h.setup(args.seconds)
    h.run.setup_s = time.perf_counter() - T_START
    log(f"set-up {h.run.setup_s:.2f}s: {compiles.compiles} compiles "
        f"({compiles.seconds:.1f}s), {compiles.cache_hits} persistent-cache hits")
    before = compiles.compiles
    trace_dir = root / ".chipbench" / f"trace-{args.workload}-{args.seed}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # a span per Python call would slow the host
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    h.window(args.seconds, record_calls=bool(args.trace))
    log(f"window {h.run.window_s:.2f}s: {compiles.compiles - before} compiles inside it")
    h.wait_first_tokens()  # at once: a request's wait counts from its due time
    if args.trace:
        jax.profiler.stop_trace()
        t = time.perf_counter()
        spans, ops, modules, n_dev = trace.events(trace.find(str(trace_dir)))
        h.run.trace = trace.reduce_events(spans, ops, modules, PROGRAMS, n_dev)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr = h.run.trace
        log(f"trace reduced in {time.perf_counter() - t:.1f}s: busy {tr.get('busy_s')} s of "
            f"{tr.get('window_s')} s; program seconds {tr.get('program_s')}, "
            f"runs {tr.get('program_runs')}")
    late = sorted(r.submitted - r.due for r in h.run.requests)
    if late:
        log(f"generator: {len(late)} requests submitted, late by at most "
            f"{late[-1] * 1e3:.1f} ms (median {late[len(late) // 2] * 1e3:.1f} ms)")
    stats = dev.memory_stats() or {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.reader(root, m["name"])(h.run)
        if value is None:
            log(f"metric {m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    run = h.run
    attempted = len(run.due_in_window()) if cell.traffic["kind"] == "poisson" else len(run.requests)
    failed = sum(1 for r in run.due_in_window() if r.first_token == float("inf"))
    h.free()
    readings = h.correctness(control=control)
    if control:  # the control's choices take the program's place in every check
        program_gap = readings["max_logit_gap"]
        readings["max_logit_gap"] = readings.get("control_gap", float("inf"))
    lim = check.limits(root, cell.name)
    checks = {
        "max_logit_gap": {"value": readings["max_logit_gap"],
                          "limit": lim["max_logit_gap"]["limit"]},
        "sampled_tokens": {"value": readings["sampled_tokens"],
                           "limit": lim["min_sampled_tokens"], "at_least": True},
        "never_served": {"value": failed, "limit": 0},
        "incomplete": {"value": readings["incomplete"], "limit": 0},
        "fallbacks": {"value": h.fallbacks, "limit": 0},
    }
    correct = all(c["value"] >= c["limit"] if c.get("at_least") else c["value"] <= c["limit"]
                  for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    if control:
        result["program_gap"] = program_gap
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {'>=' if c.get('at_least') else '<='} "
            f"{c['limit']})")
    result["checks"] = {n: {"value": c["value"], "limit": c["limit"]} for n, c in checks.items()}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    place_cache(ROOT)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    result = measure(args)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
