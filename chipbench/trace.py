"""Reduce a profiler trace (``.xplane.pb``) of the window to device metrics.

The trace holds, on the device plane, one event per run of a compiled
program ("XLA Modules", named ``jit_<function>(<hash>)``) and one per XLA
operation inside it ("XLA Ops", named by its HLO text), and on the host plane
the harness's own spans (``gen.submit``, ``engine.step``, ``gen.idle``).  The
window is the stretch the harness spans cover.  From these:

- busy seconds: the union of the program and operation intervals inside the
  window (a program that waits on its own DMA is still running);
- per program: summed device seconds and runs of the modules whose name
  holds the program's function name;
- the operations that took most time, and the longest idle gaps, each gap
  named by the harness span open at its middle.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict

from chipbench.stats import interval_union

SPANS = ("gen.submit", "engine.step", "gen.idle")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10
# control-flow ops span the ops of their bodies: left out of the op ranking
CONTAINERS = ("%while", "%conditional", "%call")


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _merged(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _short(module: str) -> str:
    """``jit_decode_step(1234)`` -> ``decode_step``."""
    return module.split("(")[0].removeprefix("jit_")


def reduce_events(spans: list, ops: list, modules: list, programs: dict,
                  n_devices: int = 1) -> dict:
    """Each event is ``(name, start_s, end_s)`` on one clock.  ``programs``
    maps a kind to the function name its modules carry.  Busy seconds are
    averaged over ``n_devices`` (``ops`` pooled from all of them)."""
    harness = [e for e in spans if e[0] in SPANS]
    if not harness or not ops:
        return {}
    w0, w1 = min(e[1] for e in harness), max(e[2] for e in harness)
    busy_iv = _merged(_clip([(s, e) for _, s, e in ops + modules], w0, w1))
    per_op = defaultdict(float)
    mods = sorted((s, e, _short(n)) for n, s, e in modules)
    starts = [s for s, _, _ in mods]
    for name, s, e in ops:
        op = name.split(" = ")[0]
        if e > w0 and s < w1 and not op.startswith(CONTAINERS):
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            per_op[f"{prog}:{op}"] += min(e, w1) - max(s, w0)
    prog_s, prog_n = defaultdict(float), defaultdict(int)
    for name, s, e in modules:
        if e <= w0 or s >= w1:
            continue
        for kind, fn in programs.items():
            if fn in name:
                prog_s[kind] += e - s
                prog_n[kind] += 1
    gaps = []
    edges = [w0] + [x for iv in busy_iv for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            label = next((n for n, s, e in harness if s <= mid < e), "between spans")
            gaps.append((label, b - a))
    return {
        "window_s": w1 - w0,
        "busy_s": interval_union(busy_iv) / n_devices,
        "program_s": dict(prog_s),
        "program_runs": dict(prog_n),
        "device_ops": sorted(([n, t] for n, t in per_op.items()), key=lambda x: -x[1])[:TOP],
        "idle_gaps": [[n, t] for n, t in sorted(gaps, key=lambda g: -g[1])[:TOP]],
    }


def events(path: str) -> tuple:
    """(host spans, device ops, device modules, device count) of one
    ``.xplane.pb``, in seconds on the profiler's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, ops, modules, n_dev = [], [], [], 0

    def grab(line, into):
        for e in line.events:
            into.append((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))

    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if MODULES_LINE not in lines:  # not a chip that runs programs
                continue
            n_dev += 1
            grab(lines[MODULES_LINE], modules)
            if OPS_LINE in lines:
                grab(lines[OPS_LINE], ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return spans, ops, modules, max(n_dev, 1)


def find(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]
