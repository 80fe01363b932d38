"""Reduction of a profiler trace: known unions, programs, gaps by span."""
import glob

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace


def test_reduce_synthetic_events():
    spans = [("gen.submit", 0.0, 0.1), ("engine.step", 0.1, 2.0), ("gen.idle", 2.0, 3.0),
             ("engine.step", 3.0, 4.0), ("other", -5.0, 10.0)]
    ops = [("fusion.1", 0.2, 0.6), ("fusion.2", 0.5, 1.0),  # overlap: union 0.8
           ("dot.3", 1.5, 1.9), ("fusion.1", 3.2, 3.5),
           ("dot.3", 3.9, 4.5)]  # clipped at the window's end (4.0)
    modules = [("jit_decode_step", 0.2, 1.0), ("jit_decode_chunk_paged", 1.5, 1.9),
               ("jit_decode_step", 3.2, 3.5), ("jit_decode_step", 5.0, 6.0)]
    r = trace.reduce_events(spans, ops, modules,
                            {"decode": "decode_step", "prefill": "decode_chunk"})
    assert r["window_s"] == pytest.approx(4.0)
    assert r["busy_s"] == pytest.approx(0.8 + 0.4 + 0.3 + 0.1)
    assert r["program_s"] == pytest.approx({"decode": 1.1, "prefill": 0.4})
    assert r["program_runs"] == {"decode": 2, "prefill": 1}
    assert r["device_ops"][0] == ["decode_step:fusion.1", pytest.approx(0.7)]
    # idle: 0-0.2 submit/step, 1.0-1.5 step, 1.9-3.2 mostly idle, 3.5-3.9 step
    gaps = r["idle_gaps"]
    assert gaps[0] == ["gen.idle", pytest.approx(1.3)]
    assert sum(g for _, g in gaps) == pytest.approx(4.0 - r["busy_s"])
    assert {n for n, _ in gaps} <= {"gen.submit", "engine.step", "gen.idle"}


def test_nothing_to_reduce_without_spans_or_ops():
    assert trace.reduce_events([], [("x", 0, 1)], [], {}) == {}
    assert trace.reduce_events([("engine.step", 0, 1)], [], [], {}) == {}


def test_recorded_trace_holds_the_harness_spans(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("engine.step"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("gen.idle"):
            pass
    jax.profiler.stop_trace()
    spans, ops, modules, n_dev = trace.events(trace.find(str(tmp_path)))
    assert [s[0] for s in spans] == ["engine.step", "gen.idle"] * 2
    assert all(e > s for _, s, e in spans) and n_dev >= 1
    with pytest.raises(FileNotFoundError):
        trace.find(str(tmp_path / "none"))
    assert glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
