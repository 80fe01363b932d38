"""The serving engine's profiler spans (``repro.serve.tracing``) and its
prefill counter: every span of the table shows in a trace, nests inside its
tick, names the admitted requests, is opened per loop and not per lane, and
leaves the tokens as they were; ``prefill_slots`` counts every computed
lane-position."""
import gc
import glob
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import EngineConfig, ServeEngine, tracing


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2.5-14b").reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, cfg.vocab_size, n)] for n in lens]


def _serve(model, params, prompts, n_slots=4, page_size=None):
    eng = ServeEngine(model, params, EngineConfig(
        n_slots=n_slots, max_len=48, prefill_chunk=8, page_size=page_size, guard="off"))
    sessions = [eng.submit(p, 5) for p in prompts]
    eng.run()
    return eng, sessions


def _traced(path, fn):
    """``fn()``'s result and the engine spans of its trace: (name, start_ns,
    end_ns, metadata) in start order."""
    jax.profiler.start_trace(str(path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        sorted(glob.glob(f"{path}/plugins/profile/*/*.xplane.pb"))[-1])
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, {k: v for k, v in e.stats})
             for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events if e.name in tracing.SPANS]
    return out, sorted(spans, key=lambda s: s[1])


@pytest.mark.parametrize("page_size", [None, 8])
def test_engine_spans_nest_in_ticks_and_name_the_requests(qwen, tmp_path, page_size):
    cfg, model, params = qwen
    prompts = _prompts(cfg, [5, 13, 9, 20, 3, 11])
    _, plain = _serve(model, params, prompts, page_size=page_size)

    def run():
        eng, sessions = _serve(model, params, prompts, page_size=page_size)
        gc.collect()  # a collection inside the trace
        return eng, sessions

    (eng, sessions), spans = _traced(tmp_path, run)
    names = {s[0] for s in spans}
    paged_only = {tracing.GROW, tracing.UPLOAD}
    assert names == (set(tracing.SPANS) if page_size else set(tracing.SPANS) - paged_only)
    ticks = [s for s in spans if s[0] == tracing.TICK]
    assert [s[3]["tick"] for s in ticks] == list(range(1, eng.tick + 1))
    for name, t0, t1, _ in spans:
        if name.startswith("serve.") and name != tracing.TICK:
            assert any(a <= t0 and t1 <= b for _, a, b, _ in ticks), name
    fed = [s[3] for s in spans if s[0] == tracing.PREFILL]
    assert sorted(int(r) for m in fed for r in str(m["rids"]).split()) == \
        sorted(s.rid for s in sessions)
    assert sum(m["tokens"] for m in fed) == eng.metrics.prefill_tokens == sum(map(len, prompts))
    assert [s.out for s in sessions] == [s.out for s in plain]  # tokens unchanged


@pytest.mark.parametrize("page_size", [None, 8])
def test_span_count_per_tick_does_not_grow_with_lanes(qwen, tmp_path, page_size):
    """Every lane busy, 2 lanes against 8: the same ticks open the same spans."""
    cfg, model, params = qwen
    counts = []
    for n in (2, 8):
        prompts = _prompts(cfg, [12] * n, seed=n)
        (eng, _), spans = _traced(tmp_path / str(n), lambda: _serve(
            model, params, prompts, n_slots=n, page_size=page_size))
        counts.append((eng.tick, Counter(s[0] for s in spans if s[0] != tracing.GC)))
    assert counts[0] == counts[1]


def test_prefill_slots_count_every_computed_position(qwen):
    cfg, model, params = qwen
    prompts = _prompts(cfg, [5, 13, 9, 20, 3, 11], seed=2)
    eng = ServeEngine(model, params, EngineConfig(n_slots=4, max_len=48, prefill_chunk=8,
                                                  guard="off"))
    calls = []
    chunk = eng._chunk
    eng._chunk = lambda *a: calls.append(1) or chunk(*a)
    for p in prompts:
        eng.submit(p, 4)
    eng.run()
    m = eng.metrics
    assert m.prefill_slots == len(calls) * 4 * 8 > m.prefill_tokens == sum(map(len, prompts))
    assert not hasattr(m, "prefill_requests")


def test_gc_hook_is_installed_once(qwen):
    cfg, model, params = qwen
    for _ in range(2):
        ServeEngine(model, params, EngineConfig(n_slots=2, max_len=16))
    assert gc.callbacks.count(tracing._on_gc) == 1
