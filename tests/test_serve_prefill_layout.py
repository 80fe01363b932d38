"""The two prefill layouts of ``ServeEngine._prefill``: all lanes x
``prefill_chunk`` positions a call, or one lane x ``n_slots * prefill_chunk``
positions.  Both give the same tokens, the rule takes whichever needs fewer
calls, the first flush compiles both, and a caller that wraps the chunk step
reads real positions only from rows whose lane holds a session."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import EngineConfig, ServeEngine


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2.5-14b").reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, cfg.vocab_size, n)] for n in lens]


def _engine(model, params, **kw):
    kw = {"n_slots": 3, "max_len": 40, "prefill_chunk": 4, "guard": "off", **kw}
    return ServeEngine(model, params, EngineConfig(**kw))


def _scenario(cfg, model, params, scenario, page_size, layout):
    """Serve one scenario with every flush forced to ``layout``."""
    kw = {"page_size": page_size}
    lens, max_new = [5, 17, 3, 9, 26, 4], 6
    if scenario == "preempt":  # a pool too small for every lane
        kw.update(max_len=24, n_pages=8)
        lens, max_new = [5, 9, 3, 7, 11, 4], 8
    eng = _engine(model, params, **kw)
    eng._single_lane = lambda spans: layout == "lane"
    prompts = _prompts(cfg, lens, seed=3)
    if scenario == "fork":
        pfx = _prompts(cfg, [10], seed=4)[0]
        eng.register_prefix(pfx)
        prompts = [pfx + p[:8] for p in prompts]
    sessions = [eng.submit(p, max_new) for p in prompts]
    if scenario == "drain":  # every live session resumes from prompt + output
        for _ in range(3):
            eng.step()
        drained = eng.drain()
        assert any(s.out for s in drained)
        for s in drained:
            eng.requeue(s)
    eng.run()
    assert all(s.done for s in sessions)
    return eng, [s.out for s in sessions]


@pytest.mark.parametrize("scenario,page_size", [
    ("ragged", None), ("drain", None), ("ragged", 4), ("preempt", 4), ("fork", 4),
])
def test_layouts_give_identical_tokens(qwen, scenario, page_size):
    cfg, model, params = qwen
    lane_eng, lane = _scenario(cfg, model, params, scenario, page_size, "lane")
    all_eng, every = _scenario(cfg, model, params, scenario, page_size, "all")
    assert lane == every
    assert lane_eng.metrics.prefill_lane_calls > 0 == all_eng.metrics.prefill_lane_calls
    if scenario == "preempt":
        assert lane_eng.metrics.preemptions > 0 and all_eng.metrics.preemptions > 0
    if scenario == "fork":
        assert lane_eng.metrics.prefix_hits == all_eng.metrics.prefix_hits == 6


@pytest.mark.parametrize("lens,single,calls", [
    ([384], True, 1),  # one long prompt: 1 call of 512 against 12 of 16 x 32
    ([64, 64, 64], False, 2),  # a burst of short ones: 2 calls against 3
    ([64, 64], True, 2),  # a tie goes to single-lane
])
def test_rule_takes_fewer_calls(qwen, lens, single, calls):
    cfg, model, params = qwen
    eng = _engine(model, params, n_slots=16, max_len=400, prefill_chunk=32)
    assert eng._single_lane(lens) is single
    for p in _prompts(cfg, lens):
        eng.submit(p, 1)
    eng.run()
    m = eng.metrics
    assert m.prefill_slots == calls * 16 * 32
    assert m.prefill_lane_calls == (calls if single else 0)
    assert m.prefill_tokens == sum(lens)


def test_one_slot_has_one_shape(qwen):
    _, model, params = qwen
    eng = _engine(model, params, n_slots=1)
    assert not eng._single_lane([30])


@pytest.fixture
def compiles():
    seen = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    yield seen
    jax.monitoring.unregister_event_duration_listener(on)


@pytest.mark.parametrize("page_size,first,max_len", [
    (None, "lane", 41), (None, "all", 45), (4, "lane", 49), (4, "all", 53),
])
def test_first_flush_compiles_both_shapes(qwen, compiles, page_size, first, max_len):
    """Each case has a cache shape of its own (``max_len``), so nothing an
    earlier test compiled is found."""
    cfg, model, params = qwen
    eng = _engine(model, params, n_slots=4, page_size=page_size, max_len=max_len)
    burst, long = _prompts(cfg, [3, 3, 3, 3]), _prompts(cfg, [30], seed=1)
    assert not eng._single_lane([3, 3, 3, 3]) and eng._single_lane([30])
    for p in (long if first == "lane" else burst):
        eng.submit(p, 3)
    eng.run()
    before = eng.metrics.prefill_lane_calls
    compiles.clear()
    for p in (burst if first == "lane" else long):  # the other shape
        eng.submit(p, 3)
    eng.run()
    assert compiles == []
    assert (eng.metrics.prefill_lane_calls > before) is (first == "all")


@pytest.mark.parametrize("page_size", [None, 4])
def test_wrapped_chunk_reads_real_rows_of_live_slots(qwen, page_size):
    """A wrapper of ``_chunk`` that reads each call's positions row by row
    and looks the row up as a lane, as a per-call work counter does."""
    cfg, model, params = qwen
    eng = _engine(model, params, n_slots=4, page_size=page_size)
    chunk, pad, rows = eng._chunk, eng._pad_pos, []

    def on_chunk(*args):
        pos = np.asarray(args[-1])
        for lane, row in enumerate(pos):
            real = row[row < pad]
            if real.size:
                assert eng.slots[lane] is not None
                rows.append((pos.shape[0], lane, real.size))
        return chunk(*args)

    eng._chunk = on_chunk
    prompts = _prompts(cfg, [30, 5, 5, 20, 9], seed=2)
    # one flush of three lanes in single-lane calls; lane 0 finishes on the
    # token its prefill samples
    for p, max_new in zip(prompts[:3], (1, 6, 6)):
        eng.submit(p, max_new)
    for _ in range(3):
        eng.step()
    for p in prompts[3:]:  # admitted beside lanes that are decoding
        eng.submit(p, 6)
    eng.run()
    assert {(n, lane) for n, lane, _ in rows} == {(1, 0)}
    assert sum(k for _, _, k in rows) == eng.metrics.prefill_tokens == sum(map(len, prompts))
    assert eng.metrics.prefill_lane_calls == len(rows)


def test_register_prefix_feeds_single_lane_calls(qwen):
    cfg, model, params = qwen
    eng = _engine(model, params, page_size=4)
    chunk, shapes = eng._chunk, []
    eng._chunk = lambda *a: shapes.append(a[-1].shape) or chunk(*a)
    eng.register_prefix(_prompts(cfg, [20])[0])
    assert shapes == [(1, 12), (1, 12)]  # 20 tokens in calls of 3 x 4
