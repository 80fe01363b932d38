"""Window arithmetic: rates over the whole window, tails over every request
and every gap, requests timed from their due time."""
import math
from types import SimpleNamespace

import numpy as np
import pytest
from conftest import REPO

from chipbench import harness, spec, stats


def session(tokens, admitted=None):
    st = SimpleNamespace(token_times=list(tokens), admitted_at=admitted,
                         first_token_at=tokens[0] if tokens else None)
    return SimpleNamespace(stats=st)


def make_run():
    shape = spec.family(REPO, {"family": "dense_gqa"}, "test").Shape(1, 8, 2, 1, 4, 16, 32, False)
    run = harness.Run(cell=None, shape=shape, peaks={}, n_slots=4, t0=100.0,
                      t_close=110.0, t_end=111.0)
    # due 100, first token 100.5, tokens every 0.1 s until 101.0
    run.requests.append(harness.Req(100.0, 100.2, 6, session(
        [100.5, 100.6, 100.7, 100.8, 100.9, 101.0], admitted=100.3)))
    # due 105, first token 107, gaps 1 s, one token after the window's end
    run.requests.append(harness.Req(105.0, 105.0, 4, session(
        [107.0, 108.0, 109.0, 112.0], admitted=106.0)))
    # due 109.5, never served
    run.requests.append(harness.Req(109.5, 109.5, 4, session([])))
    # due after the close: not the window's
    run.requests.append(harness.Req(110.5, 110.5, 4, session([110.9], admitted=110.6)))
    run.engine_metrics = SimpleNamespace(prefill_tokens=30, decode_s=[0.1, 0.3],
                                         prefill_s=[0.5, 1.0], occupancy=[2, 4],
                                         prefix_tokens_reused=10)
    return run


def read(name, run):
    return spec.reader(REPO, name)(run)


def test_percentile_matches_numpy_and_ranks_inf_last():
    xs = list(np.random.default_rng(0).random(101))
    for q in (50, 90, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([1.0, 2.0, math.inf], 50) == 2.0
    assert stats.percentile([1.0, math.inf, math.inf], 90) == math.inf
    assert math.isnan(stats.percentile([], 50))


def test_union_and_rate():
    assert stats.interval_union([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.interval_union([]) == 0
    assert stats.rate(10, 4) == 2.5
    with pytest.raises(ValueError):
        stats.rate(1, 0)


def test_ttft_from_due_time_with_unserved_as_a_miss():
    run = make_run()
    assert [r.ttft_s for r in run.due_in_window()] == [0.5, 2.0, math.inf]
    # p90 of (0.5, 2.0, inf) lies beyond every finite sample: no number
    assert read("ttft_p90_ms", run) is None
    run.requests[2].session.stats.first_token_at = 110.5
    assert read("ttft_p90_ms", run) == pytest.approx(stats.percentile([0.5, 2.0, 1.0], 90) * 1e3)


def test_tpot_and_itl_over_tokens_inside_the_window():
    run = make_run()
    # request 1: 0.1 s per token; request 2: (109 - 107) / 2; request 4: one token
    assert read("tpot_p90_ms", run) == pytest.approx(stats.percentile([0.1, 1.0], 90) * 1e3)
    gaps = [0.1] * 5 + [1.0, 1.0]  # 109 -> 112 falls outside the window
    assert read("itl_p99_ms", run) == pytest.approx(stats.percentile(gaps, 99) * 1e3)


def test_rates_take_all_the_work_over_all_the_window():
    run = make_run()
    out = 6 + 3 + 0 + 1  # output tokens stamped at or before t_end
    assert read("tokens_per_s", run) == pytest.approx((30 + out) / 11.0)
    assert read("decode_step_ms", run) == pytest.approx(200.0)
    assert read("prefill_tok_s", run) == pytest.approx(20.0)
    assert read("lane_occupancy", run) == pytest.approx(75.0)
    assert read("prefix_token_share", run) == pytest.approx(25.0)
    assert read("queue_wait_p90_ms", run) == pytest.approx(stats.percentile([0.3, 1.0], 90) * 1e3)


def test_traced_readers_find_nothing_without_a_trace():
    run = make_run()
    for name in ("mfu.tail", "decode_roofline", "idle_share.tail"):
        assert read(name, run) is None


def test_sample_takes_the_longest_then_half_the_lanes():
    from chipbench import check

    longest = ([1] * 50, [2] * 30, 30, check.COMPLETE, 3)
    rest = [([1] * 5, [2] * 4, 4, check.COMPLETE, lane % 16) for lane in range(64)]
    cut = ([1] * 99, [2] * 2, 9, "cancelled", 5)  # unfinished: never compared
    for seed in (0, 1, 2**31 + 5):
        picked = check.sample([*rest, cut, longest], seed, 16)
        assert picked[0] == longest[:2] + (3,)
        assert len({lane for _, _, lane in picked}) >= 8
        assert len(picked) <= check.SAMPLE_MAX and all(o != [2] * 2 for _, o, _ in picked)
    assert check.sample([cut], 0, 16) == []
