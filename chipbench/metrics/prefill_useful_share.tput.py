"""Scheduler: share of the lane-positions the window's prefill chunk calls
computed that held a real prompt token (EngineMetrics prefill_tokens over
prefill_slots; pad lanes and positions are the rest)."""


def read(run):
    m = run.engine_metrics
    computed = getattr(m, "prefill_slots", 0)  # a program without the counter reads None
    return 100.0 * m.prefill_tokens / computed if computed else None
