"""Model step: prompt tokens fed to prefill over the summed host time of the
window's prefill flushes (EngineMetrics.prefill_s)."""


def read(run):
    m = run.engine_metrics
    return m.prefill_tokens / sum(m.prefill_s) if m.prefill_s else None
