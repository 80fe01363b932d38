"""Model step: host time of the compiled decode step and sampler, ending in
block_until_ready (EngineMetrics.decode_s), averaged over the window's ticks."""


def read(run):
    d = run.engine_metrics.decode_s
    return 1e3 * sum(d) / len(d) if d else None
