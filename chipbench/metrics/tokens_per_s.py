"""Prompt tokens prefilled plus output tokens emitted inside the window, over
the whole window."""
from chipbench.stats import rate


def read(run):
    out = sum(len(run.token_times(r)) for r in run.requests)
    return rate(run.engine_metrics.prefill_tokens + out, run.window_s)
