"""Every gap between two consecutive output tokens of a request inside the
window, 99th percentile: the freeze an admission's prefill causes."""
from chipbench.stats import percentile


def read(run):
    gaps = []
    for r in run.requests:
        t = run.token_times(r)
        gaps += [b - a for a, b in zip(t, t[1:])]
    return percentile(gaps, 99) * 1e3 if gaps else None
