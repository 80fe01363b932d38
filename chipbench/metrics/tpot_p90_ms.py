"""Time per output token: per request, (last token - first token) / (tokens - 1)
over its tokens inside the window; 90th percentile over those requests."""
from chipbench.stats import percentile


def read(run):
    per = []
    for r in run.requests:
        t = run.token_times(r)
        if len(t) >= 2:
            per.append((t[-1] - t[0]) / (len(t) - 1))
    return percentile(per, 90) * 1e3 if per else None
