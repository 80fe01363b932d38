"""Due time to first token, 90th percentile over every request due in the
window; a request never served counts as missing every limit."""
from chipbench.stats import percentile


def read(run):
    ttft = [r.ttft_s for r in run.due_in_window()]
    value = percentile(ttft, 90) * 1e3
    return value if ttft and value != float("inf") else None
