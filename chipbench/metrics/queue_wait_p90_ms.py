"""Scheduler: due time to admission into a lane, 90th percentile over the
requests due in the window (first admission)."""
from chipbench.stats import percentile


def read(run):
    waits = [r.session.stats.admitted_at - r.due for r in run.due_in_window()
             if r.session.stats.admitted_at is not None]
    return percentile(waits, 90) * 1e3 if waits else None
