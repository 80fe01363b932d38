"""The compiled prefill chunk step: roofline time of its real work (counts.py)
over its device time in the trace."""


def read(run):
    return run.roofline_pct("prefill")
