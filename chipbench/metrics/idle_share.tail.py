"""Device: 1 - (union of busy operation intervals / traced window)."""


def read(run):
    return run.idle_pct()
