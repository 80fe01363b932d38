"""Set-up seconds: process start to the window's start (loading, weights from
the seed, engine, compilation or persistent-cache reads, warm-up)."""


def read(run):
    return run.setup_s
