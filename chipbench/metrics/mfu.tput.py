"""Whole step: model FLOPs of the real tokens processed in the window (from
chipbench/counts.py) over the window at the chip's bf16 peak."""


def read(run):
    return run.mfu_pct()
