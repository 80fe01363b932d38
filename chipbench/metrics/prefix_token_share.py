"""Paged KV: share of the prompt tokens admitted in the window whose KV came
from shared prefix pages instead of prefill."""


def read(run):
    m = run.engine_metrics
    admitted = m.prefix_tokens_reused + m.prefill_tokens
    return 100.0 * m.prefix_tokens_reused / admitted if m.prefix_tokens_reused else None
