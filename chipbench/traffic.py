"""One general generator for every traffic mix in ``chipbench/traffic/``.

A mix is a JSON file of parameters.  Two kinds of arrival exist:

- ``poisson`` — open loop: requests fall due at exponential gaps with mean
  ``1 / rate_per_s``, whatever the server does;
- ``batch`` — an offline batch: the harness keeps ``backlog_per_slot`` x
  ``n_slots`` requests queued through the whole window.

Prompt and output lengths are lognormal (``median``, ``sigma``) clipped to
``[min, max]``.  ``prefix_tokens`` > 0 puts one shared system prompt of that
length in front of every prompt.

A mix is a fixed trace, replayed in every run: lengths and gaps are the
midpoint quantiles of their distributions, taken in blocks of ``block``
requests and shuffled inside each block by the mix's own ``trace_seed``, so
any run of whole blocks holds the same set of sizes and the same total time
between arrivals.  The run's seed draws the token ids (and, elsewhere, the
weights); it never changes the work.  With some tens of requests in a
window, a tail such as p90 TTFT depends on which long prompts arrive
together, so an order drawn per seed would spread the runs of one cell far
more than the chip does.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, Optional

import numpy as np

KINDS = ("poisson", "batch")


@dataclass(frozen=True)
class Request:
    due_s: Optional[float]  # offset from the window's start; None: batch
    prompt: list
    max_new: int


def _grid(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def lengths(spec: dict, block: int) -> np.ndarray:
    """The ``block`` midpoint quantiles of a clipped lognormal, as ints."""
    z = np.array([NormalDist().inv_cdf(q) for q in _grid(block)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(int)


def gaps(rate_per_s: float, block: int) -> np.ndarray:
    """Midpoint quantiles of an exponential, scaled to mean exactly 1/rate."""
    g = -np.log1p(-_grid(block))
    return g / g.mean() / rate_per_s


def validate(mix: dict, max_len: int) -> None:
    """Refuse a mix whose longest request cannot fit a lane."""
    if mix["kind"] not in KINDS:
        raise ValueError(f"traffic kind {mix['kind']!r} not in {KINDS}")
    longest = mix.get("prefix_tokens", 0) + mix["prompt"]["max"] + mix["output"]["max"]
    if longest > max_len:
        raise ValueError(f"longest request ({longest} tokens) exceeds max_len {max_len}")
    if mix["kind"] == "poisson" and not mix.get("rate_per_s", 0) > 0:
        raise ValueError("a poisson mix needs rate_per_s > 0")


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent numpy stream per use, from any non-negative seed."""
    return np.random.default_rng([seed, *map(ord, stream)])


def prefix(mix: dict, seed: int, vocab: int) -> list:
    n = mix.get("prefix_tokens", 0)
    return [int(t) for t in rng_for(seed, "prefix").integers(1, vocab, n)]


def generate(mix: dict, seed: int, vocab: int,
             seconds: Optional[float] = None) -> Iterator[Request]:
    """Requests in submission order.  A poisson mix stops at the first
    request due at or after ``seconds``; a batch mix never stops."""
    block = int(mix.get("block", 16))
    order = rng_for(int(mix["trace_seed"]), "traffic")
    tokens = rng_for(seed, "tokens")
    shared = prefix(mix, seed, vocab)
    p_len, o_len = lengths(mix["prompt"], block), lengths(mix["output"], block)
    poisson = mix["kind"] == "poisson"
    g = gaps(mix["rate_per_s"], block) if poisson else None
    t = 0.0
    while True:
        p_perm, o_perm = order.permutation(block), order.permutation(block)
        g_perm = order.permutation(block)
        for i in range(block):
            due = None
            if poisson:
                t += g[g_perm[i]]
                if seconds is not None and t >= seconds:
                    return
                due = t
            body = tokens.integers(1, vocab, p_len[p_perm[i]])
            yield Request(due, shared + [int(x) for x in body], int(o_len[o_perm[i]]))
