"""Profiler spans of the serving engine's host loop.

:meth:`ServeEngine.step` opens the spans named here with
``jax.profiler.TraceAnnotation``, so they land in the profiler's own trace on
the clock of the device planes: a reduction of the trace can say what the
host was doing whenever the device sat idle (``docs/serving.md#tracing``).
``serve.tick`` holds every other ``serve.*`` span of its tick.  Spans wrap
loops, never one lane or one token.  Metadata is encoded into the event's
name as ``name#k=v,k=v#``, so a value must hold no ``,`` or ``#``: request
ids (the :class:`~repro.serve.session.Session` ``rid``) are joined with
spaces.  With no profiler running a span costs about a microsecond.
``host.gc`` spans each Python garbage collection, from a ``gc.callbacks``
hook that the first engine of a process installs.
"""
from __future__ import annotations

import gc

import jax

TICK = "serve.tick"  # the whole of step(); metadata: tick
ADMIT = "serve.admit"  # cancellations, deadlines, admission (page plans, CoW copies)
PREFILL = "serve.prefill"  # one prefill flush; metadata: rids, tokens (positions fed)
PREFILL_CHUNK = "serve.prefill.chunk"  # one call's input slice and dispatch; metadata: lanes
PREFILL_SAMPLE = "serve.prefill.sample"  # one chunk's ending lanes: NaN check, sampler
GROW = "serve.grow"  # paged: every lane's page growth (and preemptions)
UPLOAD = "serve.upload"  # paged: the block table copied to the device
DECODE = "serve.decode"  # the decode step's dispatch; metadata: lanes
DECODE_SYNC = "serve.decode.sync"  # NaN guard, sampler, wait for the next tokens
BOOKKEEP = "serve.bookkeep"  # quarantine, positions, token records, finalize
GC = "host.gc"  # one garbage collection; metadata: generation
SPANS = (TICK, ADMIT, PREFILL, PREFILL_CHUNK, PREFILL_SAMPLE, GROW, UPLOAD, DECODE,
         DECODE_SYNC, BOOKKEEP, GC)

span = jax.profiler.TraceAnnotation


def rids(sessions) -> str:
    """Request ids as one metadata value (space-separated)."""
    return " ".join(str(s.rid) for s in sessions)


_gc_open: list = []  # the span of the collection running now


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        s = span(GC, generation=info["generation"])
        s.__enter__()
        _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def install_gc_hook() -> None:
    """Span every garbage collection of the process (once, however often
    called)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
