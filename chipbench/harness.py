"""The cell loop: set-up, the measured window, the check against the reference.

The program under test is ``ServeEngine``, built as ``repro.launch.serve``
builds it (``EngineConfig`` defaults, ``guard="off"``, greedy sampler) with
the cell's settings.  The harness drives it from one thread: before every
``step()`` it submits each request whose due time has passed, and it times
every request from that due time, never from ``submit``.
"""
from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from chipbench import check, counts, traffic, weights

# requests that never got a first token are waited for this long after the close
FIRST_TOKEN_WAIT_S = 60.0


class CompileCounter:
    """Backend compilations and persistent-cache hits, from JAX's events."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclass
class Req:
    """One request as its client sees it (absolute perf_counter seconds)."""
    due: float
    submitted: float
    max_new: int
    session: object  # repro.serve.Session

    @property
    def first_token(self) -> float:
        t = self.session.stats.first_token_at
        return math.inf if t is None else t

    @property
    def ttft_s(self) -> float:
        return self.first_token - self.due


@dataclass
class Call:
    """One call of a compiled step, with the real work in it (traced runs)."""
    kind: str  # "decode" | "prefill"
    flops: int
    nbytes: int


@dataclass
class Run:
    """Everything a metric reader may read."""
    cell: object  # spec.Cell
    shape: object  # the family's Shape
    peaks: dict
    n_slots: int
    t0: float = 0.0
    t_close: float = 0.0  # t0 + seconds: requests due before it are the window's
    t_end: float = 0.0  # the end of the last step begun before the close
    requests: list = field(default_factory=list)
    engine_metrics: object = None  # EngineMetrics of the window alone
    setup_s: float = 0.0
    calls: Optional[list] = None  # traced runs only
    trace: Optional[dict] = None  # trace.reduce_events() of the window, traced runs only

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def due_in_window(self) -> list:
        return [r for r in self.requests if r.due < self.t_close]

    def token_times(self, r: Req) -> list:
        """``r``'s output token stamps that fall inside the window."""
        return [t for t in r.session.stats.token_times if t <= self.t_end]

    # -- shared by several readers (traced runs) ---------------------------
    def mfu_pct(self) -> Optional[float]:
        """Model FLOPs of every real token the window's calls processed, over
        the window at the chip's bf16 peak."""
        if not self.calls:
            return None
        flops = sum(c.flops for c in self.calls)
        return 100.0 * flops / (self.window_s * self.peaks["bf16_flops"])

    def idle_pct(self) -> Optional[float]:
        if not self.trace:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])

    def roofline_pct(self, kind: str) -> Optional[float]:
        """Mean roofline time of the program's calls over its mean device
        time per run in the trace."""
        calls = [c for c in self.calls or () if c.kind == kind]
        runs = (self.trace or {}).get("program_runs", {}).get(kind, 0)
        if not calls or not runs:
            return None
        least = sum(counts.roofline_s(c.flops, c.nbytes, self.peaks["bf16_flops"],
                                      self.peaks["hbm_bytes_per_s"])[0] for c in calls)
        return 100.0 * (least / len(calls)) / (self.trace["program_s"][kind] / runs)


class Harness:
    """One cell on one chip: ``setup``, ``window``, ``wait_first_tokens``,
    ``free``, then ``correctness``."""

    def __init__(self, cell, peaks: dict, seed: int, log):
        self.cell, self.peaks, self.seed, self.log = cell, peaks, seed, log
        self.mix = cell.traffic

    # -- set-up -----------------------------------------------------------
    def setup(self, seconds: float) -> None:
        from repro.models import build_model
        from repro.serve import EngineConfig, ServeEngine

        family = self.cell.family
        self.cfg = family.program_config(self.cell.config)
        self.model = build_model(self.cfg)
        self.shapes = jax.eval_shape(self.model.init, jax.random.key(0))
        params = weights.make(self.shapes, self.seed, family.leaf_std)
        self.engine = ServeEngine(self.model, params,
                                  EngineConfig(**self.cell.config["engine"], guard="off"))
        del params
        traffic.validate(self.mix, self.engine.cfg.max_len)
        vocab = self.cfg.vocab_size
        shared = traffic.prefix(self.mix, self.seed, vocab)
        if shared:
            self.engine.register_prefix(shared)
        # warm up every shape the window uses: the prefill chunk, the decode
        # step and the host-side bookkeeping ops, on the cell's own paths
        rng = traffic.rng_for(self.seed, "warmup")
        for _ in range(2):
            body = rng.integers(1, vocab, self.mix["prompt"]["min"])
            self.engine.submit(shared + [int(t) for t in body], 2)
        self.engine.run()
        self.engine.reset_metrics()
        if self.mix["kind"] == "poisson":
            self.todo = list(traffic.generate(self.mix, self.seed, vocab, seconds))
        else:
            self.todo = traffic.generate(self.mix, self.seed, vocab)
        self.backlog = int(self.mix.get("backlog_per_slot", 0) * self.engine.cfg.n_slots)
        self.lanes = {}  # request id -> lane
        jax.block_until_ready(self.engine.cache)
        self.run = Run(self.cell, family.Shape.of(self.cfg), self.peaks,
                       self.engine.cfg.n_slots)

    # -- the window ---------------------------------------------------------
    def _submit(self, req: traffic.Request, due: float) -> None:
        s = self.engine.submit(req.prompt, req.max_new)
        self.run.requests.append(Req(due, time.perf_counter(), req.max_new, s))

    def window(self, seconds: float, record_calls: bool = False) -> None:
        ann = jax.profiler.TraceAnnotation
        eng, run = self.engine, self.run
        if record_calls:
            run.calls = []
            self._record_calls()
        poisson = self.mix["kind"] == "poisson"
        queue = iter(self.todo) if poisson else self.todo
        nxt = next(queue, None) if poisson else None
        run.t0 = t0 = time.perf_counter()
        run.t_close = close = t0 + seconds
        while True:
            now = time.perf_counter()
            if now >= close:
                break
            with ann("gen.submit"):
                if poisson:
                    while nxt is not None and t0 + nxt.due_s <= now:
                        self._submit(nxt, t0 + nxt.due_s)
                        nxt = next(queue, None)
                else:
                    while eng.scheduler.pending() < self.backlog:
                        self._submit(next(queue), time.perf_counter())
            if eng.has_work():
                with ann("engine.step"):
                    eng.step()
                self._note_lanes()
            else:
                wake = close if nxt is None else min(close, t0 + nxt.due_s)
                with ann("gen.idle"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
        jax.block_until_ready((eng.cache, eng.last_token))
        run.t_end = time.perf_counter()
        if record_calls:
            self._unwrap()
        run.engine_metrics = eng.metrics
        eng.reset_metrics()
        self.fallbacks = self._fallbacks(run.engine_metrics)

    def _note_lanes(self) -> None:
        """The lane each running request was first seen on, for the sample."""
        for lane, s in enumerate(self.engine.slots):
            if s is not None:
                self.lanes.setdefault(s.rid, lane)

    def _record_calls(self) -> None:
        """Wrap the engine's two compiled steps to note the real work of each
        call, from host-side state and the positions the engine ships."""
        eng, shape, calls = self.engine, self.run.shape, self.run.calls
        decode, chunk, pad = eng._decode, eng._chunk, eng._pad_pos

        def on_decode(*args):
            live = [i for i, s in enumerate(eng.slots) if s is not None]
            toks = [(eng._lane_pos[i], True) for i in live]
            calls.append(Call("decode", shape.call_flops(toks),
                              shape.call_bytes(toks, [p + 1 for p, _ in toks])))
            return decode(*args)

        def on_chunk(*args):
            pos = np.asarray(args[-1])
            toks, reach = [], []
            for lane, row in enumerate(pos):
                real = row[row < pad]
                if real.size == 0:
                    continue
                s = eng.slots[lane]
                last = len(s.prompt) + len(s.out) - 1
                toks += [(int(p), int(p) == last) for p in real]
                reach.append(int(real.max()) + 1)
            calls.append(Call("prefill", shape.call_flops(toks),
                              shape.call_bytes(toks, reach)))
            return chunk(*args)

        eng._decode, eng._chunk = on_decode, on_chunk
        self._unwrap = lambda: (setattr(eng, "_decode", decode), setattr(eng, "_chunk", chunk))

    @staticmethod
    def _fallbacks(m) -> int:
        return m.degradations + m.op_degradations + m.quarantines + m.nan_events

    # -- after the close ------------------------------------------------------
    def wait_first_tokens(self) -> None:
        """Step on, submitting nothing, until every request due in the window
        has its first token (at most ``FIRST_TOKEN_WAIT_S``)."""
        run, eng = self.run, self.engine
        limit = run.t_end + FIRST_TOKEN_WAIT_S
        while (any(r.first_token == math.inf for r in run.due_in_window())
               and time.perf_counter() < limit and eng.has_work()):
            eng.step()
            self._note_lanes()
        self.fallbacks += self._fallbacks(eng.metrics)

    def free(self) -> None:
        """Drop the served model's state so the reference has the chip.
        Sessions go too: each holds a hook bound to the engine."""
        self.finished = [(list(r.session.prompt), list(r.session.out), r.max_new,
                          r.session.finish_reason, self.lanes.get(r.session.rid))
                         for r in self.run.requests if r.session.done]
        self.n_slots = self.engine.cfg.n_slots
        self.run.requests = []
        self._unwrap = None
        self.max_len = self.engine.cfg.max_len
        del self.engine
        gc.collect()

    def correctness(self, control: bool = False) -> dict:
        """Readings of a sample of finished requests against the reference."""
        family = self.cell.family
        return check.compare(self.shapes, self.seed, family, family.arch(self.cfg),
                             self.finished, self.max_len, self.n_slots, control=control,
                             log=self.log)
