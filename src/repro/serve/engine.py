"""Pluggable batched serving engine with paged or dense KV.

One compiled fixed-shape decode step serves all slots every tick; admission
between ticks is delegated to a swappable :class:`~repro.serve.scheduler.
Scheduler`; prompt ingestion runs as *chunked batched prefill* — compiled
``ModelApi.decode_chunk`` calls of ``n_slots * prefill_chunk`` positions, laid
either across every slot admitted that tick or along one lane at a time,
whichever takes fewer calls.  Every tick is measured into :class:`~repro.serve.metrics.
EngineMetrics` and the compiled steps trace under the :class:`EngineConfig`'s
kernel-policy backend, so one engine definition runs the pallas / interpret /
xla paths side by side.

Two KV layouts (see ``docs/serving.md`` for the full architecture guide):

- **dense** (``page_size=None``) — each slot reserves a contiguous
  ``max_len`` KV region; memory is ``n_slots * max_len`` regardless of the
  actual sequence lengths.
- **paged** (``page_size=N``) — KV lives in a global pool of fixed-size
  pages (``repro.models.attention``); each lane holds an ordered page list
  (its *block table* row) and the host-side
  :class:`~repro.serve.paging.PageAllocator` tracks ownership.  Admission is
  page-aware (a request waits when the pool, not the slot grid, is full),
  finished requests return their pages to the pool the same tick, a lane
  that outgrows its pages triggers *recompute preemption* of the
  lowest-priority latest-admitted lane (evicted sessions re-queue and resume
  exactly, replaying prompt+output through prefill), and prompts sharing a
  :meth:`ServeEngine.register_prefix` prefix reference the same physical
  pages copy-on-write — a common system prompt is stored once across every
  session that shares it.

Correctness invariants the paged path maintains:

- gathering a lane's pages reproduces its dense cache exactly, so paged and
  dense decode are token-for-token identical for the same requests,
- a page referenced by more than one owner (another lane or the prefix
  registry) is never written: forks copy the boundary page before their
  first write (CoW at page granularity),
- empty/finished lanes carry the pad position sentinel (``T*page``), which
  writes nothing — a pad lane can never scribble on a live lane's pages.

Robustness (see ``docs/robustness.md``): ``submit(deadline_s=)`` bounds a
request's wall-clock (it finishes with ``finish_reason="deadline"`` and
partial output), every re-queue of drained/preempted/quarantined work goes
through the budgeted :meth:`ServeEngine.requeue` (exponential backoff, typed
:class:`RetryBudgetExceeded`), non-finite logits quarantine the lane and
retry the session (token-exact: the poisoned token is never recorded), and a
compiled-step failure on the pallas path is attributed to a kernel op by the
numerics guard first (``EngineConfig.guard`` — per-op quarantine to the xla
oracle, breaker-style cooldown/revival, shadow-oracle drift checks of the
compiled steps; docs/robustness.md#numerics-guard), falling back to the
whole-engine one-shot ``xla`` degrade (``EngineConfig.degrade``) only when no
op is implicated.  The ``crashed`` / ``step_time_scale`` attributes are the
deterministic fault-injection surface of ``repro.serve.faults``.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import activation_sharding, param_specs
from repro.kernels import guard as kguard
from repro.kernels.api import BACKENDS, current_policy, kernel_policy
from repro.models.api import ModelApi

from . import tracing
from .metrics import EngineMetrics
from .paging import PageAllocator, PagePoolExhausted, SharedPrefix
from .sampler import greedy
from .scheduler import Scheduler, make_scheduler
from .session import (
    ACTIVE,
    FINISH_CANCELLED,
    FINISH_DEADLINE,
    FINISH_EOS,
    FINISH_MAX_LEN,
    FINISH_MAX_NEW_TOKENS,
    PREFILL,
    QUEUED,
    Session,
)


#: Model families whose caches are plain attention KV and therefore serve
#: through the batched engine today.  Recurrent families (ssm/xlstm/hybrid)
#: carry per-lane conv/ssm state that cannot yet advance independently inside
#: a shared batch — see the ROADMAP per-lane state isolation item.
SERVABLE_FAMILIES = ("dense", "moe", "vlm")


@jax.jit
def _read_lane_kv(cache, lane):
    """One lane of a dense cache, ``(L, 1, Smax, K, hd)`` per leaf.  ``lane``
    is traced, so every lane shares one executable."""
    return jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, lane, 1, axis=1), cache)


@partial(jax.jit, donate_argnums=0)
def _write_lane_kv(cache, kv, lane):
    """``cache`` with lane ``lane`` replaced by ``kv`` (in place: the cache
    is donated)."""
    return jax.tree.map(
        lambda a, b: jax.lax.dynamic_update_slice_in_dim(a, b, lane, axis=1), cache, kv)



class UnsupportedFamilyError(NotImplementedError):
    """A model family the engine cannot serve (no ``decode_chunk`` path).

    Raised once, with the family named, wherever the refusal surfaces —
    engine construction, or ``ClusterRouter.submit()`` for clusters whose
    replicas spin up lazily.  ``family`` is the offending
    ``ModelConfig.family``; ``missing`` is the ``ModelApi`` capability that
    is ``None`` for it.
    """

    def __init__(self, family: str, missing: str = "decode_chunk"):
        self.family = family
        self.missing = missing
        super().__init__(
            f"model family {family!r} has no {missing}: recurrent per-lane "
            "state cannot yet advance independently inside a shared batch; "
            f"serve one of the dense-cache families {SERVABLE_FAMILIES} "
            "instead (see the ROADMAP per-lane state isolation item)"
        )


class ReplicaCrashed(RuntimeError):
    """The engine's (simulated) process is down: ``step()`` refuses to run.

    Raised at the very top of :meth:`ServeEngine.step` while the ``crashed``
    flag is set — before any host bookkeeping mutates, so the engine's state
    stays consistent and a later revival (circuit-breaker half-open) resumes
    cleanly.  A :class:`~repro.serve.cluster.ClusterRouter` with health
    monitoring enabled catches this per replica and lets the heartbeat
    timeout drive failover; without health monitoring it propagates.
    """


class RetryBudgetExceeded(RuntimeError):
    """A session was re-queued more times than ``EngineConfig.retry_budget``.

    Raised from :meth:`ServeEngine.requeue` instead of silently looping a
    session through drain/preempt/quarantine forever.  ``session`` is the
    offending request (its partial output is intact).
    """

    def __init__(self, session: Session, budget: int):
        self.session = session
        self.budget = budget
        super().__init__(
            f"session {session.rid} re-queued {session.stats.requeues} times, "
            f"over retry_budget={budget}; partial output "
            f"({len(session.out)} tokens) is intact on the session handle"
        )


@dataclass(frozen=True)
class EngineConfig:
    """Engine-level knobs, separated from the model definition.

    ``backend``/``autotune`` scope a ``kernel_policy`` around the engine's
    compiled steps (applied at trace time), so the same engine definition can
    run every kernel path of a model whose config selects kernel-routed
    implementations (``attn_impl="pallas"``, ``ssm_impl="pallas"``).

    Fields:

    - ``n_slots`` — lanes in the compiled batch (the decode step's B).
    - ``max_len`` — logical cap on prompt+generated length per request.
    - ``prefill_chunk`` — tokens per lane of a compiled all-lanes prefill
      step; a single-lane step feeds ``n_slots * prefill_chunk`` tokens of
      one lane (a smaller chunk interleaves admission with decode sooner; a
      larger one amortizes dispatch).
    - ``page_size`` — KV slots per page.  ``None`` selects the dense layout;
      set it to enable the paged layout described in the module docstring.
    - ``n_pages`` — page-pool size.  Defaults to
      ``n_slots * ceil(max_len / page_size)`` (worst case: every lane at
      ``max_len`` — same memory as dense).  Set it *lower* to oversubscribe
      slots against real memory: lanes then share the pool and the engine
      admits/preempts on actual usage.  Must hold at least one worst-case
      lane (``ceil(max_len / page_size)``).
    - ``backend`` / ``autotune`` — kernel policy scoped around the engine's
      compiled steps (``None``: ambient policy).
    - ``mesh`` — optional :class:`jax.sharding.Mesh` for tensor-parallel
      decode (see ``docs/scaling.md``).  With a mesh, params are placed by
      the ``dist.sharding`` rules (head-sharded wq/wk/wv, row-parallel wo,
      vocab-sharded embed/lm_head over the ``model`` axis), the KV/page
      cache shards its KV-head dim, and the compiled steps trace inside
      ``activation_sharding(mesh)`` so the model's logical-axis pins apply.
      Sharded decode is token-identical to the single-device path; a dim
      that does not divide the mesh axis stays replicated.
    - ``eos_id`` — sampled token that finishes a request early.
    - ``sampler`` — logits -> token function (greedy default).
    - ``scheduler`` — stock admission policy name used when no
      :class:`Scheduler` instance is injected.
    - ``retry_budget`` / ``retry_backoff`` — bounds on the requeue loop for
      drained/preempted/quarantined sessions: over-budget requeues raise the
      typed :class:`RetryBudgetExceeded`; a nonzero backoff delays the n-th
      re-admission by ``retry_backoff * 2**(n-1)`` engine ticks (0 keeps the
      immediate-retry semantics).
    - ``quarantine_ticks`` — ticks a lane stays out of admission after its
      logits failed the NaN/Inf guard.
    - ``nan_guard`` — check sampled logits rows for non-finite values and
      quarantine + retry instead of emitting garbage tokens.
    - ``degrade`` — on a compiled-step failure under a pallas-like backend,
      fall back once to the ``xla`` backend (token-identical) instead of
      failing the whole engine; a second failure re-raises.
    - ``guard`` — numerics-guard mode for the compiled steps (see
      docs/robustness.md#numerics-guard): ``None`` inherits the ambient
      ``kernel_policy`` guard, ``"off"`` disables, ``"sample"`` shadow-checks
      every ``guard_sample``-th compiled-step output against an xla twin,
      ``"shadow"`` checks every one.  A drifting step attributes to a kernel
      op via ``repro.kernels.guard`` and quarantines *that op* to the oracle
      (whole-engine ``degrade`` stays the fallback when attribution fails);
      the drifting tick is served from the shadow output, keeping the token
      stream exact.
    - ``guard_sample`` — compiled-step sampling stride under
      ``guard="sample"``.
    - ``guard_cooldown`` — engine ticks a quarantined op waits before its
      half-open re-probe (doubling per consecutive failure, capped at 16x).
    """

    n_slots: int
    max_len: int
    prefill_chunk: int = 16  # tokens per compiled prefill step
    page_size: Optional[int] = None  # None: dense per-slot KV regions
    n_pages: Optional[int] = None  # pool size (None: worst-case default)
    backend: Optional[str] = None  # kernel_policy backend (None: ambient)
    # kernel_policy autotune for engine steps (None: ambient; bool: forced)
    autotune: Optional[bool] = None
    # tensor-parallel device mesh for the compiled steps (None: single device)
    mesh: Optional[jax.sharding.Mesh] = None
    eos_id: Optional[int] = None
    sampler: Callable = greedy
    scheduler: str = "fcfs"  # default policy when none is injected
    retry_budget: int = 64  # max requeues per session before the typed error
    retry_backoff: int = 0  # base backoff in ticks (0: immediate re-admission)
    quarantine_ticks: int = 4  # lane bench time after a NaN-guard trip
    nan_guard: bool = True  # quarantine lanes with non-finite logits
    degrade: bool = True  # pallas step failure -> one-shot xla fallback
    guard: Optional[str] = None  # numerics-guard mode (None: ambient policy)
    guard_sample: int = 8  # shadow-check stride under guard="sample"
    guard_cooldown: int = 8  # ticks before a quarantined op re-probes

    def __post_init__(self):
        if self.retry_budget < 1:
            raise ValueError("retry_budget must be >= 1")
        if self.retry_backoff < 0:
            raise ValueError("retry_backoff must be >= 0 ticks")
        if self.quarantine_ticks < 0:
            raise ValueError("quarantine_ticks must be >= 0")
        if self.n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if self.max_len < 2:
            raise ValueError("max_len must be >= 2 (prompt + one generated token)")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if self.backend is not None and self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; expected {BACKENDS}")
        if self.guard is not None and self.guard not in kguard.GUARD_MODES:
            raise ValueError(
                f"unknown guard mode {self.guard!r}; expected {kguard.GUARD_MODES}"
            )
        if self.guard_sample < 1:
            raise ValueError("guard_sample must be >= 1")
        if self.guard_cooldown < 1:
            raise ValueError("guard_cooldown must be >= 1 tick")
        if self.page_size is not None and self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.n_pages is not None:
            if self.page_size is None:
                raise ValueError("n_pages requires page_size (paged mode)")
            min_pages = -(-self.max_len // self.page_size)
            if self.n_pages < min_pages:
                raise ValueError(
                    f"n_pages={self.n_pages} cannot hold one worst-case lane "
                    f"(max_len {self.max_len} needs {min_pages} pages of "
                    f"{self.page_size})"
                )

    @property
    def table_width(self) -> int:
        """Block-table row length: pages needed for one ``max_len`` lane."""
        if self.page_size is None:
            raise ValueError("table_width is a paged-mode property")
        return -(-self.max_len // self.page_size)


class ServeEngine:
    """Continuous-batching engine over a fixed slot grid.

    ``scheduler`` accepts any :class:`Scheduler` implementation (defaults to
    the config's named stock policy); ``submit`` returns a streaming
    :class:`Session` handle with per-token callbacks, cancellation, and
    request stats.  With ``EngineConfig.page_size`` set, KV is paged (see the
    module docstring): ``register_prefix`` stores a common prompt prefix
    once, admission waits on pages rather than failing, and pool exhaustion
    mid-decode preempts (re-queues) lanes instead of corrupting them.
    """

    def __init__(self, model: ModelApi, params, config: EngineConfig,
                 scheduler: Optional[Scheduler] = None):
        if model.decode_chunk is None:
            raise UnsupportedFamilyError(model.cfg.family)
        self.paged = config.page_size is not None
        if self.paged and (model.decode_step_paged is None
                           or model.decode_chunk_paged is None):
            raise UnsupportedFamilyError(model.cfg.family, missing="decode_chunk_paged")
        self.model = model
        self.mesh = config.mesh
        if self.mesh is not None and params is not None:
            # Place params by the tensor-parallel rules before any compiled
            # step traces: the compiled steps then inherit the layout instead
            # of re-deciding it per trace.
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params
            )
            params = jax.device_put(
                params, param_specs(shapes, model.cfg, self.mesh)
            )
        self.params = params
        self.cfg = config
        self.scheduler = scheduler if scheduler is not None else make_scheduler(config.scheduler)
        if not isinstance(self.scheduler, Scheduler):
            raise TypeError(
                f"scheduler {type(self.scheduler).__name__} does not implement "
                "the Scheduler protocol (submit/select/pending)"
            )
        self.slots: list = [None] * config.n_slots
        self.finished: list = []
        self.last_token = jnp.zeros((config.n_slots,), jnp.int32)
        self._lane_pos = [0] * config.n_slots  # host mirror: next cache index
        self._rid = 0
        # -- robustness state (docs/robustness.md) -------------------------
        self.tick = 0  # monotonically increasing step counter
        self.last_step_s = 0.0  # scaled duration of the most recent step()
        # the most recent step() re-traced its compiled fns (quarantine,
        # revival, degradation): health monitors must not score the compile
        # spike as a throttle signature
        self.last_step_recompiled = False
        self._recompiled = False
        # fault-injection surface (repro.serve.faults flips these):
        self.crashed = False  # step() raises ReplicaCrashed while set
        self.step_time_scale = 1.0  # virtual dilation of reported step times
        self._inject_step_error: Optional[Exception] = None  # raised pre-decode
        self._inject_nan_lanes: set = set()  # lanes whose logits are poisoned
        # hardening state:
        self._degraded = False  # compiled steps fell back to the xla backend
        self._quarantined: dict = {}  # lane -> first tick it is usable again
        # numerics-guard state (docs/robustness.md#numerics-guard); the mode
        # must resolve before the _jit_scoped calls below so the compiled
        # steps trace with the guard in their kernel policy
        self._guard_mode = (config.guard if config.guard is not None
                            else (current_policy().guard or "off"))
        self._shadow_decode = None  # lazy xla twins of the compiled steps
        self._shadow_chunk = None
        self._guard_calls = 0  # compiled-step counter (sampling stride)
        self._op_quarantine: dict = {}  # op -> {"since": tick, "fails": n}
        self._nan_attr_tick = -1  # last tick NaN attribution ran (once/tick)
        # fault surface (repro.serve.faults): seeded logits perturbation
        # standing in for a drifting kernel inside the compiled step
        self._inject_drift: Optional[dict] = None  # {"op","scale","rng"}
        self._injected_drift_calls = 0
        if self.paged:
            ps = config.page_size
            self._table_width = config.table_width
            self.n_pages = (config.n_pages if config.n_pages is not None
                            else config.n_slots * self._table_width)
            # pad sentinel: one past the last addressable pool-view slot, so
            # pad lanes/entries write nothing and mask as "see everything"
            self._pad_pos = self._table_width * ps
            self.allocator = PageAllocator(self.n_pages, ps)
            self.page_tables: list = [[] for _ in range(config.n_slots)]
            self._bt = np.zeros((config.n_slots, self._table_width), np.int32)
            self._prefixes: dict = {}  # token tuple -> SharedPrefix
            self.cache = self._place_cache(model.init_paged_cache(self.n_pages, ps),
                                           model.paged_cache_shardings)
            self._decode = self._jit_scoped(model.decode_step_paged)
            self._chunk = self._chunk_jit = self._jit_scoped(model.decode_chunk_paged)
            self._copy_page_fn = jax.jit(
                lambda cache, s, d: jax.tree.map(
                    lambda a: a.at[:, d].set(a[:, s]), cache
                )
            )
            self.pos = jnp.full((config.n_slots,), self._pad_pos, jnp.int32)
        else:
            self.n_pages = 0
            self._pad_pos = config.max_len
            self.cache = self._place_cache(
                model.init_cache(config.n_slots, config.max_len),
                model.cache_shardings,
            )
            self._decode = self._jit_scoped(model.decode_step)
            self._chunk = self._chunk_jit = self._jit_scoped(model.decode_chunk)
            self.pos = jnp.zeros((config.n_slots,), jnp.int32)
        self._prefill_warm = False  # set once a flush compiled every prefill shape
        self.metrics = EngineMetrics(config.n_slots, n_pages=self.n_pages)
        tracing.install_gc_hook()

    # ------------------------------------------------------------------
    def _place_cache(self, cache, shardings_fn: Optional[Callable]):
        """Commit a fresh cache to the engine's mesh (identity without one)."""
        if self.mesh is None or shardings_fn is None:
            return cache
        return jax.device_put(cache, shardings_fn(cache, self.mesh))

    def _jit_scoped(self, fn: Callable, backend: Optional[str] = None) -> Callable:
        """jit ``fn`` so it traces under the config's kernel policy and mesh.

        With a policy or mesh set, jit a per-engine closure (not ``fn``
        itself): jax's trace cache is keyed on function identity, not on the
        policy contextvar or the activation-sharding mesh, so jitting the
        shared ``model.decode_*`` directly would let a second engine with a
        different backend/mesh silently reuse the first engine's trace.

        ``backend`` overrides the config's backend — the graceful-degradation
        path re-jits the steps with ``backend="xla"`` after a pallas failure.
        """
        backend = self.cfg.backend if backend is None else backend
        guard = self._guard_mode if self._guard_mode != "off" else None
        if (backend is None and self.cfg.autotune is None and self.mesh is None
                and guard is None):
            return jax.jit(fn)
        autotune, mesh = self.cfg.autotune, self.mesh

        def scoped(*args):  # fresh object per engine -> own trace cache
            # this body only runs at trace time (cache miss), so it doubles
            # as the compile-spike marker health monitors use to skip the
            # step's duration (see ``last_step_recompiled``)
            self._recompiled = True
            with kernel_policy(backend=backend, autotune=autotune, guard=guard):
                if mesh is None:
                    return fn(*args)
                with activation_sharding(mesh):
                    return fn(*args)

        return jax.jit(scoped)

    # ------------------------------------------------------------------
    # graceful degradation (docs/robustness.md)
    # ------------------------------------------------------------------
    def _backend(self) -> str:
        """Effective kernel backend of the compiled steps right now."""
        if self._degraded:
            return "xla"
        return self.cfg.backend if self.cfg.backend is not None else "pallas"

    @property
    def op_quarantined(self) -> bool:
        """Any kernel op currently quarantined to the oracle backend.  Step
        times are not fleet-comparable while set (part of the engine runs on
        a different backend), so health monitors exclude the replica from
        throttle-signature statistics."""
        return bool(self._op_quarantine)

    def _rejit_steps(self, backend: Optional[str] = None) -> None:
        """(Re-)jit both compiled steps (per-op quarantine / revival / whole-
        engine degradation all change what a fresh trace dispatches to); the
        lazy shadow twins rebuild on next use, and the next flush compiles
        every prefill shape again (:meth:`_warm_prefill`).  ``_chunk_jit``
        keeps the jitted prefill step itself: flushes call ``_chunk``, which
        callers may wrap to observe each call."""
        self._recompiled = True
        if self.paged:
            self._decode = self._jit_scoped(self.model.decode_step_paged, backend=backend)
            self._chunk = self._chunk_jit = self._jit_scoped(
                self.model.decode_chunk_paged, backend=backend)
        else:
            self._decode = self._jit_scoped(self.model.decode_step, backend=backend)
            self._chunk = self._chunk_jit = self._jit_scoped(
                self.model.decode_chunk, backend=backend)
        self._shadow_decode = self._shadow_chunk = None
        self._prefill_warm = False

    def _degrade(self, err: Exception) -> None:
        """Whole-engine fallback: re-jit decode/prefill on the ``xla``
        backend.  With the numerics guard on this is the *second* line of
        defense — per-op attribution runs first (:meth:`_guard_attribute`).

        Backend parity (the kernels' correctness contract) makes the
        degraded engine token-identical — only kernel dispatch changes, so
        in-flight lanes continue from the same cache without replay."""
        self._degraded = True
        self.metrics.record_degradation()
        self._rejit_steps(backend="xla")
        warnings.warn(
            f"serving engine degraded to the xla backend after a compiled-step "
            f"failure: {err!r}",
            RuntimeWarning,
            stacklevel=4,
        )

    # -- numerics guard (docs/robustness.md#numerics-guard) -------------
    def _op_suppressed(self, err: Exception) -> bool:
        """An injected step error attributed to an op stops firing once that
        op is quarantined — the retried step runs with the op on the oracle."""
        op = getattr(err, "op", None)
        return op is not None and kguard.is_quarantined(op)

    def _perturb(self, out):
        """Apply an injected ``kernel_drift`` fault: seeded additive noise on
        the step's logits, standing in for a drifting kernel inside the
        compiled step.  Quarantining the named op (which routes it to the
        oracle) ends the perturbation, like a real per-op degrade would."""
        inj = self._inject_drift
        if (inj is None or self._backend() == "xla"
                or kguard.is_quarantined(inj["op"])):
            return out
        logits = out[0]
        arr = np.asarray(logits).astype(np.float64)
        noise = inj["rng"].standard_normal(arr.shape)
        scale = inj["scale"] * (float(np.mean(np.abs(arr))) + 1.0)
        self._injected_drift_calls += 1
        perturbed = jnp.asarray(arr + noise * scale, dtype=logits.dtype)
        return (perturbed,) + tuple(out[1:])

    def _guard_attribute(self, err: Exception) -> bool:
        """Attribute a step failure/drift to specific kernel ops via the
        guard's canonical probes; quarantined ops re-jit the steps so fresh
        traces route them to the oracle.  False means no op was implicated
        (the caller falls back to whole-engine handling)."""
        if self._guard_mode == "off":
            return False
        bad = kguard.attribute()
        hinted = getattr(err, "op", None)
        if (hinted is not None and hinted not in bad
                and not kguard.is_quarantined(hinted)):
            kguard.quarantine(hinted, f"engine attribution: {err!r}")
            bad.append(hinted)
        if not bad:
            return False
        for op in bad:
            rec = self._op_quarantine.setdefault(op, {"since": self.tick, "fails": 0})
            rec["since"] = self.tick
            rec["fails"] += 1
        self.metrics.record_op_degradation(len(bad))
        warnings.warn(
            f"numerics guard quarantined kernel op(s) {sorted(bad)} to the "
            f"xla backend (engine stays on {self._backend()!r}): {err!r}",
            RuntimeWarning,
            stacklevel=5,
        )
        self._rejit_steps()
        return True

    def _heal_ops(self) -> None:
        """Half-open re-probe for quarantined ops whose cooldown elapsed:
        a clean canonical probe revives the op (next traces dispatch native
        again); a dirty one doubles the cooldown."""
        healed = False
        for op, rec in list(self._op_quarantine.items()):
            wait = self.cfg.guard_cooldown * 2 ** min(rec["fails"] - 1, 4)
            if self.tick - rec["since"] < wait:
                continue
            if kguard.probe(op):
                kguard.revive(op)
                del self._op_quarantine[op]
                self.metrics.record_op_revival()
                healed = True
            else:
                rec["since"] = self.tick
                rec["fails"] += 1
        if healed:
            self._rejit_steps()

    def _shadow_fn(self, which: str) -> Callable:
        """Lazy xla-backed twin of a compiled step (the shadow oracle)."""
        if which == "decode":
            if self._shadow_decode is None:
                fn = self.model.decode_step_paged if self.paged else self.model.decode_step
                self._shadow_decode = self._jit_scoped(fn, backend="xla")
            return self._shadow_decode
        if self._shadow_chunk is None:
            fn = self.model.decode_chunk_paged if self.paged else self.model.decode_chunk
            self._shadow_chunk = self._jit_scoped(fn, backend="xla")
        return self._shadow_chunk

    def _guard_verify(self, which: str, args: tuple, out):
        """Shadow-oracle check of a compiled-step output: re-run the same
        arguments through the xla twin and compare under the per-dtype
        tolerance ladder.  On drift, attribute to a kernel op (falling back
        to whole-engine degrade) and serve the *shadow* output for this tick
        — the token stream stays exact while the quarantine takes effect."""
        if self._guard_mode == "off" or self._backend() == "xla":
            return out
        self._guard_calls += 1
        due = (self._guard_mode == "shadow"
               or self._guard_calls % self.cfg.guard_sample == 0)
        if not due:
            return out
        shadow = self._shadow_fn(which)(*args)
        self.metrics.record_guard_check()
        ok, detail = kguard.trees_match(out, shadow)
        if ok:
            return out
        self.metrics.record_drift_event()
        err = RuntimeError(
            f"compiled {which} step drifted from its xla shadow: {detail}"
        )
        if not self._guard_attribute(err):
            if self.cfg.degrade:
                self._degrade(err)
            else:
                raise err
        return shadow

    def _call_compiled(self, which: str, *args):
        """Run a compiled step with the guard and degradation boundaries
        around it.

        A failure attributes to a kernel op first (per-op quarantine + retry
        with the op on the oracle); only when attribution finds nothing does
        the whole-engine :meth:`_degrade` fallback fire (or the failure
        re-raise, with ``degrade=False`` or already on xla).  Successful
        outputs pass through the shadow-oracle check of
        :meth:`_guard_verify`.
        """
        while True:
            fn = self._decode if which == "decode" else self._chunk
            try:
                inj = self._inject_step_error
                if (inj is not None and self._backend() != "xla"
                        and not self._op_suppressed(inj)):
                    raise inj
                out = fn(*args)
                out = self._perturb(out)
            except Exception as err:  # guard/degradation boundary
                if self._guard_attribute(err):
                    continue  # op quarantined + steps re-jitted: retry
                if not self.cfg.degrade or self._backend() == "xla":
                    raise
                self._degrade(err)
                continue
            return self._guard_verify(which, args, out)

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *, priority: int = 0,
               on_token: Optional[Callable] = None,
               deadline_s: Optional[float] = None) -> Session:
        """Queue a request; returns its streaming :class:`Session` handle.

        ``deadline_s`` bounds the request's wall-clock from this call: a
        session that is still queued or generating when the deadline passes
        finishes with ``finish_reason="deadline"`` and whatever output it
        has (the goodput metrics exclude its tokens).
        """
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.cfg.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} must be < max_len "
                f"{self.cfg.max_len} (no room to generate)"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be positive (or None)")
        session = Session(self._rid, prompt, max_new_tokens,
                          priority=priority, on_token=on_token,
                          deadline_s=deadline_s)
        session.stats.submitted_at = time.perf_counter()
        session._on_queued_cancel = self._record_queued_cancel
        self._rid += 1
        self.scheduler.submit(session)
        return session

    def requeue(self, session: Session) -> None:
        """Budgeted re-queue for drained / preempted / quarantined sessions.

        Every path that puts previously-admitted work back in the queue goes
        through here: recompute preemption, :meth:`drain` (via the cluster's
        failover), and NaN-guard quarantine.  The n-th requeue beyond
        ``retry_budget`` raises :class:`RetryBudgetExceeded`; with
        ``retry_backoff > 0`` re-admission is delayed exponentially
        (``backoff * 2**(n-1)`` ticks, capped at 64x).  Paged pool-misfit
        waits in admission deliberately do **not** count — they recur every
        tick for a merely-waiting request and carry no failure signal.
        """
        session.stats.requeues += 1
        self.metrics.record_requeue()
        if session.stats.requeues > self.cfg.retry_budget:
            raise RetryBudgetExceeded(session, self.cfg.retry_budget)
        if self.cfg.retry_backoff:
            wait = self.cfg.retry_backoff * 2 ** min(session.stats.requeues - 1, 6)
            session._backoff_until = self.tick + wait
        session.status = QUEUED
        session._on_queued_cancel = self._record_queued_cancel
        self.scheduler.submit(session)

    def _record_queued_cancel(self, session: Session) -> None:
        """Queued-cancel accounting: the session never occupies a slot, but
        it must still show up in metrics and the finished list."""
        self.metrics.record_finished(session)
        self.finished.append(session)

    def cancel(self, session: Session) -> None:
        """Alias for ``session.cancel()`` (kept for symmetry with submit)."""
        session.cancel()

    # ------------------------------------------------------------------
    # shared prefixes (paged mode)
    # ------------------------------------------------------------------
    def register_prefix(self, tokens) -> SharedPrefix:
        """Prefill ``tokens`` once into pool pages shared by every future
        request whose prompt starts with them (paged mode only).

        The registry holds a permanent reference on the pages, so they
        survive any individual session; forking sessions re-use the KV for
        all but (at least) the final prompt token and only prefill their
        suffix — a common system prompt costs its pages once, not once per
        lane.  Registration itself runs outside the serving metrics (it is
        one-time setup, typically before traffic).
        """
        if not self.paged:
            raise ValueError("register_prefix requires paged KV (set page_size)")
        tokens = tuple(int(t) for t in tokens)
        if not tokens:
            raise ValueError("empty prefix")
        if len(tokens) >= self.cfg.max_len:
            raise ValueError("prefix must be shorter than max_len")
        if tokens in self._prefixes:
            return self._prefixes[tokens]
        n_t = self.allocator.pages_for(len(tokens))
        if (not self.allocator.can_alloc(n_t)
                or self.allocator.free_pages - n_t < self._table_width):
            raise PagePoolExhausted(
                f"prefix of {len(tokens)} tokens needs {n_t} pages and the "
                f"pool must keep {self._table_width} pages of headroom for "
                f"one worst-case lane ({self.allocator.free_pages} free)"
            )
        pages = self.allocator.alloc(n_t)
        # Prefill the prefix KV in single-lane calls through a one-row
        # block table that maps to the prefix pages — live lanes are
        # untouched because writes target pool positions, not lanes.
        width = self.cfg.n_slots * self.cfg.prefill_chunk
        bt = np.zeros((1, self._table_width), np.int32)
        bt[0, :n_t] = pages
        n_calls = -(-len(tokens) // width)
        toks = np.zeros((1, n_calls * width), np.int32)
        poss = np.full((1, n_calls * width), self._pad_pos, np.int32)
        toks[0, : len(tokens)] = tokens
        poss[0, : len(tokens)] = np.arange(len(tokens), dtype=np.int32)
        bt_dev = jnp.asarray(bt)
        for c in range(n_calls):
            sl = slice(c * width, (c + 1) * width)
            _, self.cache = self._chunk(
                self.params, self.cache, bt_dev,
                jnp.asarray(toks[:, sl]), jnp.asarray(poss[:, sl]),
            )
        prefix = SharedPrefix(tokens=tokens, pages=pages)
        self._prefixes[tokens] = prefix
        return prefix

    def unregister_prefix(self, tokens) -> None:
        """Drop a registered prefix: the registry's page references are
        released (pages free once no lane still shares them)."""
        prefix = self._prefixes.pop(tuple(int(t) for t in tokens))
        self.allocator.free(prefix.pages)

    def _fork_plan(self, feed: list) -> tuple:
        """Longest registered prefix under ``feed`` -> (prefix, reuse) where
        ``reuse`` positions of KV are taken from shared pages instead of
        being re-prefilled.  At least the final feed token is always re-fed
        so the fork has a logits row to sample from."""
        best, reuse = None, 0
        for prefix in self._prefixes.values():
            n = min(len(prefix.tokens), len(feed) - 1)
            if n > reuse and feed[: len(prefix.tokens)] == list(prefix.tokens):
                best, reuse = prefix, n
        return best, reuse

    # ------------------------------------------------------------------
    # paged bookkeeping
    # ------------------------------------------------------------------
    def _set_lane_pages(self, lane: int, pages: list) -> None:
        self.page_tables[lane] = pages
        self._bt[lane, :] = 0
        self._bt[lane, : len(pages)] = pages

    def _copy_page(self, src: int, dst: int) -> None:
        """Device-side page copy (all layers): the CoW step of a fork."""
        self.cache = self._copy_page_fn(
            self.cache, jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)
        )

    def _release_lane(self, lane: int) -> None:
        """Return a lane's pages to the pool and pad the lane out."""
        if self.paged:
            self.allocator.free(self.page_tables[lane])
            self._set_lane_pages(lane, [])
        self.slots[lane] = None
        self.pos = self.pos.at[lane].set(self._pad_pos if self.paged else 0)

    def _try_admit_paged(self, lane: int, session: Session) -> Optional[tuple]:
        """Build the lane's page table for ``session`` (sharing a registered
        prefix when one matches); returns the prefill assignment or None if
        the pool cannot hold the request right now."""
        feed = session.prompt + session.out  # out non-empty: preempted resume
        ps = self.cfg.page_size
        n_t = self.allocator.pages_for(len(feed))
        prefix, reuse = self._fork_plan(feed)
        m = reuse // ps  # fully-shared pages (never written by this lane)
        cow = reuse % ps != 0  # boundary page: preserved KV + this lane's writes
        if not self.allocator.can_alloc(n_t - m):
            return None
        fresh = self.allocator.alloc(n_t - m)
        shared = prefix.pages[:m] if prefix is not None else []
        if shared:
            self.allocator.share(shared)
        self._set_lane_pages(lane, shared + fresh)
        if cow:
            # copy-on-write: page m holds prefix KV at positions
            # [m*ps, reuse) that this lane reuses but must not share,
            # because its own writes start inside the same page
            self._copy_page(prefix.pages[m], fresh[0])
        if prefix is not None and reuse:
            prefix.hits += 1
            self.metrics.record_prefix_hit(reuse)
        return (lane, session, feed, reuse if prefix is not None else 0)

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Preemption victim: lowest priority, then latest admitted."""
        candidates = [
            (s.priority, -(s.stats.admitted_at or 0.0), i)
            for i, s in enumerate(self.slots)
            if s is not None and i != exclude
        ]
        if not candidates:
            return None
        return min(candidates)[2]

    def _preempt(self, lane: int) -> None:
        """Recompute preemption: evict the lane, free its pages, and
        re-queue the session.  On re-admission the engine replays
        prompt+output through prefill, which reconstructs the KV exactly —
        the stream resumes with no lost or corrupted tokens."""
        session = self.slots[lane]
        self._release_lane(lane)
        session.stats.preemptions += 1
        self.metrics.record_preemption()
        self.requeue(session)

    def _grow_lane(self, lane: int) -> bool:
        """Ensure the lane owns the page its next KV write lands in,
        preempting other lanes (or, last resort, this one) when the pool is
        exhausted.  Returns False if the lane itself was evicted."""
        ps = self.cfg.page_size
        while len(self.page_tables[lane]) < self._lane_pos[lane] // ps + 1:
            while not self.allocator.can_alloc(1):
                victim = self._pick_victim(exclude=lane)
                if victim is None:
                    self._preempt(lane)
                    return False
                self._preempt(victim)
            page = self.allocator.alloc(1)[0]
            pages = self.page_tables[lane]
            self._set_lane_pages(lane, pages + [page])
        return True

    # ------------------------------------------------------------------
    def _finalize(self, lane: int, session: Session, reason: str) -> None:
        session._finish(reason)
        self.metrics.record_finished(session)
        self.finished.append(session)
        self._release_lane(lane)

    def _finish_reason(self, lane: int, session: Session, token: int) -> str:
        if self.cfg.eos_id is not None and token == self.cfg.eos_id:
            return FINISH_EOS
        if len(session.out) >= session.max_new_tokens:
            return FINISH_MAX_NEW_TOKENS
        if self._lane_pos[lane] >= self.cfg.max_len:
            return FINISH_MAX_LEN  # cache exhausted: nowhere to write the next KV
        return ""

    def _release_cancelled(self) -> None:
        for i, s in enumerate(self.slots):
            if s is not None and s.cancel_requested:
                self._finalize(i, s, FINISH_CANCELLED)

    def _expire_deadlines(self) -> None:
        """Finish in-flight sessions whose wall-clock deadline passed (their
        partial output stays on the handle)."""
        now = time.perf_counter()
        for i, s in enumerate(self.slots):
            if s is not None and s.deadline_expired(now):
                self._finalize(i, s, FINISH_DEADLINE)

    def _quarantine_lane(self, lane: int, session: Session) -> None:
        """NaN-guard response: bench the lane, retry the session elsewhere.

        The poisoned tick's token is never recorded, so the retried session
        replays prompt+output through prefill and resumes token-exact.  The
        lane's pages return to the pool immediately (every KV position is
        rewritten before it is read on re-admission, so a poisoned write
        cannot leak), but the lane itself sits out ``quarantine_ticks``.
        """
        self._release_lane(lane)
        self._quarantined[lane] = self.tick + self.cfg.quarantine_ticks
        self.metrics.record_nan_event()
        self.metrics.record_quarantine()
        self.requeue(session)

    def _admit(self) -> list:
        """Claim free non-quarantined slots for scheduler-selected sessions.

        In paged mode admission is additionally page-aware: a selected
        session that does not fit in the pool right now is re-queued via
        ``scheduler.submit`` (for the stock policies this re-appends it, so
        strict arrival order is traded for progress of smaller requests —
        see docs/serving.md#admission; such waits do not touch the retry
        budget).  Selected sessions that were cancelled while queued finish
        as ``cancelled``, ones whose deadline already passed finish as
        ``deadline``, and ones still inside their requeue backoff window go
        back to the queue untouched.
        """
        free = [
            i for i, s in enumerate(self.slots)
            if s is None and self._quarantined.get(i, 0) <= self.tick
        ]
        if not free:
            return []
        picked = self.scheduler.select(len(free), self.cfg.n_slots)
        if len(picked) > len(free):
            raise RuntimeError(
                f"scheduler returned {len(picked)} sessions for {len(free)} free slots"
            )
        now = time.perf_counter()
        assignments = []
        for session in picked:
            if session.done:  # e.g. cancelled-in-queue under a custom policy
                continue
            if session.cancel_requested:
                session._finish(FINISH_CANCELLED)
                self._record_queued_cancel(session)
                continue
            if session.deadline_expired(now):
                # expires without ever occupying a lane — same accounting
                # as a queued cancel, but reason="deadline"
                session._finish(FINISH_DEADLINE, now=now)
                self.metrics.record_finished(session)
                self.finished.append(session)
                continue
            if session._backoff_until > self.tick:
                self.scheduler.submit(session)  # backoff: not eligible yet
                continue
            lane = free[0]
            if self.paged:
                plan = self._try_admit_paged(lane, session)
                if plan is None:  # pool full: wait without losing the request
                    self.scheduler.submit(session)
                    continue
            else:
                plan = (lane, session, session.prompt + session.out, 0)
            free.pop(0)
            session.status = PREFILL
            session.stats.admitted_at = now
            self.slots[lane] = session
            assignments.append(plan)
        return assignments

    # ------------------------------------------------------------------
    def _prefill(self, assignments: list) -> None:
        """Chunked batched prefill of one flush, in whichever of two compiled
        shapes takes fewer calls (a tie goes to single-lane):

        - all lanes: ``n_slots`` x ``prefill_chunk`` positions a call, each
          admitted lane in its own row; ``ceil(longest span / prefill_chunk)``
          calls.
        - single lane: 1 x ``n_slots * prefill_chunk`` positions a call, lane
          after lane; ``sum(ceil(span / (n_slots * prefill_chunk)))`` calls.

        Both shapes compute the same positions a call, so the rule counts
        calls: one long prompt goes single-lane, a burst of short ones all
        lanes.  Positions not fed carry the pad position sentinel, which
        writes nothing — mid-generation neighbours are untouched.

        Each assignment is ``(lane, session, feed, start)``: ``feed`` is the
        token stream whose KV the lane must hold (prompt, plus prior output
        for preemption resumes) and ``start`` is the first position actually
        fed — positions below it come from shared prefix pages.
        """
        t0 = time.perf_counter()
        spans = [len(feed) - start for _, _, feed, start in assignments]
        single = self._single_lane(spans)
        with tracing.span(tracing.PREFILL, tokens=sum(spans),
                          rids=tracing.rids(s for _, s, _, _ in assignments)):
            self._warm_prefill(single)
            if single:
                # highest lane first: lane 0, admitted first, then holds its
                # session through every call (a wrapper of ``_chunk`` may
                # read row 0 of a single-lane call as lane 0)
                order = sorted(assignments, key=lambda a: -a[0])
                bts = self._block_table_args([a[0] for a in order])
                n_calls = sum(self._feed([a], bt, lane=a[0]) for a, bt in zip(order, bts))
            else:
                n_calls = self._feed(assignments, self._block_table_args())
        self.metrics.record_prefill(
            (time.perf_counter() - t0) * self.step_time_scale, sum(spans),
            n_calls * self.cfg.n_slots * self.cfg.prefill_chunk, n_calls if single else 0,
        )

    def _single_lane(self, spans: list) -> bool:
        """The layout rule: a flush of these fed spans takes single-lane
        calls when they are fewer than all-lanes calls, or as many."""
        n_slots, chunk = self.cfg.n_slots, self.cfg.prefill_chunk
        lane_calls = sum(-(-n // (n_slots * chunk)) for n in spans)
        return n_slots > 1 and lane_calls <= -(-max(spans) // chunk)  # one slot: one shape

    def _feed(self, group: list, bt_args: tuple, lane: Optional[int] = None) -> int:
        """The chunk calls that feed ``group``'s assignments, each lane's
        first token sampled after the call its feed ends in; returns the
        number of calls.  All-lanes calls with ``lane`` None; else
        single-lane calls that feed ``lane``, the group's one lane, from row
        0 — with dense KV, on that lane's KV alone, read out before its first
        call and written back after its last."""
        rows = self.cfg.n_slots if lane is None else 1
        width = self.cfg.n_slots * self.cfg.prefill_chunk // rows
        n_calls = -(-max(len(feed) - start for _, _, feed, start in group) // width)
        toks = np.zeros((rows, n_calls * width), np.int32)
        poss = np.full((rows, n_calls * width), self._pad_pos, np.int32)
        for i, _, feed, start in group:
            r = i if lane is None else 0
            toks[r, : len(feed) - start] = feed[start:]
            poss[r, : len(feed) - start] = np.arange(start, len(feed), dtype=np.int32)
        own_kv = lane is not None and not self.paged
        if own_kv:
            lane_idx = jnp.asarray(lane, jnp.int32)
            kv = _read_lane_kv(self.cache, lane_idx)
        for c in range(n_calls):
            with tracing.span(tracing.PREFILL_CHUNK, lanes=rows):
                sl = slice(c * width, (c + 1) * width)
                logits, out = self._call_compiled(
                    "chunk", self.params, kv if own_kv else self.cache, *bt_args,
                    jnp.asarray(toks[:, sl]), jnp.asarray(poss[:, sl]),
                )
                if own_kv:
                    kv = out
                else:
                    self.cache = out
            with tracing.span(tracing.PREFILL_SAMPLE):
                self._sample_prefilled(group, logits, c, width, lane is not None)
        if own_kv:
            self.cache = _write_lane_kv(self.cache, kv, lane_idx)
        return n_calls

    def _warm_prefill(self, single: bool) -> None:
        """On the first flush after (re-)jitting, compile the prefill shape
        this flush does not run — one all-pad call, which writes nothing,
        and the logits row read that sampling makes — so that no later flush
        compiles.  Calls the jitted step itself, never a wrapper of
        ``_chunk``."""
        if self._prefill_warm:
            return
        self._prefill_warm = True
        n_slots = self.cfg.n_slots
        if n_slots == 1:  # both layouts are one shape
            return
        rows = n_slots if single else 1
        width = n_slots * self.cfg.prefill_chunk // rows
        toks = jnp.asarray(np.zeros((rows, width), np.int32))
        poss = jnp.asarray(np.full((rows, width), self._pad_pos, np.int32))
        if self.paged:
            bt = jnp.asarray(self._bt[:rows])
            logits, self.cache = self._chunk_jit(self.params, self.cache, bt, toks, poss)
        elif rows == 1:
            lane = jnp.asarray(0, jnp.int32)
            logits, kv = self._chunk_jit(self.params, _read_lane_kv(self.cache, lane),
                                         toks, poss)
            self.cache = _write_lane_kv(self.cache, kv, lane)
        else:
            logits, self.cache = self._chunk_jit(self.params, self.cache, toks, poss)
        jax.block_until_ready(logits[0, 0])

    def _sample_prefilled(self, group: list, logits, c: int, width: int,
                          single: bool) -> None:
        """First token of every lane of ``group`` whose feed ends in call
        ``c`` (``width`` positions a row; a single-lane call holds its lane
        in row 0)."""
        for lane, s, feed, start in group:
            at = len(feed) - start - 1 - c * width
            if not 0 <= at < width:
                continue
            row = logits[0 if single else lane, at]
            if self.cfg.nan_guard and not bool(jnp.all(jnp.isfinite(row))):
                if self._nan_attr_tick != self.tick:
                    self._nan_attr_tick = self.tick
                    self._guard_attribute(
                        RuntimeError(f"non-finite prefill logits on lane {lane}")
                    )
                self._quarantine_lane(lane, s)  # retry the session whole
                continue
            tok = int(self.cfg.sampler(row))
            s.status = ACTIVE
            self.last_token = self.last_token.at[lane].set(tok)
            self.pos = self.pos.at[lane].set(len(feed))
            self._lane_pos[lane] = len(feed)
            s._record_token(tok)  # TTFT stamps here (first admission only)
            reason = self._finish_reason(lane, s, tok)
            if reason:
                self._finalize(lane, s, reason)

    def _block_table_args(self, lanes: Optional[list] = None):
        """The paged steps' block-table argument, copied to the device (no
        argument for dense KV): every lane's row, or with ``lanes`` a list of
        one-row tables, one for each lane."""
        if not self.paged:
            return () if lanes is None else [()] * len(lanes)
        with tracing.span(tracing.UPLOAD):
            if lanes is None:
                return (jnp.asarray(self._bt),)
            return [(jnp.asarray(self._bt[i:i + 1]),) for i in lanes]

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One engine tick: release cancellations, expire deadlines, admit +
        prefill, grow pages (preempting if the pool is dry), decode.

        Raises :class:`ReplicaCrashed` — before any state mutates — while
        the ``crashed`` fault flag is set.  Recorded step times are scaled
        by ``step_time_scale`` (the straggler-fault surface: a throttled
        replica reports dilated ticks without actually sleeping).
        """
        if self.crashed:
            raise ReplicaCrashed(
                f"engine is crashed (fault-injected); tick {self.tick}"
            )
        t_step0 = time.perf_counter()
        self.tick += 1
        with tracing.span(tracing.TICK, tick=self.tick):
            if self._op_quarantine:  # quarantined kernel ops due for a re-probe
                self._heal_ops()
            if self._quarantined:  # lanes whose bench time has elapsed come back
                self._quarantined = {
                    lane: t for lane, t in self._quarantined.items() if t > self.tick
                }
            with tracing.span(tracing.ADMIT):
                self._release_cancelled()
                self._expire_deadlines()
                admitted = self._admit()
            if admitted:
                self._prefill(admitted)
            if self.paged:
                with tracing.span(tracing.GROW):
                    for lane in range(self.cfg.n_slots):
                        if self.slots[lane] is not None:
                            self._grow_lane(lane)
            active = [i for i, s in enumerate(self.slots) if s is not None]
            if active:
                self._decode_tick(active)
            self.last_step_s = (time.perf_counter() - t_step0) * self.step_time_scale
            self.last_step_recompiled, self._recompiled = self._recompiled, False

    def _decode_tick(self, active: list) -> None:
        """One decode step over every lane, then the tokens' bookkeeping."""
        t0 = time.perf_counter()
        bt_args = self._block_table_args()
        with tracing.span(tracing.DECODE, lanes=len(active)):
            logits, self.cache = self._call_compiled(
                "decode", self.params, self.cache, *bt_args, self.last_token, self.pos
            )
            if self._inject_nan_lanes:  # fault surface: poison the real logits
                for lane in sorted(self._inject_nan_lanes):
                    if 0 <= lane < self.cfg.n_slots:
                        logits = logits.at[lane].set(jnp.nan)
        with tracing.span(tracing.DECODE_SYNC):
            bad = []
            if self.cfg.nan_guard:
                finite = np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
                bad = [i for i in active if not finite[i]]
            if bad and self._nan_attr_tick != self.tick:
                # a kernel op emitting non-finite values shows up in its probe:
                # quarantine it per-op (the lanes still retry below either way)
                self._nan_attr_tick = self.tick
                self._guard_attribute(
                    RuntimeError(f"non-finite decode logits on lane(s) {bad}")
                )
            next_tok = self.cfg.sampler(logits)
            jax.block_until_ready(next_tok)
        t_decode = time.perf_counter() - t0
        with tracing.span(tracing.BOOKKEEP):
            for i in bad:  # quarantine before pos advances: the lane pads out
                self._quarantine_lane(i, self.slots[i])
            ok = [i for i in active if i not in bad]
            self.last_token = next_tok
            # pad lanes must stay at the sentinel (a pad-lane write would land
            # in pool pages someone else owns); surviving active lanes advance
            if self.paged:
                adv = jnp.zeros((self.cfg.n_slots,), jnp.int32)
                for i in ok:
                    adv = adv.at[i].set(1)
                self.pos = self.pos + adv
            else:
                self.pos = self.pos + 1
            toks = np.asarray(next_tok)
            for i in ok:
                s = self.slots[i]
                self._lane_pos[i] += 1
                s._record_token(int(toks[i]))
                reason = self._finish_reason(i, s, int(toks[i]))
                if reason:
                    self._finalize(i, s, reason)
            scale = self.step_time_scale
            self.metrics.record_tick(
                (time.perf_counter() - t0) * scale, t_decode * scale, len(active)
            )
            if self.paged:
                self.metrics.record_pages(self.allocator.used)

    # ------------------------------------------------------------------
    def has_work(self) -> bool:
        return any(s is not None for s in self.slots) or self.scheduler.pending() > 0

    def run(self, max_ticks: int = 10_000) -> list:
        """Drive until drained (or ``max_ticks``); returns finished sessions
        (cancelled ones included, ``finish_reason == "cancelled"``).

        Exhausting the tick budget with work still pending is surfaced — a
        ``RuntimeWarning`` plus the ``tick_budget_exhausted`` metrics counter
        — instead of returning silently with sessions stranded in flight.
        """
        ticks = 0
        while self.has_work() and ticks < max_ticks:
            self.step()
            ticks += 1
        if self.has_work():
            self.metrics.record_tick_budget_exhausted()
            warnings.warn(
                f"run(max_ticks={max_ticks}) stopped with work still pending "
                f"({sum(s is not None for s in self.slots)} active lane(s), "
                f"{self.scheduler.pending()} queued)",
                RuntimeWarning,
                stacklevel=2,
            )
        return self.finished

    def drain(self) -> list:
        """Evict every in-flight and queued session, with output intact.

        Slot lanes are released (paged lanes return their pages) and every
        live session — running or queued — comes back in ``QUEUED`` state.
        Only slot-drained sessions count a preemption: they lose in-flight
        lane state and must replay through prefill, while queue-drained ones
        never held a lane and re-enter exactly as they were.  Because a
        re-admitted session replays prompt+output through prefill (the
        recompute-preemption invariant), the returned sessions can be
        re-submitted to any engine over the same params and resume
        token-exact.  This is the replica-failure path of
        :class:`~repro.serve.cluster.ClusterRouter`.
        """
        drained = []
        for lane, session in enumerate(self.slots):
            if session is not None:
                self._release_lane(lane)
                session.status = QUEUED
                session.stats.preemptions += 1  # evicted mid-flight, will resume
                drained.append(session)
        # Empty the queue via the scheduler's optional drain() extension;
        # otherwise pull through select with n_free clamped up to n_slots so
        # batch-boundary policies release too.  A custom policy that still
        # withholds sessions while claiming pending work would loop forever,
        # so stop when select comes back empty (tested: a withholding
        # scheduler strands its queue but drain() itself must terminate).
        drainer = getattr(self.scheduler, "drain", None)
        if drainer is not None:
            queued = list(drainer())
        else:
            queued = []
            while self.scheduler.pending() > 0:
                batch = self.scheduler.select(
                    max(self.scheduler.pending(), self.cfg.n_slots), self.cfg.n_slots
                )
                if not batch:
                    break
                queued.extend(batch)
        for session in queued:
            session.status = QUEUED  # no lane lost: not a preemption
            drained.append(session)
        return drained

    def summary(self) -> dict:
        return self.metrics.summary()

    def reset_metrics(self) -> None:
        """Discard accumulated telemetry and the finished list (keeps the
        compiled steps warm) — call after a warm-up pass so one-time
        compilation stays out of the measured TTFT/latency records."""
        self.metrics = EngineMetrics(self.cfg.n_slots, n_pages=self.n_pages)
        self.finished = []
        self._guard_calls = 0
        self._injected_drift_calls = 0
