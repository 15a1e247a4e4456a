"""Streaming scheduler service — the batch sweep as a continuous pipeline.

Stages (each overlapping the others):

  arrivals        ``ScenarioRequest``s from a trace (or prepared
                  ``FitnessFn``s from a client like ``serve.engine``)
  analysis        bounded host thread pool (``AnalysisPool``) producing
                  Job Analysis Tables concurrently with device compute
  admission       ready scenarios are grouped by *compatibility key*
                  (same (G, A) tables, objective, kernel flag, budget —
                  everything a compiled executable is specialized on),
                  padded to a power-of-two bucket, and dispatched through
                  the SAME compiled row executables ``run_sweep`` uses
                  (``repro.core.sweep.row_executable``).  SLO-aware
                  (default): queues dispatch in (priority class, slack)
                  order, a held partial flushes early when an urgent
                  member's slack runs out, and anytime mode splits
                  deadline-carrying scenarios into a fast interim row
                  plus a silent memo-bound refinement
  device          up to ``max_inflight`` batches enqueued at once — JAX
                  dispatch is async, so batch i+1's transfer and launch
                  overlap batch i's compute (the sweep's double-buffering,
                  continuous)
  router          results come off the device in dispatch order and are
                  routed back to their requests with full timing stamps;
                  ``compute_metrics`` turns them into service metrics

Bit-identity guarantee
----------------------
A streamed scenario's schedule is **bit-identical** to a standalone
``magma_search`` / ``run_sweep`` row with the same (scenario, seed): each
row is seeded from ``PRNGKey(request.seed)`` and evaluated by the same
vmapped per-row search the sweep runs, and rows are independent (padding
repeats the last real row; its results are sliced off).  Batching,
bucket padding, device count, and arrival order therefore change only
*when* a schedule is computed, never *what* it is — the pipeline is a
pure-throughput win (tests/test_stream.py gates this, in-process and on
8 fake devices).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from concurrent.futures import FIRST_COMPLETED, wait
from typing import (Dict, List, NamedTuple, Optional, Sequence, Tuple,
                    Union)

import jax
import numpy as np

from repro.core.encoding import Population
from repro.core.fitness import FitnessFn, ObjectiveSpec
from repro.core.magma import MagmaConfig, SearchResult
from repro.core.pareto import ParetoFront, pareto_front
from repro.core.strategies import SearchStrategy, WarmStart, plan_generations
from repro.core.sweep import (_note_run, _pad_rows, _resolve_strategy,
                              row_executable)
from repro.lint.runtime import transfer_sanitizer
from repro.obs import (FlightRecorder, NULL_SPAN, NULL_TRACER, ObsConfig,
                       RunClock, Tracer, as_obs_config)
from repro.obs import capture as _flight_capture
from repro.stream.admission import AdmissionQueues
from repro.stream.analysis import AnalysisPool, ReadyScenario
from repro.stream.metrics import StreamMetrics, compute_metrics
from repro.stream.workloads import ScenarioRequest, TraceConfig, generate_trace


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Pipeline shape knobs.

    batch_rows        admission cap: at most this many scenarios per
                      device dispatch (batches are padded up to a
                      power-of-two bucket <= batch_rows, so only
                      O(log batch_rows) executables exist per
                      compatibility key)
    analysis_workers  host threads running the Job Analyzer
    max_inflight      device batches enqueued but not yet routed; 2 =
                      classic double buffering (the next batch's transfer
                      + launch overlap the current batch's compute)
    max_devices       shard each batch over at most this many devices
                      (None: all visible)
    realtime          replay trace arrival times on the wall clock; False
                      (default) replays as-fast-as-possible — arrival is
                      the submission instant, the open-loop throughput
                      benchmark mode
    max_hold_s        liveness bound on partial-batch holding: a partial
                      batch normally waits for in-flight analyses to fill
                      it, but under sustained load of *other*
                      compatibility keys those analyses never will — once
                      the oldest held scenario has waited this long it
                      dispatches bucket-padded regardless
    slo_aware         order admission by (priority class, slack) instead
                      of deepest-queue-first, and flush a held partial
                      early when an urgent member's slack runs out (the
                      *hold* is preempted, never in-flight device work).
                      With no priorities/deadlines on the trace the
                      ordering degenerates to deepest-first, so the
                      default changes nothing for SLO-free workloads;
                      False is the priority-blind baseline the perf
                      benchmark compares against
    slo_margin_s      an urgent member whose slack (arrival + deadline -
                      now) has shrunk to this margin flushes its held
                      partial immediately
    anytime_budget    anytime mode (needs a memo and slo_aware): a
                      deadline-carrying scenario missing the memo
                      dispatches TWICE — a short-budget interim row at
                      this budget, routed to the caller fast, and a
                      silent full-budget refinement that lands in the
                      memo (idempotent record), so the next arrival of
                      the same scenario replays the refined schedule for
                      free.  Both rows are ordinary compiled-executable
                      rows: the interim is bit-identical to a standalone
                      search at the anytime budget, the refinement to
                      one at the full budget.  None disables the split
    transfer_guard    run dispatch/route device regions under
                      ``jax.transfer_guard("disallow")``
                      (``repro.lint.runtime``): every intended transfer
                      is an explicit ``device_put``/``device_get``, so
                      an implicit host<->device copy sneaking onto the
                      hot path raises instead of silently syncing.
                      Host-side batch assembly (key/param stacking)
                      happens before the guarded region.  Off by
                      default (sanitizer, not behavior)
    obs               observability (``repro.obs.ObsConfig``, a plain
                      dict of its fields — the form fleet workers get
                      over the wire — or None = disabled).  Enabled, the
                      service traces one span tree per scenario
                      (admit/analyze/queue_wait/dispatch/device/route +
                      memo spans) and the main loop's ``LOOP_PHASES``,
                      runs a flight recorder, and feeds the
                      process metrics registry.  All host-side: spans
                      never wrap jitted code, schedules stay
                      bit-identical (perf_obs gates <3% overhead)
    """
    batch_rows: int = 8
    analysis_workers: int = 2
    max_inflight: int = 2
    max_devices: Optional[int] = None
    realtime: bool = False
    max_hold_s: float = 0.25
    slo_aware: bool = True
    slo_margin_s: float = 0.05
    anytime_budget: Optional[int] = None
    transfer_guard: bool = False
    obs: Union[ObsConfig, Dict, None] = None

    def __post_init__(self):
        for field in ("batch_rows", "analysis_workers", "max_inflight"):
            if getattr(self, field) < 1:
                raise ValueError(f"{field} must be >= 1, got "
                                 f"{getattr(self, field)}")
        if self.max_devices is not None and self.max_devices < 1:
            raise ValueError(f"max_devices must be >= 1 or None, got "
                             f"{self.max_devices}")
        if self.max_hold_s < 0:
            raise ValueError(f"max_hold_s must be >= 0, got "
                             f"{self.max_hold_s}")
        if self.slo_margin_s < 0:
            raise ValueError(f"slo_margin_s must be >= 0, got "
                             f"{self.slo_margin_s}")
        if self.anytime_budget is not None:
            if self.anytime_budget < 1:
                raise ValueError(f"anytime_budget must be >= 1 or None, "
                                 f"got {self.anytime_budget}")
            if not self.slo_aware:
                raise ValueError("anytime_budget needs slo_aware=True: "
                                 "the interim/refinement split is part of "
                                 "deadline-aware admission")
        as_obs_config(self.obs)      # validate shape/values early


class CompatKey(NamedTuple):
    """Everything a compiled row executable is specialized on — only
    scenarios agreeing on all of it may share a device batch.  A
    NamedTuple so admission/dispatch/metrics address the axes by name
    while legacy consumers still unpack it positionally like the old
    bare 7-tuple (``base, G, A, use_kernel, objective, budget, is_warm =
    compat_key``).  ``objective`` is the fit's canonical
    ``ObjectiveSpec`` (a bare-name fit and a 1-tuple-spec fit group into
    the same batch)."""
    strategy: SearchStrategy
    group_size: int
    num_accels: int
    use_kernel: bool
    objective: Optional[ObjectiveSpec]
    budget: int
    warm: bool


@dataclasses.dataclass(frozen=True)
class PreparedScenario:
    """A client-supplied, already-analyzed scenario (e.g. serve.engine's
    TPU-submesh tables): skips the analysis stage, enters admission
    directly."""
    fit: FitnessFn
    seed: int
    uid: int = 0
    budget: Optional[int] = None     # None: the service's default
    strategy: Union[SearchStrategy, str, None] = None  # None: the service's
    priority: str = "normal"         # SLO class (workloads.PRIORITY_CLASSES)
    deadline_s: Optional[float] = None   # SLO latency budget from admission


@dataclasses.dataclass
class StreamResult:
    """One routed schedule + the request's trip through the pipeline
    (timestamps are offsets from the run's start)."""
    request: ScenarioRequest
    best_fitness: float
    best_accel: np.ndarray
    best_prio: np.ndarray
    history_best: np.ndarray
    n_samples: int
    arrival_s: float
    analysis_start_s: float
    ready_s: float
    dispatch_s: float
    done_s: float
    # schedule-memo provenance: an exact hit was replayed from the store
    # (no device dispatch — dispatch_s == done_s == the admission
    # instant); a warm-seeded row searched from a transferred population
    # (on an exact hit the flag says how the STORED row was solved)
    memo_exact: bool = False
    warm_seeded: bool = False
    # the sampling budget this schedule was actually computed at — the
    # request's budget, except for an anytime interim (the short anytime
    # budget) or an exact hit of a refined record (the refined budget)
    budget: int = 0
    anytime_interim: bool = False
    # the converged population (multi-objective rows and memoized
    # strategies emit one) — ``repro.core.pareto.pareto_front`` turns it
    # into the request's ParetoFront
    final_population: Optional[Population] = None

    @property
    def latency_s(self) -> float:
        """Schedule latency: arrival -> schedule routed back."""
        return self.done_s - self.arrival_s

    @property
    def deadline_met(self) -> Optional[bool]:
        """Whether the schedule was routed within its SLO deadline
        (None when the request carries no deadline)."""
        deadline = getattr(self.request, "deadline_s", None)
        if deadline is None:
            return None
        return self.latency_s <= deadline

    def to_search_result(self) -> SearchResult:
        """The row as the ``SearchResult`` a standalone search returns."""
        T = len(self.history_best)
        per_gen = self.n_samples // max(T, 1)
        return SearchResult(
            best_fitness=self.best_fitness,
            best_accel=self.best_accel, best_prio=self.best_prio,
            history_samples=per_gen * np.arange(1, T + 1),
            history_best=np.asarray(self.history_best, dtype=np.float64),
            n_samples=self.n_samples,
            wall_time_s=self.done_s - self.dispatch_s,
        )


@dataclasses.dataclass
class _BatchRecord:
    """Router-side record of one device dispatch (feeds the metrics)."""
    dispatch_s: float
    done_s: float
    rows: int
    padded_rows: int
    num_devices: int
    compat_key: Tuple
    batch: int                      # the run's dispatch counter (obs arg)


@dataclasses.dataclass
class _Inflight:
    out: tuple                      # device arrays, possibly still computing
    members: List[ReadyScenario]
    dispatch_s: float
    padded_rows: int
    num_devices: int
    compat_key: Tuple
    batch: int


# the main loop's phases: with obs on, every instant of ``_run`` lies in
# exactly one of these live spans
INTAKE = "stream.loop.intake"              # submit arrivals, drain, admit
DISPATCH = "stream.loop.dispatch"          # select, assemble, put, launch
ROUTE_WAIT = "stream.loop.route_wait"      # block on the head batch
ROUTE = "stream.loop.route"                # fetch, build results, record
WAIT_ANALYSIS = "stream.loop.wait_analysis"  # wait for an analysis
IDLE = "stream.loop.idle"                  # realtime sleep, nothing due
LOOP_PHASES = (INTAKE, DISPATCH, ROUTE_WAIT, ROUTE, WAIT_ANALYSIS, IDLE)


class _LoopPhases:
    """The main loop's current phase, held open as one live span.

    ``to(name, batch)`` closes the open phase and opens ``name`` unless
    the loop is already in it, so consecutive iterations in one phase
    (a long realtime idle) make one span; staying in it re-opens the
    span's profiler annotation if a profiler has started meanwhile.  On
    a disabled tracer it does nothing."""

    __slots__ = ("_tracer", "_key", "_span")

    def __init__(self, tracer: Tracer) -> None:
        self._tracer = tracer
        self._key: Optional[Tuple[str, Optional[int]]] = None
        self._span = NULL_SPAN

    def to(self, name: str, batch: Optional[int] = None) -> None:
        if not self._tracer.enabled:
            return
        if self._key == (name, batch):
            self._span.reannotate()
            return
        self._span.finish()
        self._key = (name, batch)
        self._span = (self._tracer.span(name) if batch is None
                      else self._tracer.span(name, batch=batch))

    def close(self) -> None:
        self._span.finish()
        self._key, self._span = None, NULL_SPAN


_NO_PHASES = _LoopPhases(NULL_TRACER)


class StreamingScheduler:
    """The streaming multi-tenant scheduling service.

    One instance holds the analysis pool (and its shared profile caches)
    and reuses compiled executables across runs, so a long-lived service
    pays compilation once per (compatibility key, bucket) and then keeps
    the device saturated.

        svc = StreamingScheduler(budget=2_000)
        results = svc.run(generate_trace(TraceConfig(num_scenarios=32)))
        print(svc.last_metrics.summary())
    """

    def __init__(self,
                 strategy: Union[SearchStrategy, str, None] = None,
                 cfg: Optional[MagmaConfig] = None,
                 budget: int = 2_000,
                 stream: Optional[StreamConfig] = None,
                 memo=None):
        self.stream = stream or StreamConfig()
        self.budget = int(budget)
        # the schedule memo (repro.memo.ScheduleMemo) consulted at
        # admission: exact hits are answered from the store and NEVER
        # enter the dispatch queue; misses are warm-seeded from the
        # nearest stored scenario when the family has one.  Every routed
        # row is recorded back (with its converged population), so a
        # long-lived service computes most schedules once.
        self.memo = memo
        if self.stream.anytime_budget is not None and memo is None:
            raise ValueError(
                "anytime mode needs a memo: the background refinement's "
                "whole purpose is landing in the store for the next "
                "arrival — without one its result would be discarded")
        self._strategy = _resolve_strategy(strategy, cfg)
        if not self._strategy.device_resident:
            raise ValueError(
                f"strategy {self._strategy.name!r} is host-only; the "
                "streaming service batches scenarios onto the device fleet "
                "and cannot run host-loop searches")
        # run-relative clock shared by result timestamps AND the span
        # tracer, so a trace file lines up with StreamResult fields
        self.clock = RunClock()
        self.obs = as_obs_config(self.stream.obs)
        if self.obs.enabled:
            self.tracer = Tracer(capacity=self.obs.trace_capacity,
                                 clock=self.clock, worker=self.obs.worker)
            self.flight: Optional[FlightRecorder] = FlightRecorder(
                max_events=self.obs.flight_events,
                dump_dir=self.obs.flight_dir,
                worker=self.obs.worker, clock=self.clock)
            if self.memo is not None:
                self.memo.tracer = self.tracer
        else:
            self.tracer = NULL_TRACER
            self.flight = None
        self.pool = AnalysisPool(self.stream.analysis_workers,
                                 clock=self._clock, tracer=self.tracer)
        self.last_metrics: Optional[StreamMetrics] = None
        self.last_batches: List[_BatchRecord] = []   # @locked:_run_lock
        self._refined = 0            # @locked:_run_lock  silent refinements
        self._dispatched = 0         # @locked:_run_lock  the next batch id
        # the last run's AdmissionQueues (counters: enqueued/dispatched/
        # stolen/depth/peak/early_flushes)  @locked:_run_lock
        self.last_admission: Optional[AdmissionQueues] = None

        # one run at a time: the clock zero, batch records, and metrics
        # are per-run state, so concurrent clients (several engines
        # sharing one service) serialize here rather than corrupt them
        self._run_lock = threading.Lock()

    # -- clock ----------------------------------------------------------------
    def _clock(self) -> float:
        return self.clock()

    def _begin_run(self) -> None:
        """Reset per-run state: the clock zero, batch records, and (when
        observability is on) the span buffer.  @holds:_run_lock"""
        self.clock.reset()
        self.last_batches = []
        self._refined = 0
        self._dispatched = 0
        if self.obs.enabled and self.obs.clear_per_run:
            self.tracer.clear()

    # -- admission helpers ----------------------------------------------------
    def _resolve_override(self, strategy) -> SearchStrategy:
        if strategy is None:
            return self._strategy
        strategy = _resolve_strategy(strategy, None)
        if not strategy.device_resident:
            raise ValueError(
                f"strategy {strategy.name!r} is host-only and cannot be "
                "streamed; run it per problem via run_strategy")
        return strategy

    def _compat_key(self, ready: ReadyScenario) -> CompatKey:
        """The scenario's :class:`CompatKey`.  Warm-seeded rows take a
        different executable (extra WarmStart input), so the warm flag is
        a compatibility axis too."""
        fit = ready.fit
        budget = ready.request.budget or self.budget
        return CompatKey(
            strategy=self._resolve_override(ready.strategy),
            group_size=fit.group_size, num_accels=fit.num_accels,
            use_kernel=fit.use_kernel, objective=fit.objective_spec,
            budget=budget, warm=ready.warm is not None)

    def _bucket(self, n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return min(b, self.stream.batch_rows)

    # -- SLO ordering ---------------------------------------------------------
    # the ordering policy (class rank / slack / early flush / member
    # take-order) lives in repro.stream.admission.AdmissionQueues now —
    # extracted so the fleet router can own queues with the same
    # semantics and steal held partials between workers
    def _admission(self) -> AdmissionQueues:
        s = self.stream
        return AdmissionQueues(batch_rows=s.batch_rows,
                               slo_aware=s.slo_aware,
                               max_hold_s=s.max_hold_s,
                               slo_margin_s=s.slo_margin_s)

    def _keep_population(self, strategy: SearchStrategy) -> bool:
        """Whether dispatches emit converged populations: memo attached
        and the strategy hands populations off, OR the strategy is
        multi-objective — its archive population IS the deliverable (the
        ParetoFront is extracted from it)."""
        return ((self.memo is not None and strategy.supports_init_population)
                or getattr(strategy, "multi_objective", False))

    def _dispatch(self, compat_key: CompatKey, members: List[ReadyScenario]
                  ) -> _Inflight:
        """Assemble, transfer and launch one batch.  @holds:_run_lock"""
        base, G, A, use_kernel, objective, budget, is_warm = compat_key
        warm_seeded = bool(is_warm)     # compat-key flag, not key material
        t_dispatch = self.tracer.now() if self.tracer.enabled else 0.0
        strategy = base.bind(A)
        generations, evolve_last = plan_generations(budget,
                                                    strategy.ask_size)
        n = len(members)
        bucket = self._bucket(n)
        # local_devices, not devices: under jax.distributed (the fleet's
        # multi-controller mode) jax.devices() is GLOBAL and a worker
        # may only address its own — identical single-controller
        avail = len(jax.local_devices())
        ndev = avail if self.stream.max_devices is None else max(1, min(
            self.stream.max_devices, avail))
        ndev = min(ndev, bucket)
        padded = -(-bucket // ndev) * ndev           # dense shards

        keys = np.stack([np.asarray(jax.random.PRNGKey(m.request.seed))
                         for m in members])
        params = jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]),
            *[m.fit.params for m in members])
        params, keys = _pad_rows(params, keys, padded)

        fn, target = row_executable(
            strategy, generations, evolve_last, G, use_kernel, objective,
            ndev, keep_population=self._keep_population(base),
            warm=warm_seeded)
        if warm_seeded:
            warm = WarmStart(
                accel=np.stack([np.asarray(m.warm.accel) for m in members]),
                prio=np.stack([np.asarray(m.warm.prio) for m in members]),
                jitter=np.asarray([m.warm.jitter for m in members],
                                  dtype=np.float32))
            warm, _ = _pad_rows(warm, keys[:len(members)], padded)
        # batch assembly above is pure host numpy; only the transfers +
        # launch below run under the (optional) disallow guard
        with transfer_sanitizer(self.stream.transfer_guard):
            args = (jax.device_put(keys, target),
                    jax.device_put(params, target))
            if warm_seeded:
                args += (jax.device_put(warm, target),)
            _note_run(fn, args, strategy, generations, ndev)
            out = fn(*args)             # async: returns immediately
        inf = _Inflight(out=out, members=members, dispatch_s=self._clock(),
                        padded_rows=padded, num_devices=ndev,
                        compat_key=compat_key, batch=self._dispatched)
        self._dispatched += 1
        if self.tracer.enabled:
            # host-side stamps only — the device work was launched above
            # and its span is emitted at route time, when its end is known
            for m in members:
                uid = m.request.uid
                self.tracer.emit("queue_wait",
                                 m.admitted_s or m.ready_s, t_dispatch,
                                 scope=uid, batch=inf.batch)
                self.tracer.emit("dispatch", t_dispatch, inf.dispatch_s,
                                 scope=uid, rows=len(members),
                                 bucket=padded, devices=ndev,
                                 warm=warm_seeded, batch=inf.batch)
            if self.flight is not None:
                self.flight.note("dispatch", rows=len(members),
                                 bucket=padded, devices=ndev,
                                 uids=[m.request.uid for m in members])
        return inf

    def _prepared_ready(self, p: PreparedScenario) -> ReadyScenario:
        """A client-supplied scenario as an admission-queue entry (the
        synthetic request carries the placeholder provenance fields)."""
        now = self._clock()
        req = ScenarioRequest(
            uid=p.uid, arrival_s=now, mix="<prepared>",
            setting="<prepared>", bw_gb=p.fit.bw_sys / 1024 ** 3,
            group_size=p.fit.group_size, seed=p.seed,
            objective=p.fit.objective_spec.token, budget=p.budget,
            priority=p.priority, deadline_s=p.deadline_s)
        return ReadyScenario(request=req, fit=p.fit, analysis_start_s=now,
                             ready_s=now,
                             strategy=self._resolve_override(p.strategy))

    def _route(self, inf: _Inflight, results: List[StreamResult],
               phases: _LoopPhases = _NO_PHASES) -> None:
        """Fetch a finished batch and route rows.  @holds:_run_lock"""
        with transfer_sanitizer(self.stream.transfer_guard):
            phases.to(ROUTE_WAIT, inf.batch)
            jax.block_until_ready(inf.out)
            done = self._clock()
            phases.to(ROUTE, inf.batch)
            outs = [jax.device_get(o) for o in inf.out]
        bf, ba, bp, hist = outs[:4]
        pops = outs[4:6] if len(outs) >= 6 else None
        base, _, A, _, _, budget, is_warm = inf.compat_key
        strategy = base.bind(A)
        generations, _ = plan_generations(budget, strategy.ask_size)
        n_samples = strategy.ask_size * generations
        for i, m in enumerate(inf.members):
            if m.silent:
                # anytime background refinement: recorded below, never
                # routed — the caller already has (or will get) the
                # interim schedule
                self._refined += 1
            else:
                res = StreamResult(
                    request=m.request,
                    best_fitness=float(bf[i]),
                    best_accel=ba[i], best_prio=bp[i], history_best=hist[i],
                    n_samples=n_samples,
                    arrival_s=m.request.arrival_s,
                    analysis_start_s=m.analysis_start_s,
                    ready_s=m.ready_s,
                    dispatch_s=inf.dispatch_s,
                    done_s=done,
                    warm_seeded=is_warm,
                    budget=budget,
                    anytime_interim=m.anytime,
                    final_population=(Population(accel=pops[0][i],
                                                 prio=pops[1][i])
                                      if pops is not None else None),
                )
                results.append(res)
                if self.flight is not None \
                        and res.deadline_met is False \
                        and self.obs.dump_on_deadline_miss:
                    self.flight.on_deadline_miss(
                        m.request.uid, res.latency_s,
                        m.request.deadline_s)
            if self.memo is not None:
                self.memo.record(
                    m.fit, strategy, budget, m.request.seed,
                    {"best_fitness": bf[i], "best_accel": ba[i],
                     "best_prio": bp[i], "history_best": hist[i]},
                    population=((pops[0][i], pops[1][i])
                                if pops is not None else None),
                    family=m.request.mix, warm=m.warm,
                    scope=m.request.uid)
        self.last_batches.append(_BatchRecord(
            dispatch_s=inf.dispatch_s, done_s=done, rows=len(inf.members),
            padded_rows=inf.padded_rows, num_devices=inf.num_devices,
            compat_key=inf.compat_key, batch=inf.batch))
        if self.tracer.enabled:
            t_routed = self.tracer.now()
            for m in inf.members:
                uid = m.request.uid
                self.tracer.emit("device", inf.dispatch_s, done,
                                 scope=uid, rows=len(inf.members),
                                 devices=inf.num_devices, batch=inf.batch)
                self.tracer.emit("route", done, t_routed, scope=uid,
                                 silent=m.silent, batch=inf.batch)
            if self.flight is not None:
                self.flight.note("route", rows=len(inf.members),
                                 device_s=done - inf.dispatch_s)

    # -- the pipeline ---------------------------------------------------------
    def run(self,
            requests: Sequence[ScenarioRequest] = (),
            prepared: Sequence[PreparedScenario] = ()
            ) -> List[StreamResult]:
        """Drive the full pipeline over a trace (plus any prepared
        scenarios) and return results ordered by request uid.  Metrics for
        the run land in ``self.last_metrics``.  One run executes at a
        time (per-run clock/metrics state); concurrent callers serialize.
        """
        with self._run_lock:
            with _flight_capture(self.flight, "stream.run"):
                return self._run(requests, prepared)

    def _admit(self, ready: ReadyScenario, queues: AdmissionQueues,
               results: List[StreamResult], sp) -> None:
        """Admission of one analyzed scenario: memo consult, anytime
        split, queue push.  ``sp`` is the open ``admit`` span (outcome
        args land on it; the no-op handle when tracing is off).
        @holds:_run_lock"""
        uid = ready.request.uid
        budget = ready.request.budget or self.budget
        if self.memo is not None:
            strategy = self._resolve_override(ready.strategy)
            hit = self.memo.lookup(ready.fit, strategy, budget,
                                   ready.request.seed, scope=uid)
            if hit is not None:
                # exact hit: the stored schedule IS the answer,
                # bit-for-bit — no device dispatch, the request never
                # enters a queue (dispatch_s == done_s == now)
                now = self._clock()
                results.append(StreamResult(
                    request=ready.request,
                    best_fitness=float(hit.best_fitness),
                    best_accel=np.asarray(hit.best_accel),
                    best_prio=np.asarray(hit.best_prio),
                    history_best=np.asarray(hit.history_best),
                    n_samples=hit.n_samples,
                    arrival_s=ready.request.arrival_s,
                    analysis_start_s=ready.analysis_start_s,
                    ready_s=ready.ready_s,
                    dispatch_s=now, done_s=now,
                    memo_exact=True,
                    # provenance, not a second hit: the counters
                    # treat exact and warm as disjoint (exact wins)
                    warm_seeded=hit.warm_seeded,
                    budget=budget,
                    final_population=(
                        None if hit.population is None else
                        Population(accel=hit.population[0],
                                   prio=hit.population[1])),
                ))
                sp.set(outcome="memo_exact")
                return
            # miss: seed from the nearest stored scenario of the
            # same transfer family, when one exists (the memo's
            # donor-distance guard refuses far donors — cold init)
            ready.warm = self.memo.warm_start(
                ready.fit, strategy, family=ready.request.mix,
                scope=uid)
        anytime = self.stream.anytime_budget
        if anytime is not None and anytime < budget \
                and ready.request.deadline_s is not None:
            # anytime split: the caller gets a short-budget interim
            # schedule fast; a silent full-budget twin refines in
            # the background and lands in the memo, upgrading the
            # NEXT arrival of this scenario to an exact replay of
            # the refined schedule
            interim = dataclasses.replace(
                ready,
                request=dataclasses.replace(ready.request,
                                            budget=anytime),
                anytime=True)
            if self.tracer.enabled:
                interim.admitted_s = self._clock()
            queues.push(self._compat_key(interim), interim)
            ready.silent = True
        if self.tracer.enabled:
            ready.admitted_s = self._clock()
        queues.push(self._compat_key(ready), ready)
        sp.set(outcome="queued", warm=ready.warm is not None,
               split=ready.silent)

    def _run(self, requests, prepared) -> List[StreamResult]:
        """The pipeline body (entered by ``run()``).  @holds:_run_lock"""
        self._begin_run()
        realtime = self.stream.realtime

        to_submit = deque(sorted(requests, key=lambda r: (r.arrival_s, r.uid)))
        queues = self._admission()
        self.last_admission = queues      # counters readable post-run
        inflight: deque = deque()
        futs = set()
        results: List[StreamResult] = []
        phases = _LoopPhases(self.tracer)
        phases.to(INTAKE)

        def admit(ready: ReadyScenario):
            if self.tracer.enabled:
                with self.tracer.span("admit", scope=ready.request.uid) as sp:
                    self._admit(ready, queues, results, sp)
            else:
                self._admit(ready, queues, results, NULL_SPAN)

        for p in prepared:
            admit(self._prepared_ready(p))

        while to_submit or futs or queues or inflight:
            progressed = False

            # 1. feed due arrivals into the analysis pool
            while to_submit and (not realtime
                                 or to_submit[0].arrival_s <= self._clock()):
                phases.to(INTAKE)
                req = to_submit.popleft()
                if not realtime:
                    # as-fast-as-possible replay: arrival == submission
                    req = dataclasses.replace(req, arrival_s=self._clock())
                futs.add(self.pool.submit(req))
                progressed = True

            # 2. drain finished analyses into the admission queues
            if futs:
                done, futs = wait(futs, timeout=0)
                if done:
                    phases.to(INTAKE)
                for f in done:
                    admit(f.result())
                    progressed = bool(done) or progressed

            # 3. admission: FULL batches whenever a queue has them; while
            # any analysis is in flight, partials are HELD — analyses
            # complete in milliseconds and fill the batch, whereas a
            # small row-batch wastes device efficiency (per-row cost
            # rises sharply below batch_rows) and, on a shared-core host,
            # steals CPU from the very analyses that would fill it.  With
            # nothing being analyzed (stream draining, or sparse realtime
            # arrivals), partials go out bucket-padded rather than letting
            # the device idle — and a partial that _must_flush (oldest
            # member waited max_hold_s, or an urgent member's slack ran
            # out) dispatches regardless, so a rare compatibility key
            # cannot starve behind a sustained stream of other keys.
            # SLO-aware: queues go out in (class rank, slack, -depth)
            # order — batch work never delays an urgent schedule; blind
            # (slo_aware=False): deepest queue first so batches fill out.
            # (Policy + accounting live in AdmissionQueues.)
            while len(inflight) < self.stream.max_inflight:
                key = queues.select(self._clock(), bool(futs))
                if key is None:
                    break          # hold the partials: more is coming
                phases.to(DISPATCH, self._dispatched)
                inflight.append(self._dispatch(key, queues.take(key)))
                progressed = True

            # 4. route: block on the head batch when the pipeline is full
            if inflight and len(inflight) >= self.stream.max_inflight:
                self._route(inflight.popleft(), results, phases)
                progressed = True

            if not progressed:
                if inflight:
                    # nothing else to do until the head batch finishes
                    # (held partials dispatch right after it routes)
                    self._route(inflight.popleft(), results, phases)
                elif futs:         # analyses still running: wait for one
                    phases.to(WAIT_ANALYSIS)
                    wait(futs, timeout=0.01, return_when=FIRST_COMPLETED)
                elif realtime and to_submit:
                    phases.to(IDLE)
                    time.sleep(min(0.01, max(
                        0.0, to_submit[0].arrival_s - self._clock())))

        phases.close()
        wall = self._clock()
        results.sort(key=lambda r: r.request.uid)
        queues.check()               # enqueued == dispatched+stolen+depth
        self.last_metrics = compute_metrics(results, self.last_batches, wall,
                                            refinements=self._refined,
                                            admission=queues)
        return results

    def run_trace(self, trace: TraceConfig) -> List[StreamResult]:
        """Generate ``trace`` and run it through the pipeline."""
        return self.run(generate_trace(trace))

    def warmup(self, requests: Sequence[ScenarioRequest] = (),
               prepared: Sequence[PreparedScenario] = ()
               ) -> "StreamingScheduler":
        """Pre-compile every bucket-size executable the given workload can
        hit (and pre-fill the analyzer profile caches).

        Greedy admission makes batch sizes timing-dependent — whichever
        scenarios are ready go out — so without warmup a cold bucket's
        XLA compile can land mid-stream and stall the pipeline for
        seconds.  A production service compiles at startup; call this
        with a representative trace before serving (the perf benchmark
        does, so it measures the pipeline, not compilation).
        """
        from repro.costmodel import get_setting
        with self._run_lock:
            # one representative per executable-relevant signature
            # (derivable without analysis), so warming a big trace costs
            # a few analyses.  Anytime mode adds the short-budget interim
            # signature for every deadline-carrying request — interim
            # rows must reuse precompiled executables like any other row
            reps: Dict[Tuple, ScenarioRequest] = {}
            anytime = self.stream.anytime_budget
            for req in requests:
                variants = [req]
                if anytime is not None and req.deadline_s is not None \
                        and anytime < (req.budget or self.budget):
                    variants.append(
                        dataclasses.replace(req, budget=anytime))
                for rq in variants:
                    sig = (rq.group_size,
                           get_setting(rq.setting).num_sub_accels,
                           rq.objective, rq.budget or self.budget)
                    reps.setdefault(sig, rq)
            seen: Dict[Tuple, ReadyScenario] = {}

            def note(r: ReadyScenario):
                seen.setdefault(self._compat_key(r), r)
                strategy = self._resolve_override(r.strategy)
                if self.memo is not None and \
                        strategy.bind(r.fit.num_accels).\
                        supports_init_population:
                    # memo near-hits dispatch through the warm-input
                    # executable: precompile it too (zero-jitter dummy
                    # seed; warmup results are discarded)
                    bound = strategy.bind(r.fit.num_accels)
                    G = r.fit.group_size
                    w = WarmStart(
                        accel=np.zeros((bound.ask_size, G), np.int32),
                        prio=np.full((bound.ask_size, G), 0.5, np.float32),
                        jitter=np.float32(0.0))
                    rw = dataclasses.replace(r, warm=w)
                    seen.setdefault(self._compat_key(rw), rw)

            for req in reps.values():
                note(self.pool.analyze(req))
            for p in prepared:
                note(self._prepared_ready(p))
            for key, ready in seen.items():
                bucket = 1
                while True:
                    members = [ready] * min(bucket, self.stream.batch_rows)
                    jax.block_until_ready(self._dispatch(key, members).out)
                    if bucket >= self.stream.batch_rows:
                        break
                    bucket *= 2
            self.pool.prestart()         # worker threads spawn lazily
            self.last_batches = []       # warmup dispatches are not metrics
            return self

    def run_serial(self, requests: Sequence[ScenarioRequest],
                   shared_cache: bool = False) -> List[StreamResult]:
        """The pre-stream workflow as a baseline: analyze EVERY scenario
        first (host, one at a time), then sweep the batches (device), with
        no overlap anywhere.  ``shared_cache=False`` (default) replicates
        the old ``M3E.prepare`` exactly — a fresh ``JobAnalyzer`` per
        scenario, no cross-scenario profile reuse; ``shared_cache=True``
        grants the baseline the stream's shared digest cache, isolating
        the *pipelining* contribution from the *cache* contribution.
        Same admission grouping, same compiled executables, bit-identical
        results either way.  Metrics land in ``self.last_metrics``."""
        with self._run_lock:
            with _flight_capture(self.flight, "stream.run_serial"):
                return self._run_serial(requests, shared_cache)

    def _run_serial(self, requests, shared_cache) -> List[StreamResult]:
        """Serial baseline body (``run_serial()``).  @holds:_run_lock"""
        self._begin_run()          # serial baseline: no anytime splits
        results: List[StreamResult] = []

        # every request is on hand when the batch starts (the same
        # as-fast-as-possible convention the pipelined run uses), so all
        # arrivals stamp at t~0 — a scenario analyzed late has been
        # *waiting*, and its schedule latency must say so
        now = self._clock()
        ready: List[ReadyScenario] = [
            self.pool.analyze(dataclasses.replace(req, arrival_s=now),
                              fresh_analyzer=not shared_cache)
            for req in sorted(requests, key=lambda r: (r.arrival_s, r.uid))]

        queues: Dict[Tuple, deque] = {}
        for r in ready:
            queues.setdefault(self._compat_key(r), deque()).append(r)
        for key, q in queues.items():
            while q:
                members = [q.popleft()
                           for _ in range(min(len(q),
                                              self.stream.batch_rows))]
                # dispatch-then-route immediately: the device never has a
                # second batch enqueued behind the current one
                self._route(self._dispatch(key, members), results)

        wall = self._clock()
        results.sort(key=lambda r: r.request.uid)
        self.last_metrics = compute_metrics(results, self.last_batches, wall,
                                            refinements=self._refined)
        return results

    def schedule_prepared(self, fit: FitnessFn, seed: int = 0,
                          budget: Optional[int] = None,
                          strategy: Union[SearchStrategy, str, None] = None,
                          priority: str = "normal",
                          deadline_s: Optional[float] = None
                          ) -> StreamResult:
        """Schedule ONE prepared scenario through the stream (the
        ``serve.engine`` client path).  Without a memo, bit-identical to
        a standalone ``run_strategy``/``magma_search`` with the same
        seed, budget and (device-resident) strategy.  With a memo, a
        re-seen scenario replays the service's previous answer and a
        first-seen one may be warm-seeded from a stored population —
        same quality, but only cold-solved (never-warm-seeded) scenarios
        keep the standalone bit-identity (see
        ``repro.memo.ScheduleMemo.lookup``).  ``priority``/``deadline_s``
        are the caller's SLO (serve.engine passes its tenants'
        strictest); under anytime mode a deadline-carrying first-seen
        scenario returns the interim schedule while the full-budget
        refinement lands in the memo."""
        return self.run(prepared=[PreparedScenario(
            fit=fit, seed=seed, budget=budget, strategy=strategy,
            priority=priority, deadline_s=deadline_s)])[0]

    def schedule_front(self, fit: FitnessFn, seed: int = 0,
                       budget: Optional[int] = None,
                       strategy: Union[SearchStrategy, str, None] = "nsga2",
                       priority: str = "normal",
                       deadline_s: Optional[float] = None) -> ParetoFront:
        """Schedule one prepared multi-column scenario and return its
        Pareto frontier — the streamed twin of ``M3E.search_front``.
        ``fit`` carries the vector ``ObjectiveSpec``; the strategy must
        be ``multi_objective`` (default nsga2).  The front is extracted
        host-side from the routed archive population by re-evaluating it
        through ``fit.objectives`` — every front point bit-identical to a
        standalone evaluation — and memo replays of a re-seen frontier
        request rebuild the identical front from the stored population.
        """
        strat = self._resolve_override(strategy)
        if not getattr(strat, "multi_objective", False):
            raise ValueError(
                f"strategy {strat.name!r} is single-objective; "
                "schedule_front needs a multi_objective strategy "
                "such as 'nsga2'")
        res = self.schedule_prepared(fit, seed=seed, budget=budget,
                                     strategy=strategy, priority=priority,
                                     deadline_s=deadline_s)
        if res.final_population is None:
            raise RuntimeError(
                "schedule_front got a result without a population "
                "(a memo record stored without one?)")
        return pareto_front(fit, res.final_population,
                            n_samples=res.n_samples,
                            wall_time_s=res.done_s - res.dispatch_s)

    def export_trace(self, path: str) -> str:
        """Write the current span buffer as a Chrome trace-event file
        (Perfetto-loadable; ``python -m repro.obs <path>`` summarizes
        it).  Meaningful only with ``StreamConfig.obs`` enabled — a
        disabled tracer exports an empty trace."""
        from repro.obs.export import write_chrome_trace
        return write_chrome_trace(path, self.tracer.spans(),
                                  meta={"service": "repro.stream",
                                        "worker": self.obs.worker})

    def close(self) -> None:
        self.pool.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
