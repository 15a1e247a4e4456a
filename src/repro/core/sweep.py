"""Fleet-scale scenario sweeps — shard (strategy, scenario x seed) search
grids across devices and stream oversized grids in double-buffered chunks.

The paper's headline experiments (Fig. 8/9/11/13/17, Table IV) are grids
of many independent searches: S stacked scenario tables (same ``(G, A)``,
different ``lat``/``bw``/``bw_sys``/objective) x K PRNG seeds — times a
method axis for the comparison figures.  Any **device-resident**
``repro.core.strategies`` strategy rides the same machinery
(``run_sweep(strategy=...)``; MAGMA is the default), so every
method-vs-method comparison executes as compiled, sharded sweeps rather
than sequential host searches.  The device-resident engine already fuses
one strategy's grid into one vmapped XLA call; this module scales that
call out:

  1. the grid is flattened to ``N = S*K`` rows — row ``s*K + k`` is
     scenario ``s`` with seed ``seeds[k]`` — and evaluated by a single
     ``jax.vmap`` of the scanned per-row search;
  2. with more than one device the vmapped search is wrapped in
     ``shard_map`` over a 1-D ``repro.dist.sharding.flat_mesh``, so each
     device runs its contiguous slice of rows SPMD (rows are
     embarrassingly parallel: no collectives).  On a single device the
     same vmapped function runs unsharded — the fallback is the code
     path, not a reimplementation;
  3. grids larger than device memory stream through the mesh in fixed-
     size chunks: while chunk ``i`` computes, chunk ``i+1`` is already
     being ``jax.device_put`` (async host->device transfer overlaps
     compute), so a bounded device footprint costs one compiled call per
     chunk, not per row.

Rows are padded (by repeating the last real row) so every chunk has the
same shape — one executable serves the whole stream — and padding is
sliced off before results reshape back to ``(S, K)``.  Every row is
bit-identical to a standalone ``magma_search`` with the same scenario
and seed, across device counts and chunk sizes (tests/test_sweep.py).

``magma_search_batch`` and ``benchmarks.common.run_problems_batched``
route through :func:`run_sweep`; ``benchmarks/perf_sweep.py`` measures
it and emits ``BENCH_sweep.json``.  CPU CI exercises the sharded path
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import dataclasses
import re
import time
from functools import lru_cache, partial
from typing import Dict, List, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.fitness import (FitnessFn, FitnessParams, ObjectiveSpec,
                                as_objective_spec, evaluate_objectives,
                                evaluate_params, normalize_scenarios)
from repro.core.magma import BatchSearchResult, MagmaConfig
from repro.core.strategies import (MagmaStrategy, SearchStrategy, available,
                                   get_strategy, plan_generations,
                                   scan_strategy)
from repro.dist.sharding import flat_mesh

SWEEP_AXIS = "sweep"


@dataclasses.dataclass(frozen=True)
class SweepConfig:
    """How a scenario grid is partitioned across devices and time.

    chunk_rows     max (scenario, seed) rows resident per compiled call;
                   None runs the whole grid as one chunk.  Rounded up to
                   a multiple of the device count so every shard is
                   dense.
    max_devices    shard over at most this many devices (None: all
                   available).  ``max_devices=1`` forces the
                   single-device vmapped path — the reference the
                   sharded path is tested bit-identical against.
    transfer_guard run the chunk loop under ``jax.transfer_guard(
                   "disallow")`` (``repro.lint.runtime``): every
                   intended transfer is an explicit ``device_put``/
                   ``device_get``, so any implicit host<->device copy
                   sneaking onto the hot path raises instead of silently
                   syncing.  Off by default (sanitizer, not behavior).
    obs            observability (``repro.obs.ObsConfig``, a dict of its
                   fields, or None = disabled): the chunk loop emits one
                   ``sweep.chunk`` span per compiled call into the
                   process-wide tracer (``repro.obs.get_tracer()``).
                   Host-side stamps only — never inside the compiled
                   call, so rows stay bit-identical.
    """
    chunk_rows: Optional[int] = None
    max_devices: Optional[int] = None
    transfer_guard: bool = False
    obs: object = None


@dataclasses.dataclass
class SweepResult(BatchSearchResult):
    """BatchSearchResult plus how the grid was executed."""
    num_devices: int = 1
    rows: int = 0                  # real (scenario, seed) rows
    padded_rows: int = 0           # rows actually computed (incl. padding)
    chunk_rows: int = 0            # rows per compiled call
    chunk_wall_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def num_chunks(self) -> int:
        return len(self.chunk_wall_s)

    @property
    def generations(self) -> int:
        return int(self.history_samples.shape[0])

    def gens_per_sec(self) -> List[float]:
        """Aggregate generations/second per chunk (all rows of the chunk
        advance one generation together)."""
        return [self.chunk_rows * self.generations / max(w, 1e-12)
                for w in self.chunk_wall_s]


def _row_search(key, params, strategy: SearchStrategy, generations: int,
                evolve_last: bool, group_size: int, use_kernel: bool,
                objective: Optional[ObjectiveSpec], keep_population: bool = False,
                warm=None):
    """One (scenario, seed) row — identical trace to ``run_strategy``'s
    scanned engine: seed the strategy state from the row key, run the
    shared scan.  Bit-for-bit parity with a standalone search depends on
    the strategy's ``init`` key-split order; don't reorder.

    ``warm`` is an optional per-row ``strategies.WarmStart`` (the memo's
    near-hit population seed, jittered device-side inside ``init``);
    ``keep_population`` additionally emits the converged population —
    extra scan *outputs* only, the search trace is unchanged, so both
    variants stay bit-identical on the schedule outputs."""
    if getattr(strategy, "multi_objective", False):
        def eval_fn(a, pr):
            return evaluate_objectives(params, a, pr,
                                       num_accels=strategy.num_accels,
                                       use_kernel=use_kernel,
                                       objective=objective)
    else:
        def eval_fn(a, pr):
            return evaluate_params(params, a, pr,
                                   num_accels=strategy.num_accels,
                                   use_kernel=use_kernel, objective=objective)

    state = strategy.init(key, params, init_population=warm)
    out = scan_strategy(strategy, state, eval_fn, group_size, generations,
                        evolve_last)
    if keep_population:
        pop = strategy.population(out[4])
        return out[:4] + (pop.accel, pop.prio)
    return out[:4]       # (best_fit, best_accel, best_prio, history)


@lru_cache(maxsize=None)
def _chunk_fn(mesh, strategy: SearchStrategy, generations: int,
              evolve_last: bool, group_size: int, use_kernel: bool,
              objective: Optional[ObjectiveSpec], keep_population: bool = False,
              warm: bool = False):
    """Compiled (rows_keys, rows_params[, rows_warm]) -> per-row results,
    cached so repeated sweeps with the same mesh/shape/strategy reuse one
    executable (strategies are frozen dataclasses: equal configs hash
    equal).  ``mesh is None`` is the single-device fallback: the same
    vmapped search, just not wrapped in shard_map.  ``keep_population``
    and ``warm`` select the memo variants (extra outputs / a warm-start
    input batch) — distinct executables, same search trace."""
    base = partial(_row_search, strategy=strategy, generations=generations,
                   evolve_last=evolve_last, group_size=group_size,
                   use_kernel=use_kernel, objective=objective,
                   keep_population=keep_population)
    if warm:
        search = jax.vmap(lambda k, p, w: base(k, p, warm=w))
        n_in = 3
    else:
        search = jax.vmap(lambda k, p: base(k, p))
        n_in = 2
    if mesh is None:
        return jax.jit(search)
    spec = PartitionSpec(SWEEP_AXIS)
    # check_vma=False: rows never communicate, so there is no collective
    # whose replication the varying-manual-axes check could protect; with
    # it on, every scan carry initialised from constants (the event
    # simulation's t0/ptr0, the strategies' counters) would need a pcast
    return jax.jit(jax.shard_map(search, mesh=mesh,
                                 in_specs=(spec,) * n_in, out_specs=spec,
                                 check_vma=False))


def row_executable(strategy: SearchStrategy, generations: int,
                   evolve_last: bool, group_size: int, use_kernel: bool,
                   objective, num_devices: int,
                   keep_population: bool = False, warm: bool = False):
    """(compiled row-batch fn, device_put target) for ``num_devices``.

    The public face of the chunk executable cache: ``repro.stream``'s
    admission stage dispatches ready-scenario batches through the very
    same compiled functions ``run_sweep`` uses, so a streamed scenario
    cannot diverge from a batch sweep row.  The returned ``fn`` maps
    ``(keys (N, 2), params with leading N)`` -> per-row results; call it
    without blocking to overlap device compute with host-side analysis
    (JAX dispatch is async), and ``jax.block_until_ready`` the outputs
    when routing results.  ``N`` must be a multiple of ``num_devices``.

    ``keep_population=True`` appends ``(pop_accel (N, P, G), pop_prio
    (N, P, G))`` to the outputs (the converged populations the memo
    records for warm-start transfer); ``warm=True`` makes the fn take a
    third input — a stacked ``strategies.WarmStart`` with leading N —
    seeding each row's initial population device-side.  Neither changes
    the schedule outputs for a given (key, params): same search trace.
    """
    # canonicalize so a bare name ('edp'), a 1-tuple spec, and the spec a
    # FitnessFn carries all hit the SAME cached executable — the stream
    # passes fit.objective_spec, run_sweep the normalize_scenarios spec
    objective = as_objective_spec(objective)
    if (getattr(strategy, "multi_objective", False) and objective is None):
        raise ValueError(
            f"strategy {strategy.name!r} is multi_objective and needs a "
            "static ObjectiveSpec shared by every row; the dynamic "
            "per-row objective_code select is scalar-only")
    mesh = None if num_devices == 1 else _sweep_mesh(num_devices)
    target = (NamedSharding(mesh, PartitionSpec(SWEEP_AXIS))
              if mesh is not None else jax.local_devices()[0])
    fn = _chunk_fn(mesh, strategy, generations, evolve_last, group_size,
                   use_kernel, objective, keep_population, warm)
    return fn, target


# the search's named scopes (``scan_strategy``, ``bw_allocator``), which
# a device trace's operations are attributed to; ``other`` is the rest
SEARCH_SCOPES = ("search.ask", "search.eval", "search.tell",
                 "fitness.decode", "fitness.tables", "fitness.events")
_SCOPE_RE = re.compile("|".join(re.escape(s) for s in SEARCH_SCOPES))
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')

# every row executable that has run in this process, recorded at its
# first dispatch: (fn, padded rows) -> (abstract arguments, static shape)
_RAN: Dict[tuple, tuple] = {}


def _note_run(fn, args, strategy: SearchStrategy, generations: int,
              num_devices: int) -> None:
    """Record a row executable's abstract arguments ``(keys, params[,
    warm])`` once, for :func:`executable_scopes`; a dictionary lookup on
    later dispatches."""
    key = (fn, args[0].shape[0])
    if key in _RAN:
        return
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        args)
    _RAN[key] = (abstract, dict(
        P=strategy.ask_size, A=strategy.num_accels,
        G=int(args[1].lat.shape[-2]),
        generations=generations, rows=args[0].shape[0],
        devices=num_devices))


@dataclasses.dataclass(frozen=True)
class ExecutableScopes:
    """One compiled row executable's operations by named scope.

    ``scopes`` maps each HLO instruction name (as a device trace names
    the operation, without ``%``) to the innermost of
    :data:`SEARCH_SCOPES` in its ``op_name`` metadata, or ``other``.
    ``module`` is the HLO module's name, as the trace names its runs
    (before the run's program id).  The static shape: population ``P``,
    ``A`` sub-accelerators, group ``G``, ``generations``, padded
    ``rows`` over ``devices``."""
    module: str
    scopes: Dict[str, str]
    P: int
    A: int
    G: int
    generations: int
    rows: int
    devices: int


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name -> innermost search scope or ``other``} of an
    HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        op = _OP_NAME_RE.search(line)
        found = _SCOPE_RE.findall(op.group(1)) if op else ()
        out[m.group(1)] = found[-1] if found else "other"
    return out


def executable_scopes() -> List[ExecutableScopes]:
    """Every row executable that has run in this process, its operations
    mapped to the search's named scopes from the compiled module's text.

    For reading a device trace after the fact: each executable is looked
    up again by its recorded abstract arguments (the in-memory or
    persistent compile cache answers), never on the dispatch path."""
    out = []
    for (fn, _), (args, shape) in list(_RAN.items()):
        if not hasattr(fn, "lower"):      # a caller's wrapper, not jitted
            continue
        text = fn.lower(*args).compile().as_text()
        m = re.match(r"HloModule ([^\s,]+)", text)
        out.append(ExecutableScopes(module=m.group(1) if m else "",
                                    scopes=instruction_scopes(text),
                                    **shape))
    return out


@lru_cache(maxsize=None)
def _sweep_mesh(num_devices: int):
    """Meshes cached by size: a fresh Mesh per call would miss the jit
    cache keyed on it."""
    return flat_mesh(num_devices, SWEEP_AXIS)


def _flatten_grid(params: FitnessParams, keys: np.ndarray):
    """(S scenarios, K seeds) -> N=S*K host-resident rows, scenario-major.

    Host numpy on purpose: chunks of an oversized grid must live on host
    until their ``device_put`` — materializing the whole grid on device
    is exactly what chunked streaming avoids.

    Each scenario's tables are replicated per seed (the legacy nested
    vmap broadcast them instead).  Deliberate trade-off: uniform rows
    keep sharding/chunking/padding trivial and bit-parity auditable,
    and the (G, A) tables are KB-scale next to the per-row population
    and history state that actually bounds chunk_rows."""
    S = int(params.lat.shape[0])
    K = int(keys.shape[0])
    rows_params = jax.tree.map(
        lambda x: np.repeat(np.asarray(x), K, axis=0), params)
    rows_keys = np.tile(keys, (S, 1))
    return rows_params, rows_keys, S * K


def _pad_rows(rows_params, rows_keys, total: int):
    """Pad to ``total`` rows by repeating the last real row (valid data:
    padding must simulate cleanly, its results are sliced off)."""
    pad = total - rows_keys.shape[0]
    if pad <= 0:
        return rows_params, rows_keys
    rep = lambda x: np.concatenate([x, np.repeat(x[-1:], pad, axis=0)])
    return jax.tree.map(rep, rows_params), rep(rows_keys)


def _resolve_strategy(strategy, cfg: Optional[MagmaConfig]) -> SearchStrategy:
    """``strategy`` may be None (MAGMA, configured by ``cfg``), a registry
    name, or a ``SearchStrategy`` instance (then ``cfg`` must be None —
    instances carry their own config)."""
    if strategy is None:
        return MagmaStrategy(cfg or MagmaConfig())
    if isinstance(strategy, str):
        if cfg is not None:
            return get_strategy(strategy, cfg=cfg)   # magma accepts cfg;
        return get_strategy(strategy)                # others reject it clearly
    if not isinstance(strategy, SearchStrategy):
        raise ValueError(f"strategy must be None, a registry name, or a "
                         f"SearchStrategy; got {type(strategy).__name__}")
    if cfg is not None:
        raise ValueError("pass cfg only with the default MAGMA strategy (or "
                         "strategy='magma'); strategy instances carry their "
                         "own config")
    return strategy


@dataclasses.dataclass
class RowsResult:
    """Per-row results of :func:`run_rows` (leading axis: the N real rows),
    plus how the batch was executed.  ``run_sweep`` reshapes this into the
    ``(S, K)`` grid view; ``repro.stream`` routes rows straight back to
    their scenario requests."""
    best_fitness: np.ndarray       # (N,)
    best_accel: np.ndarray         # (N, G)
    best_prio: np.ndarray          # (N, G)
    history_best: np.ndarray       # (N, T)
    generations: int
    wall_time_s: float
    num_devices: int = 1
    rows: int = 0
    padded_rows: int = 0
    chunk_rows: int = 0
    chunk_wall_s: List[float] = dataclasses.field(default_factory=list)


def run_rows(rows_params: FitnessParams, rows_keys, *,
             strategy: SearchStrategy, generations: int, evolve_last: bool,
             use_kernel: bool = False, objective: Optional[ObjectiveSpec] = None,
             sweep: SweepConfig | None = None,
             memo=None, rows_family: Optional[Sequence[str]] = None
             ) -> RowsResult:
    """Execute N independent (scenario, key) search rows on the device
    fleet — the execution core shared by :func:`run_sweep` (which flattens
    an S x K grid into rows) and the ``repro.stream`` admission stage
    (which batches whichever scenarios are ready, each with its own key).

    ``rows_params`` is a ``FitnessParams`` with leading axis N (host
    numpy leaves — chunks must stay on host until their ``device_put``);
    ``rows_keys`` is ``(N, 2)`` raw PRNG key data.  ``strategy`` must
    already be bound to the scenario's accelerator count.  Rows are
    padded to dense shards / equal chunks by repeating the last real row
    and the padding is sliced off, so row ``i`` of the result is
    bit-identical to a standalone ``run_strategy`` with that scenario and
    key, regardless of device count, chunking, or which other rows share
    the batch.

    ``memo`` (a ``repro.memo.ScheduleMemo``) records every solved row —
    schedule plus, for strategies with population hand-off, the converged
    population for warm-start transfer — under its content fingerprint as
    the chunks drain; ``rows_family`` optionally tags each row's transfer
    family (task-type string).  Recording adds outputs to the compiled
    call, never changes the search trace: rows stay bit-identical.
    """
    sweep = sweep or SweepConfig()
    rows_keys = np.asarray(rows_keys)
    N = int(rows_keys.shape[0])
    G = int(rows_params.lat.shape[-2])

    avail = len(jax.local_devices())     # addressable, not global:
    ndev = avail if sweep.max_devices is None else max(1, min(  # fleet-safe
        sweep.max_devices, avail))
    ndev = min(ndev, N)              # never more shards than real rows

    chunk_rows = N if sweep.chunk_rows is None else max(1, sweep.chunk_rows)
    chunk_rows = min(chunk_rows, N)
    chunk_rows = -(-chunk_rows // ndev) * ndev        # dense shards
    n_chunks = -(-N // chunk_rows)
    padded = n_chunks * chunk_rows   # last partial chunk reuses the same
    rows_params, rows_keys = _pad_rows(rows_params, rows_keys, padded)

    keep_pop = memo is not None and strategy.supports_init_population
    fn, target = row_executable(strategy, generations, evolve_last, G,
                                use_kernel, objective, ndev,
                                keep_population=keep_pop)

    def put_chunk(i):
        sl = slice(i * chunk_rows, (i + 1) * chunk_rows)
        return (jax.device_put(rows_keys[sl], target),
                jax.device_put(jax.tree.map(lambda x: x[sl], rows_params),
                               target))

    from repro.lint.runtime import transfer_sanitizer
    from repro.obs import NULL_TRACER, as_obs_config, get_tracer
    tracer = (get_tracer() if as_obs_config(sweep.obs).enabled
              else NULL_TRACER)

    t0 = time.perf_counter()
    outs, walls = [], []
    with transfer_sanitizer(sweep.transfer_guard):
        buf = put_chunk(0)
        _note_run(fn, buf, strategy, generations, ndev)
        for i in range(n_chunks):
            # double buffer: enqueue the NEXT chunk's host->device
            # transfer before dispatching this chunk's compute, so the
            # copy overlaps it
            nxt = put_chunk(i + 1) if i + 1 < n_chunks else None
            tc = time.perf_counter()
            with tracer.span("sweep.chunk", chunk=i, rows=chunk_rows,
                             devices=ndev):
                out = fn(*buf)
                jax.block_until_ready(out)
            walls.append(time.perf_counter() - tc)
            # results go to host immediately (explicit device_get — the
            # loop runs transfer-guard clean): keeping them on device
            # would grow the footprint with the whole grid, not the chunk
            outs.append(tuple(jax.device_get(o) for o in out))
            buf = nxt
    wall = time.perf_counter() - t0

    def gather(j):
        return np.concatenate([o[j] for o in outs])[:N]

    rr = RowsResult(
        best_fitness=gather(0), best_accel=gather(1), best_prio=gather(2),
        history_best=gather(3), generations=generations, wall_time_s=wall,
        num_devices=ndev, rows=N, padded_rows=padded, chunk_rows=chunk_rows,
        chunk_wall_s=walls,
    )
    if memo is not None:
        _record_rows(memo, rr, rows_params, rows_keys, strategy,
                     generations, evolve_last, use_kernel, objective,
                     rows_family,
                     (gather(4), gather(5)) if keep_pop else None)
    return rr


def _record_rows(memo, rr: RowsResult, rows_params, rows_keys,
                 strategy: SearchStrategy, generations: int,
                 evolve_last: bool, use_kernel: bool,
                 objective: Optional[ObjectiveSpec],
                 rows_family: Optional[Sequence[str]], pops) -> None:
    """Feed every solved row into the schedule memo.  The sampling budget
    is reconstructed from (generations, evolve_last) — the fingerprint
    depends only on that pair, so any budget that plans to the same
    protocol shares the entry."""
    from repro.memo.engine import row_view
    P = strategy.ask_size
    budget = generations * P + int(evolve_last)
    for i in range(rr.rows):
        fit = row_view(jax.tree.map(lambda x: np.asarray(x)[i], rows_params),
                       num_accels=strategy.num_accels,
                       use_kernel=use_kernel, objective=objective)
        memo.record(
            fit, strategy, budget, np.asarray(rows_keys[i]),
            {"best_fitness": rr.best_fitness[i],
             "best_accel": rr.best_accel[i],
             "best_prio": rr.best_prio[i],
             "history_best": rr.history_best[i]},
            population=(pops[0][i], pops[1][i]) if pops is not None else None,
            family="" if rows_family is None else rows_family[i])


def run_sweep(scenarios: Union[Sequence[FitnessFn], FitnessParams],
              budget: int = 10_000,
              cfg: MagmaConfig | None = None,
              seeds: Sequence[int] = (0,),
              num_accels: Optional[int] = None,
              use_kernel: bool = False,
              sweep: SweepConfig | None = None,
              strategy: Union[SearchStrategy, str, None] = None,
              memo=None,
              memo_family: Union[str, Sequence[str]] = ""
              ) -> SweepResult:
    """Run an S x K (scenario x seed) search grid sharded across devices.

    ``scenarios``/``num_accels``/``use_kernel`` follow
    ``magma_search_batch`` (which is now a thin wrapper over this).
    ``strategy`` selects the optimizer: None runs MAGMA (configured by
    ``cfg``), a registry name or any device-resident
    ``repro.core.strategies.SearchStrategy`` runs that method instead —
    same sharding, chunking, and bit-identity guarantees.  Host-only
    strategies are rejected with a ``ValueError``.  The grid is
    partitioned per ``sweep`` (:class:`SweepConfig`); results come back
    with ``(S, K)`` leading axes and row ``[s, k]`` bit-identical to a
    standalone ``run_strategy(strategy, scenarios[s], seed=seeds[k])``
    (for MAGMA: ``magma_search``) regardless of device count or chunking.

    ``memo`` (a ``repro.memo.ScheduleMemo``) records every solved row for
    exact-hit replay / warm-start transfer; ``memo_family`` tags the
    rows' transfer family — one string for the whole grid or one per
    scenario.
    """
    params, num_accels, use_kernel, objective = normalize_scenarios(
        scenarios, num_accels, use_kernel)
    strategy = _resolve_strategy(strategy, cfg)
    if not strategy.device_resident:
        raise ValueError(
            f"strategy {strategy.name!r} is host-only and cannot ride the "
            f"device-resident sweep; run it per problem via run_strategy/"
            f"M3E.search, or pick one of "
            f"{', '.join(available(device_resident=True))}")
    strategy = strategy.bind(num_accels)
    S = int(params.lat.shape[0])
    G = int(params.lat.shape[-2])
    P = strategy.ask_size
    generations, evolve_last = plan_generations(budget, P)

    seeds = np.asarray(list(seeds), dtype=np.int64)
    keys = np.stack([np.asarray(jax.random.PRNGKey(int(s))) for s in seeds])
    rows_params, rows_keys, N = _flatten_grid(params, keys)

    if isinstance(memo_family, str):
        rows_family = [memo_family] * N
    else:                    # one family per scenario, repeated per seed
        memo_family = list(memo_family)
        if len(memo_family) != S:
            raise ValueError(
                f"memo_family must be one string or one per scenario "
                f"({S}); got {len(memo_family)}")
        rows_family = [f for f in memo_family for _ in seeds]
    rr = run_rows(rows_params, rows_keys, strategy=strategy,
                  generations=generations, evolve_last=evolve_last,
                  use_kernel=use_kernel, objective=objective, sweep=sweep,
                  memo=memo, rows_family=rows_family)

    def grid(x, trailing):
        return x.reshape((S, len(seeds)) + trailing)

    return SweepResult(
        best_fitness=grid(rr.best_fitness, ()),
        best_accel=grid(rr.best_accel, (G,)),
        best_prio=grid(rr.best_prio, (G,)),
        history_samples=P * np.arange(1, generations + 1),
        history_best=grid(rr.history_best, (generations,)),
        n_samples=P * generations,
        wall_time_s=rr.wall_time_s,
        seeds=seeds,
        num_devices=rr.num_devices,
        rows=N,
        padded_rows=rr.padded_rows,
        chunk_rows=rr.chunk_rows,
        chunk_wall_s=rr.chunk_wall_s,
    )
