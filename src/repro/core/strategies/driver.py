"""Shared device-resident driver: any ask/tell strategy as ONE ``lax.scan``.

:func:`scan_strategy` is the core every execution path shares — a whole
search folded into a single scan whose carry holds ``(strategy state,
best-so-far)`` on device, emitting the per-generation best as scan
outputs.  ``run_strategy`` wraps it for a single (problem, seed);
``repro.core.sweep`` vmaps/shards it over (scenario x seed) grids.  The
trace mirrors the original MAGMA engine exactly (evaluate, fold best,
then ``tell``; the final generation tells only when the sample budget is
not yet exhausted), which is what keeps the MAGMA strategy bit-identical
to the legacy ``magma_search`` engines.

``engine='loop'`` steps the same ask/eval/tell sequence from the host
(one dispatch + sync per generation) — the parity/benchmark baseline
each device strategy is tested against, and the sequential-host-loop
reference ``benchmarks/perf_strategies.py`` reports speedups over.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fitness import (FitnessFn, ObjectiveSpec, evaluate_objectives,
                                evaluate_params)
from repro.core.magma import SearchResult
from repro.core.strategies.base import SearchStrategy


def plan_generations(budget: int, ask_size: int) -> Tuple[int, bool]:
    """(generations, evolve_last) for a sampling budget — the legacy MAGMA
    loop's semantics: floor(budget / ask_size) generations, with a final
    ``tell`` only when that undershoots the budget."""
    generations = max(1, budget // ask_size)
    return generations, generations * ask_size < budget


def scan_strategy(strategy: SearchStrategy, state, eval_fn, group_size: int,
                  generations: int, evolve_last: bool):
    """Run ``generations`` ask->eval->tell steps as one ``lax.scan``.

    Returns ``(best_fit, best_accel, best_prio, history, state)`` with
    ``history`` the per-generation best-so-far curve.  The three phases
    run under the named scopes ``search.ask``, ``search.eval`` and
    ``search.tell``: HLO metadata only, which a device trace reads back
    through ``repro.core.sweep.executable_scopes``.

    Multi-objective strategies (``strategy.multi_objective``) run the same
    scan with ``eval_fn`` returning a ``(P, M)`` matrix: ``tell`` consumes
    the full matrix while the anytime best/history track column 0 (the
    first name of the ``ObjectiveSpec`` — the documented anytime scalar),
    so every output shape is unchanged.  The final ``tell`` always runs
    for them — the archive is the result, and it must fold in the last
    evaluated offspring regardless of the sample budget's remainder.
    """
    mo = getattr(strategy, "multi_objective", False)

    def eval_update(accel, prio, bf, ba, bp):
        fit = eval_fn(accel, prio)
        col = fit[:, 0] if mo else fit
        i = jnp.argmax(col)
        better = col[i] > bf
        bf = jnp.where(better, col[i], bf)
        ba = jnp.where(better, accel[i], ba)
        bp = jnp.where(better, prio[i], bp)
        return fit, bf, ba, bp

    def step(carry, _):
        state, bf, ba, bp = carry
        with jax.named_scope("search.ask"):
            state, accel, prio = strategy.ask(state)
        with jax.named_scope("search.eval"):
            fit, bf, ba, bp = eval_update(accel, prio, bf, ba, bp)
        with jax.named_scope("search.tell"):
            state = strategy.tell(state, fit)
        return (state, bf, ba, bp), bf

    G = group_size
    carry0 = (state, jnp.float32(-jnp.inf),
              jnp.zeros((G,), jnp.int32), jnp.zeros((G,), jnp.float32))
    carry, hist = jax.lax.scan(step, carry0, None, length=generations - 1)
    state, bf, ba, bp = carry
    with jax.named_scope("search.ask"):
        state, accel, prio = strategy.ask(state)
    with jax.named_scope("search.eval"):
        fit, bf, ba, bp = eval_update(accel, prio, bf, ba, bp)
    hist = jnp.concatenate([hist, bf[None]])
    if evolve_last or mo:    # legacy loop evolves once more; mo archives
        with jax.named_scope("search.tell"):
            state = strategy.tell(state, fit)
    return bf, ba, bp, hist, state


@partial(jax.jit, static_argnames=("strategy", "num_accels", "generations",
                                   "evolve_last", "use_kernel", "objective"))
def _run_scan(strategy: SearchStrategy, key, params, init_population,
              num_accels: int, generations: int, evolve_last: bool,
              use_kernel: bool, objective: Optional[ObjectiveSpec]):
    if getattr(strategy, "multi_objective", False):
        def eval_fn(a, p):
            return evaluate_objectives(params, a, p, num_accels=num_accels,
                                       use_kernel=use_kernel,
                                       objective=objective)
    else:
        def eval_fn(a, p):
            return evaluate_params(params, a, p, num_accels=num_accels,
                                   use_kernel=use_kernel, objective=objective)
    state = strategy.init(key, params, init_population=init_population)
    return scan_strategy(strategy, state, eval_fn, params.lat.shape[-2],
                         generations, evolve_last)


def _run_loop(strategy: SearchStrategy, key, fitness_fn: FitnessFn,
              init_population, generations: int, evolve_last: bool):
    """Host-stepped ask/eval/tell loop (one dispatch per generation)."""
    mo = getattr(strategy, "multi_objective", False)
    state = strategy.init(key, fitness_fn.params,
                          init_population=init_population)
    bf, ba, bp = -np.inf, None, None
    hist = []
    for g in range(generations):
        state, accel, prio = strategy.ask(state)
        fit = np.asarray(fitness_fn.objectives(accel, prio) if mo
                         else fitness_fn(accel, prio))
        col = fit[:, 0] if mo else fit
        i = int(np.argmax(col))
        if col[i] > bf:
            bf = float(col[i])
            ba, bp = np.asarray(accel[i]), np.asarray(prio[i])
        hist.append(bf)
        if g + 1 < generations or evolve_last or mo:
            state = strategy.tell(state, jnp.asarray(fit))
    return bf, ba, bp, np.asarray(hist), state


def run_strategy(strategy: SearchStrategy, fitness_fn: FitnessFn,
                 budget: int = 10_000, seed: int = 0,
                 engine: Optional[str] = None,
                 init_population=None,
                 keep_population: bool = False) -> SearchResult:
    """Run any registered strategy on one problem for ``budget`` samples.

    Device-resident strategies run as one compiled scan (``engine='scan'``,
    the default) or the host-stepped parity loop (``engine='loop'``);
    host-only strategies dispatch to their own search loop (``engine``
    must be None or ``'host'``).  Every path returns the same
    ``SearchResult``.
    """
    if not strategy.device_resident:
        if engine not in (None, "host"):
            raise ValueError(
                f"strategy {strategy.name!r} is host-only; engine="
                f"{engine!r} is not available (use None or 'host')")
        if init_population is not None or keep_population:
            raise ValueError(
                f"strategy {strategy.name!r} is host-only; population "
                "hand-off (init_population/keep_population) is not supported")
        return strategy.search(fitness_fn, budget, seed)

    if (fitness_fn.num_objectives > 1
            and not getattr(strategy, "multi_objective", False)):
        raise ValueError(
            f"strategy {strategy.name!r} is single-objective but the "
            f"fitness has {fitness_fn.num_objectives} columns "
            f"({fitness_fn.objective_spec.token!r}); use a multi_objective "
            "strategy such as 'nsga2' or a scalar ObjectiveSpec")
    strategy = strategy.bind(fitness_fn.num_accels)
    engine = engine or "scan"
    generations, evolve_last = plan_generations(budget, strategy.ask_size)
    key = jax.random.PRNGKey(seed)
    P = strategy.ask_size

    t0 = time.perf_counter()
    if engine == "scan":
        bf, ba, bp, hist, state = _run_scan(
            strategy, key, fitness_fn.params, init_population,
            fitness_fn.num_accels, generations, evolve_last,
            fitness_fn.use_kernel, fitness_fn.objective_spec)
        jax.block_until_ready(hist)
        bf = float(bf)
        ba, bp = np.asarray(ba), np.asarray(bp)
    elif engine == "loop":
        bf, ba, bp, hist, state = _run_loop(
            strategy, key, fitness_fn, init_population, generations,
            evolve_last)
    else:
        raise ValueError(f"unknown engine {engine!r}; expected 'scan' or "
                         "'loop'")
    wall = time.perf_counter() - t0

    return SearchResult(
        best_fitness=bf, best_accel=ba, best_prio=bp,
        history_samples=P * np.arange(1, generations + 1),
        history_best=np.asarray(hist, dtype=np.float64),
        n_samples=P * generations, wall_time_s=wall,
        final_population=strategy.population(state)
        if keep_population else None,
    )
