"""Device-resident MAGMA engine: determinism + equivalence guarantees.

The scanned engine (one compiled call per search) must be *bitwise*
interchangeable with the legacy per-generation host loop, and each row of
a vmapped ``magma_search_batch`` must match the standalone search with the
same (scenario, seed)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.encoding import random_population
from repro.core.fitness import FitnessFn, stack_fitness_params
from repro.core.job_analyzer import table_from_arrays
from repro.core.magma import (MagmaConfig, _next_generation, magma_search,
                              magma_search_batch)


def _fitness(G=24, A=4, seed=0, bw_sys=2.0, objective="throughput",
             use_kernel=False, energy=False):
    rng = np.random.default_rng(seed)
    lat = rng.uniform(0.1, 3.0, (G, A))
    bw = rng.uniform(0.1, 5.0, (G, A))
    en = rng.uniform(0.5, 2.0, (G, A)) if energy else None
    table = table_from_arrays(lat, bw, rng.uniform(1, 10, G), energy=en)
    return FitnessFn(table, bw_sys=bw_sys, objective=objective,
                     use_kernel=use_kernel)


CFG = MagmaConfig(population=20)


def _assert_results_equal(a, b, *, check_population=False):
    assert a.best_fitness == b.best_fitness
    np.testing.assert_array_equal(a.best_accel, b.best_accel)
    np.testing.assert_array_equal(a.best_prio, b.best_prio)
    np.testing.assert_array_equal(a.history_samples, b.history_samples)
    np.testing.assert_array_equal(a.history_best, b.history_best)
    assert a.n_samples == b.n_samples
    if check_population:
        np.testing.assert_array_equal(np.asarray(a.final_population.accel),
                                      np.asarray(b.final_population.accel))
        np.testing.assert_array_equal(np.asarray(a.final_population.prio),
                                      np.asarray(b.final_population.prio))


@pytest.mark.parametrize("objective", ["throughput", "latency"])
@pytest.mark.parametrize("budget", [400, 450])    # divisible + ragged budget
def test_scan_engine_matches_loop_bitwise(objective, budget):
    fit = _fitness(objective=objective)
    for seed in (0, 3):
        r_loop = magma_search(fit, budget=budget, cfg=CFG, seed=seed,
                              engine="loop", keep_population=True)
        r_scan = magma_search(fit, budget=budget, cfg=CFG, seed=seed,
                              engine="scan", keep_population=True)
        _assert_results_equal(r_loop, r_scan, check_population=True)


def test_scan_engine_matches_loop_with_kernel():
    """The Pallas makespan path must trace inside the generation scan."""
    from repro.kernels.ops import INTERPRET
    fit = _fitness(use_kernel=INTERPRET)
    cfg = MagmaConfig(population=10)
    r_loop = magma_search(fit, budget=50, cfg=cfg, seed=1, engine="loop")
    r_scan = magma_search(fit, budget=50, cfg=cfg, seed=1, engine="scan")
    _assert_results_equal(r_loop, r_scan)


def test_scan_engine_same_seed_deterministic():
    fit = _fitness()
    r1 = magma_search(fit, budget=400, cfg=CFG, seed=5)
    r2 = magma_search(fit, budget=400, cfg=CFG, seed=5)
    _assert_results_equal(r1, r2)
    r3 = magma_search(fit, budget=400, cfg=CFG, seed=6)
    assert not np.array_equal(r3.best_prio, r1.best_prio)


def test_scan_engine_warmstart_init_population():
    """init_population flows into the scanned search identically."""
    fit = _fitness()
    init = random_population(jax.random.PRNGKey(99), CFG.population,
                             fit.group_size, fit.num_accels)
    r_loop = magma_search(fit, budget=400, cfg=CFG, seed=0, engine="loop",
                          init_population=init)
    r_scan = magma_search(fit, budget=400, cfg=CFG, seed=0,
                          init_population=init)
    _assert_results_equal(r_loop, r_scan)


def test_batch_rows_match_standalone_searches():
    """magma_search_batch[s, k] == magma_search(scenario s, seed seeds[k])."""
    scenarios = [
        _fitness(bw_sys=2.0, objective="throughput"),
        _fitness(bw_sys=0.5, objective="latency"),
        _fitness(bw_sys=20.0, objective="throughput"),
    ]
    seeds = [0, 1, 7]
    batch = magma_search_batch(scenarios, budget=400, cfg=CFG, seeds=seeds)
    assert batch.best_fitness.shape == (3, 3)
    for s, fit in enumerate(scenarios):
        for k, seed in enumerate(seeds):
            row = batch.result(s, k)
            ref = magma_search(fit, budget=400, cfg=CFG, seed=seed)
            _assert_results_equal(row, ref)


def test_batch_stacked_params_roundtrip():
    fns = [_fitness(bw_sys=b) for b in (1.0, 4.0)]
    params = stack_fitness_params(fns)
    batch = magma_search_batch(params, budget=200, cfg=CFG, seeds=[0],
                               num_accels=fns[0].num_accels)
    ref = magma_search_batch(fns, budget=200, cfg=CFG, seeds=[0])
    np.testing.assert_array_equal(batch.best_fitness, ref.best_fitness)


def test_batch_rejects_mismatched_scenarios():
    with pytest.raises(ValueError):
        magma_search_batch([_fitness(G=24), _fitness(G=25)], budget=100)


def test_batch_rejects_mixed_kernel_scenarios():
    """Kernel and jnp simulators only agree to ~1e-4, so a mixed batch
    would silently break the bit-for-bit standalone guarantee."""
    with pytest.raises(ValueError, match="use_kernel"):
        magma_search_batch([_fitness(), _fitness(use_kernel=True)],
                           budget=100)


# ---------------------------------------------------------------------------
# vectorized operator semantics (live engine code)
# ---------------------------------------------------------------------------
def _children_for(cfg, G=10, A=3, P=12, n_elite=4, seed=0):
    """Run the engine's _next_generation_body and return (elites, children)
    as numpy arrays."""
    from repro.core.magma import _next_generation_body
    pop = random_population(jax.random.PRNGKey(seed), P, G, A)
    fits = jnp.arange(P, dtype=jnp.float32)       # distinct: no sort ties
    na, np_ = _next_generation_body(jax.random.PRNGKey(seed + 1), pop.accel,
                                    pop.prio, fits, cfg, A, n_elite)
    order = np.argsort(-np.asarray(fits))[:n_elite]
    e_a = np.asarray(pop.accel)[order]
    e_p = np.asarray(pop.prio)[order]
    return (e_a, e_p), (np.asarray(na)[n_elite:], np.asarray(np_)[n_elite:])


def _pairs(n_elite):
    return [(d, m) for d in range(n_elite) for m in range(n_elite)]


def test_vectorized_crossover_gen_semantics():
    """Every child of a gen-only generation is a single-genome pivot cross
    of SOME elite pair (the reference _crossover_gen semantics), checked
    against the live vectorized implementation."""
    cfg = MagmaConfig(population=12, mutation_rate=0.0, p_crossover_gen=1.0,
                      p_crossover_rg=0.0, p_crossover_accel=0.0)
    (e_a, e_p), (c_a, c_p) = _children_for(cfg)
    G = e_a.shape[1]
    for a, p in zip(c_a, c_p):
        ok = False
        for d, m in _pairs(len(e_a)):
            for piv in range(1, G):
                cross_a = np.concatenate([e_a[d, :piv], e_a[m, piv:]])
                cross_p = np.concatenate([e_p[d, :piv], e_p[m, piv:]])
                if (np.array_equal(a, cross_a) and np.array_equal(p, e_p[d])) \
                   or (np.array_equal(a, e_a[d]) and np.array_equal(p, cross_p)):
                    ok = True
                    break
            if ok:
                break
        assert ok, (a, p)


def test_vectorized_crossover_rg_semantics():
    """rg-only children take the SAME index range of both genomes from
    some elite mom, rest from some elite dad."""
    cfg = MagmaConfig(population=12, mutation_rate=0.0, p_crossover_gen=0.0,
                      p_crossover_rg=1.0, p_crossover_accel=0.0)
    (e_a, e_p), (c_a, c_p) = _children_for(cfg, seed=1)
    G = e_a.shape[1]
    for a, p in zip(c_a, c_p):
        ok = False
        for d, m in _pairs(len(e_a)):
            for lo in range(G):
                for hi in range(lo + 1, G + 1):
                    inside = (np.arange(G) >= lo) & (np.arange(G) < hi)
                    if np.array_equal(a, np.where(inside, e_a[m], e_a[d])) and \
                       np.array_equal(p, np.where(inside, e_p[m], e_p[d])):
                        ok = True
                        break
                if ok:
                    break
            if ok:
                break
        assert ok, (a, p)


def test_vectorized_crossover_accel_semantics():
    """accel-only children copy some elite mom's complete schedule for one
    core; displaced dad jobs are re-assigned, everything else is dad."""
    cfg = MagmaConfig(population=12, mutation_rate=0.0, p_crossover_gen=0.0,
                      p_crossover_rg=0.0, p_crossover_accel=1.0)
    A = 3
    (e_a, e_p), (c_a, c_p) = _children_for(cfg, A=A, seed=2)
    for a, p in zip(c_a, c_p):
        ok = False
        for d, m in _pairs(len(e_a)):
            for core in range(A):
                from_mom = e_a[m] == core
                displaced = (e_a[d] == core) & ~from_mom
                untouched = ~from_mom & ~displaced
                if np.all(a[from_mom] == core) and \
                   np.array_equal(p[from_mom], e_p[m][from_mom]) and \
                   np.array_equal(a[untouched], e_a[d][untouched]) and \
                   np.array_equal(p[~from_mom], e_p[d][~from_mom]) and \
                   np.all((a[displaced] >= 0) & (a[displaced] < A)):
                    ok = True
                    break
            if ok:
                break
        assert ok, (a, p)


def test_vectorized_mutation_only_valid():
    """mutation-only (all crossovers off): children are valid genomes and
    non-mutated genes come from some elite dad."""
    cfg = MagmaConfig(population=12, mutation_rate=0.3,
                      enable_crossover_gen=False, enable_crossover_rg=False,
                      enable_crossover_accel=False)
    A = 4
    (e_a, e_p), (c_a, c_p) = _children_for(cfg, A=A, seed=3)
    assert c_a.min() >= 0 and c_a.max() < A
    assert c_p.min() >= 0.0 and c_p.max() <= 1.0
    # each child keeps a majority of some dad's genes at rate 0.3
    for a, p in zip(c_a, c_p):
        kept = max(np.sum((a == e_a[d]) & (p == e_p[d]))
                   for d in range(len(e_a)))
        assert kept >= e_a.shape[1] // 3, kept


# ---------------------------------------------------------------------------
# MagmaConfig hashing / recompilation regression
# ---------------------------------------------------------------------------
def test_magma_config_frozen_and_hashable():
    cfg1 = MagmaConfig(population=30)
    cfg2 = MagmaConfig(population=30)
    assert cfg1 == cfg2 and hash(cfg1) == hash(cfg2)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg1.population = 40


def test_equal_configs_do_not_retrigger_jit():
    """Two equal-but-distinct MagmaConfig instances must hit the same jit
    cache entry (the old astuple-based __hash__ was fragile)."""
    fit = _fitness(G=10, A=3)
    pop = random_population(jax.random.PRNGKey(0), 8, 10, 3)
    fits = fit(pop.accel, pop.prio)
    cfg1 = MagmaConfig(population=8)
    _next_generation(jax.random.PRNGKey(1), pop, fits, cfg1, 3, 2)
    n0 = _next_generation._cache_size()
    cfg2 = MagmaConfig(population=8)
    assert cfg2 is not cfg1
    _next_generation(jax.random.PRNGKey(2), pop, fits, cfg2, 3, 2)
    assert _next_generation._cache_size() == n0
