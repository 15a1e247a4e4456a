"""Pareto co-search benchmark: one NSGA-II run vs. weighted-sum scans.

The question the multi-objective tier answers: given a total sampling
budget, is ONE device-resident nsga2 co-search a better way to map the
latency/energy/EDP trade-off than the classical alternative — spending
the same budget on K independent weighted-sum scalarizations (each a
registered ``register_objective`` column, searched by MAGMA) and keeping
their best points?

Both sides get exactly ``K x per-run budget`` samples.  Quality is exact
hypervolume (``repro.core.pareto.hypervolume``) against a shared
reference point (the dominated corner of the union, with margin), with
every candidate point re-evaluated through the scalar objective columns
— the same bit-identity discipline as ``pareto_front``.

Results go to stdout and ``BENCH_pareto.json`` (schema in
benchmarks/README.md).  Exits non-zero on any non-finite number or if
nsga2's hypervolume falls below the weighted-sum scan's, so CI gates on
the tier actually earning its keep.

    PYTHONPATH=src python -m benchmarks.perf_pareto [--quick]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from benchmarks.common import GB
from repro.core import M3E, MagmaConfig
from repro.core import fitness as F
from repro.core.fitness import FitnessFn, register_objective
from repro.core.pareto import hypervolume, non_dominated_mask, pareto_front
from repro.core.strategies import get_strategy, run_strategy
from repro.costmodel import get_setting
from repro.workloads import build_task_groups

OBJECTIVES = ("latency", "energy", "edp")


def build_problem(group_size: int, bw_gb: float):
    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    return M3E(accel=get_setting("S2"), bw_sys=bw_gb * GB,
               objective=OBJECTIVES).prepare(group)


def weight_grid(k: int, m: int) -> np.ndarray:
    """K deterministic weight vectors on the (M-1)-simplex: the corners
    first (pure single-objective scans), then an even interior fill."""
    corners = np.eye(m)
    rng = np.random.default_rng(0)
    extra = rng.dirichlet(np.ones(m), size=max(k - m, 0))
    return np.concatenate([corners, extra])[:k]


def register_wsum_objectives(fit: FitnessFn, weights: np.ndarray):
    """One registered scalar column per weight vector, normalized by the
    objective scales of a reference random population (the classical
    scalarization recipe — and the ``register_objective`` satellite demo:
    these are ordinary registry columns, searchable by ANY scalar
    strategy, memo-fingerprinted like the built-ins)."""
    from repro.core.encoding import random_population

    pop = random_population(jax.random.PRNGKey(0), 256, fit.group_size,
                            fit.num_accels)
    ref = np.asarray(fit.objectives(pop.accel, pop.prio))
    scales = np.maximum(np.abs(ref).mean(axis=0), 1e-30)
    names = []
    for i, w in enumerate(weights):
        name = f"wsum_{i}"
        w_over_s = tuple(float(wj) / float(sj)
                         for wj, sj in zip(w, scales))

        def wsum(params, ms, en, _c=w_over_s):
            return _c[0] * (-ms) + _c[1] * (-en) + _c[2] * (-en * ms)

        register_objective(name, wsum, needs_energy=True,
                           description=f"weighted sum {np.round(w, 3)}",
                           overwrite=name in F.OBJECTIVE_CODES)
        names.append(name)
    return names, scales


def cleanup_wsum(names):
    for n in names:
        F._OBJECTIVES.pop(n, None)
        F.OBJECTIVE_CODES.pop(n, None)


def run(budget_per_run: int, num_weights: int, group_size: int,
        population: int, bw_gb: float, seed: int):
    fit = build_problem(group_size, bw_gb)
    total = budget_per_run * num_weights
    weights = weight_grid(num_weights, len(OBJECTIVES))
    print(f"== perf: pareto co-search (S2/Mix, G={group_size}, "
          f"P={population}, {num_weights} x {budget_per_run} = {total} "
          f"samples/side, bw {bw_gb} GB/s) ==")

    # -- weighted-sum scan: K scalarized MAGMA searches -------------------
    names, scales = register_wsum_objectives(fit, weights)
    try:
        strat = get_strategy("magma", cfg=MagmaConfig(population=population))
        genomes = []
        t0 = time.perf_counter()
        for name in names:
            wfit = FitnessFn(fit.table, bw_sys=fit.bw_sys, objective=name)
            res = run_strategy(strat, wfit, budget=budget_per_run,
                               seed=seed)
            genomes.append((res.best_accel, res.best_prio))
        wall_wsum = time.perf_counter() - t0
    finally:
        cleanup_wsum(names)
    accel = np.stack([g[0] for g in genomes])
    prio = np.stack([g[1] for g in genomes])
    pts_wsum = np.asarray(fit.objectives(accel, prio), dtype=np.float64)
    pts_wsum = pts_wsum[non_dominated_mask(pts_wsum)]

    # -- nsga2: ONE co-search at the same total budget --------------------
    nsga2 = get_strategy("nsga2", population=population)
    t0 = time.perf_counter()
    res = run_strategy(nsga2, fit, budget=total, seed=seed,
                       keep_population=True)
    wall_nsga2 = time.perf_counter() - t0
    front = pareto_front(fit, res.final_population,
                         n_samples=res.n_samples, wall_time_s=wall_nsga2)
    pts_nsga2 = front.objectives.astype(np.float64)

    # shared reference: the dominated corner of the union, 10% margin
    union = np.concatenate([pts_wsum, pts_nsga2])
    ref = union.min(axis=0) - 0.1 * (union.max(axis=0) - union.min(axis=0)
                                     + 1e-30)
    hv_nsga2 = hypervolume(pts_nsga2, ref)
    hv_wsum = hypervolume(pts_wsum, ref)

    print(f"wsum  scan: {len(pts_wsum):3d} non-dominated points, "
          f"hv {hv_wsum:.6e}  ({wall_wsum:6.2f} s)")
    print(f"nsga2 front: {len(front):3d} points, "
          f"hv {hv_nsga2:.6e}  ({wall_nsga2:6.2f} s)")
    print(f"hypervolume ratio nsga2/wsum: "
          f"{hv_nsga2 / max(hv_wsum, 1e-30):.4f}")

    report = {
        "bench": "perf_pareto",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
        "objectives": list(OBJECTIVES),
        "budget_per_run": budget_per_run,
        "num_weight_vectors": num_weights,
        "budget_total": total,
        "population": population,
        "group_size": group_size,
        "bw_gb": bw_gb,
        "seed": seed,
        "objective_scales": [float(s) for s in scales],
        "ref_point": [float(r) for r in ref],
        "wsum": {"points": len(pts_wsum), "hypervolume": hv_wsum,
                 "wall_s": wall_wsum},
        "nsga2": {"points": len(front), "hypervolume": hv_nsga2,
                  "wall_s": wall_nsga2,
                  "best_per_objective": {
                      n: float(front.objectives[:, j].max())
                      for j, n in enumerate(front.names)}},
        "hv_ratio": hv_nsga2 / max(hv_wsum, 1e-30),
        "unix_time": time.time(),
    }

    flat = [report["hv_ratio"], hv_nsga2, hv_wsum, wall_wsum, wall_nsga2]
    if not all(np.isfinite(v) for v in flat):
        print(f"NON-FINITE RESULTS: {flat}", file=sys.stderr)
        sys.exit(1)
    if hv_nsga2 < hv_wsum * (1.0 - 1e-9):
        print(f"GATE FAILED: nsga2 hypervolume {hv_nsga2:.6e} < "
              f"weighted-sum scan {hv_wsum:.6e} at equal budget",
              file=sys.stderr)
        sys.exit(1)
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", type=int, default=2_000,
                    help="samples per weighted-sum run (nsga2 gets K x this)")
    ap.add_argument("--weights", type=int, default=8,
                    help="weight vectors K (>= 3: the pure corners)")
    ap.add_argument("--group-size", type=int, default=64)
    ap.add_argument("--population", type=int, default=64)
    ap.add_argument("--bw-gb", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tiny budget/grid")
    ap.add_argument("--out", default="BENCH_pareto.json")
    args = ap.parse_args()

    if args.quick:
        args.budget, args.group_size, args.population = 300, 16, 20
        args.weights = 4

    if args.weights < len(OBJECTIVES):
        sys.exit(f"--weights must be >= {len(OBJECTIVES)} "
                 "(the pure single-objective corners)")

    report = run(args.budget, args.weights, args.group_size,
                 args.population, args.bw_gb, args.seed)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
