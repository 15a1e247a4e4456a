"""Fleet-sweep benchmark: `repro.core.sweep` sharded scenario grids.

Builds a Fig. 13-style scenario grid (one accelerator setting, a ladder
of system bandwidths) x seeds, runs it through ``run_sweep``, and
reports how the grid was executed: devices, chunks, per-chunk wall time
and generations/second, plus the best objective per scenario.  With
``--compare`` it also times the forced single-device vmapped path and
checks the sharded results are bit-identical to it (the guarantee CI
gates on).

Results go to stdout and, machine-readable, to ``BENCH_sweep.json``
(schema documented in benchmarks/README.md).  The process exits
non-zero on any non-finite result, so CI can gate on it.

    PYTHONPATH=src python -m benchmarks.perf_sweep [--quick] [--compare]
    # fake an 8-device fleet on CPU:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.perf_sweep --quick
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
from repro.core.sweep import SweepConfig, run_sweep
from repro.costmodel import get_setting
from repro.lint.runtime import RecompileGuard
from repro.workloads import build_task_groups

BW_LADDER = (0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 64.0, 256.0)


def build_grid(setting: str, group_size: int, num_scenarios: int):
    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    bws = BW_LADDER[:num_scenarios]
    fits = [M3E(accel=get_setting(setting), bw_sys=bw * GB).prepare(group)
            for bw in bws]
    return bws, fits


def run(budget: int, group_size: int, num_scenarios: int, seeds: int,
        chunk_rows, population: int, compare: bool):
    cfg = MagmaConfig(population=population)
    bws, fits = build_grid("S2", group_size, num_scenarios)
    seed_list = list(range(seeds))

    sweep_cfg = SweepConfig(chunk_rows=chunk_rows)
    single_cfg = SweepConfig(max_devices=1)
    # warm-up compiles (sharded AND, with --compare, the single-device
    # variant); the measured runs below reuse the cached executables,
    # matching the fleet workflow (compile once, sweep often).  The
    # guard holds them to it: any compile after guard.warmup() aborts
    # the benchmark naming the executable instead of silently folding a
    # multi-second XLA stall into the timings
    guard = RecompileGuard(label="perf_sweep")
    with guard:
        run_sweep(fits, budget=budget, cfg=cfg, seeds=seed_list,
                  sweep=sweep_cfg)
        if compare:
            run_sweep(fits, budget=budget, cfg=cfg, seeds=seed_list,
                      sweep=single_cfg)
        guard.warmup()
        res = run_sweep(fits, budget=budget, cfg=cfg, seeds=seed_list,
                        sweep=sweep_cfg)
        single = (run_sweep(fits, budget=budget, cfg=cfg, seeds=seed_list,
                            sweep=single_cfg) if compare else None)

    print(f"== perf: sharded scenario sweep (S2/Mix, G={group_size}, "
          f"P={population}, {res.generations} generations) ==")
    print(f"grid: {len(fits)} scenarios x {seeds} seeds = {res.rows} rows "
          f"({res.padded_rows} padded) on {res.num_devices} device(s), "
          f"{res.num_chunks} chunk(s) of {res.chunk_rows} rows")
    for i, (w, g) in enumerate(zip(res.chunk_wall_s, res.gens_per_sec())):
        print(f"  chunk {i}: {w:7.3f} s   {g:9.1f} gen/s")
    print(f"total wall: {res.wall_time_s:.3f} s")

    report = {
        "bench": "perf_sweep",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "num_devices": res.num_devices,
        "budget": budget,
        "population": population,
        "generations": res.generations,
        "group_size": group_size,
        "num_scenarios": len(fits),
        "num_seeds": seeds,
        "rows": res.rows,
        "padded_rows": res.padded_rows,
        "chunk_rows": res.chunk_rows,
        "num_chunks": res.num_chunks,
        "wall_time_s": res.wall_time_s,
        "chunks": [{"wall_s": w, "gens_per_s": g}
                   for w, g in zip(res.chunk_wall_s, res.gens_per_sec())],
        "best_objective_per_scenario": {
            f"bw{bw:g}GB": float(res.best_fitness[i].mean())
            for i, bw in enumerate(bws)},
        "recompiles_post_warmup": len(guard.post_warmup),
        "unix_time": time.time(),
    }
    print(f"recompiles after warmup: {len(guard.post_warmup)} (guarded)")

    if compare:
        np.testing.assert_array_equal(res.best_fitness, single.best_fitness)
        np.testing.assert_array_equal(res.history_best, single.history_best)
        print(f"single-device vmapped path: {single.wall_time_s:.3f} s "
              f"(bit-identical)   sharded speedup "
              f"{single.wall_time_s / max(res.wall_time_s, 1e-12):.2f}x")
        report["single_device_wall_s"] = single.wall_time_s
        report["sharded_speedup"] = (single.wall_time_s /
                                     max(res.wall_time_s, 1e-12))

    bad = [k for k, v in report["best_objective_per_scenario"].items()
           if not np.isfinite(v)]
    if bad or not np.isfinite(res.history_best).all():
        print(f"NON-FINITE RESULTS: {bad or 'history_best'}", file=sys.stderr)
        sys.exit(1)
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", type=int, default=2_000)
    ap.add_argument("--group-size", type=int, default=100)
    ap.add_argument("--scenarios", type=int, default=8,
                    help=f"BW-ladder points (max {len(BW_LADDER)})")
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--population", type=int, default=100)
    ap.add_argument("--chunk-rows", type=int, default=None,
                    help="stream the grid in chunks of this many rows")
    ap.add_argument("--compare", action="store_true",
                    help="also time the forced single-device path and "
                         "verify bit-identity")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tiny budget/grid, chunked, --compare")
    ap.add_argument("--out", default="BENCH_sweep.json")
    args = ap.parse_args()

    if args.quick:
        args.budget, args.group_size, args.population = 300, 16, 20
        # 4 scenarios x 3 seeds = 12 rows with chunk_rows=6: two chunks on
        # <=6 devices, a padded partial chunk on 8 — either way the
        # streaming path is exercised, not just the one-shot call
        args.scenarios, args.seeds = 4, 3
        args.chunk_rows = args.chunk_rows or 6
        args.compare = True

    report = run(args.budget, args.group_size, args.scenarios, args.seeds,
                 args.chunk_rows, args.population, args.compare)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
