"""Strategy-grid benchmark: every optimizer as a device-resident sweep.

The Fig. 11 / Table IV comparison workload — a (strategy x scenario x
seed) convergence grid — executed the post-refactor way: per strategy,
the whole (scenario x seed) grid runs as ONE
``repro.core.sweep.run_sweep(strategy=...)`` call (compiled; sharded
when more than one device is visible), against the sequential
host-stepped loop (``run_strategy(..., engine='loop')`` per row) as the
pre-refactor baseline.  MAGMA rows are additionally asserted
bit-identical to standalone ``magma_search`` — the sweep never trades
correctness for throughput.

Results go to stdout and, machine-readable, to ``BENCH_strategies.json``
(schema in benchmarks/README.md).  Exits non-zero on any non-finite
number, so CI can gate on it.

    PYTHONPATH=src python -m benchmarks.perf_strategies [--quick]
    # fake an 8-device fleet on CPU:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.perf_strategies --quick
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
from repro.core.magma import magma_search
from repro.core.strategies import get_strategy, run_strategy, strategy_info
from repro.core.sweep import run_sweep
from repro.costmodel import get_setting
from repro.workloads import build_task_groups

BW_LADDER = (1.0, 4.0, 16.0, 64.0)
DEFAULT_STRATEGIES = ("magma", "stdga", "de", "pso", "random")


def build_grid(setting: str, group_size: int, num_scenarios: int):
    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    bws = BW_LADDER[:num_scenarios]
    fits = [M3E(accel=get_setting(setting), bw_sys=bw * GB).prepare(group)
            for bw in bws]
    return bws, fits


def _strategy(name: str, population: int):
    if name == "magma":
        return get_strategy(name, cfg=MagmaConfig(population=population))
    return get_strategy(name, population=population)


def run(budget: int, group_size: int, num_scenarios: int, seeds: int,
        population: int, strategies, host_loop: bool):
    bws, fits = build_grid("S2", group_size, num_scenarios)
    seed_list = list(range(seeds))
    rows = len(fits) * seeds

    print(f"== perf: strategy sweep grid (S2/Mix, G={group_size}, "
          f"P={population}, {len(fits)} scenarios x {seeds} seeds = "
          f"{rows} rows, budget {budget}) ==")

    out = {}
    for name in strategies:
        strategy = _strategy(name, population)
        # warm-up compile; the measured run reuses the cached executable
        res = run_sweep(fits, budget=budget, seeds=seed_list,
                        strategy=strategy)
        res = run_sweep(fits, budget=budget, seeds=seed_list,
                        strategy=strategy)
        gens = res.generations
        gens_per_s = rows * gens / max(res.wall_time_s, 1e-12)

        entry = {
            "device_resident": True,
            "wall_s": res.wall_time_s,
            "gens_per_s": gens_per_s,
            "num_devices": res.num_devices,
            "best_mean": float(res.best_fitness.mean()),
        }

        if name == "magma":
            # acceptance gate: sweep rows == standalone magma_search, bitwise
            for s in range(len(fits)):
                for k, seed in enumerate(seed_list):
                    ref = magma_search(fits[s], budget=budget,
                                       cfg=strategy.cfg, seed=seed)
                    assert res.best_fitness[s, k] == ref.best_fitness, \
                        (name, s, seed)
                    np.testing.assert_array_equal(res.history_best[s, k],
                                                  ref.history_best)
            entry["magma_bit_identical"] = True

        if host_loop:
            # pre-refactor baseline: one host-stepped search per row
            def seq():
                for f in fits:
                    for seed in seed_list:
                        run_strategy(strategy, f, budget=budget, seed=seed,
                                     engine="loop")
            # warm one row: the loop engine recompiles nothing per row, so
            # a single search pays all compile cost without doubling the
            # (dominant) sequential baseline
            run_strategy(strategy, fits[0], budget=budget,
                         seed=seed_list[0], engine="loop")
            t0 = time.perf_counter()
            seq()
            entry["host_loop_s"] = time.perf_counter() - t0
            entry["speedup_vs_host_loop"] = (entry["host_loop_s"] /
                                             max(res.wall_time_s, 1e-12))

        out[name] = entry
        extra = (f"   {entry['speedup_vs_host_loop']:5.1f}x vs host loop "
                 f"({entry['host_loop_s']:7.3f} s)" if host_loop else "")
        print(f"{name:8s} sweep {res.wall_time_s:7.3f} s "
              f"({gens_per_s:9.1f} gen/s on {res.num_devices} device(s))"
              f"{extra}")

    report = {
        "bench": "perf_strategies",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
        "budget": budget,
        "population": population,
        "group_size": group_size,
        "num_scenarios": len(fits),
        "num_seeds": seeds,
        "rows": rows,
        "scenario_bws_gb": list(bws),
        "strategies": out,
        "unix_time": time.time(),
    }
    bad = [f"{n}.{k}" for n, e in out.items() for k, v in e.items()
           if isinstance(v, float) and not np.isfinite(v)]
    if bad:
        print(f"NON-FINITE RESULTS: {bad}", file=sys.stderr)
        sys.exit(1)
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", type=int, default=2_000)
    ap.add_argument("--group-size", type=int, default=100)
    ap.add_argument("--scenarios", type=int, default=4,
                    help=f"BW-ladder points (max {len(BW_LADDER)})")
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--population", type=int, default=100)
    ap.add_argument("--strategies", default=",".join(DEFAULT_STRATEGIES),
                    help="comma list of device-resident strategy names")
    ap.add_argument("--no-host-loop", action="store_true",
                    help="skip the sequential host-loop baseline timing")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tiny budget/grid")
    ap.add_argument("--out", default="BENCH_strategies.json")
    args = ap.parse_args()

    if args.quick:
        args.budget, args.group_size, args.population = 300, 16, 20
        args.scenarios, args.seeds = 2, 4

    strategies = [s for s in args.strategies.split(",") if s]
    for s in strategies:
        info = strategy_info(s)
        if not info.device_resident:
            sys.exit(f"{s} is host-only; this benchmark sweeps "
                     "device-resident strategies")

    report = run(args.budget, args.group_size, args.scenarios, args.seeds,
                 args.population, strategies, not args.no_host_loop)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
