"""Compare every Table-IV optimization method on one problem, with
convergence curves and the warm-start workflow.

Methods come from the ``repro.core.strategies`` registry: device-resident
strategies (magma + the black-box ports) run as one compiled scan each,
host-only methods (cmaes/tbpsa/RL/heuristics) run their own loops — all
behind the same ask/tell API and ``SearchResult`` contract.

    PYTHONPATH=src python examples/scheduler_search.py [--budget 2000]
"""
import argparse

import numpy as np

from repro.core import M3E
from repro.core.strategies import available, strategy_info
from repro.core.warmstart import WarmStartEngine
from repro.costmodel import get_setting
from repro.workloads import build_task_groups

GB = 1024 ** 3
METHODS = ["magma", "stdga", "de", "cmaes", "tbpsa", "pso", "random",
           "a2c", "ppo2", "herald_like", "ai_mt_like"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=2_000)
    ap.add_argument("--setting", default="S4")
    ap.add_argument("--bw", type=float, default=1.0)
    args = ap.parse_args()

    m3e = M3E(accel=get_setting(args.setting), bw_sys=args.bw * GB,
              warm_start=WarmStartEngine())
    groups = build_task_groups("Mix", group_size=100, num_groups=2, seed=0)

    assert set(METHODS) == set(available()), \
        "registry drifted from this demo's lineup"
    print(f"== ({args.setting}, Mix, BW={args.bw:g} GB/s), "
          f"budget {args.budget} ==")
    fits = {}
    for method in METHODS:
        kind = ("device" if strategy_info(method).device_resident
                else "host  ")
        res = m3e.search(groups[0], method=method, budget=args.budget,
                         seed=0)
        fits[method] = res.best_fitness
        curve = res.history_best
        pts = np.linspace(0, len(curve) - 1, 5).astype(int)
        spark = " -> ".join(f"{curve[i] / 1e9:.0f}" for i in pts)
        print(f"{method:12s} [{kind}] {res.best_fitness / 1e9:9.2f} "
              f"GFLOPs/s   [{spark}]   {res.wall_time_s:5.1f}s")
    best = max(fits, key=fits.get)
    print(f"\nbest method: {best}")

    # warm start onto a new group of the same task type (Table V workflow)
    warm = m3e.search(groups[1], method="magma", budget=100, seed=1)
    print(f"warm-started on a NEW group, 1 generation: "
          f"{warm.best_fitness / 1e9:.2f} GFLOPs/s "
          f"(vs full-search level {fits['magma'] / 1e9:.2f})")

    # device-resident scenario sweep, per strategy: a BW grid x 2 seeds
    # through repro.core.sweep — sharded across however many devices are
    # visible (try XLA_FLAGS=--xla_force_host_platform_device_count=8),
    # one vmapped XLA call per chunk (Fig. 12-style sweep, and the
    # Fig. 11 method-comparison workload when strategies vary)
    from repro.core.sweep import run_sweep
    import time
    bws = (0.5, 1.0, 4.0, 16.0)
    sweep_fits = [M3E(accel=get_setting(args.setting), bw_sys=b * GB
                      ).prepare(groups[0]) for b in bws]
    for name in ("magma", "de"):
        t0 = time.perf_counter()
        batch = run_sweep(sweep_fits, budget=args.budget, seeds=(0, 1),
                          strategy=name)
        dt = time.perf_counter() - t0
        print(f"\nbatched BW sweep, strategy={name} ({len(bws)} scenarios "
              f"x 2 seeds on {batch.num_devices} device(s), "
              f"{batch.num_chunks} compiled call(s), {dt:.1f}s):")
        for i, b in enumerate(bws):
            mean = batch.best_fitness[i].mean() / 1e9
            print(f"  BW={b:5.1f} GB/s   {mean:9.2f} GFLOPs/s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
