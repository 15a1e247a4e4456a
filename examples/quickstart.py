"""Quickstart: schedule a multi-tenant job group with MAGMA in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py

Builds the paper's (Mix, S2 heterogeneous, BW=16 GB/s) problem, runs the
MAGMA search next to two manual baselines, and prints the found mapping.
"""
import sys

from repro.core import M3E
from repro.costmodel import get_setting
from repro.workloads import build_task_groups

GB = 1024 ** 3


def main():
    budget = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000
    group = build_task_groups("Mix", group_size=60, seed=0)[0]
    m3e = M3E(accel=get_setting("S2"), bw_sys=16 * GB)

    print(f"group: {len(group)} jobs, {group.total_flops / 1e9:.1f} GFLOPs, "
          f"accelerator: {m3e.accel.describe()}")
    results = {}
    for method in ("magma", "herald_like", "ai_mt_like", "random"):
        res = m3e.search(group, method=method, budget=budget, seed=0)
        results[method] = res
        print(f"{method:12s} throughput = {res.best_fitness / 1e9:8.2f} "
              f"GFLOPs/s   ({res.n_samples} samples, "
              f"{res.wall_time_s:.2f} s)")

    best = results["magma"]
    print("\nMAGMA mapping (per-core job queues):")
    for a, queue in enumerate(m3e.describe_mapping(best)):
        sub = m3e.accel.sub_accels[a]
        print(f"  {sub.name:14s} ({sub.dataflow}): {queue}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
