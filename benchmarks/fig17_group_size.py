"""Fig. 17: group-size sweep on (Mix, S2, BW=16) with MAGMA.
Validation: performance is flat-ish except for very small groups (the
paper: group=4 clearly lower; larger groups do not change much).

Throughput is normalized per-job (total fitness depends on the job mix, so
each group size re-samples its own group; we report FLOPs/s)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import GB, std_parser
from repro.core import M3E, MagmaConfig
from repro.costmodel import get_setting
from repro.workloads import build_task_groups


def run(budget, sizes=(4, 20, 50, 100, 200), seeds=1):
    from repro.core.sweep import run_sweep

    m3e = M3E(accel=get_setting("S2"), bw_sys=16 * GB)
    print("== Fig 17: group size sweep (Mix, S2, BW=16) ==")
    print("group_size,throughput_GFLOPs")
    out = {}
    for gs in sizes:
        # group sizes change G, so each size is its own sweep (the seed
        # axis shards across visible devices)
        group = build_task_groups("Mix", group_size=gs, seed=0)[0]
        cfg = MagmaConfig(population=min(gs, 100))
        batch = run_sweep([m3e.prepare(group)], budget=budget,
                          cfg=cfg, seeds=list(range(seeds)))
        out[gs] = float(batch.best_fitness[0].mean())
        print(f"{gs},{out[gs] / 1e9:.2f}")
    big = [v for k, v in out.items() if k >= 50]
    assert out[4] < max(big), "tiny group should underperform"
    return out


def main():
    args = std_parser(__doc__).parse_args()
    budget = 10_000 if args.full else args.budget
    sizes = (4, 20, 50, 100, 200, 1000) if args.full else (4, 20, 50, 100, 200)
    run(budget, sizes, args.seeds)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
