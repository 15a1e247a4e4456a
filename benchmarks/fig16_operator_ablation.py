"""Fig. 16: MAGMA operator ablation on (Vision, S2, BW=16) and
(Mix, S3, BW=16): mutation-only vs +crossover-gen vs all four operators.
Validation: each added operator level improves (or matches) sample
efficiency.

Each ablation level runs all its seeds as one
``run_sweep(strategy=MagmaStrategy(cfg))`` call — compiled and sharded,
every row bit-identical to a standalone
``m3e.search(seed=s, strategy_kwargs={"cfg": cfg})``."""
from __future__ import annotations

import numpy as np

from benchmarks.common import GB, std_parser
from repro.core import M3E, MagmaConfig
from repro.core.strategies import MagmaStrategy
from repro.core.sweep import run_sweep
from repro.costmodel import get_setting
from repro.workloads import build_task_groups

LEVELS = {
    "mutation_only": MagmaConfig(enable_crossover_gen=False,
                                 enable_crossover_rg=False,
                                 enable_crossover_accel=False),
    "mut+crossover_gen": MagmaConfig(enable_crossover_rg=False,
                                     enable_crossover_accel=False),
    "all_four": MagmaConfig(),
}


def run(budget, group_size=100, seeds=2):
    out = {}
    for task, setting in (("Vision", "S2"), ("Mix", "S3")):
        m3e = M3E(accel=get_setting(setting), bw_sys=16 * GB)
        group = build_task_groups(task, group_size=group_size, seed=0)[0]
        fit = m3e.prepare(group)
        print(f"\n== Fig 16: ({task}, {setting}, BW=16) ==")
        vals = {}
        for name, cfg in LEVELS.items():
            batch = run_sweep([fit], budget=budget, seeds=list(range(seeds)),
                              strategy=MagmaStrategy(cfg))
            vals[name] = float(batch.best_fitness[0].mean())
        norm = vals["all_four"]
        for name, v in vals.items():
            print(f"{name:20s} {v / norm:.3f}")
        out[f"{task}-{setting}"] = vals
    return out


def main():
    ap = std_parser(__doc__)
    ap.set_defaults(seeds=2)       # ablation deltas need seed averaging
    args = ap.parse_args()
    budget = 10_000 if args.full else args.budget
    run(budget, args.group_size, args.seeds)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
