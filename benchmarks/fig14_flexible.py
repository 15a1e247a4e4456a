"""Fig. 14: fixed vs flexible PE arrays (Section VI-F) on S1 and S3,
Vision and Mix, with MAGMA.  Validation: flexible >= fixed throughput
(higher utilization; higher per-job BW requirement is the trade-off)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import GB, std_parser
from repro.core import M3E
from repro.core.job_analyzer import JobAnalyzer
from repro.costmodel import MaestroModel, get_setting
from repro.costmodel.maestro import FlexibleMaestroModel
from repro.workloads import build_task_groups


def run(budget, group_size=100):
    fixed_m = MaestroModel()
    flex_m = FlexibleMaestroModel()
    print("== Fig 14: fixed vs flexible PE arrays (MAGMA) ==")
    out = {}
    for setting, bw in (("S1", 16.0), ("S3", 256.0)):
        accel = get_setting(setting)
        for task in ("Vision", "Mix"):
            group = build_task_groups(task, group_size=group_size, seed=0)[0]
            fits = {}
            for name, model in (("fixed", fixed_m), ("flexible", flex_m)):
                m3e = M3E(accel=accel, bw_sys=bw * GB)
                fit = None
                table = JobAnalyzer(accel, model).analyze(group.jobs)
                from repro.core.fitness import FitnessFn
                from repro.core.magma import magma_search
                fit_fn = FitnessFn(table, bw_sys=bw * GB)
                res = magma_search(fit_fn, budget=budget, seed=0)
                fits[name] = res.best_fitness
            ratio = fits["fixed"] / fits["flexible"]
            out[f"{task}-{setting}"] = ratio
            print(f"{task}-{setting}: fixed/flexible = {ratio:.3f} "
                  f"(flexible abs {fits['flexible'] / 1e9:.1f} GFLOPs)")

    # job analysis: flexible lowers latency, raises BW (Fig 14 a-b)
    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    accel = get_setting("S1")
    t_fix = JobAnalyzer(accel, fixed_m).analyze(group.jobs)
    t_flex = JobAnalyzer(accel, flex_m).analyze(group.jobs)
    print(f"mean lat: fixed {t_fix.lat.mean():.3e} s -> "
          f"flexible {t_flex.lat.mean():.3e} s")
    print(f"mean BW : fixed {t_fix.bw.mean() / 2**30:.2f} -> "
          f"flexible {t_flex.bw.mean() / 2**30:.2f} GB/s")
    assert t_flex.lat.mean() <= t_fix.lat.mean() * 1.001
    return out


def main():
    args = std_parser(__doc__).parse_args()
    budget = 10_000 if args.full else args.budget
    run(budget, args.group_size)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
