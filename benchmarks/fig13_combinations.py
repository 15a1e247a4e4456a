"""Fig. 13: sub-accelerator combinations — S3 (Large Homog), S4 (Large
Hetero), S5 (BigLittle) under BW=1 and BW=256 GB/s with MAGMA.
Validation: hetero (S4) > homog (S3) at BW=1; homog wins at BW=256;
BigLittle (S5) best at BW=1 despite the least compute."""
from __future__ import annotations

from benchmarks.common import GB, std_parser
from repro.core import M3E
from repro.costmodel import MaestroModel, get_setting
from repro.workloads import build_task_groups
from repro.core.job_analyzer import JobAnalyzer


def run(budget, group_size=100, seeds=1, sweep=None):
    from repro.core.sweep import run_sweep

    print("== Fig 13: S3/S4/S5 x BW (Mix, MAGMA), normalized to S5 ==")
    results = {1.0: {}, 256.0: {}}
    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    # per setting, both BW scenarios x all seeds run as one sweep (same
    # job tables, different bw_sys), sharded across visible devices
    for setting in ("S3", "S4", "S5"):
        fits = [M3E(accel=get_setting(setting), bw_sys=bw * GB).prepare(group)
                for bw in (1.0, 256.0)]
        batch = run_sweep(fits, budget=budget, seeds=list(range(seeds)),
                          sweep=sweep)
        for i, bw in enumerate((1.0, 256.0)):
            results[bw][setting] = float(batch.best_fitness[i].mean())
    for bw, row in results.items():
        norm = row["S5"]
        print(f"BW={bw:g}: " + ", ".join(
            f"{k}={v / norm:.3f}" for k, v in row.items()))

    # job-analysis side (Fig 13 a-b): S4 higher latency but lower BW than S3
    model = MaestroModel()
    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    for setting in ("S3", "S4", "S5"):
        table = JobAnalyzer(get_setting(setting), model).analyze(group.jobs)
        print(f"{setting}: mean no-stall lat {table.lat.mean():.3e} s, "
              f"mean req BW {table.bw.mean() / 2**30:.2f} GB/s")
    return results


def main():
    args = std_parser(__doc__).parse_args()
    budget = 10_000 if args.full else args.budget
    run(budget, args.group_size, args.seeds)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
