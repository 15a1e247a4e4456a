"""Fig. 11: convergence curves — best-so-far fitness vs samples for every
method on (Vision, S2, BW=16) and (Mix, S3, BW=16).  Validation: baselines
plateau at or below MAGMA's curve.

Every device-resident strategy runs its seeds for a scenario as ONE
``repro.core.sweep.run_sweep(strategy=...)`` call (compiled, sharded
across visible devices); curves are the seed-mean best-so-far history.
Host-only methods (cmaes/tbpsa/RL/heuristics) keep per-seed host loops."""
from __future__ import annotations

import numpy as np

from benchmarks.common import GB, resolve, std_parser
from repro.core import M3E
from repro.core.strategies import get_strategy, run_strategy
from repro.core.sweep import run_sweep
from repro.costmodel import get_setting
from repro.workloads import build_task_groups


def _mean_curve(method: str, fit, budget: int, seeds) -> np.ndarray:
    """Seed-mean best-so-far curve — one sweep for device strategies."""
    strategy = get_strategy(method)
    if strategy.device_resident:
        res = run_sweep([fit], budget=budget, seeds=list(seeds),
                        strategy=strategy)
        return np.asarray(res.history_best[0]).mean(axis=0)
    curves = [run_strategy(strategy, fit, budget=budget, seed=s).history_best
              for s in seeds]
    # tbpsa's curve length adapts per seed; best-so-far is monotone, so
    # extend shorter runs by carrying their final best forward
    n = max(len(c) for c in curves)
    return np.mean([np.concatenate([c, np.full(n - len(c), c[-1])])
                    for c in curves], axis=0)


def run(budget, methods, group_size=100, seeds=1):
    seed_list = list(range(seeds))
    report = {"bench": "fig11_convergence", "budget": budget,
              "group_size": group_size, "num_seeds": seeds, "problems": {}}
    for task, setting in (("Vision", "S2"), ("Mix", "S3")):
        m3e = M3E(accel=get_setting(setting), bw_sys=16 * GB)
        group = build_task_groups(task, group_size=group_size, seed=0)[0]
        fit = m3e.prepare(group)
        print(f"\n== Fig 11: ({task}, {setting}, BW=16), "
              f"{seeds} seed(s) ==")
        print("method,samples_curve...,final")
        finals, curves = {}, {}
        for method in methods:
            curve = _mean_curve(method, fit, budget, seed_list)
            pts = np.linspace(0, len(curve) - 1, 8).astype(int)
            spark = ",".join(f"{curve[i]:.3e}" for i in pts)
            print(f"{method},{spark}")
            finals[method] = float(curve[-1])
            curves[method] = [float(c) for c in curve]
        best = max(finals, key=finals.get)
        print(f"best: {best}")
        report["problems"][f"{task}/{setting}"] = {
            "finals": finals, "best_method": best, "curves": curves}
    return report


def main():
    import json
    import time

    ap = std_parser(__doc__)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the curves/finals as JSON "
                         "(machine-readable, like the perf_* benchmarks)")
    args = ap.parse_args()
    budget, methods = resolve(args)
    report = run(budget, methods, args.group_size, args.seeds)
    if args.json:
        report["unix_time"] = time.time()
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
