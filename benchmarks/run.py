"""Benchmark aggregator: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--budget N] [--full]

Runs every reproduction benchmark at a CI-friendly budget (default 800
samples; the paper protocol is 10K via --full) and prints a
``name,seconds,headline`` CSV summary at the end.  Exits non-zero when any
benchmark failed.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

import numpy as np

from benchmarks import (fig07_job_analysis, fig08_homogeneous,
                        fig09_heterogeneous, fig12_bw_sweep,
                        fig13_combinations, fig14_flexible,
                        fig15_solution_analysis, fig16_operator_ablation,
                        fig17_group_size, perf_makespan, perf_scan_engine,
                        tableV_warmstart)
from benchmarks.common import FAST_METHODS, summarize_vs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=800)
    ap.add_argument("--group-size", type=int, default=60)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    budget = 10_000 if args.full else args.budget
    gs = 100 if args.full else args.group_size
    methods = FAST_METHODS

    rows = []
    failed = []

    def bench(name, fn, headline_fn=lambda r: ""):
        t0 = time.perf_counter()
        try:
            r = fn()
            rows.append((name, time.perf_counter() - t0, headline_fn(r)))
        except Exception as e:                           # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
            rows.append((name, time.perf_counter() - t0,
                         f"FAILED {type(e).__name__}"))

    bench("fig07_job_analysis", lambda: fig07_job_analysis.run(),
          lambda r: "orderings_ok")
    bench("fig08_homogeneous",
          lambda: fig08_homogeneous.run(budget, methods, gs),
          lambda r: "magma_adv=%.2fx" % np.mean(
              list(summarize_vs(r).values())))
    bench("fig09_heterogeneous",
          lambda: fig09_heterogeneous.run(budget, methods, gs),
          lambda r: "magma_adv=%.2fx" % np.mean(
              list(summarize_vs(r).values())))
    bench("fig12_bw_sweep",
          lambda: fig12_bw_sweep.run(budget, methods, gs))
    bench("fig13_combinations",
          lambda: fig13_combinations.run(budget, gs),
          lambda r: "BW1: " + " ".join(
              f"{k}={v / r[1.0]['S5']:.2f}" for k, v in r[1.0].items()))
    bench("fig14_flexible", lambda: fig14_flexible.run(budget, gs),
          lambda r: "fixed/flex=" + " ".join(f"{v:.2f}" for v in r.values()))
    bench("fig15_solution_analysis",
          lambda: fig15_solution_analysis.run(budget, gs),
          lambda r: "magma_finish=%.1fms herald=%.1fms" % (
              r["magma"][0] * 1e3, r["herald_like"][0] * 1e3))
    bench("fig16_operator_ablation",
          lambda: fig16_operator_ablation.run(budget, gs))
    bench("fig17_group_size",
          lambda: fig17_group_size.run(budget, seeds=1))
    bench("tableV_warmstart",
          lambda: tableV_warmstart.run(group_size=gs, epochs=(0, 1, 10, 20)),
          lambda r: "Trf0_vs_raw=%.1fx" % r["gain0"])
    bench("perf_makespan", lambda: perf_makespan.run(gs),
          lambda r: "epoch=%.2fms search=%.1fs" % (r["epoch_ms"],
                                                   r["search_s"]))
    bench("perf_scan_engine", lambda: perf_scan_engine.run(budget, 16),
          lambda r: "scan=%.1fx sweep=%.1fx" % (r["scan_speedup"],
                                                r["sweep_speedup"]))

    print("\n==== benchmark summary (name,seconds,headline) ====")
    for name, dt, head in rows:
        print(f"{name},{dt:.1f},{head}")
    if failed:
        print(f"FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
