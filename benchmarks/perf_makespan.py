"""Framework-perf microbenchmark: the M3E fitness hot-loop.

The paper reports 0.25 s per 100-individual epoch on a desktop CPU.  This
times the vectorized jit(vmap(scan)) evaluator and the Pallas ``makespan``
kernel on the same epoch.  The kernel compiles with Mosaic, so it is
timed only on a TPU; elsewhere its time is reported as not measured.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import GB, std_parser
from repro.core.encoding import random_population
from repro.core.fitness import FitnessFn
from repro.core import M3E
from repro.costmodel import get_setting
from repro.workloads import build_task_groups


def _time(fn, *args, reps=20):
    fn(*args)                      # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(group_size=100, pop=100):
    m3e = M3E(accel=get_setting("S4"), bw_sys=16 * GB)
    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    fit = m3e.prepare(group)
    dev = jax.devices()[0]
    popn = random_population(jax.random.PRNGKey(0), pop, fit.group_size,
                             fit.num_accels)

    t_vec = _time(lambda: fit(popn.accel, popn.prio))
    t_ker = None
    if dev.platform == "tpu":
        fit_k = FitnessFn(fit.table, bw_sys=fit.bw_sys, use_kernel=True)
        t_ker = _time(lambda: fit_k(popn.accel, popn.prio), reps=3)
    print("== perf: fitness evaluation, 100-individual epoch, "
          f"G={group_size}, A={fit.num_accels}, on {dev.platform} "
          f"({dev.device_kind}) ==")
    print(f"paper (desktop CPU, python): 250.0 ms/epoch")
    print(f"vectorized dense event scan: {t_vec * 1e3:8.3f} ms/epoch "
          f"({0.25 / t_vec:.0f}x the paper)")
    print("pallas makespan (Mosaic):    " +
          (f"{t_ker * 1e3:8.3f} ms/epoch" if t_ker is not None
           else "not measured (needs a TPU)"))
    # full search wall time: legacy per-generation loop vs the
    # device-resident scanned engine (the default)
    from repro.core.magma import magma_search
    out = {"epoch_ms": t_vec * 1e3,
           "kernel_epoch_ms": None if t_ker is None else t_ker * 1e3}
    for engine in ("loop", "scan"):
        magma_search(fit, budget=10_000, seed=0, engine=engine)  # compile
        t0 = time.perf_counter()
        magma_search(fit, budget=10_000, seed=0, engine=engine)
        t_full = time.perf_counter() - t0
        out[f"search_{engine}_s"] = t_full
        print(f"full 10K-sample MAGMA search ({engine:4s} engine): "
              f"{t_full:.2f} s (paper: ~25 s)")
    out["search_s"] = out["search_scan_s"]      # back-compat key
    return out


def main():
    ap = std_parser(__doc__)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the timings as JSON (CI artifact)")
    args = ap.parse_args()
    out = run(args.group_size)
    if args.json:
        import json
        out.update(bench="perf_makespan", group_size=args.group_size)
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
