"""Fleet benchmark: aggregate scenarios/sec scaling 1 -> 2 workers on a
deliberately skewed trace, with the two hard gates CI cares about.

The trace is maximally imbalanced by construction: every scenario
shares one compatibility signature (one setting, one group size), so a
static partition sends ALL of it to one worker and the second worker
only earns its keep through work-stealing — the scaling number measures
the router + steal path, not a lucky hash.  Each fleet is warmed with a
disjoint-seed twin of the trace first (row-executable compiles happen
there), so the measured runs compare scheduling, not XLA.

The scaling ratio is reported, not gated: worker processes are real OS
processes, so aggregate scenarios/sec scales with workers only when the
host grants them cores (``host_cpus`` lands in the report).  On the
single-core CI container two workers timeshare one core and the ratio
sits below 1x by the routing overhead; the hard gates below hold on any
machine.

Gates (exit non-zero on any violation, plus a NaN gate over the whole
report):

  * every 2-worker fleet schedule is bit-identical to the standalone
    single-host ``run_sweep`` row for its (scenario, seed) — the fleet
    guarantee, checked in-process against freshly analyzed tables;
  * replaying the trace steal-free routes every scenario to its home
    worker and yields >= 1 cross-worker memo exact hit (a schedule one
    worker solved, replayed by another through the shared sharded
    store) with every replayed array bit-identical to run 1.

Results go to stdout and, machine-readable, to ``BENCH_fleet.json``
(schema in benchmarks/README.md; ``--out`` to change).

    PYTHONPATH=src python -m benchmarks.perf_fleet [--quick]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

from repro.core.sweep import run_sweep
from repro.fleet import FleetConfig, launch_fleet
from repro.stream import TraceConfig, analyze_serial, generate_trace


def _skewed_trace(n: int, group_size: int, seed: int):
    """One compat signature for the whole trace: the worst case for a
    static partition, the best case for demonstrating stealing."""
    return generate_trace(TraceConfig(
        num_scenarios=n, group_size=group_size, seed=seed,
        settings=("S1",), mixes=("Light", "Heavy"),
        bw_ladder_gb=(1.0, 4.0, 16.0)))


def _fleet_side(tag: str, m: dict) -> dict:
    print(f"{tag:10s} wall {m['wall_s']:7.2f} s   "
          f"{m['scenarios_per_sec']:6.2f} scen/s   "
          f"per-worker {tuple(m['per_worker_scenarios'])}   "
          f"steals {m['steals']} ({m['stolen_members']} members)   "
          f"latency p50/p99 {m['latency_p50_s']:.2f}/"
          f"{m['latency_p99_s']:.2f} s")
    return m


def _check_bit_identical(results, budget: int) -> None:
    for r in results:
        fit = analyze_serial([r.request])[0].fit
        ref = run_sweep([fit], budget=budget, seeds=[r.request.seed])
        assert r.best_fitness == ref.best_fitness[0, 0], r.request
        np.testing.assert_array_equal(r.best_accel, ref.best_accel[0, 0])
        np.testing.assert_array_equal(r.best_prio, ref.best_prio[0, 0])
        np.testing.assert_array_equal(r.history_best,
                                      ref.history_best[0, 0])


def _assert_finite(obj, path="report") -> None:
    if isinstance(obj, dict):
        for k, v in obj.items():
            _assert_finite(v, f"{path}.{k}")
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            _assert_finite(v, f"{path}[{i}]")
    elif isinstance(obj, float):
        assert math.isfinite(obj), f"non-finite {path} = {obj}"


def run(num_scenarios: int, group_size: int, budget: int,
        devices_per_worker: int, batch_rows: int, chunk_rows: int,
        seed: int) -> dict:
    trace = _skewed_trace(num_scenarios, group_size, seed)
    warm = _skewed_trace(num_scenarios, group_size, seed + 1)
    print(f"== perf: fleet scaling (skewed trace, {num_scenarios} "
          f"scenarios, G={group_size}, budget={budget}, "
          f"{devices_per_worker} fake device(s)/worker) ==")

    sides = {}
    rerun_m = None
    results2 = rerun = None
    post_warmup_compiles = 0
    for workers in (1, 2):
        with tempfile.TemporaryDirectory() as memo:
            cfg = FleetConfig(num_workers=workers,
                              devices_per_worker=devices_per_worker,
                              budget=budget, memo_path=memo,
                              stream={"batch_rows": batch_rows},
                              chunk_rows=chunk_rows,
                              recompile_guard=True)
            t0 = time.perf_counter()
            with launch_fleet(cfg) as fleet:
                print(f"{workers}-worker fleet up in "
                      f"{time.perf_counter() - t0:.1f} s")
                fleet.warmup(warm)       # compiles live here, not below:
                fleet.mark_warm()        # every bucket precompiled, any
                                         # later worker compile is a
                                         # violation worker_stats() shows
                res = fleet.run(trace)
                sides[workers] = _fleet_side(
                    f"{workers}-worker", fleet.last_metrics.summary())
                if workers == 2:
                    results2 = res
                    # steal-free replay: every scenario goes HOME, so
                    # the ones run 1 stole replay records solved on the
                    # other side of the fleet
                    rerun = fleet.run(trace, steal=False)
                    rerun_m = fleet.last_metrics.summary()
                post_warmup_compiles += sum(
                    d.get("recompiles_post_warmup", 0)
                    for d in fleet.worker_stats().values())

    cpus = os.cpu_count() or 1
    scaling = (sides[2]["scenarios_per_sec"]
               / max(sides[1]["scenarios_per_sec"], 1e-12))
    print(f"scaling 1 -> 2 workers: {scaling:.2f}x aggregate "
          f"scenarios/sec ({cpus} host core(s); two workers timeshare "
          f"a single core, so > 1x needs cores >= workers)")

    assert post_warmup_compiles == 0, \
        (f"{post_warmup_compiles} worker jit compile(s) after the warm "
         f"boundary — a bucket the warm trace missed polluted the "
         f"measured runs")
    print("recompiles after warm boundary: 0 across all workers (guarded)")

    _check_bit_identical(results2, budget)
    print(f"all {len(results2)} fleet schedules bit-identical to "
          f"standalone run_sweep rows")

    for a, b in zip(results2, rerun):
        assert a.best_fitness == b.best_fitness
        np.testing.assert_array_equal(a.best_accel, b.best_accel)
        np.testing.assert_array_equal(a.history_best, b.history_best)
    assert rerun_m["memo_exact_hits"] == len(rerun), rerun_m
    assert rerun_m["memo_foreign_hits"] >= 1, \
        ("no cross-worker memo hit: nothing was stolen in run 1?",
         sides[2], rerun_m)
    print(f"steal-free replay: {rerun_m['memo_exact_hits']} exact hits, "
          f"{rerun_m['memo_foreign_hits']} crossed a worker boundary "
          f"(rate {rerun_m['cross_worker_hit_rate']:.2f})")

    import jax
    return {
        "bench": "perf_fleet",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "host_cpus": cpus,
        "devices_per_worker": devices_per_worker,
        "num_scenarios": num_scenarios,
        "group_size": group_size,
        "budget": budget,
        "batch_rows": batch_rows,
        "chunk_rows": chunk_rows,
        "trace_seed": seed,
        "one_worker": sides[1],
        "two_worker": sides[2],
        "scaling_2w_over_1w": scaling,
        "rerun_steal_free": rerun_m,
        "cross_worker_hits": rerun_m["memo_foreign_hits"],
        "recompiles_post_warmup": post_warmup_compiles,
        "bit_identical": True,
        "unix_time": time.time(),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenarios", type=int, default=32)
    ap.add_argument("--group-size", type=int, default=16)
    ap.add_argument("--budget", type=int, default=600)
    ap.add_argument("--devices-per-worker", type=int, default=2,
                    help="fake host-platform devices per worker (the "
                         "2-core CI container: keep it small)")
    ap.add_argument("--batch-rows", type=int, default=4)
    ap.add_argument("--chunk-rows", type=int, default=4)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 12 scenarios at budget 120")
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args()
    if args.quick:
        args.scenarios, args.group_size, args.budget = 12, 8, 120

    report = run(num_scenarios=args.scenarios, group_size=args.group_size,
                 budget=args.budget,
                 devices_per_worker=args.devices_per_worker,
                 batch_rows=args.batch_rows, chunk_rows=args.chunk_rows,
                 seed=args.seed)
    _assert_finite(report)               # NaN gate: CI fails on any
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, default=float)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
