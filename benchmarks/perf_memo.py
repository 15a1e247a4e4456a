"""Schedule-memo benchmark: reuse turns repeat traffic into free schedules.

Two measurements, one report (``BENCH_memo.json``, schema in
benchmarks/README.md):

  hit-rate scaling   one solved scenario pool + measured request streams
                     whose exact-hit fraction ramps 0% -> 90%: sustained
                     scenarios/sec of the memoized service at each rate,
                     against the same stream through a memo-less service
                     (every request searched).  Exact hits are answered
                     from the store with zero device dispatches, so
                     throughput should scale sharply with the hit rate —
                     the "compute most schedules once" claim, measured.
  warm-start         generations-to-target-fitness with vs without warm
                     seeding (Section V-C / Table V as a *memo* feature):
                     a converged population recorded on one Mix group
                     seeds its siblings via nearest-fingerprint transfer;
                     the warm search must reach the cold search's
                     (fractional) final best fitness in measurably fewer
                     generations.

Exits non-zero on any non-finite number (CI gates on it) and asserts the
warm-start win at the configured scale.

    PYTHONPATH=src python -m benchmarks.perf_memo [--quick]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import numpy as np

from repro.core import M3E, MagmaConfig
from repro.core.strategies import MagmaStrategy, run_strategy
from repro.costmodel import get_setting
from repro.lint.runtime import RecompileGuard
from repro.memo import ScheduleMemo
from repro.stream import (StreamConfig, StreamingScheduler, TraceConfig,
                          generate_trace)
from repro.workloads import build_task_groups

GB = 1024 ** 3


# ---------------------------------------------------------------------------
# hit-rate -> scenarios/sec
# ---------------------------------------------------------------------------
def _trace(n, seed, group_size):
    return generate_trace(TraceConfig(
        num_scenarios=n, arrival="batch", group_size=group_size,
        mixes=("Heavy", "Light"), settings=("S2",),
        bw_ladder_gb=(1.0, 4.0, 16.0), seed=seed))


def _measured_stream(pool, fresh, hit_rate, n):
    """n requests: round(hit_rate*n) duplicates of solved pool scenarios
    (exact hits), the rest fresh (cold searches), interleaved
    deterministically and re-uid'd."""
    n_dup = int(round(hit_rate * n))
    reqs = [dataclasses.replace(pool[i % len(pool)], uid=0)
            for i in range(n_dup)]
    reqs += [dataclasses.replace(fresh[i], uid=0) for i in range(n - n_dup)]
    rng = np.random.default_rng(1234)
    rng.shuffle(reqs)
    return [dataclasses.replace(r, uid=i) for i, r in enumerate(reqs)]


def run_hit_sweep(num_requests, pool_size, group_size, budget, batch_rows,
                  reps, rates):
    pool = _trace(pool_size, seed=0, group_size=group_size)
    fresh_all = _trace(num_requests * len(rates) * reps, seed=1,
                       group_size=group_size)
    stream_cfg = StreamConfig(batch_rows=batch_rows, analysis_workers=1)
    svc = StreamingScheduler(budget=budget, stream=stream_cfg,
                             memo=ScheduleMemo())
    plain = StreamingScheduler(budget=budget, stream=stream_cfg)
    # compile every bucket (memo-on also compiles the keep-population and
    # warm-seeded executables) so the sweep measures the service; the
    # guard holds the measured loops to zero compiles — a bucket the
    # warmup missed would otherwise fold a multi-second XLA stall into
    # one hit-rate point and skew the whole ramp
    guard = RecompileGuard(label="perf_memo").__enter__()
    svc.warmup(pool + fresh_all[:1])
    plain.warmup(pool + fresh_all[:1])
    guard.warmup()

    out = []
    fresh_at = 0
    for rate in rates:
        sps, base_sps, hits, batches = [], [], [], []
        for _ in range(reps):
            fresh = fresh_all[fresh_at:fresh_at + num_requests]
            fresh_at += num_requests
            stream = _measured_stream(pool, fresh, rate, num_requests)
            svc.memo = ScheduleMemo()              # fresh store per rep
            svc.run(pool)                          # solve the pool
            svc.run(stream)                        # measured pass
            m = svc.last_metrics
            plain.run(stream)
            sps.append(m.scenarios_per_sec)
            base_sps.append(plain.last_metrics.scenarios_per_sec)
            hits.append(m.memo_exact_hits)
            batches.append(m.num_batches)
        row = {
            "hit_rate": rate,
            "scenarios_per_sec": float(np.median(sps)),
            "no_memo_scenarios_per_sec": float(np.median(base_sps)),
            "speedup_vs_no_memo": float(np.median(sps)
                                        / max(np.median(base_sps), 1e-12)),
            "exact_hits": int(np.median(hits)),
            "num_batches": int(np.median(batches)),
        }
        out.append(row)
        print(f"hit-rate {rate:4.0%}: {row['scenarios_per_sec']:7.2f} "
              f"scen/s (no memo {row['no_memo_scenarios_per_sec']:7.2f}) "
              f"-> {row['speedup_vs_no_memo']:5.2f}x, "
              f"{row['exact_hits']} exact hits, "
              f"{row['num_batches']} device batches")
    guard.__exit__(None, None, None)     # detach + raise on violations
    print(f"recompiles after warmup: {len(guard.post_warmup)} (guarded)")
    return out


# ---------------------------------------------------------------------------
# warm-start: generations to target fitness
# ---------------------------------------------------------------------------
def _gens_to(hist, target):
    """1-based generation at which the curve first reaches ``target``
    (len(hist)+1 when it never does)."""
    idx = np.nonzero(np.asarray(hist) >= target)[0]
    return int(idx[0]) + 1 if len(idx) else len(hist) + 1


def run_warmstart(group_size, budget, pop, n_groups, target_frac):
    """The service's near-hit case, measured: solve several Mix groups at
    a base system BW and record their converged populations, then
    schedule *near-same* scenarios (the same groups at shifted BWs —
    different tables, same transfer family).  Nearest-fingerprint lookup
    must pick each group's own record among all stored ones, and the
    warm-seeded search must reach the cold search's (fractional) final
    best in fewer generations."""
    cfg = MagmaConfig(population=pop)
    strat = MagmaStrategy(cfg)
    groups = build_task_groups("Mix", group_size=group_size,
                               num_groups=n_groups, seed=0)
    memo = ScheduleMemo()
    for gi, g in enumerate(groups):
        fit0 = M3E(accel=get_setting("S2"), bw_sys=16 * GB).prepare(g)
        ref = run_strategy(strat, fit0, budget=budget, seed=gi,
                           keep_population=True)
        memo.record(fit0, strat, budget, gi, ref,
                    population=ref.final_population, family="Mix")

    cold_gens, warm_gens, cold_best, warm_best = [], [], [], []
    for gi, g in enumerate(groups):
        for bw in (8, 32):
            fit = M3E(accel=get_setting("S2"), bw_sys=bw * GB).prepare(g)
            cold = run_strategy(strat, fit, budget=budget, seed=10 + gi)
            ws = memo.warm_start(fit, strat, family="Mix")
            assert ws is not None, "memo lost the seeded family"
            warm = run_strategy(strat, fit, budget=budget, seed=10 + gi,
                                init_population=ws)
            target = target_frac * cold.best_fitness
            cold_gens.append(_gens_to(cold.history_best, target))
            warm_gens.append(_gens_to(warm.history_best, target))
            cold_best.append(cold.best_fitness)
            warm_best.append(warm.best_fitness)

    res = {
        "n_groups": n_groups,
        "target_frac": target_frac,
        "generations": int(budget // pop),
        "cold_gens_mean": float(np.mean(cold_gens)),
        "warm_gens_mean": float(np.mean(warm_gens)),
        "gens_speedup": float(np.mean(cold_gens) / np.mean(warm_gens)),
        "cold_best_mean": float(np.mean(cold_best)),
        "warm_best_mean": float(np.mean(warm_best)),
        "warm_vs_cold_best": float(np.mean(np.array(warm_best)
                                           / np.array(cold_best))),
    }
    print(f"warm-start: {res['cold_gens_mean']:.1f} -> "
          f"{res['warm_gens_mean']:.1f} mean generations to "
          f"{target_frac:.0%} of cold best "
          f"({res['gens_speedup']:.1f}x fewer), warm/cold final best "
          f"{res['warm_vs_cold_best']:.3f}")
    assert res["warm_gens_mean"] < res["cold_gens_mean"], \
        "warm seeding did not reach the target fitness faster"
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=32,
                    help="measured requests per hit-rate point")
    ap.add_argument("--pool", type=int, default=8,
                    help="unique solved scenarios duplicates draw from")
    ap.add_argument("--group-size", type=int, default=48)
    ap.add_argument("--budget", type=int, default=1_000)
    ap.add_argument("--batch-rows", type=int, default=8)
    ap.add_argument("--population", type=int, default=50)
    ap.add_argument("--groups", type=int, default=3,
                    help="warm-start transfer target groups")
    ap.add_argument("--target-frac", type=float, default=0.98,
                    help="warm-start target as a fraction of the cold "
                         "search's final best fitness")
    ap.add_argument("--reps", type=int, default=3,
                    help="reps per hit-rate point (medians reported)")
    ap.add_argument("--rates", default="0,0.5,0.9",
                    help="comma list of exact-hit fractions")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tiny trace/budget")
    ap.add_argument("--out", default="BENCH_memo.json")
    args = ap.parse_args()

    if args.quick:
        args.requests, args.pool, args.group_size = 16, 4, 24
        args.budget, args.population, args.reps = 600, 30, 2

    rates = [float(r) for r in args.rates.split(",")]
    print(f"== perf: schedule memo ({args.requests} requests/point, "
          f"pool {args.pool}, G={args.group_size}, budget={args.budget}, "
          f"{len(jax.devices())} device(s)) ==")
    hit_rows = run_hit_sweep(args.requests, args.pool, args.group_size,
                             args.budget, args.batch_rows, args.reps, rates)
    warm = run_warmstart(args.group_size, args.budget, args.population,
                         args.groups, args.target_frac)

    report = {
        "bench": "perf_memo",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
        "num_requests": args.requests,
        "pool_size": args.pool,
        "group_size": args.group_size,
        "budget": args.budget,
        "batch_rows": args.batch_rows,
        "population": args.population,
        "reps": args.reps,
        "hit_sweep": hit_rows,
        "warmstart": warm,
        "unix_time": time.time(),
    }

    flat = [warm["gens_speedup"], warm["warm_vs_cold_best"]]
    for row in hit_rows:
        flat += [row["scenarios_per_sec"], row["speedup_vs_no_memo"]]
    if not np.isfinite(flat).all():
        print("NON-FINITE RESULTS", file=sys.stderr)
        sys.exit(1)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
