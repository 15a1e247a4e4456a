"""Framework-perf benchmark: device-resident MAGMA engine.

Three comparisons, all at the paper's population 100 x 100 generations
(10K-sample budget):

  1. single search — engine='loop' (legacy: one jitted dispatch + host
     sync per generation) vs engine='scan' (whole search folded into one
     ``lax.scan``: a single compiled call).
  2. scenario sweep — a Fig. 8/9-style grid of >= 8 (scenario x seed)
     searches: the legacy workflow (sequential per-generation-loop
     searches) vs ONE vmapped ``magma_search_batch`` call.  This is the
     workflow the device-resident engine exists for: the sweep pays
     dispatch + host-sync overhead once instead of once per generation
     per scenario.
  3. batch vs sequential scan — ``magma_search_batch`` must also beat the
     same searches run as sequential (scanned) ``magma_search`` calls.

Compile time is excluded (warm-up call first), matching how the search
amortizes in the fleet-scheduler workflow: one compile, thousands of
deployments.  Ratios are hardware-dependent: host dispatch/sync overhead
is a few ms per generation here, so the gap widens with small groups
(default G=16, a realistic per-deployment group — see Fig. 17's group
sweep) and on accelerator backends, and narrows when the G-step event
simulation dominates (``--group-size 100``).

    PYTHONPATH=src python -m benchmarks.perf_scan_engine [--group-size 16]
"""
from __future__ import annotations

import time

from benchmarks.common import GB, std_parser
from repro.core import M3E
from repro.core.magma import MagmaConfig, magma_search, magma_search_batch
from repro.costmodel import get_setting
from repro.workloads import build_task_groups


def _timed(fn, reps=3):
    fn()                      # warm-up / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]       # median: the container is noisy


def run(budget=10_000, group_size=16, seeds=4):
    cfg = MagmaConfig(population=100)
    generations = max(1, budget // cfg.population)

    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    fits = [M3E(accel=get_setting("S2"), bw_sys=bw * GB).prepare(group)
            for bw in (1.0, 4.0, 16.0, 64.0)]
    seed_list = list(range(seeds))
    n = len(fits) * len(seed_list)

    print(f"== perf: device-resident MAGMA engine (P={cfg.population}, "
          f"{generations} generations, G={group_size}, "
          f"A={fits[0].num_accels}) ==")

    t_loop = _timed(lambda: magma_search(fits[0], budget=budget, cfg=cfg,
                                         seed=0, engine="loop"))
    t_scan = _timed(lambda: magma_search(fits[0], budget=budget, cfg=cfg,
                                         seed=0, engine="scan"))
    print(f"[1] single search")
    print(f"    per-generation host loop:   {t_loop:7.3f} s "
          f"({generations / t_loop:7.1f} gen/s)")
    print(f"    device-resident lax.scan:   {t_scan:7.3f} s "
          f"({generations / t_scan:7.1f} gen/s)   "
          f"{t_loop / t_scan:.1f}x")

    def sweep_loop():
        return [magma_search(f, budget=budget, cfg=cfg, seed=s,
                             engine="loop")
                for f in fits for s in seed_list]

    def sweep_scan():
        return [magma_search(f, budget=budget, cfg=cfg, seed=s)
                for f in fits for s in seed_list]

    def sweep_batch():
        return magma_search_batch(fits, budget=budget, cfg=cfg,
                                  seeds=seed_list)

    t_sloop = _timed(sweep_loop)
    t_sscan = _timed(sweep_scan)
    t_batch = _timed(sweep_batch)
    print(f"[2] {n}-search sweep ({len(fits)} scenarios x "
          f"{len(seed_list)} seeds)")
    print(f"    sequential loop engine:     {t_sloop:7.3f} s")
    print(f"    one magma_search_batch:     {t_batch:7.3f} s   "
          f"{t_sloop / t_batch:.1f}x")
    print(f"[3] batch vs sequential scanned searches")
    print(f"    sequential scan engine:     {t_sscan:7.3f} s")
    print(f"    one magma_search_batch:     {t_batch:7.3f} s   "
          f"{t_sscan / t_batch:.1f}x")
    return {"t_loop": t_loop, "t_scan": t_scan,
            "scan_speedup": t_loop / t_scan,
            "t_sweep_loop": t_sloop, "t_sweep_scan": t_sscan,
            "t_sweep_batch": t_batch,
            "sweep_speedup": t_sloop / t_batch,
            "batch_speedup": t_sscan / t_batch}


def main():
    ap = std_parser(__doc__)
    ap.set_defaults(group_size=16, seeds=4)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the timings as JSON (CI artifact)")
    args = ap.parse_args()
    budget = 10_000 if args.full else args.budget
    out = run(budget, args.group_size, args.seeds)
    if args.json:
        import json
        out.update(bench="perf_scan_engine", budget=budget,
                   group_size=args.group_size, seeds=args.seeds)
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
