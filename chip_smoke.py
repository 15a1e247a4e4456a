#!/usr/bin/env python3
"""Smoke run of the MAGMA scheduling service on TPU chips, at the paper's
protocol (MAGMA §VI, Table III): groups of G=100 jobs, 10,000 samples per
search (a population of 100 for 100 generations), the heterogeneous
settings S2 (4 sub-accelerators) and S4 (8), and the Vision, Lang, Recom
and Mix task sets.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the paths across four chips

One chip, three phases:

  served   16 seeded Poisson requests served by ``StreamingScheduler``
           (``batch_rows=8``, ``max_devices=1``) after ``warmup()``.  Every
           request is routed with a finite, positive fitness.  One request
           per (G, A) key equals a standalone ``magma_search`` bit for bit,
           and its best schedule's makespan agrees with the float64
           ``simulate_numpy`` oracle.  A service with a ``ScheduleMemo``
           serves the trace twice: the second pass is all exact hits.
  offline  a ``run_sweep`` grid of the four task sets on S4 x 2 seeds;
           every row equals its standalone ``magma_search``.
  kernel   the same grid with the Pallas makespan kernel compiled by
           Mosaic (``tpu_custom_call`` in the compiled HLO); the kernel's
           makespans of a fixed population agree with
           ``simulate_population`` and the oracle.

``--four-chips`` runs only the paths across chips: (b) a fleet of four
workers, each pinned to its own chip, serving the trace, compared with
standalone searches that this process runs after the fleet has closed
(a process that has touched JAX holds every chip); then (a) the served
trace and the grid sharded over four chips, compared row by row with one
chip in the same process.

Times printed are from a smoke run, not benchmark figures.  Without a TPU
the script exits non-zero before any phase.  Any failed phase makes it
exit non-zero; on success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

GROUP_SIZE = 100                # jobs per group (paper §VI)
BUDGET = 10_000                 # samples per search (paper §VI)
SETTINGS = ("S2", "S4")         # Table III: small / large heterogeneous
MIXES = ("Vision", "Lang", "Recom", "Mix")
NUM_REQUESTS = 16
SEEDS = (0, 1)                  # the offline grid's search seeds
GRID_BW_GB = 16.0               # the offline grid's system bandwidth
RTOL = 1e-4                     # f32 device makespan vs the f64 oracle


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def rel_err(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def same_row(a, b, what: str) -> None:
    """Bit-identity of two search results (stream/sweep/fleet rows)."""
    import numpy as np
    check(float(a.best_fitness) == float(b.best_fitness),
          f"{what}: best_fitness {a.best_fitness!r} != {b.best_fitness!r}")
    for field in ("best_accel", "best_prio", "history_best"):
        check(np.array_equal(np.asarray(getattr(a, field)),
                             np.asarray(getattr(b, field))),
              f"{what}: {field} differs")


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------
def smoke_trace(group_size: int = GROUP_SIZE, num: int = NUM_REQUESTS,
                seed: int = 0):
    from repro.stream import TraceConfig, generate_trace
    return generate_trace(TraceConfig(
        num_scenarios=num, arrival="poisson", group_size=group_size,
        settings=SETTINGS, mixes=MIXES, seed=seed))


def grid_fits(group_size: int = GROUP_SIZE):
    """The offline grid: every task set on S4 at one system bandwidth."""
    from repro.stream import ScenarioRequest, analyze_serial
    reqs = [ScenarioRequest(uid=i, arrival_s=0.0, mix=m, setting="S4",
                            bw_gb=GRID_BW_GB, group_size=group_size, seed=i)
            for i, m in enumerate(MIXES)]
    return [r.fit for r in analyze_serial(reqs)]


def oracle_makespan(fit, accel, prio) -> float:
    """float64 reference: decode on the host, simulate with numpy."""
    from repro.core.bw_allocator import simulate_numpy
    from repro.core.encoding import decode_to_lists
    queues = decode_to_lists(accel, prio, fit.num_accels)
    return simulate_numpy(queues, fit.table.lat, fit.table.bw, fit.bw_sys)


def device_makespan(fit, accel, prio) -> float:
    import jax.numpy as jnp
    from repro.core.bw_allocator import simulate
    return float(simulate(jnp.asarray(accel), jnp.asarray(prio),
                          fit.params.lat, fit.params.bw, fit.bw_sys,
                          fit.num_accels))


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------
def phase_served(trace, budget: int = BUDGET, max_devices: int = 1) -> dict:
    import numpy as np
    from repro.core.magma import magma_search
    from repro.costmodel import get_setting
    from repro.memo import ScheduleMemo
    from repro.stream import StreamConfig, StreamingScheduler, analyze_serial

    stream = StreamConfig(batch_rows=8, max_devices=max_devices)
    with StreamingScheduler(budget=budget, stream=stream) as svc:
        t0 = time.perf_counter()
        svc.warmup(trace)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        results = svc.run(trace)
        serve_s = time.perf_counter() - t0
        batches = len(svc.last_batches)
    check([r.request.uid for r in results] == sorted(t.uid for t in trace),
          "not every request was routed")
    for r in results:
        check(math.isfinite(r.best_fitness) and r.best_fitness > 0,
              f"request {r.request.uid}: best_fitness {r.best_fitness!r}")

    # one request per (G, A) compatibility key: standalone search + oracle
    keys = {}
    for r in results:
        A = get_setting(r.request.setting).num_sub_accels
        keys.setdefault((r.request.group_size, A), r)
    oracle = {}
    for (G, A), r in sorted(keys.items()):
        fit = analyze_serial([r.request])[0].fit
        same_row(r, magma_search(fit, budget=budget, seed=r.request.seed),
                 f"request {r.request.uid} (G={G}, A={A}) vs magma_search")
        ms64 = oracle_makespan(fit, r.best_accel, r.best_prio)
        ms32 = device_makespan(fit, r.best_accel, r.best_prio)
        err = rel_err(ms32, ms64)
        check(err <= RTOL, f"request {r.request.uid}: f32 makespan {ms32!r} "
                           f"vs oracle {ms64!r} (rel {err:.2e})")
        oracle[f"G{G}_A{A}"] = float(err)

    # memo replay: the first pass solves (and records) every request, the
    # second answers every one from the store
    memo = ScheduleMemo(near=False)
    with StreamingScheduler(budget=budget, stream=stream, memo=memo) as svc:
        first = svc.run(trace)
        second = svc.run(trace)
    for a, b, c in zip(results, first, second):
        same_row(b, a, f"request {a.request.uid}: memo-attached pass 1")
        same_row(c, a, f"request {a.request.uid}: memo replay")
    hits = sum(r.memo_exact for r in second)
    check(not any(r.memo_exact for r in first), "pass 1 hit an empty memo")
    check(hits == len(trace), f"memo replay: {hits}/{len(trace)} exact hits")
    return {"requests": len(results), "batches": batches,
            "compile_s": compile_s, "serve_s": serve_s,
            "keys_checked": len(keys), "oracle_rel_err": oracle,
            "memo_exact_hits": hits}


def phase_offline(fits, budget: int = BUDGET, seeds=SEEDS) -> dict:
    from repro.core.magma import magma_search
    from repro.core.sweep import SweepConfig, run_sweep
    t0 = time.perf_counter()
    res = run_sweep(fits, budget=budget, seeds=seeds,
                    sweep=SweepConfig(max_devices=1))
    wall_s = time.perf_counter() - t0
    for s, fit in enumerate(fits):
        for k, seed in enumerate(seeds):
            row = _grid_row(res, s, k)
            same_row(row, magma_search(fit, budget=budget, seed=seed),
                     f"grid row ({s}, seed {seed}) vs magma_search")
    return {"rows": res.rows, "wall_s_incl_compile": wall_s}


def phase_kernel(fits, budget: int = BUDGET, seeds=SEEDS,
                 interpret: bool = False) -> dict:
    """The grid through the Pallas makespan kernel.  ``interpret`` runs the
    kernel in the Pallas interpreter (tests off the TPU); the chip run
    compiles it with Mosaic and checks the HLO holds the kernel."""
    import jax
    import numpy as np
    from repro.core.bw_allocator import simulate_population
    from repro.core.encoding import random_population
    from repro.core.strategies import MagmaStrategy, plan_generations
    from repro.core.sweep import SweepConfig, row_executable, run_sweep
    from repro.kernels import ops as kops

    use_kernel = kops.INTERPRET if interpret else True
    t0 = time.perf_counter()
    res = run_sweep(fits, budget=budget, seeds=seeds, use_kernel=use_kernel,
                    sweep=SweepConfig(max_devices=1))
    wall_s = time.perf_counter() - t0
    for s, fit in enumerate(fits):
        for k in range(len(seeds)):
            row = _grid_row(res, s, k)
            check(math.isfinite(row.best_fitness) and row.best_fitness > 0,
                  f"kernel row ({s}, {k}): best_fitness {row.best_fitness!r}")
            err = rel_err(fit.table.total_flops / row.best_fitness,
                          oracle_makespan(fit, row.best_accel, row.best_prio))
            check(err <= RTOL, f"kernel row ({s}, {k}): makespan vs oracle "
                               f"rel {err:.2e}")

    custom_call = None
    if not interpret:
        fit = fits[0]
        strategy = MagmaStrategy().bind(fit.num_accels)
        gens, evolve_last = plan_generations(budget, strategy.ask_size)
        fn, _ = row_executable(strategy, gens, evolve_last, fit.group_size,
                               True, fit.objective_spec, 1)
        n = res.padded_rows
        keys = jax.ShapeDtypeStruct((n, 2), np.uint32)
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((n,) + np.shape(x),
                                           np.asarray(x).dtype), fit.params)
        hlo = fn.lower(keys, params).compile().as_text()
        custom_call = "tpu_custom_call" in hlo
        check(custom_call, "the kernel sweep's compiled HLO holds no "
                           "tpu_custom_call: the kernel did not compile")

    # one fixed population: kernel vs the jnp scan vs the oracle
    fit = fits[0]
    pop = random_population(jax.random.PRNGKey(0), 100, fit.group_size,
                            fit.num_accels)
    args = (pop.accel, pop.prio, fit.params.lat, fit.params.bw, fit.bw_sys,
            fit.num_accels)
    ker = np.asarray(kops.population_makespan(*args, interpret=interpret))
    ref = np.asarray(simulate_population(*args))
    accel, prio = np.asarray(pop.accel), np.asarray(pop.prio)
    ora = np.array([oracle_makespan(fit, accel[i], prio[i])
                    for i in range(len(accel))])
    err_jnp = float(np.max(np.abs(ker - ref) / ref))
    err_ora = float(np.max(np.abs(ker - ora) / ora))
    check(err_jnp <= RTOL, f"kernel vs simulate_population: rel {err_jnp:.2e}")
    check(err_ora <= RTOL, f"kernel vs oracle: rel {err_ora:.2e}")
    return {"rows": res.rows, "wall_s_incl_compile": wall_s,
            "tpu_custom_call": custom_call, "max_rel_vs_jnp": err_jnp,
            "max_rel_vs_oracle": err_ora}


def _grid_row(res, s: int, k: int):
    """Row (s, k) of a SweepResult with the attribute names of a result."""
    from types import SimpleNamespace
    return SimpleNamespace(best_fitness=res.best_fitness[s, k],
                           best_accel=res.best_accel[s, k],
                           best_prio=res.best_prio[s, k],
                           history_best=res.history_best[s, k])


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------
def phase_fleet(trace, budget: int = BUDGET, num_workers: int = 4,
                devices_per_worker=None) -> dict:
    """(b) serve the trace on a fleet, one worker per chip.  Touches no JAX
    device in this process: it only routes."""
    from repro.fleet import FleetConfig, launch_fleet
    cfg = FleetConfig(num_workers=num_workers,
                      devices_per_worker=devices_per_worker, budget=budget,
                      stream={"batch_rows": 8}, ready_timeout_s=240.0)
    t0 = time.perf_counter()
    with launch_fleet(cfg) as fleet:
        startup_s = time.perf_counter() - t0
        infos = [w.info for w in fleet.workers]
        t0 = time.perf_counter()
        results = fleet.run(trace)
        serve_s = time.perf_counter() - t0
    return {"results": results, "workers": infos, "startup_s": startup_s,
            "serve_s_incl_compile": serve_s}


def check_fleet(fleet: dict, trace, budget: int = BUDGET,
                platform: str = "tpu") -> dict:
    """Compare the fleet's rows with standalone searches (run after the
    fleet has closed)."""
    from repro.core.magma import magma_search
    from repro.stream import analyze_serial
    infos = fleet["workers"]
    for info in infos:
        check(info.get("platform") == platform and info.get("devices") == 1,
              f"fleet worker {info.get('worker')}: {info}")
    if platform == "tpu":
        # each worker saw one device, so the runtime honoured its pin
        chips = sorted(i.get("chip") or "" for i in infos)
        check(chips == [str(c) for c in range(len(infos))],
              f"fleet workers are not pinned one chip each: {chips}")
    results = fleet["results"]
    check([r.request.uid for r in results] == sorted(t.uid for t in trace),
          "the fleet did not route every request")
    check(not any(r.memo_exact or r.warm_seeded for r in results),
          "fleet rows came from a memo")
    for r in results:
        fit = analyze_serial([r.request])[0].fit
        same_row(r, magma_search(fit, budget=budget, seed=r.request.seed),
                 f"fleet request {r.request.uid} ({r.worker_id}) vs "
                 "magma_search")
    return {"requests": len(results),
            "per_worker": {i["worker"]: sum(r.worker_id == i["worker"]
                                            for r in results) for i in infos},
            "startup_s": fleet["startup_s"],
            "serve_s_incl_compile": fleet["serve_s_incl_compile"]}


def phase_sharded(trace, fits, budget: int = BUDGET, devices: int = 4,
                  seeds=SEEDS) -> dict:
    """(a) the served trace and the grid on ``devices`` devices, row by
    row against one device in the same process."""
    from repro.core.sweep import SweepConfig, run_sweep
    from repro.stream import StreamConfig, StreamingScheduler
    out = {}
    served = {}
    for n in (devices, 1):
        with StreamingScheduler(budget=budget, stream=StreamConfig(
                batch_rows=8, max_devices=n)) as svc:
            t0 = time.perf_counter()
            served[n] = svc.run(trace)
            out[f"serve_s_incl_compile_{n}dev"] = time.perf_counter() - t0
            used = {b.num_devices for b in svc.last_batches}
        if n > 1:
            check(max(used) == n, f"no batch used {n} devices: {used}")
    for a, b in zip(served[devices], served[1]):
        same_row(a, b, f"request {a.request.uid}: {devices} devices vs 1")
    grids = {}
    for n in (devices, 1):
        t0 = time.perf_counter()
        grids[n] = run_sweep(fits, budget=budget, seeds=seeds,
                             sweep=SweepConfig(max_devices=n))
        out[f"grid_s_incl_compile_{n}dev"] = time.perf_counter() - t0
    check(grids[devices].num_devices == devices,
          f"the grid ran on {grids[devices].num_devices} devices")
    for s in range(len(fits)):
        for k in range(len(seeds)):
            same_row(_grid_row(grids[devices], s, k), _grid_row(grids[1], s, k),
                     f"grid row ({s}, {k}): {devices} devices vs 1")
    out["requests"] = len(trace)
    out["grid_rows"] = grids[1].rows
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def run_phase(name: str, fn, failures: list):
    """Run one phase, print its outcome; a failure is recorded and the
    remaining phases still run."""
    t0 = time.perf_counter()
    try:
        info = fn()
    except Exception as e:                      # reported, then exit non-zero
        traceback.print_exc()
        failures.append(name)
        print(f"[smoke] phase {name}: FAIL after "
              f"{time.perf_counter() - t0:.1f}s: {type(e).__name__}: {e}",
              flush=True)
        return None
    shown = {k: v for k, v in (info or {}).items() if k != "results"}
    print(f"[smoke] phase {name}: ok in {time.perf_counter() - t0:.1f}s "
          f"(smoke-run times, not benchmark figures) {shown}", flush=True)
    return info


def device_summary():
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[smoke] device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths across four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"[smoke] no repro package under {SRC}: run chip_smoke.py "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    failures: list = []

    if args.four_chips:
        # the fleet goes first: its workers need the chips, and this
        # process may touch JAX only once they are gone
        from repro.fleet.launch import tpu_chip_count
        chips = tpu_chip_count()
        if chips != 4:
            print(f"[smoke] --four-chips needs a host with four TPU chips; "
                  f"found {chips}", file=sys.stderr)
            return 2
        print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
        trace = smoke_trace()
        fleet = run_phase("fleet.serve", lambda: phase_fleet(trace),
                          failures)
        device = device_summary()
        if device["platform"] != "tpu" or device["count"] != 4:
            print("[smoke] --four-chips needs a host with four TPU chips",
                  file=sys.stderr)
            return 2
        if fleet is not None:
            run_phase("fleet.check", lambda: check_fleet(fleet, trace),
                      failures)
        run_phase("sharded", lambda: phase_sharded(trace, grid_fits()),
                  failures)
    else:
        device = device_summary()
        if device["platform"] != "tpu":
            print(f"[smoke] no TPU found (JAX platform "
                  f"{device['platform']!r}); this smoke run needs a TPU chip",
                  file=sys.stderr)
            return 2
        print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
        trace = smoke_trace()
        run_phase("served", lambda: phase_served(trace), failures)
        fits = grid_fits()
        run_phase("offline", lambda: phase_offline(fits), failures)
        run_phase("kernel", lambda: phase_kernel(fits), failures)

    if failures:
        print(f"[smoke] FAILED phases: {', '.join(failures)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
