"""Offline driver: a closed loop of ``repro.core.sweep.run_sweep`` calls.

Set-up analyzes the grid (every task set x bandwidth of the traffic, on
each setting of the configuration, each with a job-group layout seed
drawn from the run's seed) with the system's own analyzer, then makes
one grid call to compile.  The window runs grid calls back to back, each
with ``seeds_per_scenario`` fresh search seeds, and starts no call after
``seconds``; its work is every row of every call.

With ``trace`` the profiler records the window's second and third calls.
Afterwards the fitness entry point that the configuration selects
(``simulate_population``, or ``kernels.ops.population_makespan`` with
``use_kernel``) is timed alone on a random population of the search's
own shape and the first scenario's tables, for the fitness roofline.
"""
from __future__ import annotations

import tempfile
import time

import numpy as np

from benchlib import counts, profile, traffic
from benchlib.runner import Run

PROBE_CALLS = 20


class Session:
    def __init__(self, cell, seed, seconds, trace, log_dir):
        self.cell, self.seconds, self.trace = cell, seconds, trace
        self.log_dir = log_dir
        self.rng = np.random.default_rng(seed)
        self.scenarios = traffic.grid(cell.traffic, cell.config)
        for s in self.scenarios:
            s["layout_seed"] = int(self.rng.integers(0, traffic.SEED_RANGE))
        self.k = int(cell.traffic["seeds_per_scenario"])

    def setup(self):
        from repro.stream import ScenarioRequest, analyze_serial
        reqs = [ScenarioRequest(uid=i, arrival_s=0.0, mix=s["mix"],
                                setting=s["setting"], bw_gb=s["bw_gb"],
                                group_size=s["group_size"],
                                seed=s["layout_seed"])
                for i, s in enumerate(self.scenarios)]
        t0 = time.perf_counter()
        self.fits = [r.fit for r in analyze_serial(reqs)]
        analysis_s = time.perf_counter() - t0
        # one run_sweep call per setting: a call's rows share (G, A)
        self.groups = {}
        for i, s in enumerate(self.scenarios):
            self.groups.setdefault(s["setting"], []).append(i)
        t0 = time.perf_counter()
        self._call(traffic.call_seeds(self.rng, self.k))   # compiles
        self.setup_parts = {"analysis_s": analysis_s,
                            "first_call_s": time.perf_counter() - t0}

    def _call(self, seeds):
        from repro.core.sweep import SweepConfig, run_sweep
        cfg = self.cell.config
        out = []
        for idx in self.groups.values():
            res = run_sweep([self.fits[i] for i in idx], budget=cfg["budget"],
                            seeds=seeds, strategy=cfg["strategy"],
                            use_kernel=bool(cfg.get("use_kernel")),
                            sweep=SweepConfig(max_devices=self.cell.chips))
            out.append((idx, res))
        return out

    def window(self) -> Run:
        import jax
        run = Run(seconds=self.seconds, calls=[])
        annotation = None
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < self.seconds:
            n = len(run.calls)
            if self.trace and n == 1:
                profile.start(self.log_dir)
                annotation = jax.profiler.TraceAnnotation(profile.WINDOW)
                annotation.__enter__()
            seeds = traffic.call_seeds(self.rng, self.k)
            start = time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("bench.call"):
                out = self._call(seeds)
            end = time.perf_counter() - t0
            if annotation is not None and n == 2:
                annotation.__exit__(None, None, None)
                annotation = None
                jax.profiler.stop_trace()
            evals = 0
            for idx, res in out:
                evals += res.rows * res.n_samples
                for si, i in enumerate(idx):
                    for k in range(len(seeds)):
                        run.answers.append({
                            "request": self.scenarios[i],
                            "fitness": float(res.best_fitness[si, k]),
                            "accel": res.best_accel[si, k],
                            "prio": res.best_prio[si, k]})
            run.calls.append({"start_s": start, "end_s": end,
                              "evals": evals})
        if annotation is not None:
            annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
        run.due = len(run.answers)
        if self.trace:
            run.trace = profile.load(self.log_dir)
            run.probe = self.probe
        return run

    def probe(self):
        """(seconds per population evaluation on the device, operations,
        bytes) of the fitness entry point, from its own trace."""
        import jax
        import jax.numpy as jnp
        from repro.core.bw_allocator import simulate_population
        from repro.kernels import ops as kops
        from repro.core.strategies import get_strategy
        fit = self.fits[0]
        G, A = fit.group_size, fit.num_accels
        P = get_strategy(self.cell.config["strategy"]).ask_size
        rng = np.random.default_rng(0)
        accel = jnp.asarray(rng.integers(0, A, (P, G)), jnp.int32)
        prio = jnp.asarray(rng.random((P, G)), jnp.float32)
        kernel = (kops.population_makespan
                  if self.cell.config.get("use_kernel")
                  else simulate_population)

        @jax.jit
        def fitness_probe(accel, prio, lat, bw, bw_sys):
            return kernel(accel, prio, lat, bw, bw_sys, A)

        args = (accel, prio, fit.params.lat, fit.params.bw,
                fit.params.bw_sys)
        fitness_probe(*args).block_until_ready()
        with tempfile.TemporaryDirectory() as d:
            profile.start(d)
            with jax.profiler.TraceAnnotation(profile.WINDOW):
                for _ in range(PROBE_CALLS):
                    fitness_probe(*args).block_until_ready()
            jax.profiler.stop_trace()
            tr = profile.load(d)
        secs, runs = profile.module_time(tr, lambda n: "fitness_probe" in n)
        if runs == 0:
            return None
        flops, nbytes = counts.fitness_work(P, A, G)
        return secs / runs, flops, nbytes

    def close(self):
        self.fits = None
