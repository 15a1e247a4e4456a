"""Served driver: an open loop into ``repro.stream.StreamingScheduler``.

The traffic's requests (``benchlib.traffic.requests``) go to one
``StreamingScheduler.run`` with ``realtime=True``: each request's
``due_s`` is its arrival time on the service's run clock, which starts at
the window's start, so a stalled service meets its arrivals late and the
latency counts the wait.  Set-up builds the service from the
configuration and calls ``warmup`` with the run's own requests, which
compiles every (key, bucket) executable this traffic can reach.  The run
returns when every request is routed; the window's work is every request
due in ``[0, seconds)``.

``offer`` replaces the requests between windows of one session, so the
rate sweep (``bench/knee.py``) warms the service once.

With ``trace`` the service records its ``repro.obs`` spans over the
whole window, and the profiler records a sub-window of
``TRACE_SECONDS`` centred in it, from a timer thread: every search run
in it leaves some hundred thousand operation events, and the trace is
read inside the run's time limit.
"""
from __future__ import annotations

import math
import threading
import time

from benchlib import profile, traffic
from benchlib.runner import Run

TRACE_SECONDS = 2.0


class Session:
    def __init__(self, cell, seed, seconds, trace, log_dir):
        self.cell, self.seconds, self.trace = cell, seconds, trace
        self.log_dir = log_dir
        self.requests = traffic.requests(cell.traffic, cell.config, seed,
                                         seconds)
        self.svc = None

    def offer(self, requests):
        """Serve ``requests`` (``benchlib.traffic.requests`` dicts) in the
        next window."""
        from repro.stream import ScenarioRequest
        self.requests = requests
        self.sreqs = [ScenarioRequest(
            uid=r["uid"], arrival_s=r["due_s"], mix=r["mix"],
            setting=r["setting"], bw_gb=r["bw_gb"],
            group_size=r["group_size"], seed=r["seed"]) for r in requests]

    def setup(self):
        from repro.stream import StreamConfig, StreamingScheduler
        cfg = self.cell.config
        if cfg.get("use_kernel"):
            raise ValueError("the served path has no kernel switch; a "
                             "served configuration runs the jnp fitness")
        stream = StreamConfig(**cfg["stream"], realtime=True,
                              max_devices=self.cell.chips,
                              obs={"enabled": True} if self.trace else None)
        self.svc = StreamingScheduler(
            strategy=cfg["strategy"], budget=cfg["budget"], stream=stream)
        self.offer(self.requests)
        t0 = time.perf_counter()
        self.svc.warmup(self.sreqs)
        self.setup_parts = {"warmup_s": time.perf_counter() - t0}

    def _profile_sub_window(self) -> threading.Thread:
        import jax
        length = TRACE_SECONDS
        start_at = max(0.0, self.seconds / 2 - length / 2)

        def capture():
            time.sleep(start_at)
            profile.start(self.log_dir)
            with jax.profiler.TraceAnnotation(profile.WINDOW):
                time.sleep(length)
            jax.profiler.stop_trace()

        thread = threading.Thread(target=capture, name="bench-profiler")
        thread.start()
        return thread

    def window(self) -> Run:
        capture = self._profile_sub_window() if self.trace else None
        try:
            results = self.svc.run(self.sreqs)
        finally:
            if capture is not None:
                capture.join()     # the capture ends on its own timer
        by_uid = {r.request.uid: r for r in results}
        run = Run(seconds=self.seconds, due=len(self.requests))
        run.requests = []
        for q in self.requests:
            r = by_uid.get(q["uid"])
            ok = r is not None and math.isfinite(r.best_fitness)
            run.requests.append({
                "due_s": q["due_s"],
                "analysis_s": r.analysis_start_s if r else None,
                "done_s": r.done_s if ok else None})
            if r is not None:
                run.answers.append({
                    "request": dict(q, layout_seed=q["seed"]),
                    "fitness": float(r.best_fitness),
                    "accel": r.best_accel, "prio": r.best_prio})
        run.batches = [b.rows for b in self.svc.last_batches]
        if self.trace:
            run.spans = [(s.name, s.start_s, s.end_s)
                         for s in self.svc.tracer.spans()]
            run.trace = profile.load(self.log_dir)
        return run

    def close(self):
        if self.svc is not None:
            self.svc.close()
            self.svc = None
