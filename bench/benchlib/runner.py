"""One run of one cell: set-up, the measured window, the metrics, the
comparison with the reference, and the result line.

A driver (``bench/drivers/<name>.py``) exposes ``Session(cell, seed,
seconds, trace, log_dir)`` with ``setup()``, ``window() -> Run`` and
``close()``, and may note the parts of its set-up in ``setup_parts``.
Metric readers get the ``Run``.  Besides the contract's keys the result
carries ``compiles_in_window`` (compilations or persistent-cache loads
while the window ran; 0 when every shape was warmed) and
``setup_parts`` (seconds from process start to the driver's set-up, and
the parts the driver notes).
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, List, Optional

from benchlib import cells, check, profile


@dataclasses.dataclass
class Run:
    """What one window left behind, for the metric readers and the check.

    Times of ``requests``, ``calls`` and ``spans`` are seconds from the
    window's start."""
    seconds: float
    due: int = 0                          # requests or rows due
    answers: List[dict] = dataclasses.field(default_factory=list)
    requests: Optional[List[dict]] = None  # served: due_s, analysis_s, done_s
    batches: Optional[List[int]] = None    # served: real rows per dispatch
    calls: Optional[List[dict]] = None     # offline: start_s, end_s, evals
    spans: Optional[List[tuple]] = None    # obs spans: (name, start, end)
    trace: Optional[profile.Trace] = None  # the traced sub-window
    probe: Optional[Callable] = None       # () -> fitness kernel reading
    setup_s: float = math.nan
    device_kind: str = ""


class CompileCounter:
    """Counts compilations and persistent-cache loads while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.count = 0
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _bump(self, event):
        if self.armed and event in self.EVENTS:
            with self._lock:
                self.count += 1

    def _duration(self, event, duration, **kw):
        self._bump(event)

    def _event(self, event, **kw):
        self._bump(event)


def device_info(trace_run: Optional[Run] = None) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
    if trace_run is not None and trace_run.trace is not None:
        info["busy_s"] = profile.busy_s(trace_run.trace)
        info["window_s"] = trace_run.trace.window_s
    return info


@dataclasses.dataclass
class Measured:
    """A run's window and readings, before the check."""
    run: Run
    metrics: dict
    device: dict
    breakdown: Optional[dict]
    compiles: int
    setup_parts: dict


def measure(cell: cells.Cell, seed: int, seconds: float, trace: bool,
            t_start: float, log_dir: str, bench: str = cells.BENCH
            ) -> Measured:
    """Set up, run the window, read the metrics; free the program's state."""
    import jax
    drv = cells.driver(cell.traffic["driver"], bench)
    readers = {m["name"]: cells.metric(m["name"], bench)
               for m in cell.metrics(trace)}
    counter = CompileCounter()
    session = drv.Session(cell, seed, seconds, trace, log_dir)
    before_setup_s = time.perf_counter() - t_start
    try:
        with jax.profiler.TraceAnnotation("bench.setup"):
            session.setup()
        counter.armed = True
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            run = session.window()
        counter.armed = False
        run.setup_s = t0 - t_start
        run.device_kind = jax.devices()[0].device_kind
        device = device_info(run if trace else None)
        metrics = {}
        for m in cell.metrics(trace):
            value = readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        breakdown = None
        if trace and run.trace is not None:
            breakdown = {"device_ops": profile.top_ops(run.trace),
                         "idle_gaps": profile.idle_gaps(run.trace)}
    finally:
        counter.armed = False
        session.close()
    parts = dict(before_setup_s=before_setup_s,
                 **getattr(session, "setup_parts", {}))
    return Measured(run, metrics, device, breakdown, counter.count, parts)


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log_dir: str, bench: str = cells.BENCH,
             say=print) -> dict:
    """Run ``cell`` once and return the result line's object."""
    m = measure(cell, seed, seconds, trace, t_start, log_dir, bench)
    say(f"compiles or cache loads inside the window: {m.compiles}")
    limits = cell.config["limits"]
    numbers = check.Judge().compare(m.run.answers, m.run.due)
    result = {"correct": check.verdict(numbers, limits),
              "attempted": m.run.due, "failed": int(numbers["missing"]),
              "metrics": m.metrics, "device": m.device}
    if m.breakdown is not None:
        result["breakdown"] = m.breakdown
    result["compiles_in_window"] = m.compiles
    result["setup_parts"] = m.setup_parts
    # the compared numbers come last, here and on standard error
    result["compared"] = {k: {"value": numbers[k], "limit": limits[k]}
                          for k in check.NUMBERS}
    return result
