"""Device traces: capture with ``jax.profiler`` and reduce to numbers.

``load`` reads the newest ``*.xplane.pb`` under a log directory with
``jax.profiler.ProfileData`` into plain event lists; everything after it
is pure arithmetic over ``(name, start_ns, dur_ns)`` tuples, checked on a
small recorded chip trace under ``bench/tests/data/``.

Conventions:

- device planes are those named ``/device:TPU:<n>``; their ``XLA Ops``
  line holds one event per operation executed (a ``while`` op's event
  spans the events of its body's operations, every iteration included),
  named here by the HLO instruction alone; their ``XLA Modules`` line
  holds one event per program run;
- host events are the runtime's and the benchmark's annotations (the
  Python tracer is off, see ``start``); they name what the host did in
  a device gap;
- the traced window is the host annotation ``bench.traced``, which the
  benchmark opens right after the profiler starts and closes right
  before it stops; its length is ``window_s``;
- busy time is the union of a device's operation intervals inside the
  window, averaged over the devices used.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, dur_ns)

WINDOW = "bench.traced"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]           # device plane -> XLA Ops events
    modules: Dict[str, List[Event]]       # device plane -> XLA Modules
    host: List[Event]                     # host events of every thread
    window: Optional[Tuple[float, float]]  # (start_ns, end_ns)

    @property
    def window_s(self) -> Optional[float]:
        return None if self.window is None else \
            (self.window[1] - self.window[0]) * 1e-9


def short(name: str) -> str:
    """An operation's HLO instruction name (``%while.43``) without the
    instruction text the trace gives with it."""
    return name.split(" = ", 1)[0]


def start(log_dir: str) -> None:
    """Start the profiler without its Python tracer: the served loop and
    the analysis threads would record millions of host events, and a
    trace is read after the window, inside the run's time limit."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def newest_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest under a log directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = newest_xplane(path)
        if path is None:
            raise FileNotFoundError("no .xplane.pb under the log directory")
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = [(short(e.name), float(e.start_ns),
                        float(e.duration_ns)) for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events)
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    window = (min(w[0] for w in wins), max(w[1] for w in wins)) \
        if wins else None
    return Trace(ops=ops, modules=modules, host=host, window=window)


def _clip(events: List[Event], window) -> List[Tuple[float, float]]:
    lo, hi = window
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s + d > lo and s < hi]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds of the window in which an operation ran, averaged over the
    devices that ran any."""
    if trace.window is None:
        return None
    per = [sum(e - s for s, e in union(_clip(evs, trace.window)))
           for evs in trace.ops.values() if evs]
    return sum(per) / len(per) * 1e-9 if per else None


def top_ops(trace: Trace, k: int = 10) -> List[list]:
    """The ``k`` operation names with the most device time in the window
    (seconds, summed over devices)."""
    if trace.window is None:
        return []
    tot: Dict[str, float] = defaultdict(float)
    lo, hi = trace.window
    for evs in trace.ops.values():
        for n, s, d in evs:
            if s + d > lo and s < hi:
                tot[n] += min(s + d, hi) - max(s, lo)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v * 1e-9] for n, v in best]


def idle_gaps(trace: Trace, k: int = 10) -> List[list]:
    """The ``k`` longest stretches of the window with no operation on the
    first device, each named by the host events that overlap it most
    (the benchmark's annotations and the runtime's own)."""
    if trace.window is None or not trace.ops:
        return []
    lo, hi = trace.window
    evs = trace.ops[sorted(trace.ops)[0]]
    busy = union(_clip(evs, trace.window))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:k]:
        out.append([f"idle while host: {label(trace.host, gs, ge)}",
                    (ge - gs) * 1e-9])
    return out


def label(host: List[Event], lo: float, hi: float) -> str:
    """The host event names that cover most of ``[lo, hi)``, innermost
    (shortest) first, at most three."""
    cover = []
    for n, s, d in host:
        ov = min(s + d, hi) - max(s, lo)
        if ov > 0.5 * (hi - lo) and n != WINDOW:
            cover.append((d, n))
    names = []
    for _, n in sorted(cover):
        if n not in names:
            names.append(n)
    return " < ".join(names[:3]) if names else "no host event"


def module_time(trace: Trace, match) -> Tuple[float, int]:
    """(seconds, runs) of the runs of programs whose name ``match``
    accepts that started inside the window, on the first device; a run is
    recorded once it has ended, before the profiler stopped."""
    if not trace.modules:
        return 0.0, 0
    lo, hi = trace.window if trace.window else (-float("inf"), float("inf"))
    evs = trace.modules[sorted(trace.modules)[0]]
    sel = [d for n, s, d in evs if match(n) and lo <= s < hi]
    return sum(sel) * 1e-9, len(sel)


def idle_share(trace: Trace) -> Optional[float]:
    """Percent of the window in which no operation ran on the device."""
    busy = busy_s(trace)
    if busy is None or not trace.window_s:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)


def busiest_program_ms(trace: Trace) -> Optional[float]:
    """Device milliseconds per run of the program with the most device
    time in the window, on the first device."""
    if not trace.modules or trace.window is None:
        return None
    totals: Dict[str, float] = defaultdict(float)
    for name, _, d in trace.modules[sorted(trace.modules)[0]]:
        totals[name] += d
    if not totals:
        return None
    busiest = max(totals, key=totals.get)
    secs, runs = module_time(trace, lambda n: n == busiest)
    return 1e3 * secs / runs if runs else None
