"""The program's own names in a device trace, reduced to numbers.

Two sources, both written by the system under test:

- the served loop's phases: spans named ``stream.loop.*``
  (``repro.stream.service``), which tile the main loop's time; a device
  gap under ``stream.loop.idle`` is the traffic's, a gap under any other
  phase is the host's (work pending, the loop busy or waiting).  Each is
  a host annotation in the trace, save the one still open when the
  profiler stopped (and one open when it started, until the loop next
  looks); the run's obs spans hold them all, and are put on the trace's
  clock by matching them to the annotations (``clock_offset``);
- the search's named scopes: ``repro.core.sweep.executable_scopes()``
  maps each HLO instruction of a row executable that ran in this process
  to ``search.ask`` / ``search.eval`` / ``search.tell`` /
  ``fitness.decode`` / ``fitness.tables`` / ``fitness.events`` or
  ``other``.

Device time of an executable is counted over its *leaf* operations: on
the ``XLA Ops`` line a ``while``, ``call`` or ``conditional`` event spans
the events of its body, so an event that contains another is not counted.
A program without these names (one older than them) gives ``None``
everywhere, never an error.
"""
from __future__ import annotations

import dataclasses
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from benchlib import counts, profile

LOOP = "stream.loop."
LOOP_IDLE = "stream.loop.idle"
MATCH_NS = 50e3          # an annotation and its span agree in length
FITNESS = ("search.eval", "fitness.decode", "fitness.tables",
           "fitness.events")

Interval = Tuple[float, float]


def _first_device(trace: profile.Trace):
    return trace.ops[sorted(trace.ops)[0]] if trace.ops else []


def _within(events, window) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s + d > lo and s < hi]


def gaps(trace: profile.Trace) -> List[Interval]:
    """Stretches of the window with no operation on the first device."""
    lo, hi = trace.window
    out, t = [], lo
    for s, e in profile.union(_within(_first_device(trace), trace.window)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


@dataclasses.dataclass
class IdleSplit:
    """Seconds of the window with the first device idle, by the main
    loop's phase at the time."""
    window_s: float
    idle_s: float                 # all device idle time in the window
    loop_idle_s: float            # under stream.loop.idle: nothing due
    host_s: float                 # under any other stream.loop.* phase
    unattributed_s: float         # under no phase (profiler edges)

    def share(self, seconds: float) -> float:
        return 100.0 * seconds / self.window_s


def clock_offset(trace: profile.Trace, spans: Sequence[tuple]
                 ) -> Optional[float]:
    """Trace nanoseconds minus run-clock nanoseconds, from the loop-phase
    annotations and the obs spans ``(name, start_s, end_s)`` of the same
    name and length: every true pair gives the same offset, chance pairs
    scatter.  ``None`` when nothing pairs."""
    by_name: Dict[str, list] = defaultdict(list)
    for n, a, b in spans:
        if n.startswith(LOOP):
            by_name[n].append((a * 1e9, (b - a) * 1e9))
    cands = [s - a for n, s, d in trace.host if n.startswith(LOOP)
             for a, dur in by_name.get(n, ()) if abs(dur - d) < MATCH_NS]
    if not cands:
        return None
    best = Counter(round(c * 1e-6) for c in cands).most_common(1)[0][0]
    near = sorted(c for c in cands if abs(c * 1e-6 - best) <= 1.0)
    return near[len(near) // 2]


def idle_split(trace: profile.Trace, spans: Optional[Sequence[tuple]] = None
               ) -> Optional[IdleSplit]:
    """The device's idle time split by loop phase; ``None`` without a
    window, a device, or any loop phase in the window.  With the run's
    obs ``spans`` the phases are those spans on the trace's clock (all of
    them); without, the trace's annotations."""
    if trace.window is None or not trace.ops:
        return None
    phases = [e for e in trace.host if e[0].startswith(LOOP)]
    off = clock_offset(trace, spans) if spans else None
    if off is not None:
        phases = [(n, a * 1e9 + off, (b - a) * 1e9) for n, a, b in spans
                  if n.startswith(LOOP)]
    if not _within(phases, trace.window):
        return None
    idle = profile.union(_within(
        [e for e in phases if e[0] == LOOP_IDLE], trace.window))
    busy = profile.union(_within(
        [e for e in phases if e[0] != LOOP_IDLE], trace.window))
    g = gaps(trace)
    total = sum(e - s for s, e in g)
    loop_idle, host = overlap(g, idle), overlap(g, busy)
    return IdleSplit(window_s=(trace.window[1] - trace.window[0]) * 1e-9,
                     idle_s=total * 1e-9, loop_idle_s=loop_idle * 1e-9,
                     host_s=host * 1e-9,
                     unattributed_s=max(0.0, total - loop_idle - host) * 1e-9)


def idle_host_share(trace: profile.Trace,
                    spans: Optional[Sequence[tuple]] = None
                    ) -> Optional[float]:
    """Percent of the window in which the first device ran nothing while
    the main loop was in a phase other than ``stream.loop.idle``."""
    split = idle_split(trace, spans)
    return None if split is None else split.share(split.host_s)


def leaves(events) -> list:
    """The events that contain no other event (their body's events)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for k, (n, s, d) in enumerate(evs):
        nxt = evs[k + 1] if k + 1 < len(evs) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d:
            continue                          # a container: skip
        out.append((n, s, d))
    return out


def module_runs(trace: profile.Trace, module: str) -> List[Interval]:
    """Runs of the program ``module`` (its name before the program id)
    that started inside the window, on the first device."""
    if not trace.modules or trace.window is None:
        return []
    lo, hi = trace.window
    evs = trace.modules[sorted(trace.modules)[0]]
    return [(s, s + d) for n, s, d in evs
            if n.split("(", 1)[0] == module and lo <= s < hi]


def run_leaves(trace: profile.Trace, runs: List[Interval]) -> list:
    """Leaf operations of the first device inside the given runs."""
    if not runs:
        return []
    runs = sorted(runs)
    inside, i = [], 0
    for ev in sorted(_first_device(trace), key=lambda e: e[1]):
        while i < len(runs) and runs[i][1] <= ev[1]:
            i += 1
        if i < len(runs) and runs[i][0] <= ev[1]:
            inside.append(ev)
    return leaves(inside)


def scope_seconds(ops, scopes: Dict[str, str]) -> Dict[str, float]:
    """Seconds of leaf operations by named scope (``other`` for an
    operation the map does not name)."""
    out: Dict[str, float] = defaultdict(float)
    for n, _, d in ops:
        out[scopes.get(n.lstrip("%"), "other")] += d * 1e-9
    return dict(out)


@dataclasses.dataclass
class ScopeReading:
    """The row executable's leaf device time in the window by scope."""
    executable: object            # repro.core.sweep.ExecutableScopes
    runs: int                     # its runs that started in the window
    seconds: Dict[str, float]     # scope -> leaf seconds

    @property
    def total_s(self) -> float:
        return sum(self.seconds.values())

    @property
    def fitness_s(self) -> float:
        return sum(self.seconds.get(k, 0.0) for k in FITNESS)

    @property
    def fitness_share(self) -> Optional[float]:
        return 100.0 * self.fitness_s / self.total_s if self.total_s else None

    @property
    def named_share(self) -> Optional[float]:
        named = self.total_s - self.seconds.get("other", 0.0)
        return 100.0 * named / self.total_s if self.total_s else None

    def fitness_roofline(self, device_kind: str) -> Optional[float]:
        """Percent of the roofline: the fitness work of every population
        evaluation in the window's runs (``counts.fitness_work`` x rows
        on the first device x generations x runs) over its leaf time."""
        e = self.executable
        if not self.fitness_s:
            return None
        flops, nbytes = counts.fitness_work(e.P, e.A, e.G)
        n = (e.rows // e.devices) * e.generations * self.runs
        share, _ = counts.roofline_share(flops * n, nbytes * n,
                                         self.fitness_s, device_kind)
        return share


def search_scopes(trace: profile.Trace) -> Optional[ScopeReading]:
    """The busiest program of the window read by its named scopes, when
    it is a row executable that ``executable_scopes`` knows."""
    if trace.window is None or not trace.modules:
        return None
    try:
        from repro.core.sweep import executable_scopes
    except ImportError:                   # a program without the scopes
        return None
    totals: Dict[str, float] = defaultdict(float)
    lo, hi = trace.window
    for n, s, d in trace.modules[sorted(trace.modules)[0]]:
        if lo <= s < hi:
            totals[n.split("(", 1)[0]] += d
    if not totals:
        return None
    module = max(totals, key=totals.get)
    runs = module_runs(trace, module)
    ops = run_leaves(trace, runs)
    best = None
    for ex in executable_scopes():
        if ex.module != module:
            continue
        known = sum(d for n, _, d in ops if n.lstrip("%") in ex.scopes)
        if best is None or known > best[0]:
            best = (known, ex)
    if best is None or not ops:
        return None
    ex = best[1]
    return ScopeReading(executable=ex, runs=len(runs),
                        seconds=scope_seconds(ops, ex.scopes))
