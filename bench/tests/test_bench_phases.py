"""The readers of the program's own names: loop phases and search scopes."""
import dataclasses
import os

import pytest

from benchlib import counts, phases, profile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6
TPU = "/device:TPU:0"


def served():
    """Device busy [0,2) and [5,6) of a 10 ms window.  The loop: no phase
    over [0,1) (open before the profiler), dispatch [1,2), route_wait
    [2,3), route [3,4), idle [4,8), intake [8,9.5), nothing after."""
    ops = [("fusion.1", 0.0, 2 * MS), ("fusion.2", 5 * MS, 1 * MS)]
    host = [(profile.WINDOW, 0.0, 10 * MS),
            ("stream.loop.dispatch", 1 * MS, 1 * MS),
            ("stream.loop.route_wait", 2 * MS, 1 * MS),
            ("stream.loop.route", 3 * MS, 1 * MS),
            ("stream.loop.idle", 4 * MS, 4 * MS),
            ("stream.loop.intake", 8 * MS, 1.5 * MS),
            ("analyze", 2.5 * MS, 6 * MS)]
    return profile.Trace(ops={TPU: ops}, modules={TPU: []}, host=host,
                         window=(0.0, 10 * MS))


def test_idle_splits_by_the_loop_phase():
    # gaps [2,5) and [6,10): route_wait+route 2 ms and intake 1.5 ms are
    # the host's, idle [4,5)+[6,8) 3 ms the traffic's, [9.5,10) nobody's
    split = phases.idle_split(served())
    assert split.window_s == pytest.approx(1e-2)
    assert split.idle_s == pytest.approx(7e-3)
    assert split.host_s == pytest.approx(3.5e-3)
    assert split.loop_idle_s == pytest.approx(3e-3)
    assert split.unattributed_s == pytest.approx(0.5e-3)
    assert phases.idle_host_share(served()) == pytest.approx(35.0)
    assert split.share(split.idle_s) == pytest.approx(
        profile.idle_share(served()))


def run_spans(offset_s):
    """The loop's obs spans of ``served()`` on a run clock ``offset_s``
    behind the trace's, with the phases the trace lacks: the one open
    when the profiler started, [0,1), and the one open when it stopped,
    idle from 9.5 ms on.  A chance match of the dispatch span's length
    elsewhere in the run."""
    ms = 1e-3
    spans = [("stream.loop.intake", -5 * ms, 1 * ms),
             ("stream.loop.dispatch", 1 * ms, 2 * ms),
             ("stream.loop.route_wait", 2 * ms, 3 * ms),
             ("stream.loop.route", 3 * ms, 4 * ms),
             ("stream.loop.idle", 4 * ms, 8 * ms),
             ("stream.loop.intake", 8 * ms, 9.5 * ms),
             ("stream.loop.idle", 9.5 * ms, 30 * ms),
             ("stream.loop.dispatch", 40 * ms, 41 * ms),
             ("queue_wait", 0.0, 1 * ms)]
    return [(n, a - offset_s, b - offset_s) for n, a, b in spans]


def test_clock_offset_pairs_annotations_with_spans():
    assert phases.clock_offset(served(), run_spans(2.5)) == \
        pytest.approx(2.5e9, abs=1.0)
    assert phases.clock_offset(served(), [("queue_wait", 0.0, 1.0)]) is None


def test_spans_cover_the_phases_the_trace_lacks():
    # as above, with [9.5,10) now under the idle phase that was open when
    # the profiler stopped
    split = phases.idle_split(served(), run_spans(2.5))
    assert split.host_s == pytest.approx(3.5e-3)
    assert split.loop_idle_s == pytest.approx(3.5e-3)
    assert split.unattributed_s == pytest.approx(0.0, abs=1e-12)
    assert phases.idle_host_share(served(), run_spans(2.5)) == \
        pytest.approx(35.0)
    # spans that pair with nothing leave the annotations in charge
    assert phases.idle_split(served(), [("queue_wait", 0.0, 1.0)]) == \
        phases.idle_split(served())


def test_no_loop_phase_reads_nothing():
    tr = served()
    tr.host = [e for e in tr.host if not e[0].startswith(phases.LOOP)]
    assert phases.idle_split(tr) is None
    assert phases.idle_host_share(tr) is None
    tr.window = None
    assert phases.idle_host_share(tr) is None


def searched():
    """Two runs of ``jit_search`` and one of another program.  Run one
    [0,12): an outer while [0,11) holds fusion.a [0,2) and an inner while
    [3,10) holding fusion.b [3,5) and fusion.c [6,9); copy.d [11,12)
    follows.  Run two [20,24): fusion.b [20,23), an unknown op [23,24)."""
    ops = [("%while.1", 0.0, 11 * MS), ("%fusion.a", 0.0, 2 * MS),
           ("%while.2", 3 * MS, 7 * MS), ("%fusion.b", 3 * MS, 2 * MS),
           ("%fusion.c", 6 * MS, 3 * MS), ("%copy.d", 11 * MS, 1 * MS),
           ("%fusion.x", 14 * MS, 2 * MS),
           ("%fusion.b", 20 * MS, 3 * MS), ("%fusion.z", 23 * MS, 1 * MS)]
    modules = [("jit_search(123)", 0.0, 12 * MS),
               ("jit_small(7)", 14 * MS, 2 * MS),
               ("jit_search(123)", 20 * MS, 4 * MS)]
    return profile.Trace(ops={TPU: ops}, modules={TPU: modules},
                         host=[(profile.WINDOW, 0.0, 30 * MS)],
                         window=(0.0, 30 * MS))


@dataclasses.dataclass(frozen=True)
class FakeExecutable:
    module: str
    scopes: dict
    P: int = 10
    A: int = 4
    G: int = 8
    generations: int = 5
    rows: int = 4
    devices: int = 2


SCOPES = {"while.1": "other", "fusion.a": "search.ask",
          "while.2": "fitness.events", "fusion.b": "fitness.events",
          "fusion.c": "search.eval", "copy.d": "other"}


def test_nested_while_is_counted_once():
    tr = searched()
    runs = phases.module_runs(tr, "jit_search")
    assert runs == [(0.0, 12 * MS), (20 * MS, 24 * MS)]
    names = [n for n, _, _ in phases.run_leaves(tr, runs)]
    assert names == ["%fusion.a", "%fusion.b", "%fusion.c", "%copy.d",
                     "%fusion.b", "%fusion.z"]
    secs = phases.scope_seconds(phases.run_leaves(tr, runs), SCOPES)
    assert secs == {"search.ask": pytest.approx(2e-3),
                    "fitness.events": pytest.approx(5e-3),
                    "search.eval": pytest.approx(3e-3),
                    "other": pytest.approx(2e-3)}


def test_search_scopes_read_the_busiest_row_executable(monkeypatch):
    from repro.core import sweep
    ex = FakeExecutable("jit_search", SCOPES)
    monkeypatch.setattr(sweep, "executable_scopes",
                        lambda: [FakeExecutable("jit_small", {}), ex],
                        raising=False)
    reading = phases.search_scopes(searched())
    assert reading.executable is ex and reading.runs == 2
    assert reading.total_s == pytest.approx(12e-3)
    # eval 3 ms + events 5 ms of 12 ms of leaves
    assert reading.fitness_share == pytest.approx(100 * 8 / 12)
    assert reading.named_share == pytest.approx(100 * 10 / 12)
    flops, nbytes = counts.fitness_work(10, 4, 8)
    n = (4 // 2) * 5 * 2          # rows on the first device x gens x runs
    want, _ = counts.roofline_share(flops * n, nbytes * n, 8e-3,
                                    "TPU v5 lite")
    assert reading.fitness_roofline("TPU v5 lite") == pytest.approx(want)


def test_a_program_without_scopes_reads_nothing(monkeypatch):
    from repro.core import sweep
    monkeypatch.delattr(sweep, "executable_scopes", raising=False)
    assert phases.search_scopes(searched()) is None
    monkeypatch.setattr(sweep, "executable_scopes", lambda: [],
                        raising=False)
    assert phases.search_scopes(searched()) is None
    assert phases.search_scopes(served()) is None


def test_recorded_served_trace():
    """A served run recorded whole on one TPU v5e: six realtime requests
    on S1 (G=10, 200 samples), obs on, inside ``bench.traced``.  Trimmed
    to what ``profile.load`` reads: no HLO metadata plane, operation names
    cut to the instruction.  Beside it, the run's 43 loop-phase spans."""
    import json

    from jax.profiler import ProfileData
    path = os.path.join(DATA, "served_phases.xplane.pb")
    tr = profile.load(path)
    with open(os.path.join(DATA, "served_phases.spans.json")) as f:
        spans = [tuple(sp) for sp in json.load(f)]
    assert tr.window_s == pytest.approx(0.406391986)
    names = {n for n, _, _ in tr.host if n.startswith(phases.LOOP)}
    assert names == {"stream.loop.intake", "stream.loop.dispatch",
                     "stream.loop.route_wait", "stream.loop.route",
                     "stream.loop.idle", "stream.loop.wait_analysis"}
    split = phases.idle_split(tr)
    assert split.idle_s == pytest.approx(
        split.loop_idle_s + split.host_s + split.unattributed_s)
    assert split.share(split.idle_s) == pytest.approx(
        profile.idle_share(tr))
    assert split.loop_idle_s == pytest.approx(0.323232147)
    assert split.host_s == pytest.approx(0.077765759)
    # the window opened 1.3 ms before the run's first phase
    assert split.unattributed_s == pytest.approx(0.001663568)
    # the spans land where the annotations' own run_s stats put them
    offsets = [e.start_ns - float(dict(e.stats)["run_s"]) * 1e9
               for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name.startswith(phases.LOOP)]
    assert len(offsets) == len(spans) == 43
    off = phases.clock_offset(tr, spans)
    assert min(offsets) - 1e4 <= off <= max(offsets) + 1e4
    by_spans = phases.idle_split(tr, spans)
    assert by_spans.host_s == pytest.approx(0.078040667, rel=1e-6)
    assert by_spans.unattributed_s == pytest.approx(0.001327888, rel=1e-6)
    assert phases.idle_host_share(tr, spans) == pytest.approx(
        100 * 0.078040667 / 0.406391986, rel=1e-6)
    # the device's longest idle stretches are named by the loop's phases
    gaps = profile.idle_gaps(tr)
    labels = [g[0].split(": ", 1)[1] for g in gaps]
    assert labels == ["stream.loop.idle"] * 6 + ["stream.loop.dispatch"] * 4
