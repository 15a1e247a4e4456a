"""The reduction from a device trace to numbers."""
import os

import pytest

from benchlib import profile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic():
    ms = 1e6
    ops = [("fusion.1", 0 * ms, 2 * ms), ("fusion.2", 1 * ms, 2 * ms),
           ("while.3", 5 * ms, 1 * ms), ("fusion.1", 8 * ms, 4 * ms)]
    host = [(profile.WINDOW, 0.0, 10 * ms), ("bench.call", 2.5 * ms, 3 * ms),
            ("ExecuteSharded", 6.2 * ms, 1.7 * ms)]
    return profile.Trace(ops={"/device:TPU:0": ops},
                         modules={"/device:TPU:0": [
                             ("jit_search", 0.0, 6 * ms),
                             ("jit_search", 8 * ms, 4 * ms),
                             ("jit_small", 6.5 * ms, 0.5 * ms)]},
                         host=host, window=(0.0, 10 * ms))


def test_busy_is_the_union_clipped_to_the_window():
    tr = synthetic()
    # [0,3) + [5,6) + [8,10) = 6 ms of the 10 ms window
    assert profile.busy_s(tr) == pytest.approx(6e-3)
    assert profile.idle_share(tr) == pytest.approx(40.0)
    assert tr.window_s == pytest.approx(1e-2)


def test_top_ops_and_gaps():
    tr = synthetic()
    assert profile.top_ops(tr, 2) == [["fusion.1", pytest.approx(4e-3)],
                                      ["fusion.2", pytest.approx(2e-3)]]
    gaps = profile.idle_gaps(tr)
    assert [g[1] for g in gaps] == [pytest.approx(2e-3),
                                    pytest.approx(2e-3)]
    assert gaps[0][0] == "idle while host: bench.call"
    assert gaps[1][0] == "idle while host: ExecuteSharded"


def test_programs_by_name():
    tr = synthetic()
    # runs that started inside the window count whole
    assert profile.module_time(tr, lambda n: n == "jit_search") == (
        pytest.approx(10e-3), 2)
    assert profile.busiest_program_ms(tr) == pytest.approx(5.0)
    late = profile.Trace(ops=tr.ops, modules=tr.modules, host=tr.host,
                         window=(1e6, 10e6))
    assert profile.module_time(late, lambda n: n == "jit_search") == (
        pytest.approx(4e-3), 1)


def test_no_window_reads_nothing():
    tr = profile.Trace(ops={}, modules={}, host=[], window=None)
    assert profile.busy_s(tr) is None and profile.idle_share(tr) is None
    assert profile.top_ops(tr) == [] and profile.idle_gaps(tr) == []
    assert profile.busiest_program_ms(tr) is None


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: two small jitted programs, three
    runs each with a 2 ms host sleep between, inside ``bench.traced``."""
    tr = profile.load(os.path.join(DATA, "tiny.xplane.pb"))
    assert list(tr.ops) == ["/device:TPU:0"]
    assert len(tr.ops["/device:TPU:0"]) == 36
    assert tr.window_s == pytest.approx(0.01292174)
    assert profile.busy_s(tr) == pytest.approx(2.0182e-05)
    assert 99.8 < profile.idle_share(tr) < 99.9
    top = profile.top_ops(tr, 2)
    assert [t[0] for t in top] == ["%while", "%multiply_add_fusion.2"]
    # the host slept in the longest gaps; the Python tracer says so
    gaps = profile.idle_gaps(tr, 3)
    assert all(g[0] == "idle while host: $time sleep" for g in gaps)
    assert gaps[0][1] == pytest.approx(0.003614612)
    assert profile.module_time(tr, lambda n: n.startswith("jit__lambda")) \
        == (pytest.approx(2.0221e-05), 5)
    assert profile.busiest_program_ms(tr) == pytest.approx(0.007955)
