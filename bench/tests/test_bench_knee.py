"""The rate sweep's reading of one window, and the served session taking
new requests between windows."""
import importlib.util
import os

import pytest

from benchlib import cells, traffic
from benchlib.runner import Run

KNEE = os.path.join(cells.BENCH, "knee.py")
CELL = cells.of_driver("served")[0]


@pytest.fixture(scope="module")
def knee():
    spec = importlib.util.spec_from_file_location("bench_knee", KNEE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def window(done, batches):
    run = Run(seconds=10.0, due=len(done))
    run.requests = [{"due_s": float(i), "done_s": d}
                    for i, d in enumerate(done)]
    run.batches = batches
    return run


def test_a_window_that_keeps_up(knee):
    cell = cells.cell(CELL)
    r = knee.row(2.0, 1, window([0.5, 1.5, 2.5, 9.0], [1, 1, 2]), cell)
    assert (r["rate_hz"], r["realisation"], r["offered"]) == (2.0, 1, 4)
    assert r["backlog_at_end"] == 0 and r["routed_by_end"] == 4
    assert r["p50_s"] == pytest.approx(0.5)
    assert r["p90_s"] == pytest.approx(6.0)
    assert r["rows_per_batch"] == pytest.approx(4 / 3)
    assert r["multi_row_share"] == pytest.approx(1 / 3)
    assert r["keeps_up"]


@pytest.mark.parametrize("late,keeps_up", [(16, True), (17, False)])
def test_backlog_beyond_the_pipeline_does_not_keep_up(knee, late,
                                                      keeps_up):
    cell = cells.cell(CELL)      # 2 batches of 8 rows in flight
    done = [0.5] * 4 + [12.0] * (late - 1) + [None]
    r = knee.row(3.0, 0, window(done, [8] * 4), cell)
    assert r["backlog_at_end"] == late
    assert r["keeps_up"] is keeps_up


def test_the_served_session_serves_offered_requests():
    cell = cells.cell(CELL)
    session = cells.driver("served").Session(cell, 7, 51.0, False, None)
    assert len(session.requests) == round(cell.traffic["rate_hz"] * 51.0)
    other = traffic.requests(dict(cell.traffic, rate_hz=1.0), cell.config,
                             7, 51.0, realisation=3)
    session.offer(other)
    assert session.requests == other
    assert [(q.uid, q.arrival_s, q.setting, q.seed) for q in session.sreqs] \
        == [(r["uid"], r["due_s"], r["setting"], r["seed"]) for r in other]
