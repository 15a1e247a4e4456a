"""The comparison fails what it must: the control (the reference in
bfloat16 in the system's place) and each fault planted underneath the
timed path, on both drivers; and passes a sound run.  The runs skip the
look for a chip and run at the configuration's own sizes (G=100, 10,000
samples) on a few requests."""
import tempfile
import time

import numpy as np
import pytest

from benchlib import cells, check, faults, runner, traffic


def offline_cell():
    cell = cells.cell(cells.of_driver("offline")[0])
    cell.traffic = dict(cell.traffic, mixes=["Lang", "Recom"],
                        bw_gb=[16, 64], seeds_per_scenario=2)
    return cell


def served_cell():
    cell = cells.cell(cells.of_driver("served")[0])
    cell.config = dict(cell.config, settings=["S4"],
                       stream=dict(cell.config["stream"], batch_rows=2))
    cell.traffic = dict(cell.traffic, rate_hz=2.0, mixes=["Lang", "Recom"],
                        bw_gb=[16, 64])
    return cell


@pytest.fixture(autouse=True)
def arrive_together(monkeypatch):
    """The served runs' four requests arrive at once, so the service
    dispatches 2-row batches."""
    monkeypatch.setattr(traffic, "_arrivals",
                        lambda t, n, seconds, rng: np.zeros(n))


CELLS = {"offline": (offline_cell, 0.1), "served": (served_cell, 2.0)}


def numbers(kind, seed, control=None):
    make, seconds = CELLS[kind]
    cell = make()
    with tempfile.TemporaryDirectory() as d:
        m = runner.measure(cell, seed, seconds, False, time.perf_counter(), d)
    got = check.Judge().compare(m.run.answers, m.run.due,
                                control_dtype=check.control_dtype(control))
    return got, cell.config["limits"], m.run


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct_and_its_control_is_not(kind):
    got, limits, run = numbers(kind, 5)
    assert run.due >= 4 and got["missing"] == 0
    assert check.verdict(got, limits), got
    ctl = check.Judge().compare(run.answers, run.due,
                                control_dtype=check.control_dtype("bfloat16"))
    assert ctl["fitness_gap"] > limits["fitness_gap"], ctl
    assert not check.verdict(ctl, limits)


# which number each fault has to fail
FAILS = {"unchanged": "mean_shortfall", "half_batch": "fitness_gap",
         "altered": "fitness_gap"}


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(kind, fault):
    with faults.planted(fault):
        got, limits, _ = numbers(kind, 6)
    assert got[FAILS[fault]] > limits[FAILS[fault]], got
    assert not check.verdict(got, limits)
