"""The plain reference against the system's own float64 oracle and job
analysis (the two must describe the same problem)."""
import ml_dtypes
import numpy as np
import pytest

from benchlib.reference import Problem, group_layers


def program_fit(mix, setting, bw, G, seed):
    from repro.stream import ScenarioRequest, analyze_serial
    req = ScenarioRequest(uid=0, arrival_s=0.0, mix=mix, setting=setting,
                          bw_gb=bw, group_size=G, seed=seed)
    return analyze_serial([req])[0].fit


CASES = [("Vision", "S1", 1.0, 16, 3), ("Lang", "S2", 4.0, 24, 2 ** 31 - 2),
         ("Recom", "S4", 16.0, 20, 7), ("Mix", "S6", 64.0, 32, 11),
         ("HeavyLight", "S5", 16.0, 12, 5)]


@pytest.mark.parametrize("mix,setting,bw,G,seed", CASES)
def test_tables_match_the_systems_analysis(mix, setting, bw, G, seed):
    p = Problem(mix, setting, bw, G, seed)
    fit = program_fit(mix, setting, bw, G, seed)
    np.testing.assert_allclose(p.lat, fit.table.lat, rtol=1e-12)
    np.testing.assert_allclose(p.bw, fit.table.bw, rtol=1e-12)
    assert p.flops == pytest.approx(fit.table.total_flops, rel=1e-12)
    assert p.bw_sys == fit.bw_sys


@pytest.mark.parametrize("mix,setting,bw,G,seed", CASES)
def test_makespan_matches_simulate_numpy(mix, setting, bw, G, seed):
    from repro.core.bw_allocator import simulate_numpy
    from repro.core.encoding import decode_to_lists
    p = Problem(mix, setting, bw, G, seed)
    rng = np.random.default_rng(seed)
    for _ in range(5):
        accel = rng.integers(0, p.num_accels, G)
        prio = rng.random(G).astype(np.float32)
        ref = simulate_numpy(decode_to_lists(accel, prio, p.num_accels),
                             p.lat, p.bw, p.bw_sys)
        assert p.makespan(p.queues(accel, prio)) == pytest.approx(
            ref, rel=1e-12)


def test_lower_precision_control_departs_from_float64():
    p = Problem("Mix", "S4", 16.0, 100, 1)
    rng = np.random.default_rng(0)
    gaps = []
    for _ in range(8):
        accel = rng.integers(0, p.num_accels, 100)
        prio = rng.random(100)
        ref = p.fitness(accel, prio)
        gaps.append(abs(p.fitness(accel, prio, ml_dtypes.bfloat16) - ref)
                    / ref)
        assert abs(p.fitness(accel, prio, np.float32) - ref) / ref < 1e-5
    assert max(gaps) > 1e-3


def test_group_layout_and_invalid_schedule():
    assert len(group_layers("Recom", 10, 0)) == 10
    p = Problem("Recom", "S1", 4.0, 10, 0)
    with pytest.raises(ValueError):
        p.queues(np.full(10, 4), np.zeros(10))
    accel, prio = p.greedy()
    assert sorted(j for q in p.queues(accel, prio) for j in q) == list(
        range(10))
