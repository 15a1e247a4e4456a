"""ObjectiveSpec API: scalar bit-identity, registry, ProblemSpec shim.

The refactor contract (vector-valued objectives) is only safe if every
scalar objective is BIT-IDENTICAL through the new path: the pinned
constants below were captured on the pre-spec code (static if/elif
branches, ``objective: str`` threading) and every release must keep
reproducing them — evaluation bytes, converged best fitness on both
engines, and the memo fingerprints (old stored records must exact-hit).
"""
import hashlib
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import fitness as F
from repro.core.encoding import random_population
from repro.core.fitness import (FitnessFn, ObjectiveSpec, ProblemSpec,
                                as_objective_spec, available_objectives,
                                evaluate_objectives, evaluate_params,
                                normalize_scenarios, objective_info,
                                objective_token, register_objective)
from repro.core.job_analyzer import table_from_arrays
from repro.core.magma import MagmaConfig
from repro.core.strategies import MagmaStrategy, run_strategy
from repro.memo import ScheduleMemo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Captured on the pre-ObjectiveSpec code (commit fdc1301) under jax 0.9.0,
# whose default threefry draws other bits than 0.4.x: G=16, A=3 tables from
# default_rng(0), FitnessFn(bw_sys=2.0), MagmaConfig(population=20),
# budget=300 seed=0, eval population random_population(PRNGKey(7), 32).
PINNED = {
    "throughput": {
        "eval_sha": "135d2f18aaddffdb0bb890fdc338464bbb1f394fa4707becce"
                    "5fb2dd303c81b3",
        "eval_first3": [2.295694589614868, 2.974745273590088,
                        2.1814029216766357],
        "best_fitness": 6.015331745147705,
        "fingerprint": "95327f16f0e4cf34cc780b5e77551e6638142dba511f51ae"
                       "378b1f7391979104",
    },
    "latency": {
        "eval_sha": "fa674bca126e30d6dbe8b99c2b506015d4bb5d2f0d8599689f"
                    "e95e9ddfd0117a",
        "eval_first3": [-41.72596740722656, -32.20110321044922,
                        -43.91214370727539],
        "best_fitness": -15.924321174621582,
        "fingerprint": "0306d6b96a028465297251a108b096f2f5463652bd5d2f93"
                       "c922f7a3e33a606d",
    },
    "energy": {
        "eval_sha": "95435af36c6eeab450299ff3c6ead43dd31df18b9bdf26f32e"
                    "9661210e8069b1",
        "eval_first3": [-36.11945724487305, -40.32465744018555,
                        -32.795310974121094],
        "best_fitness": -20.234142303466797,
        "fingerprint": "edc78a23295310f42cfc7c7db62c3619868796543fdcb073"
                       "63220d3335b8a428",
    },
    "edp": {
        "eval_sha": "b0eecf4c69662ca6e55e3803898dc6b19b5b8128693affdbd3"
                    "a0110355fc72a0",
        "eval_first3": [-1507.1192626953125, -1298.4984130859375,
                        -1440.1124267578125],
        "best_fitness": -603.2894287109375,
        "fingerprint": "aa06ce9cd3525de23e7612aba1d295f49e23a6c9f38ae61d"
                       "dd99617b9b3cb3a1",
    },
}


def _fitness(objective, G=16, A=3, seed=0, bw_sys=2.0):
    rng = np.random.default_rng(seed)
    table = table_from_arrays(rng.uniform(0.1, 3.0, (G, A)),
                              rng.uniform(0.1, 5.0, (G, A)),
                              rng.uniform(1, 10, G),
                              energy=rng.uniform(0.5, 4.0, (G, A)))
    return FitnessFn(table, bw_sys=bw_sys, objective=objective)


# ---------------------------------------------------------------------------
# pinned scalar parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("objective", sorted(PINNED))
def test_scalar_objective_bit_identical_to_pre_spec(objective):
    pin = PINNED[objective]
    fit = _fitness(objective)
    pop = random_population(jax.random.PRNGKey(7), 32, fit.group_size,
                            fit.num_accels)
    vals = np.asarray(evaluate_params(fit.params, pop.accel, pop.prio,
                                      num_accels=fit.num_accels,
                                      objective=objective))
    sha = hashlib.sha256(
        np.ascontiguousarray(vals.astype("<f4")).tobytes()).hexdigest()
    assert sha == pin["eval_sha"]
    np.testing.assert_array_equal(
        vals[:3], np.array(pin["eval_first3"], dtype=np.float32))
    # the spec path and the (P, 1) vector path see the same bytes
    spec_vals = np.asarray(evaluate_params(
        fit.params, pop.accel, pop.prio, num_accels=fit.num_accels,
        objective=ObjectiveSpec((objective,))))
    np.testing.assert_array_equal(vals, spec_vals)
    mat = np.asarray(evaluate_objectives(
        fit.params, pop.accel, pop.prio, num_accels=fit.num_accels,
        objective=ObjectiveSpec((objective,))))
    assert mat.shape == (32, 1)
    np.testing.assert_array_equal(vals, mat[:, 0])


@pytest.mark.parametrize("objective", sorted(PINNED))
@pytest.mark.parametrize("engine", ["scan", "loop"])
def test_search_converges_to_pinned_fitness(objective, engine):
    fit = _fitness(objective)
    res = run_strategy(MagmaStrategy(MagmaConfig(population=20)), fit,
                       budget=300, seed=0, engine=engine)
    assert float(res.best_fitness) == PINNED[objective]["best_fitness"]


@pytest.mark.parametrize("objective", sorted(PINNED))
def test_memo_fingerprint_unchanged(objective):
    """Pre-refactor stored records must still exact-hit: the fingerprint
    of (scenario, strategy, budget, seed) is byte-for-byte stable whether
    the objective arrives as a bare name or a scalar spec."""
    memo = ScheduleMemo()
    strat = MagmaStrategy(MagmaConfig(population=20))
    fp_name = memo.fingerprint(_fitness(objective), strat, 300, 0)
    assert fp_name == PINNED[objective]["fingerprint"]
    fp_spec = memo.fingerprint(_fitness(ObjectiveSpec((objective,))),
                               strat, 300, 0)
    assert fp_spec == fp_name


def test_multi_spec_fingerprints_are_distinct():
    memo = ScheduleMemo()
    strat = MagmaStrategy(MagmaConfig(population=20))
    fp = memo.fingerprint(_fitness(("latency", "energy")), strat, 300, 0)
    assert fp not in {p["fingerprint"] for p in PINNED.values()}
    # and order matters (column 0 is the anytime scalar)
    fp2 = memo.fingerprint(_fitness(("energy", "latency")), strat, 300, 0)
    assert fp2 != fp


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_builtin_codes_are_historical():
    assert [objective_info(n).code for n in
            ("throughput", "latency", "energy", "edp")] == [0, 1, 2, 3]
    assert available_objectives()[:4] == ("throughput", "latency",
                                          "energy", "edp")


def test_unknown_objective_lists_registered():
    with pytest.raises(ValueError, match="registered objectives:.*latency"):
        objective_info("speed")
    with pytest.raises(ValueError, match="unknown objective 'speed'"):
        ObjectiveSpec(("speed",))
    with pytest.raises(ValueError, match="registered objectives"):
        _fitness("speed")


def test_register_objective_roundtrip():
    name = "neg_sq_makespan_test"
    try:
        info = register_objective(
            name, lambda params, ms, en: -(ms * ms),
            description="test-only")
        assert info.code == len(F._OBJECTIVES) - 1
        assert F.OBJECTIVE_CODES[name] == info.code
        fit = _fitness(name)
        pop = random_population(jax.random.PRNGKey(7), 8, fit.group_size,
                                fit.num_accels)
        got = np.asarray(fit(pop.accel, pop.prio))
        lat = np.asarray(_fitness("latency")(pop.accel, pop.prio))
        np.testing.assert_allclose(got, -(lat * lat), rtol=1e-6)
        # duplicate registration is loud; overwrite keeps the code
        with pytest.raises(ValueError, match="already registered"):
            register_objective(name, lambda params, ms, en: ms)
        info2 = register_objective(name, lambda params, ms, en: ms,
                                   overwrite=True)
        assert info2.code == info.code
    finally:
        F._OBJECTIVES.pop(name, None)
        F.OBJECTIVE_CODES.pop(name, None)


def test_objective_spec_tokens_and_validation():
    assert ObjectiveSpec(("latency",)).token == "latency"
    assert ObjectiveSpec(("latency", "energy")).token == \
        "pareto:latency+energy"
    assert objective_token("edp") == "edp"
    assert objective_token(("latency", "edp")) == "pareto:latency+edp"
    assert objective_token(None) is None
    assert as_objective_spec(None) is None
    spec = as_objective_spec(["latency", "energy"])
    assert spec.codes == (1, 2) and spec.needs_energy \
        and not spec.is_scalar and spec.num_objectives == 2
    assert as_objective_spec(spec) is spec
    with pytest.raises(ValueError, match="at least one"):
        ObjectiveSpec(())
    with pytest.raises(ValueError, match="duplicate"):
        ObjectiveSpec(("latency", "latency"))
    # hashable: usable as jit static / executable-cache key
    assert hash(spec) == hash(ObjectiveSpec(("latency", "energy")))


# ---------------------------------------------------------------------------
# ProblemSpec shim
# ---------------------------------------------------------------------------
def test_problem_spec_unpacks_like_the_old_tuple():
    fns = [_fitness("latency", seed=0), _fitness("latency", seed=1)]
    spec = normalize_scenarios(fns)
    assert isinstance(spec, ProblemSpec)
    params, num_accels, use_kernel, objective = spec       # 4-tuple shim
    assert params is spec.params and num_accels == 3
    assert use_kernel is False
    assert objective == ObjectiveSpec(("latency",))
    # mixed scalar objectives fall back to the dynamic select (None)
    mixed = normalize_scenarios([_fitness("latency"), _fitness("edp")])
    assert mixed.objective is None
    # multi-column scenarios cannot mix with anything else
    with pytest.raises(ValueError, match="multi"):
        normalize_scenarios([_fitness(("latency", "energy")),
                             _fitness("edp")])


# ---------------------------------------------------------------------------
# sweep parity on 8 fake devices
# ---------------------------------------------------------------------------
def test_run_sweep_scalar_parity_multidevice():
    """8 fake devices: the sharded sweep over ObjectiveSpec scenarios is
    bit-identical to standalone searches of the same scalar objectives."""
    code = """
        import jax, numpy as np
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core.fitness import FitnessFn, ObjectiveSpec
        from repro.core.job_analyzer import table_from_arrays
        from repro.core.magma import MagmaConfig
        from repro.core.strategies import MagmaStrategy, run_strategy
        from repro.core.sweep import run_sweep

        def fit(seed, objective):
            rng = np.random.default_rng(seed)
            return FitnessFn(table_from_arrays(
                rng.uniform(0.1, 3, (16, 3)), rng.uniform(0.1, 5, (16, 3)),
                rng.uniform(1, 10, 16),
                energy=rng.uniform(0.5, 4, (16, 3))),
                bw_sys=2.0, objective=objective)

        strat = MagmaStrategy(MagmaConfig(population=20))
        for obj in ("throughput", "latency", "energy", "edp"):
            fns = [fit(s, ObjectiveSpec((obj,))) for s in range(4)]
            swept = run_sweep(fns, budget=300, seeds=[0, 1],
                              strategy=strat)
            assert swept.num_devices == 8, swept.num_devices
            for i, fn in enumerate(fns):
                for j, seed in enumerate([0, 1]):
                    solo = run_strategy(strat, fit(i, obj), budget=300,
                                        seed=seed)
                    assert float(swept.best_fitness[i, j]) == \\
                        float(solo.best_fitness), (obj, i, seed)
        print("PARITY-OK")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PARITY-OK" in out.stdout
