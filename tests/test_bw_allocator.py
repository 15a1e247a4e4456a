"""BW Allocator (Algorithm 1): jnp scan vs float64 oracle + invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bw_allocator import (
    simulate_numpy, simulate_population, throughput)
from repro.core.encoding import decode_to_lists, random_population


def _rand_tables(rng, G, A):
    lat = rng.uniform(0.05, 5.0, (G, A))
    bw = rng.uniform(0.01, 10.0, (G, A))
    return lat, bw


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 30), st.integers(1, 6),
       st.floats(0.5, 50.0), st.integers(0, 10_000))
def test_scan_matches_numpy_oracle(G, A, bw_sys, seed):
    rng = np.random.default_rng(seed)
    lat, bw = _rand_tables(rng, G, A)
    pop = random_population(jax.random.PRNGKey(seed), 4, G, A)
    ms = np.asarray(simulate_population(
        pop.accel, pop.prio, jnp.asarray(lat, jnp.float32),
        jnp.asarray(bw, jnp.float32), bw_sys, A))
    for p in range(4):
        queues = decode_to_lists(pop.accel[p], pop.prio[p], A)
        want = simulate_numpy(queues, lat, bw, bw_sys)
        assert ms[p] == pytest.approx(want, rel=2e-3), (p, ms[p], want)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 20), st.integers(2, 4), st.integers(0, 10_000))
def test_more_bandwidth_never_hurts(G, A, seed):
    rng = np.random.default_rng(seed)
    lat, bw = _rand_tables(rng, G, A)
    pop = random_population(jax.random.PRNGKey(seed), 2, G, A)
    ms = []
    for bw_sys in (1.0, 4.0, 1e9):
        ms.append(np.asarray(simulate_population(
            pop.accel, pop.prio, jnp.asarray(lat, jnp.float32),
            jnp.asarray(bw, jnp.float32), bw_sys, A)))
    assert np.all(ms[0] >= ms[1] - 1e-5)
    assert np.all(ms[1] >= ms[2] - 1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 20), st.integers(1, 4), st.integers(0, 10_000))
def test_unlimited_bw_equals_queue_latency_sum(G, A, seed):
    """With infinite system BW the makespan is the max per-queue latency sum."""
    rng = np.random.default_rng(seed)
    lat, bw = _rand_tables(rng, G, A)
    pop = random_population(jax.random.PRNGKey(seed), 1, G, A)
    queues = decode_to_lists(pop.accel[0], pop.prio[0], A)
    want = max((sum(lat[j, a] for j in q) for a, q in enumerate(queues)),
               default=0.0)
    got = float(simulate_population(
        pop.accel, pop.prio, jnp.asarray(lat, jnp.float32),
        jnp.asarray(bw, jnp.float32), 1e12, A)[0])
    assert got == pytest.approx(want, rel=1e-3)


def test_serial_single_accel():
    """One accelerator, ample BW: makespan = sum of latencies."""
    lat = np.array([[1.0], [2.0], [3.0]])
    bw = np.ones((3, 1))
    ms = simulate_numpy([[0, 1, 2]], lat, bw, bw_sys=100.0)
    assert ms == pytest.approx(6.0)


def test_bw_contention_slows_down():
    """Two jobs each needing the full pipe, in parallel -> 2x slowdown."""
    lat = np.array([[1.0, 1.0], [1.0, 1.0]])
    bw = np.full((2, 2), 8.0)
    ms = simulate_numpy([[0], [1]], lat, bw, bw_sys=8.0)
    assert ms == pytest.approx(2.0, rel=1e-6)


def test_throughput_objective():
    assert float(throughput(100.0, jnp.float32(4.0))) == pytest.approx(25.0)


# ---------------------------------------------------------------------------
# the grouped, gather-free scan against the dense (A, G) queue tables
# ---------------------------------------------------------------------------
def _tables_makespans(accel, prio, lat, bw, bw_sys, num_accels):
    """The earlier formulation, kept here as the reference: per-core
    queue tables gathered from the decoded queues, and an event scan that
    gathers each core's current job at its pointer."""
    from repro.core.encoding import decode
    latf = lat.astype(jnp.float32)
    bwf = bw.astype(jnp.float32)

    def tables_one(a, p):
        sched = decode(a, p, num_accels)
        qlat = jnp.take_along_axis(latf.T, sched.queue, axis=1)
        qbw = jnp.take_along_axis(bwf.T, sched.queue, axis=1)
        return qlat, jnp.maximum(qbw, 1e-3), sched.count

    qlat, qbw, count = jax.vmap(tables_one)(accel, prio)
    P, A, G = qlat.shape
    qbytes = qlat * qbw
    iota_a = jax.lax.broadcasted_iota(jnp.int32, (P, A), 1)

    def pick(q, ptr):
        return jnp.take_along_axis(q, ptr[:, :, None], axis=2)[..., 0]

    ptr0 = jnp.zeros((P, A), jnp.int32)
    rem0 = jnp.where(ptr0 < count, pick(qbytes, ptr0), 0.0)

    def step(state, _):
        t, rem, ptr = state
        active = ptr < count
        req = jnp.where(active, pick(qbw, ptr), 0.0)
        total = jnp.sum(req, axis=1)
        scale = jnp.minimum(1.0, bw_sys / jnp.maximum(total, 1e-30))
        alloc = req * scale[:, None]
        runtime = jnp.where(active, rem / jnp.maximum(alloc, 1e-30), jnp.inf)
        any_active = jnp.any(active, axis=1)
        dt = jnp.where(any_active, jnp.min(runtime, axis=1), 0.0)
        rem = jnp.maximum(rem - dt[:, None] * alloc, 0.0)
        fin = jnp.argmin(runtime, axis=1)
        fin_oh = (iota_a == fin[:, None]) & any_active[:, None]
        ptr = ptr + fin_oh.astype(jnp.int32)
        nxt = pick(qbytes, ptr)
        rem = jnp.where(fin_oh, jnp.where(ptr < count, nxt, 0.0), rem)
        return (t + dt, rem, ptr), None

    (t, _, _), _ = jax.lax.scan(
        step, (jnp.zeros((P,), jnp.float32), rem0, ptr0), None, length=G)
    return t


def _hard_population(A, G, seed, P=12):
    """Random individuals, then some with every job on one core and some
    whose priorities tie within a core; with G < A some cores are empty."""
    pop = random_population(jax.random.PRNGKey(seed), P, G, A)
    accel, prio = pop.accel, pop.prio
    accel = accel.at[0].set(A - 1).at[1].set(0)
    prio = prio.at[2:6].set(jnp.round(prio[2:6] * 3) / 3)
    prio = prio.at[6].set(0.5)
    return accel, prio


@pytest.mark.parametrize("G", [1, 7, 100, 200])
@pytest.mark.parametrize("A", [1, 4, 8, 16])
def test_grouped_scan_bit_identical_to_queue_tables(A, G):
    rng = np.random.default_rng(A * 1000 + G)
    lat = jnp.asarray(rng.uniform(1e-5, 1e-2, (2, G, A)), jnp.float32)
    bw = jnp.asarray(rng.uniform(0.0, 3e10, (2, G, A)), jnp.float32)
    accel, prio = _hard_population(A, G, seed=A + G)
    want = jax.jit(_tables_makespans, static_argnums=(4, 5))
    for bw_sys in (1e9, 16e9):                      # a Python float
        got = np.asarray(simulate_population(accel, prio, lat[0], bw[0],
                                             bw_sys, A))
        ref = np.asarray(want(accel, prio, lat[0], bw[0], bw_sys, A))
        assert np.array_equal(got, ref), (bw_sys, got - ref)
    # a traced bw_sys, under vmap over rows as the row executable runs it
    bw_sys = jnp.asarray([4e9, 64e9], jnp.float32)
    got = jax.vmap(lambda la, b, s: simulate_population(
        accel, prio, la, b, s, A))(lat, bw, bw_sys)
    ref = jax.vmap(lambda la, b, s: _tables_makespans(
        accel, prio, la, b, s, A))(lat, bw, bw_sys)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_single_and_decoded_go_through_the_population_scan():
    from repro.core.bw_allocator import simulate, simulate_decoded
    from repro.core.encoding import decode
    A, G = 4, 9
    rng = np.random.default_rng(5)
    lat, bw = (jnp.asarray(x, jnp.float32) for x in _rand_tables(rng, G, A))
    accel, prio = _hard_population(A, G, seed=3)
    pop = np.asarray(simulate_population(accel, prio, lat, bw, 3.0, A))
    for p in range(accel.shape[0]):
        one = float(simulate(accel[p], prio[p], lat, bw, 3.0, A))
        dec = float(simulate_decoded(decode(accel[p], prio[p], A), lat, bw,
                                     3.0))
        assert one == dec == pop[p], (p, one, dec, pop[p])


def test_fitness_has_no_gather():
    """The mechanism: the fitness picks by dense one-hot selects, never
    by a gather — neither in the lowered ``simulate_population`` nor among
    the ``fitness.*`` instructions of a compiled row executable."""
    import re

    from repro.core.fitness import FitnessParams, ObjectiveSpec
    from repro.core.strategies import MagmaStrategy
    from repro.core.sweep import _chunk_fn, instruction_scopes
    A, G = 4, 16
    accel, prio = _hard_population(A, G, seed=1)
    lowered = simulate_population.lower(
        accel, prio, jnp.ones((G, A)), jnp.ones((G, A)), 8.0, A).as_text()
    assert "gather" not in lowered

    fn = _chunk_fn(None, MagmaStrategy().bind(A), 2, False, G, False,
                   ObjectiveSpec(("throughput",)))

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct((2,) + shape, dtype)
    params = FitnessParams(lat=sds((G, A)), bw=sds((G, A)), bw_sys=sds(()),
                           flops=sds(()), energy=sds((G, A)),
                           objective_code=sds((), jnp.int32))
    text = fn.lower(sds((2,), jnp.uint32), params).compile().as_text()
    scoped = [s for line in text.splitlines()
              for s in instruction_scopes(line).values()]
    assert {"fitness.decode", "fitness.tables", "fitness.events"} <= set(scoped)
    gathers = [line for line in text.splitlines()
               if re.search(r"= \S+ gather\(", line)]
    assert not [g for g in gathers
                if next(iter(instruction_scopes(g).values())).startswith(
                    "fitness.")]
