"""Warm-start engine (Section V-C / Table V)."""
import jax
import numpy as np
import pytest

from repro.core import M3E, MagmaConfig
from repro.core.warmstart import WarmStartEngine
from repro.costmodel import get_setting
from repro.workloads import build_task_groups

GB = 1024 ** 3


def test_warmstart_transfer_beats_random_init():
    """Trf-0-ep (warm-started, 1 generation) >> Raw (random, 1 generation)."""
    ws = WarmStartEngine()
    m3e = M3E(accel=get_setting("S2"), bw_sys=1 * GB, warm_start=ws)
    groups = build_task_groups("Lang", group_size=40, num_groups=2, seed=0)
    cfg = MagmaConfig(population=40)
    # optimize on group 0 -> populates the cache
    m3e.search(groups[0], method="magma", budget=2000, seed=0, strategy_kwargs={"cfg": cfg})
    assert ws.has("Lang")
    # Trf-0-ep: one generation from the transferred population
    warm = m3e.search(groups[1], method="magma", budget=40, seed=1, strategy_kwargs={"cfg": cfg})
    cold = M3E(accel=get_setting("S2"), bw_sys=1 * GB).search(
        groups[1], method="magma", budget=40, seed=1, strategy_kwargs={"cfg": cfg})
    assert warm.best_fitness > cold.best_fitness


def test_warmstart_ignores_mismatched_group_size():
    ws = WarmStartEngine()
    from repro.core.encoding import random_population
    ws.remember("Vision", random_population(jax.random.PRNGKey(0), 8, 10, 4))
    assert ws.init_population("Vision", jax.random.PRNGKey(1), 20, 4) is None
    assert ws.init_population("Recom", jax.random.PRNGKey(1), 10, 4) is None
    pop = ws.init_population("Vision", jax.random.PRNGKey(1), 10, 4)
    assert pop is not None and pop.accel.shape == (8, 10)
    assert float(pop.prio.min()) >= 0.0 and float(pop.prio.max()) < 1.0


def test_warmstart_jitter_pinned_seed():
    """Seed discipline: the jittered warm-start population is a pure
    function of (key, stored population) — same key, same bits; new key,
    new jitter.  Values pinned like tests/test_strategies.py pins
    best-fitness per strategy (jax threefry is stable across
    hosts/devices), so any accidental host-RNG leak or key-order change
    in the jitter path fails loudly.  Pinned on jax 0.9.0, whose default
    ``jax_threefry_partitionable=True`` draws different bits than 0.4.x."""
    from repro.core.encoding import random_population
    ws = WarmStartEngine()
    ws.remember("Vision", random_population(jax.random.PRNGKey(0), 8, 10, 4))
    p1 = ws.init_population("Vision", jax.random.PRNGKey(3), 10, 4)
    p2 = ws.init_population("Vision", jax.random.PRNGKey(3), 10, 4)
    np.testing.assert_array_equal(np.asarray(p1.accel), np.asarray(p2.accel))
    np.testing.assert_array_equal(np.asarray(p1.prio), np.asarray(p2.prio))
    p3 = ws.init_population("Vision", jax.random.PRNGKey(4), 10, 4)
    assert (np.asarray(p1.prio) != np.asarray(p3.prio)).any()
    # accel transfers un-jittered; prio jitter is pinned to the key
    assert int(np.asarray(p1.accel).sum()) == 113
    assert float(np.asarray(p1.prio, dtype=np.float64).sum()) == \
        pytest.approx(35.40254068002105, rel=1e-9)


def test_warmstart_remember_is_content_addressed():
    """Re-remembering the identical population is a no-op overwrite in
    the backing memo store; new knowledge appends (latest wins)."""
    from repro.core.encoding import random_population
    ws = WarmStartEngine()
    pop = random_population(jax.random.PRNGKey(0), 8, 10, 4)
    ws.remember("Lang", pop)
    ws.remember("Lang", pop)
    assert len(ws.store) == 1
    pop2 = random_population(jax.random.PRNGKey(9), 8, 10, 4)
    ws.remember("Lang", pop2)
    assert len(ws.store) == 2
    got = ws.init_population("Lang", jax.random.PRNGKey(1), 10, 4)
    # latest remembered population wins (legacy last-write-wins)
    base = np.clip(np.asarray(pop2.prio), 0.0, 0.999)
    assert np.abs(np.asarray(got.prio) - base).max() < 0.2
