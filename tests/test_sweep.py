"""Sharded scenario sweeps: every partitioning of a grid is bit-identical.

``repro.core.sweep`` may flatten, pad, chunk, and shard a (scenario x
seed) grid arbitrarily, but each result row must stay bitwise equal to a
standalone ``magma_search`` with that (scenario, seed) — and therefore
to the single-device vmapped path and the legacy nested-vmap engine.
Multi-device coverage spawns a subprocess with fake devices (the parent
process has already locked jax to 1 CPU device); CI additionally runs
this whole file under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.fitness import FitnessFn, normalize_scenarios
from repro.core.job_analyzer import table_from_arrays
from repro.core.magma import (MagmaConfig, _scan_search_batched, _search_plan,
                              magma_search, magma_search_batch)
from repro.core.sweep import SweepConfig, _chunk_fn, run_sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = MagmaConfig(population=20)
BUDGET = 300


def _fitness(G=16, A=3, seed=0, bw_sys=2.0, objective="throughput"):
    rng = np.random.default_rng(seed)
    table = table_from_arrays(rng.uniform(0.1, 3.0, (G, A)),
                              rng.uniform(0.1, 5.0, (G, A)),
                              rng.uniform(1, 10, G))
    return FitnessFn(table, bw_sys=bw_sys, objective=objective)


def _grid(n=3):
    return [_fitness(seed=i, bw_sys=b)
            for i, b in zip(range(n), (1.0, 4.0, 16.0, 64.0, 0.5))]


def _assert_same(a, b):
    np.testing.assert_array_equal(a.best_fitness, b.best_fitness)
    np.testing.assert_array_equal(a.best_accel, b.best_accel)
    np.testing.assert_array_equal(a.best_prio, b.best_prio)
    np.testing.assert_array_equal(a.history_best, b.history_best)
    np.testing.assert_array_equal(a.history_samples, b.history_samples)


def test_sweep_rows_match_standalone_searches():
    """Flattened sweep row [s, k] == magma_search(scenario s, seed k)."""
    fns = _grid(3)
    seeds = [0, 2, 5]
    res = run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=seeds)
    assert res.best_fitness.shape == (3, 3)
    assert res.rows == 9
    for s, fn in enumerate(fns):
        for k, seed in enumerate(seeds):
            ref = magma_search(fn, budget=BUDGET, cfg=CFG, seed=seed)
            assert res.best_fitness[s, k] == ref.best_fitness
            np.testing.assert_array_equal(res.best_accel[s, k],
                                          ref.best_accel)
            np.testing.assert_array_equal(res.best_prio[s, k], ref.best_prio)
            np.testing.assert_array_equal(res.history_best[s, k],
                                          ref.history_best)


def test_sweep_matches_legacy_nested_vmap_engine():
    """The flattened-row sweep reproduces the nested-vmap grid engine
    (vmap over seeds inside vmap over scenarios) bit-for-bit."""
    fns = _grid(3)
    seeds = [0, 1]
    params, num_accels, use_kernel, objective = normalize_scenarios(fns)
    generations, evolve_last = _search_plan(BUDGET, CFG)
    keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in seeds])
    bf, ba, bp, hist = _scan_search_batched(
        keys, params, CFG, num_accels, max(1, round(CFG.elite_frac *
                                                    CFG.population)),
        generations, evolve_last, CFG.population, fns[0].group_size,
        use_kernel, objective)
    res = run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=seeds)
    np.testing.assert_array_equal(res.best_fitness, np.asarray(bf))
    np.testing.assert_array_equal(res.best_accel, np.asarray(ba))
    np.testing.assert_array_equal(res.best_prio, np.asarray(bp))
    np.testing.assert_array_equal(res.history_best, np.asarray(hist))


@pytest.mark.parametrize("chunk_rows,n_chunks,padded", [
    (2, 3, 6),    # chunk boundary == grid boundary
    (4, 2, 8),    # last chunk partial: 2 real rows + 2 padding
    (6, 1, 6),    # chunk == whole grid
])
def test_chunked_streaming_bit_identical(chunk_rows, n_chunks, padded):
    fns = _grid(3)
    seeds = [0, 1]
    base = run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=seeds)
    ch = run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=seeds,
                   sweep=SweepConfig(chunk_rows=chunk_rows))
    _assert_same(base, ch)
    if ch.num_devices == 1:       # exact chunk counts only meaningful at D=1
        assert (ch.num_chunks, ch.padded_rows) == (n_chunks, padded)
    assert len(ch.chunk_wall_s) == ch.num_chunks
    assert all(w > 0 for w in ch.chunk_wall_s)


def test_ragged_grid_padding_sliced_off():
    """A 5-row grid through chunk_rows=3 pads the last chunk; results keep
    exactly the real rows."""
    fns = _grid(5)
    res = run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=[7],
                    sweep=SweepConfig(chunk_rows=3))
    assert res.best_fitness.shape == (5, 1)
    assert res.rows == 5
    if res.num_devices == 1:
        assert res.padded_rows == 6 and res.num_chunks == 2
    for s, fn in enumerate(fns):
        ref = magma_search(fn, budget=BUDGET, cfg=CFG, seed=7)
        assert res.best_fitness[s, 0] == ref.best_fitness


def test_batch_api_routes_through_sweep():
    """magma_search_batch returns a SweepResult and matches run_sweep."""
    from repro.core.sweep import SweepResult
    fns = _grid(2)
    batch = magma_search_batch(fns, budget=BUDGET, cfg=CFG, seeds=[0, 3])
    assert isinstance(batch, SweepResult)
    _assert_same(batch, run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=[0, 3]))


def test_repeat_sweep_reuses_compiled_chunk_fn():
    """Identical grid shape + config must not rebuild the chunk
    executable (meshes and jitted fns are cached)."""
    fns = _grid(2)
    run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=[0])
    n0 = _chunk_fn.cache_info()
    run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=[0])
    n1 = _chunk_fn.cache_info()
    assert n1.currsize == n0.currsize
    assert n1.hits == n0.hits + 1


def test_executable_scopes_name_ask_eval_and_tell():
    """The compiled row executable's instructions map to the search's
    named scopes; its static shape comes with the map."""
    from repro.core.strategies import get_strategy
    from repro.core.sweep import executable_scopes
    fns = _grid(2)
    run_sweep(fns, budget=BUDGET, seeds=[0, 1], strategy="de")
    P = get_strategy("de").ask_size
    mine = [e for e in executable_scopes()
            if (e.P, e.A, e.G, e.rows) == (P, 3, 16, 4)]
    assert mine and mine[0].generations == BUDGET // P
    assert mine[0].module and mine[0].devices >= 1
    named = set(mine[0].scopes.values())
    assert {"search.ask", "search.eval", "search.tell",
            "fitness.events"} <= named


def _program_lines(hlo_text):
    """A compiled module's instruction lines with their metadata dropped
    and every name replaced by its order of first appearance."""
    import re
    names = {}
    out = []
    for line in hlo_text.splitlines():
        if " = " in line:
            line = re.sub(r",? metadata=\{[^}]*\}", "", line)
            out.append(re.sub(r"%[\w.\-]+", lambda m: "%" + str(
                names.setdefault(m.group(0), len(names))), line))
    return out


def test_named_scopes_leave_the_compiled_program_unchanged():
    """The search's named scopes are metadata: without them the compiled
    row executable has the same instructions, up to their names."""
    import contextlib

    import jax.numpy as jnp
    from repro.core.fitness import FitnessParams, ObjectiveSpec
    from repro.core.strategies import MagmaStrategy, plan_generations
    strategy = MagmaStrategy(CFG).bind(3)
    gens, evolve_last = plan_generations(BUDGET, strategy.ask_size)

    def compiled():
        jax.clear_caches()
        fn = _chunk_fn.__wrapped__(None, strategy, gens, evolve_last, 16,
                                   False, ObjectiveSpec(("throughput",)))

        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct((4,) + shape, dtype)
        params = FitnessParams(lat=sds((16, 3)), bw=sds((16, 3)),
                               bw_sys=sds(()), flops=sds(()),
                               energy=sds((16, 3)),
                               objective_code=sds((), jnp.int32))
        return fn.lower(sds((2,), jnp.uint32), params).compile().as_text()

    scoped = compiled()
    assert "search.eval" in scoped and "fitness.events" in scoped
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = compiled()
    jax.clear_caches()              # no trace without scopes outlives this
    assert "search.eval" not in plain
    assert _program_lines(scoped) == _program_lines(plain)


def test_instruction_scopes_take_the_innermost_scope():
    from repro.core.sweep import instruction_scopes
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[]}",
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(f)/vmap(search.eval)/jit(g)/'
        'fitness.events/while/body/add" stack_frame_id=2}',
        '  ROOT %while.1 = (s32[]) while(%t), body=%b, '
        'metadata={op_name="jit(f)/while"}',
        "  %copy.2 = f32[4]{0} copy(%fusion.3)"])
    assert instruction_scopes(text) == {"fusion.3": "fitness.events",
                                        "while.1": "other",
                                        "copy.2": "other"}


def test_mixed_objectives_traced_branch():
    """Scenarios with different objectives share one compiled sweep (the
    traced per-scenario objective select) and still match standalone."""
    fns = [_fitness(seed=0, objective="throughput"),
           _fitness(seed=1, objective="latency")]
    res = run_sweep(fns, budget=BUDGET, cfg=CFG, seeds=[0])
    for s, fn in enumerate(fns):
        ref = magma_search(fn, budget=BUDGET, cfg=CFG, seed=0)
        assert res.best_fitness[s, 0] == ref.best_fitness


# ---------------------------------------------------------------------------
# multi-device: subprocess with fake devices
# ---------------------------------------------------------------------------
def _run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_sweep_bit_identical_multidevice():
    """8 fake devices: sharded grid (ragged: 6 rows over 8 devices) ==
    forced single-device path == standalone search, bitwise; chunked
    streaming across the mesh agrees too."""
    out = _run_sub("""
        import jax, numpy as np
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core.fitness import FitnessFn
        from repro.core.job_analyzer import table_from_arrays
        from repro.core.magma import MagmaConfig, magma_search
        from repro.core.sweep import SweepConfig, run_sweep

        def fit(seed, bw):
            rng = np.random.default_rng(seed)
            return FitnessFn(table_from_arrays(
                rng.uniform(0.1, 3, (16, 3)), rng.uniform(0.1, 5, (16, 3)),
                rng.uniform(1, 10, 16)), bw_sys=bw)

        cfg = MagmaConfig(population=20)
        fns = [fit(0, 1.0), fit(1, 4.0), fit(2, 16.0)]
        seeds = [0, 1]
        sharded = run_sweep(fns, budget=300, cfg=cfg, seeds=seeds)
        assert sharded.num_devices == 6, sharded.num_devices  # 6 rows
        single = run_sweep(fns, budget=300, cfg=cfg, seeds=seeds,
                           sweep=SweepConfig(max_devices=1))
        assert single.num_devices == 1
        for a, b in zip(
                (sharded.best_fitness, sharded.best_accel,
                 sharded.best_prio, sharded.history_best),
                (single.best_fitness, single.best_accel,
                 single.best_prio, single.history_best)):
            np.testing.assert_array_equal(a, b)
        ref = magma_search(fns[1], budget=300, cfg=cfg, seed=1)
        assert sharded.best_fitness[1, 1] == ref.best_fitness
        np.testing.assert_array_equal(sharded.best_accel[1, 1],
                                      ref.best_accel)

        # chunked streaming over the mesh: 4x4 grid, exact and partial
        fns4 = fns + [fit(3, 64.0)]
        seeds4 = [0, 1, 2, 3]
        base = run_sweep(fns4, budget=300, cfg=cfg, seeds=seeds4,
                         sweep=SweepConfig(max_devices=1))
        for cr, want_chunks in ((8, 2), (6, 2)):   # 6 rounds up to 8
            ch = run_sweep(fns4, budget=300, cfg=cfg, seeds=seeds4,
                           sweep=SweepConfig(chunk_rows=cr))
            assert (ch.num_devices, ch.num_chunks) == (8, want_chunks)
            np.testing.assert_array_equal(ch.best_fitness,
                                          base.best_fitness)
            np.testing.assert_array_equal(ch.history_best,
                                          base.history_best)
        print('SHARDED-OK')
    """)
    assert "SHARDED-OK" in out
