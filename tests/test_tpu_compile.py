"""Described-chip compiles for a TPU v5e (topology ``v5e:2x2``).

The TPU compiler is installed even where no chip is attached, so the
scheduler's device programs are compiled here at the paper's sizes
(population P=100, group G=100, A=4 or 8 sub-accelerators, 10,000
samples, 8 rows per batch).  That catches what the chip's compiler would
refuse.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.  The persistent compilation
cache is off around these compiles, since an entry written for a
described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

P, G, ROWS, BUDGET = 100, 100, 8, 10_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs on disk
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _rows_args(A: int, sharding):
    """Shapes of one batch of ROWS (key, FitnessParams) rows."""
    from repro.core.fitness import FitnessParams

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct((ROWS,) + shape, dtype, sharding=sharding)
    params = FitnessParams(lat=sds((G, A)), bw=sds((G, A)), bw_sys=sds(()),
                           flops=sds(()), energy=sds((G, A)),
                           objective_code=sds((), jnp.int32))
    return sds((2,), jnp.uint32), params


def _row_fn(A: int, use_kernel: bool, mesh=None):
    from repro.core.fitness import ObjectiveSpec
    from repro.core.strategies import MagmaStrategy, plan_generations
    from repro.core.sweep import _chunk_fn
    strategy = MagmaStrategy().bind(A)
    gens, evolve_last = plan_generations(BUDGET, strategy.ask_size)
    return _chunk_fn(mesh, strategy, gens, evolve_last, G, use_kernel,
                     ObjectiveSpec(("throughput",)))


@pytest.mark.parametrize("A", [4, 8])
def test_makespan_kernel_compiles_with_mosaic(one_chip, no_persistent_cache,
                                              A):
    from repro.kernels.makespan import makespan_pallas

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = makespan_pallas.lower(
        sds((P, A, G)), sds((P, A, G)), sds((P, A), jnp.int32), sds(()),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("A,use_kernel", [(4, False), (8, True)])
def test_row_executable_compiles_for_one_chip(one_chip, no_persistent_cache,
                                              A, use_kernel):
    """The batch program the stream and the sweep dispatch: the jnp event
    scan, and the Pallas kernel compiled by Mosaic inside the search."""
    keys, params = _rows_args(A, one_chip)
    compiled = _row_fn(A, use_kernel).lower(keys, params).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30


def test_sharded_row_executable_compiles_for_four_chips(
        topo, no_persistent_cache):
    """The same batch sharded over the 2x2 mesh: rows never communicate,
    so the program holds no collective."""
    from repro.core.sweep import SWEEP_AXIS
    from repro.dist.sharding import flat_mesh
    mesh = flat_mesh(4, SWEEP_AXIS, devices=topo.devices)
    keys, params = _rows_args(4, NamedSharding(mesh, PartitionSpec(SWEEP_AXIS)))
    text = _row_fn(4, False, mesh).lower(keys, params).compile().as_text()
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text
    assert np.prod(mesh.devices.shape) == 4
