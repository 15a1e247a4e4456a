"""Pallas kernels vs. pure-jnp oracles (interpret mode, shape/dtype sweeps).

Every call passes ``interpret=True`` (or ``ops.INTERPRET`` for the flags
that route a search or a model through a kernel): the kernels compile
with Mosaic by default, which only a TPU can do."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.encoding import random_population
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# makespan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("P,G,A", [(4, 10, 2), (8, 33, 4), (16, 60, 8),
                                   (3, 100, 16), (1, 7, 3)])
def test_makespan_matches_simulation(P, G, A):
    key = jax.random.PRNGKey(P * 1000 + G)
    kp, k1, k2 = jax.random.split(key, 3)
    pop = random_population(kp, P, G, A)
    lat = jax.random.uniform(k1, (G, A), minval=0.05, maxval=5.0)
    bw = jax.random.uniform(k2, (G, A), minval=0.01, maxval=10.0)
    for bw_sys in (0.5, 4.0, 1e6):
        got = ops.population_makespan(pop.accel, pop.prio, lat, bw, bw_sys, A,
                                      interpret=True)
        want = ref.population_makespan_ref(pop.accel, pop.prio, lat, bw,
                                           bw_sys, A)
        np.testing.assert_allclose(got, want, rtol=2e-3)


@pytest.mark.parametrize("pop_block", [4, 8])
def test_makespan_pop_blocks(pop_block):
    from repro.kernels.makespan import makespan_pallas
    key = jax.random.PRNGKey(7)
    P, G, A = 10, 24, 4
    kp, k1, k2 = jax.random.split(key, 3)
    pop = random_population(kp, P, G, A)
    lat = jax.random.uniform(k1, (G, A), minval=0.1, maxval=2.0)
    bw = jax.random.uniform(k2, (G, A), minval=0.1, maxval=2.0)
    a = ops.population_makespan(pop.accel, pop.prio, lat, bw, 2.0, A,
                                interpret=True)
    b = ref.population_makespan_ref(pop.accel, pop.prio, lat, bw, 2.0, A)
    np.testing.assert_allclose(a, b, rtol=2e-3)


def test_compiled_kernel_off_tpu_raises():
    """Compiled (Mosaic) is the default; off the TPU it raises instead of
    quietly running the interpreter."""
    assert jax.default_backend() == "cpu"
    pop = random_population(jax.random.PRNGKey(0), 4, 10, 2)
    lat = jnp.ones((10, 2))
    with pytest.raises(ValueError, match="interpret"):
        ops.population_makespan(pop.accel, pop.prio, lat, lat, 1.0, 2)


def test_fitness_kernel_path_matches_jnp():
    """FitnessFn(use_kernel=INTERPRET) == FitnessFn(use_kernel=False)."""
    from repro.core.fitness import FitnessFn
    from repro.core.job_analyzer import table_from_arrays
    rng = np.random.default_rng(0)
    G, A = 30, 4
    table = table_from_arrays(rng.uniform(0.1, 2, (G, A)),
                              rng.uniform(0.1, 2, (G, A)),
                              rng.uniform(1, 5, G))
    pop = random_population(jax.random.PRNGKey(1), 8, G, A)
    f_jnp = FitnessFn(table, bw_sys=1.0)
    f_ker = FitnessFn(table, bw_sys=1.0, use_kernel=ops.INTERPRET)
    np.testing.assert_allclose(np.asarray(f_ker(pop.accel, pop.prio)),
                               np.asarray(f_jnp(pop.accel, pop.prio)),
                               rtol=2e-3)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,Hq,Hkv,D,win", [
    (2, 64, 4, 2, 32, 0),
    (1, 128, 8, 8, 64, 0),
    (2, 96, 4, 1, 16, 24),     # padding S + MQA + window
    (1, 64, 6, 2, 128, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, Hq, Hkv, D, win, dtype):
    keys = jax.random.split(jax.random.PRNGKey(B * S + Hq), 3)
    q = jax.random.normal(keys[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(keys[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(keys[2], (B, S, Hkv, D), dtype)
    got = ops.flash_attention(q, k, v, causal=True, window=win,
                              block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_non_causal():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 64, 4, 32))
    k = jax.random.normal(keys[1], (1, 64, 2, 32))
    v = jax.random.normal(keys[2], (1, 64, 2, 32))
    got = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("Bt,L,Dm,N,chunk", [
    (2, 40, 64, 4, 16),
    (1, 129, 256, 16, 32),     # L padding
    (2, 16, 128, 8, 8),
    (1, 64, 384, 64, 16),      # multiple d blocks
])
def test_ssm_scan_sweep(Bt, L, Dm, N, chunk):
    keys = jax.random.split(jax.random.PRNGKey(L * Dm), 5)
    x = jax.random.normal(keys[0], (Bt, L, Dm))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (Bt, L, Dm))) * 0.1
    A = -jnp.exp(jax.random.normal(keys[2], (Dm, N)) * 0.5)
    Bm = jax.random.normal(keys[3], (Bt, L, N))
    Cm = jax.random.normal(keys[4], (Bt, L, N))
    y, h = ops.ssm_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, hr = ref.ssm_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h, hr, atol=1e-4, rtol=1e-4)


def test_ssm_scan_bf16_inputs():
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    Bt, L, Dm, N = 1, 32, 128, 16
    x = jax.random.normal(keys[0], (Bt, L, Dm), jnp.bfloat16)
    dt = (jax.nn.softplus(jax.random.normal(keys[1], (Bt, L, Dm))) * 0.1)
    A = -jnp.exp(jax.random.normal(keys[2], (Dm, N)) * 0.5)
    Bm = jax.random.normal(keys[3], (Bt, L, N), jnp.bfloat16)
    Cm = jax.random.normal(keys[4], (Bt, L, N), jnp.bfloat16)
    y, h = ops.ssm_scan(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    yr, hr = ref.ssm_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, yr, atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(h, hr, atol=5e-2, rtol=5e-2)


def test_mamba_block_kernel_path_matches_reference():
    """mamba1_block with the SSM kernel (cfg.use_flash) == lax.scan path."""
    from repro.configs import get_smoke_config
    from repro.models import module
    from repro.models.registry import get_model
    cfg = get_smoke_config("falcon-mamba-7b").replace(dtype="float32")
    model = get_model(cfg)
    values, _ = module.split(model.init(jax.random.PRNGKey(0)))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    loss_ref, _ = model.loss(values, batch)
    model_k = get_model(cfg.replace(use_flash=ops.INTERPRET))
    loss_ker, _ = model_k.loss(values, batch)
    np.testing.assert_allclose(float(loss_ker), float(loss_ref),
                               rtol=1e-4, atol=1e-5)
