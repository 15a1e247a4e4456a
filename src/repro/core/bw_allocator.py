"""BW Allocator — Algorithm 1 of the paper, as a vectorizable JAX scan.

Event-driven simulation of one group of jobs executing on A sub-accelerators
that share the system bandwidth:

  - each sub-accelerator runs its queue in priority order;
  - at any instant the live jobs' *required* BWs are summed; if they exceed
    the system BW every job is throttled proportionally
    (``alloc = req * BW_sys / sum(req)``), otherwise each gets its request;
  - a job's remaining work is measured in bytes (no-stall latency x required
    BW, the paper's ``CurJobs``); it completes when its bytes drain at the
    allocated rate — so with full allocation its runtime is exactly the
    no-stall latency;
  - on every completion the allocation is recomputed (one event per step).

Exactly one job finishes per event step, so ``G`` steps simulate a group of
``G`` jobs; ties drain in consecutive zero-dt steps.  The scan is jit- and
vmap-friendly: MAGMA evaluates a whole population with one vmapped call.
Its parts run under the named scopes ``fitness.decode``,
``fitness.tables`` (queue-table gathers) and ``fitness.events`` (the
scan): metadata only, which a device trace reads back.

``simulate_numpy`` is the float64 oracle used by the tests and as the
reference for the Pallas ``makespan`` kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoding import DecodedSchedule, decode

_BW_FLOOR = 1e-3    # bytes/s; keeps rem/alloc well-defined
_TINY = 1e-30


@partial(jax.jit, static_argnames=())
def _queue_tables(sched: DecodedSchedule, lat: jnp.ndarray, bw: jnp.ndarray):
    """Gather per-queue-slot (latency, bw): q*[a, i] = table[queue[a, i], a]."""
    A = sched.queue.shape[0]
    lat_t = lat.T  # (A, G)
    bw_t = bw.T
    qlat = jnp.take_along_axis(lat_t, sched.queue, axis=1)
    qbw = jnp.take_along_axis(bw_t, sched.queue, axis=1)
    return qlat, jnp.maximum(qbw, _BW_FLOOR)


def simulate_tables(qlat: jnp.ndarray, qbw: jnp.ndarray, count: jnp.ndarray,
                    bw_sys) -> jnp.ndarray:
    """(P,) makespans from dense queue tables: qlat/qbw (P, A, G), count
    (P, A).  The whole population advances through one event scan — every
    per-event quantity is a dense (P, A) array (no per-individual scatter
    or gather chains, which XLA:CPU serializes)."""
    P, A, G = qlat.shape
    with jax.named_scope("fitness.tables"):
        qbytes = qlat * qbw              # remaining work, paper's CurJobs
    iota_a = jax.lax.broadcasted_iota(jnp.int32, (P, A), 1)

    def pick(q, ptr):
        return jnp.take_along_axis(q, ptr[:, :, None], axis=2)[..., 0]

    ptr0 = jnp.zeros((P, A), jnp.int32)
    rem0 = jnp.where(ptr0 < count, pick(qbytes, ptr0), 0.0)
    t0 = jnp.zeros((P,), jnp.float32)

    def step(state, _):
        t, rem, ptr = state
        active = ptr < count
        req = jnp.where(active, pick(qbw, ptr), 0.0)
        total = jnp.sum(req, axis=1)
        scale = jnp.minimum(1.0, bw_sys / jnp.maximum(total, _TINY))
        alloc = req * scale[:, None]
        runtime = jnp.where(active, rem / jnp.maximum(alloc, _TINY), jnp.inf)
        any_active = jnp.any(active, axis=1)
        dt = jnp.where(any_active, jnp.min(runtime, axis=1), 0.0)
        rem = jnp.maximum(rem - dt[:, None] * alloc, 0.0)
        fin = jnp.argmin(runtime, axis=1)
        fin_oh = (iota_a == fin[:, None]) & any_active[:, None]
        ptr = ptr + fin_oh.astype(jnp.int32)
        nxt_active = ptr < count
        nxt = pick(qbytes, ptr)
        rem = jnp.where(fin_oh, jnp.where(nxt_active, nxt, 0.0), rem)
        return (t + dt, rem, ptr), None

    with jax.named_scope("fitness.events"):
        (t, _, _), _ = jax.lax.scan(step, (t0, rem0, ptr0), None, length=G)
    return t


def simulate_decoded(sched: DecodedSchedule, lat: jnp.ndarray, bw: jnp.ndarray,
                     bw_sys: float) -> jnp.ndarray:
    """Makespan (seconds, f32) of one decoded schedule."""
    qlat, qbw = _queue_tables(sched, lat.astype(jnp.float32),
                              bw.astype(jnp.float32))
    return simulate_tables(qlat[None], qbw[None], sched.count[None],
                           bw_sys)[0]


@partial(jax.jit, static_argnames=("num_accels",))
def simulate(accel: jnp.ndarray, prio: jnp.ndarray, lat: jnp.ndarray,
             bw: jnp.ndarray, bw_sys: float, num_accels: int) -> jnp.ndarray:
    """Makespan of one *encoded* individual."""
    sched = decode(accel, prio, num_accels)
    return simulate_decoded(sched, lat, bw, bw_sys)


@partial(jax.jit, static_argnames=("num_accels",))
def simulate_population(accel: jnp.ndarray, prio: jnp.ndarray, lat: jnp.ndarray,
                        bw: jnp.ndarray, bw_sys: float, num_accels: int) -> jnp.ndarray:
    """(P,) makespans for a whole population — the M3E fitness hot-loop."""
    latf = lat.astype(jnp.float32)
    bwf = bw.astype(jnp.float32)

    def tables_one(a, p):
        with jax.named_scope("fitness.decode"):
            sched = decode(a, p, num_accels)
        with jax.named_scope("fitness.tables"):
            qlat, qbw = _queue_tables(sched, latf, bwf)
        return qlat, qbw, sched.count

    qlat, qbw, count = jax.vmap(tables_one)(accel, prio)
    return simulate_tables(qlat, qbw, count, bw_sys)


# ---------------------------------------------------------------------------
# float64 host oracle
# ---------------------------------------------------------------------------
def simulate_numpy(queues, lat, bw, bw_sys) -> float:
    """Reference event simulation.

    queues: list (len A) of job-id lists in execution order.
    lat/bw: (G, A) float64 job-analysis arrays.
    """
    lat = np.asarray(lat, dtype=np.float64)
    bw = np.maximum(np.asarray(bw, dtype=np.float64), _BW_FLOOR)
    A = len(queues)
    ptr = [0] * A
    rem = np.zeros(A)
    req = np.zeros(A)
    active = np.zeros(A, dtype=bool)
    for a in range(A):
        if queues[a]:
            j = queues[a][0]
            rem[a] = lat[j, a] * bw[j, a]
            req[a] = bw[j, a]
            active[a] = True
            ptr[a] = 1
    t = 0.0
    while active.any():
        live_req = np.where(active, req, 0.0)
        total = live_req.sum()
        scale = min(1.0, bw_sys / total) if total > 0 else 1.0
        alloc = live_req * scale
        with np.errstate(divide="ignore", invalid="ignore"):
            runtime = np.where(active, rem / np.maximum(alloc, _TINY), np.inf)
        dt = runtime.min()
        t += dt
        rem = np.maximum(rem - dt * alloc, 0.0)
        for a in range(A):
            if active[a] and rem[a] <= 1e-12 * max(1.0, dt * alloc[a]):
                if ptr[a] < len(queues[a]):
                    j = queues[a][ptr[a]]
                    rem[a] = lat[j, a] * bw[j, a]
                    req[a] = bw[j, a]
                    ptr[a] += 1
                else:
                    active[a] = False
                    rem[a] = 0.0
                    req[a] = 0.0
    return t


def throughput(total_flops: float, makespan) -> jnp.ndarray:
    """Objective (Section IV-C): group FLOPs per second."""
    return total_flops / jnp.maximum(makespan, _TINY)
