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

The scan runs on the decode sort's grouped order and gathers nothing.  Each
job's own work (latency x bw on the core it is mapped to) and bw are dense
selects over ``A``; the (accel, prio) sort that groups the jobs carries them
along, so core ``a``'s queue is the flat slice ``[start[a], end[a])``.  The
carry holds each core's flat position and the head job's remaining bytes and
bw; an event advances only the finishing core and fetches its next job with
one exact one-hot select over ``G``.  Its parts run under the named scopes
``fitness.decode`` (the sort), ``fitness.tables`` (per-job work and the
queue heads) and ``fitness.events`` (the scan): metadata only, which a
device trace reads back.

``simulate_numpy`` is the float64 oracle used by the tests and as the
reference for the Pallas ``makespan`` kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoding import DecodedSchedule

_BW_FLOOR = 1e-3    # bytes/s; keeps rem/alloc well-defined
_TINY = 1e-30


def _select(x: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """Sum of ``x`` over the last axis where ``onehot``: an exact pick
    when at most one lane is set (0 when none is)."""
    return jnp.sum(jnp.where(onehot, x, 0.0), axis=-1)


def _grouped_work(accel: jnp.ndarray, prio: jnp.ndarray, lat: jnp.ndarray,
                  bw: jnp.ndarray, num_accels: int):
    """One individual's jobs in execution order, grouped by core: (G,)
    bytes and (G,) bw of each job on its own core, and (A,) jobs per core.

    The order is ``decode``'s: one stable sort on (accel, prio), ties by
    job id; the payloads ride along in the sort."""
    iota_a = jnp.arange(num_accels, dtype=accel.dtype)
    with jax.named_scope("fitness.tables"):
        own = accel[:, None] == iota_a[None, :]              # (G, A)
        lat_own = _select(lat, own)
        bw_own = jnp.maximum(_select(bw, own), _BW_FLOOR)
        work = lat_own * bw_own          # remaining work, paper's CurJobs
    with jax.named_scope("fitness.decode"):
        _, _, work, bw_own = jax.lax.sort((accel, prio, work, bw_own),
                                          num_keys=2)
        count = jnp.sum(accel[None, :] == iota_a[:, None], axis=1,
                        dtype=jnp.int32)      # G on the lanes
    return work, bw_own, count


def simulate_grouped(work: jnp.ndarray, wbw: jnp.ndarray, count: jnp.ndarray,
                     bw_sys) -> jnp.ndarray:
    """(P,) makespans from jobs in grouped order: work/wbw (P, G) each
    job's bytes and bw on its core, core ``a``'s queue at flat positions
    ``[start[a], start[a] + count[a])``; count (P, A).

    The whole population advances through one event scan of dense (P, A)
    arrays; only the finishing core moves, and its next job is one
    one-hot select over ``G`` per individual (no gather, which the TPU
    runs element by element)."""
    P, G = work.shape
    A = count.shape[1]
    iota_a = jax.lax.broadcasted_iota(jnp.int32, (P, A), 1)
    iota_g = jax.lax.broadcasted_iota(jnp.int32, (P, G), 1)
    with jax.named_scope("fitness.tables"):
        end = jnp.cumsum(count, axis=1)
        pos0 = end - count
        head = iota_g[:, None, :] == pos0[:, :, None]        # (P, A, G)
        rem0 = jnp.where(pos0 < end, _select(work[:, None, :], head), 0.0)
        bw0 = _select(wbw[:, None, :], head)
    t0 = jnp.zeros((P,), jnp.float32)

    def step(state, _):
        t, rem, cur_bw, pos = state
        active = pos < end
        req = jnp.where(active, cur_bw, 0.0)
        total = jnp.sum(req, axis=1)
        scale = jnp.minimum(1.0, bw_sys / jnp.maximum(total, _TINY))
        alloc = req * scale[:, None]
        runtime = jnp.where(active, rem / jnp.maximum(alloc, _TINY), jnp.inf)
        any_active = jnp.any(active, axis=1)
        dt = jnp.where(any_active, jnp.min(runtime, axis=1), 0.0)
        rem = jnp.maximum(rem - dt[:, None] * alloc, 0.0)
        fin = jnp.argmin(runtime, axis=1)
        fin_oh = (iota_a == fin[:, None]) & any_active[:, None]
        pos = pos + fin_oh.astype(jnp.int32)
        nxt_pos = jnp.sum(jnp.where(fin_oh, pos, 0), axis=1)
        nxt_live = nxt_pos < jnp.sum(jnp.where(fin_oh, end, 0), axis=1)
        nxt = iota_g == nxt_pos[:, None]                     # (P, G)
        nxt_rem = jnp.where(nxt_live, _select(work, nxt), 0.0)
        rem = jnp.where(fin_oh, nxt_rem[:, None], rem)
        cur_bw = jnp.where(fin_oh, _select(wbw, nxt)[:, None], cur_bw)
        return (t + dt, rem, cur_bw, pos), None

    with jax.named_scope("fitness.events"):
        (t, _, _, _), _ = jax.lax.scan(step, (t0, rem0, bw0, pos0), None,
                                       length=G)
    return t


def _encode(sched: DecodedSchedule):
    """The genome a decoded schedule came from, up to priorities: each
    job's core, and its slot in that core's queue as its priority."""
    A, G = sched.queue.shape
    slot = jnp.arange(G, dtype=jnp.int32)
    member = slot[None, :] < sched.count[:, None]            # (A, G)
    hit = member[None] & (sched.queue[None] == slot[:, None, None])
    accel = jnp.sum(jnp.where(hit, jnp.arange(A)[None, :, None], 0),
                    axis=(1, 2), dtype=jnp.int32)
    prio = jnp.sum(jnp.where(hit, slot[None, None, :], 0), axis=(1, 2))
    return accel, prio.astype(jnp.float32)


def simulate_decoded(sched: DecodedSchedule, lat: jnp.ndarray, bw: jnp.ndarray,
                     bw_sys: float) -> jnp.ndarray:
    """Makespan (seconds, f32) of one decoded schedule."""
    accel, prio = _encode(sched)
    return simulate(accel, prio, lat, bw, bw_sys, sched.queue.shape[0])


@partial(jax.jit, static_argnames=("num_accels",))
def simulate(accel: jnp.ndarray, prio: jnp.ndarray, lat: jnp.ndarray,
             bw: jnp.ndarray, bw_sys: float, num_accels: int) -> jnp.ndarray:
    """Makespan of one *encoded* individual."""
    return simulate_population(accel[None], prio[None], lat, bw, bw_sys,
                               num_accels)[0]


@partial(jax.jit, static_argnames=("num_accels",))
def simulate_population(accel: jnp.ndarray, prio: jnp.ndarray, lat: jnp.ndarray,
                        bw: jnp.ndarray, bw_sys: float, num_accels: int) -> jnp.ndarray:
    """(P,) makespans for a whole population — the M3E fitness hot-loop."""
    latf = lat.astype(jnp.float32)
    bwf = bw.astype(jnp.float32)
    work, wbw, count = jax.vmap(
        lambda a, p: _grouped_work(a, p, latf, bwf, num_accels))(accel, prio)
    return simulate_grouped(work, wbw, count, bw_sys)


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
