"""Genome encoding/decoding (Section IV-A, Fig. 5a).

An individual = two genomes of length G (group size):

  accel genome   int32 in [0, A)   — sub-accelerator selection per job
  prio genome    float32 in [0, 1) — job priority (0 = highest)

Decoding produces, per sub-accelerator, the ordered queue of its jobs: one
stable sort on (accel, prio) groups the jobs by accelerator in priority
order.  ``decode`` materializes the queues as dense (A, G) arrays of job
indices plus a per-accelerator count, for the Pallas makespan kernel and the
host helpers; the jnp simulator (``repro.core.bw_allocator``) runs on the
sort's grouped order itself.  Everything is jit/vmap-friendly.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Individual(NamedTuple):
    accel: jnp.ndarray   # (G,) int32
    prio: jnp.ndarray    # (G,) float32


class Population(NamedTuple):
    accel: jnp.ndarray   # (P, G) int32
    prio: jnp.ndarray    # (P, G) float32

    @property
    def size(self) -> int:
        return self.accel.shape[0]


class DecodedSchedule(NamedTuple):
    queue: jnp.ndarray   # (A, G) int32 job ids, first count[a] valid
    count: jnp.ndarray   # (A,)  int32


def random_population(key: jax.Array, pop: int, group: int, accels: int) -> Population:
    ka, kp = jax.random.split(key)
    return Population(
        accel=jax.random.randint(ka, (pop, group), 0, accels, dtype=jnp.int32),
        prio=jax.random.uniform(kp, (pop, group), dtype=jnp.float32),
    )


@partial(jax.jit, static_argnames=("num_accels",))
def decode(accel: jnp.ndarray, prio: jnp.ndarray, num_accels: int) -> DecodedSchedule:
    """Decode one individual into per-accelerator ordered queues.

    ONE stable lexicographic sort on the (accel, prio) key pair groups
    the jobs by accelerator in priority order (ties by job id, exactly
    like a per-accelerator stable priority sort) — instead of one sort
    per accelerator.  Queue ``a`` is the slice at ``offset[a]`` of the
    grouped job-id vector; slots past ``count[a]`` are padding from the
    neighbouring groups (never read by the simulators, which gate on
    ``count``)."""
    G = accel.shape[0]
    job_ids = jnp.arange(G, dtype=jnp.int32)
    _, _, grouped = jax.lax.sort((accel, prio, job_ids), num_keys=2)
    count = jnp.sum(accel[None, :] == jnp.arange(num_accels,
                                                 dtype=accel.dtype)[:, None],
                    axis=1, dtype=jnp.int32)
    offset = jnp.cumsum(count) - count               # exclusive prefix sum
    idx = jnp.minimum(offset[:, None] + job_ids[None, :], G - 1)
    return DecodedSchedule(queue=grouped[idx], count=count)


def decode_to_lists(accel, prio, num_accels: int):
    """Host-side convenience: list of job-id lists per accelerator."""
    accel = np.asarray(accel)
    prio = np.asarray(prio)
    out = []
    for a in range(num_accels):
        ids = np.where(accel == a)[0]
        out.append([int(i) for i in ids[np.argsort(prio[ids], kind="stable")]])
    return out
