"""Work of the fitness kernel, counted from its shapes, and its roofline.

The fitness of MAGMA is Algorithm 1, the bandwidth allocator's event
simulation, run for every individual of a population.  What the algorithm
must do, whatever implements it:

- operations: per individual, ``G`` events, each over the ``A`` cores:
  the sum of the live requests, one allocation (multiply), one drain time
  (divide), the minimum, and the remaining bytes (multiply, subtract):
  6 per core per event;
- bytes: the genomes read once (an int32 core and a float32 priority per
  job), the job analysis tables read once for the whole population
  (float32 latency and bandwidth per job and core), and one float32
  makespan written per individual.

Nothing is counted that an implementation may choose to do (queue tables
gathered per individual, padding, recomputation), so the share of the
roofline cannot read high.
"""
from __future__ import annotations

import json
import os
from typing import Tuple

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def fitness_work(P: int, A: int, G: int) -> Tuple[float, float]:
    """(operations, bytes) of one population evaluation."""
    flops = 6.0 * P * G * A
    nbytes = 8.0 * P * G + 8.0 * G * A + 4.0 * P
    return flops, nbytes


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a kind not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> Tuple[float, str]:
    """(percent of the roofline, the bound that applies)."""
    pk = peaks(device_kind)
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
