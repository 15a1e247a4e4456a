"""The seeded request generator every traffic file is read by.

A traffic file (``bench/traffic/<name>.json``) holds only parameters; the
same seed always gives the same requests.  Every seed offers the same
work at the same instants, in another order: the arrival times are one
fixed realisation of the traffic's arrival process (``realisation``, 0
for every cell; the rate sweep ``bench/knee.py`` draws others), and the
run's seed only decides which request comes at which instant.  Each
categorical field (task set, bandwidth, and the configuration's
setting) takes its listed values
in equal shares, and in every block of consecutive requests as long as
the list each value comes once, in an order the seed draws.  So a run's
seed changes the order of the work and the search seeds, not its amount
or its timing.

Arrival processes (times are the requests' due times in seconds from the
window's start, all inside ``[0, seconds)``):

``poisson``  ``round(rate_hz * seconds)`` arrivals at uniform random
             times: a Poisson process of rate ``rate_hz`` conditioned on
             its count.
``closed``   no arrival times: the offline driver runs the grid of every
             (task set, bandwidth) pair back to back.

The Poisson arithmetic follows ``repro.stream.workloads`` (the system's
own generator), with the count fixed instead of the span.  Every request
has one priority class, no deadline, and the configuration's group size.
"""
from __future__ import annotations

import numpy as np

SEED_RANGE = 2 ** 31 - 1


def _pool(values, n: int, rng: np.random.Generator) -> list:
    """``n`` draws taking ``values`` in equal shares: each block of
    ``len(values)`` consecutive draws holds every value once, in an order
    ``rng`` permutes."""
    if not values:
        raise ValueError("a traffic pool needs at least one value")
    out = []
    while len(out) < n:
        out.extend(values[i] for i in rng.permutation(len(values)))
    return out[:n]


def _arrivals(traffic: dict, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    if traffic["arrival"] == "poisson":
        return np.sort(rng.uniform(0.0, seconds, n))
    raise ValueError(f"unknown arrival process {traffic['arrival']!r}")


def requests(traffic: dict, config: dict, seed: int, seconds: float,
             realisation: int = 0) -> list:
    """The open-loop requests of one run: dicts with ``uid``, ``due_s``,
    ``mix``, ``setting``, ``bw_gb``, ``group_size`` and ``seed``, sorted
    by due time.  ``realisation`` picks the arrival times."""
    n = max(1, int(round(traffic["rate_hz"] * seconds)))
    due = _arrivals(traffic, n, seconds, np.random.default_rng(realisation))
    rng = np.random.default_rng(seed)
    mixes = _pool(traffic["mixes"], n, rng)
    settings = _pool(config["settings"], n, rng)
    bws = _pool(traffic["bw_gb"], n, rng)
    seeds = rng.integers(0, SEED_RANGE, n)
    return [{"uid": i, "due_s": float(due[i]), "mix": mixes[i],
             "setting": settings[i], "bw_gb": float(bws[i]),
             "group_size": int(config["group_size"]), "seed": int(seeds[i])}
            for i in range(n)]


def grid(traffic: dict, config: dict) -> list:
    """The offline grid's scenarios: every (setting, task set, bandwidth)
    of the configuration and traffic, as request dicts without a seed."""
    return [{"mix": m, "setting": s, "bw_gb": float(b),
             "group_size": config["group_size"]}
            for s in config["settings"] for m in traffic["mixes"]
            for b in traffic["bw_gb"]]


def call_seeds(rng: np.random.Generator, k: int) -> list:
    """``k`` fresh search seeds for one offline grid call."""
    return [int(s) for s in rng.integers(0, SEED_RANGE, k)]
