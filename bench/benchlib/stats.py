"""Latency arithmetic of the benchmark.

Percentiles take the nearest rank from above (``numpy.percentile`` with
``method="higher"``, as ``repro.obs.stats`` does): with a few hundred
samples, interpolation would read below the observed tail.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile; None for no values, inf when any value is
    (a request never routed counts as missing every limit)."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q,
                               method="higher"))


def mean(values: Sequence[float]) -> Optional[float]:
    return float(np.mean(values)) if len(values) else None


def latencies(requests) -> list:
    """Routed minus due of every request; one never routed counts as
    infinitely late."""
    return [r["done_s"] - r["due_s"] if r["done_s"] is not None
            else math.inf for r in requests]
