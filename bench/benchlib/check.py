"""The comparison that decides ``correct``.

Every answer due in the window is judged against the plain reference
(``benchlib.reference``), once the window has closed:

``missing``      requests due in the window that got no schedule, or one
                 with a non-finite fitness.  Limit 0.
``fitness_gap``  the widest relative gap between the fitness the system
                 reported for a schedule and the reference's float64
                 fitness of that schedule on that request's own problem
                 (tables built by the reference's cost model).  It covers
                 host analysis, the fitness simulation, and routing: a
                 schedule handed to another request reads that request's
                 tables.  A schedule that leaves a job without a core
                 reads as an infinite gap.
``mean_shortfall``  the mean over the answers of the share by which a
                 schedule's reference fitness falls short of the
                 Herald-like greedy mapper's on the same problem (0 where
                 the search beats it).  It covers the search: a search
                 that does not evolve returns the best of random
                 schedules, far below the greedy mapper on most problems.
                 The mean, not the widest: a sound search now and then
                 ends far below the greedy mapper on one problem (58% on
                 one of 96 rows in one chip run), so the widest swings up
                 to where a search that does not evolve reads.

Each number's limit is in the configuration file (``limits``); PERF.md
gives the readings each was set from.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional

import numpy as np

from benchlib.reference import Problem

NUMBERS = ("missing", "fitness_gap", "mean_shortfall")


class Judge:
    """Reference problems and greedy fitnesses, cached per problem."""

    def __init__(self):
        self._problems: Dict[tuple, Problem] = {}
        self._greedy: Dict[tuple, float] = {}

    @staticmethod
    def _key(request: dict) -> tuple:
        return (request["mix"], request["setting"], float(request["bw_gb"]),
                int(request["group_size"]), int(request["layout_seed"]))

    def problem(self, request: dict) -> Problem:
        key = self._key(request)
        if key not in self._problems:
            self._problems[key] = Problem(*key)
        return self._problems[key]

    def greedy(self, request: dict) -> float:
        key = self._key(request)
        if key not in self._greedy:
            p = self.problem(request)
            self._greedy[key] = p.fitness(*p.greedy())
        return self._greedy[key]

    def compare(self, answers: Iterable[dict], due: int,
                control_dtype=None) -> Dict[str, float]:
        """The numbers of one run.  ``answers`` are dicts with the
        request, ``fitness`` (as reported), ``accel`` and ``prio``;
        ``due`` is how many requests were due.  With ``control_dtype``
        the reported fitness is replaced by the reference's own in that
        lower precision: the control, which must come out not correct."""
        gap = short = 0.0
        answered = 0
        for a in answers:
            p = self.problem(a["request"])
            try:
                ref = p.fitness(a["accel"], a["prio"])
            except ValueError:
                gap = math.inf
                continue
            got = (a["fitness"] if control_dtype is None
                   else p.fitness(a["accel"], a["prio"], control_dtype))
            if not math.isfinite(got):
                continue
            answered += 1
            gap = max(gap, abs(got - ref) / ref)
            short += max(0.0, 1.0 - ref / self.greedy(a["request"]))
        return {"missing": float(due - answered), "fitness_gap": gap,
                "mean_shortfall": short / max(answered, 1)}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)


def lines(numbers: Dict[str, float], limits: Dict[str, float]) -> list:
    return [f"{k} {numbers[k]!r} limit {limits[k]!r}" for k in NUMBERS]


def control_dtype(name: Optional[str]):
    if name is None:
        return None
    if name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.dtype(name)
