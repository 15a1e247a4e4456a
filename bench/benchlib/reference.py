"""The plain reference the benchmark judges answers by.

Given a request (task set, accelerator setting, system bandwidth, group
size, seed) it builds the job group, profiles every (job,
sub-accelerator) pair with the analytical cost model, decodes a schedule
into per-core queues and runs Algorithm 1 of MAGMA (arXiv:2104.13997,
the bandwidth allocator) as an event simulation.  Everything runs on the
host in numpy, in float64 unless a lower ``dtype`` is asked for (the
control).  It imports nothing of the system under test.

Semantics, from the paper and the repository's documented contract:

- a group takes ``group_size`` jobs round-robin over the task set's
  models, each model starting at a layer drawn by
  ``numpy.random.default_rng(seed).integers(0, len(layers))``;
- a job's no-stall latency is its MACs over the array's PEs at the
  spatial utilization of its dataflow (HB maps K x C, LB maps rows x R*S,
  LB pays 3x on FC layers), at 200 MHz; its required bandwidth is the
  bytes it moves (tiles re-fetched when the other operand does not fit
  half the scratchpad) over that latency;
- sub-accelerator ``a`` runs the jobs whose gene is ``a`` in ascending
  priority (ties by job id); at every instant the live jobs share the
  system bandwidth in proportion to their requests when these exceed it;
  one job completes per event;
- fitness is throughput: the group's FLOPs over the makespan.
"""
from __future__ import annotations

import math
from functools import lru_cache

import numpy as np

from benchlib import zoo

GB = 1024 ** 3
FREQ_HZ = 200e6
PE_W = 64
LB_FC_PENALTY = 3.0
BW_FLOOR = 1e-3          # bytes/s: a job never asks for less


def _eff(dim: int, size: int) -> float:
    if dim <= 0:
        return 1.0 / size
    return dim / (math.ceil(dim / size) * size)


@lru_cache(maxsize=None)
def profile(layer: tuple, pe_h: int, dataflow: str, sg_bytes: int):
    """(no-stall latency s, required bandwidth B/s) of one layer on one
    ``pe_h x 64`` array."""
    kind, N, K, C, Y, X, R, S, stride = layer
    macs = N * K * C * Y * X * R * S
    w_bytes = C * R * S if kind == "dwconv" else K * C * R * S
    in_bytes = (N * C * (Y * stride + R - stride) * (X * stride + S - stride))
    out_bytes = N * K * Y * X
    pes = pe_h * PE_W
    half = sg_bytes / 2
    if dataflow == "HB":
        util = _eff(K, pe_h) * _eff(C, PE_W)
        latency = macs / (pes * util) / FREQ_HZ
        moved = w_bytes + in_bytes * max(1, math.ceil(w_bytes / half)) \
            + out_bytes
    elif dataflow == "LB":
        util = _eff(Y * max(1, N), pe_h) * _eff(R * S, PE_W)
        cycles = macs / (pes * util)
        if kind == "fc":
            cycles *= LB_FC_PENALTY
        latency = cycles / FREQ_HZ
        moved = in_bytes + w_bytes * max(1, math.ceil(in_bytes / half)) \
            + out_bytes
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")
    return latency, moved / latency


def group_layers(task: str, group_size: int, seed: int):
    """The ``group_size`` layers of one job group, in job order."""
    models = zoo.TASK_SETS[task]
    rng = np.random.default_rng(seed)
    streams = []
    for m in models:
        layers = zoo.MODELS[m]()
        start = int(rng.integers(0, len(layers)))
        streams.append(layers[start:] + layers[:start])
    return [streams[i % len(streams)][(i // len(streams))
                                      % len(streams[i % len(streams)])]
            for i in range(group_size)]


class Problem:
    """One request's job analysis: ``lat``/``bw`` (G, A) float64, the
    group's FLOPs and the system bandwidth in B/s."""

    def __init__(self, task: str, setting: str, bw_gb: float,
                 group_size: int, seed: int):
        layers = group_layers(task, group_size, seed)
        subs = zoo.sub_accels(setting)
        prof = np.array([[profile(l, *s) for s in subs] for l in layers])
        self.lat = prof[..., 0]
        self.bw = prof[..., 1]
        self.job_flops = np.array([2.0 * math.prod(l[1:8]) for l in layers])
        self.flops = float(self.job_flops.sum())
        self.bw_sys = float(bw_gb) * GB
        self.num_accels = len(subs)

    @classmethod
    def of(cls, request: dict) -> "Problem":
        return cls(request["mix"], request["setting"], request["bw_gb"],
                   request["group_size"], request["seed"])

    def queues(self, accel, prio):
        """Per-core job queues: jobs of gene ``a`` by ascending priority,
        ties by job id."""
        accel = np.asarray(accel)
        prio = np.asarray(prio, dtype=np.float64)
        if accel.shape != (self.lat.shape[0],) or accel.min() < 0 \
                or accel.max() >= self.num_accels:
            raise ValueError("schedule does not assign every job to a core")
        return [list(np.flatnonzero(accel == a)[
            np.argsort(prio[accel == a], kind="stable")])
            for a in range(self.num_accels)]

    def makespan(self, queues, dtype=np.float64) -> float:
        """Algorithm 1: the event simulation of ``queues``, computed in
        ``dtype`` (float64 for the reference; a lower type for the
        control).  One job completes per event, the one whose remaining
        bytes drain first at its allocation."""
        cast = np.dtype(dtype).type
        lat = self.lat.astype(dtype)
        bw = np.maximum(self.bw, BW_FLOOR).astype(dtype)
        A = self.num_accels
        ptr = [0] * A
        rem = np.zeros(A, dtype)
        req = np.zeros(A, dtype)

        def load(a):
            if ptr[a] < len(queues[a]):
                j = queues[a][ptr[a]]
                rem[a] = lat[j, a] * bw[j, a]
                req[a] = bw[j, a]
                ptr[a] += 1
            else:
                rem[a] = req[a] = cast(0)

        for a in range(A):
            load(a)
        t = cast(0)
        bw_sys = cast(self.bw_sys)
        for _ in range(sum(len(q) for q in queues)):
            live = req > 0
            total = req.sum(dtype=dtype)
            scale = min(cast(1), bw_sys / total)
            alloc = req * scale
            runtime = np.where(live, rem / np.where(live, alloc, cast(1)),
                               np.inf).astype(dtype)
            fin = int(np.argmin(runtime))
            dt = runtime[fin]
            t = cast(t + dt)
            rem = np.maximum(rem - dt * alloc, cast(0)).astype(dtype)
            load(fin)
        return float(t)

    def fitness(self, accel, prio, dtype=np.float64) -> float:
        """Throughput (FLOP/s) of an encoded schedule."""
        ms = self.makespan(self.queues(accel, prio), dtype)
        return float(np.dtype(dtype).type(self.flops)
                     / np.dtype(dtype).type(ms))

    def greedy(self):
        """The paper's Herald-like baseline mapper: jobs largest-compute
        first, each to the core where it would finish earliest by its
        no-stall latency, queued in assignment order.  Returns the
        schedule as (accel, prio)."""
        G, A = self.lat.shape
        finish = np.zeros(A)
        accel = np.zeros(G, dtype=np.int64)
        prio = np.zeros(G)
        for rank, g in enumerate(np.argsort(-self.job_flops, kind="stable")):
            eft = finish + self.lat[g]
            a = int(np.argmin(eft))
            accel[g] = a
            finish[a] = eft[a]
            prio[g] = rank / G
        return accel, prio
