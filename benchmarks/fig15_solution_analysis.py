"""Fig. 15: analysis of found solutions — the BW-allocation timeline of
Herald-like vs MAGMA on (Mix, S5, BW=1 GB/s).

Paper's observation: MAGMA *spreads* BW-intensive jobs across the runtime
to balance bandwidth demand; Herald-like front-loads them and stalls on
contention.  We replay both mappings through the event simulation and
report the requested-BW-over-time profile: MAGMA should show (i) a lower
peak/mean demand ratio and (ii) a shorter finish time.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import GB, std_parser
from repro.core import M3E
from repro.core.encoding import decode_to_lists
from repro.costmodel import get_setting
from repro.workloads import build_task_groups


def timeline(queues, lat, bw, bw_sys, n_bins=20):
    """Replay the BW allocator, recording requested BW per time bin."""
    lat = np.asarray(lat, float)
    bw = np.maximum(np.asarray(bw, float), 1e-3)
    A = len(queues)
    ptr = [0] * A
    rem = np.zeros(A)
    req = np.zeros(A)
    active = np.zeros(A, bool)
    for a in range(A):
        if queues[a]:
            j = queues[a][0]
            rem[a], req[a], active[a], ptr[a] = \
                lat[j, a] * bw[j, a], bw[j, a], True, 1
    t = 0.0
    events = []          # (t_start, dt, total requested BW)
    while active.any():
        live = np.where(active, req, 0.0)
        total = live.sum()
        scale = min(1.0, bw_sys / total) if total > 0 else 1.0
        alloc = live * scale
        with np.errstate(divide="ignore", invalid="ignore"):
            rt = np.where(active, rem / np.maximum(alloc, 1e-30), np.inf)
        dt = rt.min()
        events.append((t, dt, total))
        t += dt
        rem = np.maximum(rem - dt * alloc, 0.0)
        for a in range(A):
            if active[a] and rem[a] <= 1e-12 * max(1.0, dt * alloc[a]):
                if ptr[a] < len(queues[a]):
                    j = queues[a][ptr[a]]
                    rem[a], req[a], ptr[a] = \
                        lat[j, a] * bw[j, a], bw[j, a], ptr[a] + 1
                else:
                    active[a], rem[a], req[a] = False, 0.0, 0.0
    # bin the demand curve
    bins = np.zeros(n_bins)
    for t0, dt, demand in events:
        b = min(int(t0 / t * n_bins), n_bins - 1)
        bins[b] += demand * dt
    widths = t / n_bins
    return t, bins / widths


def run(budget=2_000, group_size=100):
    m3e = M3E(accel=get_setting("S5"), bw_sys=1 * GB)
    group = build_task_groups("Mix", group_size=group_size, seed=0)[0]
    fit = m3e.prepare(group)
    out = {}
    print("== Fig 15: BW demand over time, (Mix, S5, BW=1) ==")
    for method in ("herald_like", "magma"):
        res = m3e.search(group, method=method, budget=budget, seed=0)
        queues = decode_to_lists(res.best_accel, res.best_prio,
                                 fit.num_accels)
        t, curve = timeline(queues, fit.table.lat, fit.table.bw, 1 * GB)
        peak_over_mean = curve.max() / max(curve.mean(), 1e-30)
        out[method] = (t, peak_over_mean)
        bars = "".join("#" if v > curve.mean() else "."
                       for v in curve)
        print(f"{method:12s} finish={t*1e3:8.2f} ms  "
              f"peak/mean demand={peak_over_mean:5.2f}  [{bars}]")
    assert out["magma"][0] <= out["herald_like"][0] * 1.02, \
        "MAGMA should finish no later than Herald-like"
    return out


def main():
    args = std_parser(__doc__).parse_args()
    budget = 10_000 if args.full else args.budget
    run(budget, args.group_size)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
