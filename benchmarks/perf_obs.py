"""Observability benchmark: tracing overhead, span completeness, export.

The obs layer's contract is "watch everything, change nothing": spans
and counters are host-side bookkeeping only, never inside a jitted
call.  This benchmark holds it to that, with four gates:

  overhead       the SAME warmed service object replays one trace with
                 ``ObsConfig(enabled=True)`` vs disabled, interleaved
                 for ``--reps`` (side order alternates per rep) with
                 per-side medians; the instrumented side must sustain
                 >= 97% of the plain side's scenarios/sec (<3%
                 overhead; ``--quick`` loosens the gate to 90% — its
                 ~0.15 s walls sit inside the CI container's
                 scheduling noise);
  completeness   with obs on, every scenario's span tree is complete:
                 analyze -> admit -> queue_wait -> dispatch -> device ->
                 route, one of each per uid, well-ordered; a separate
                 memoized pass checks memo.lookup / memo.record spans on
                 the cold run and memo.lookup(outcome=exact hit) spans
                 on the replay;
  export         the Chrome trace written by ``export_trace`` parses,
                 round-trips through ``read_trace``, and summarizes to
                 finite per-stage percentiles;
  bit-identity   every schedule from the instrumented run equals the
                 standalone ``run_sweep`` row for its (scenario, seed) —
                 tracing cannot touch the math.

Plus the standing RecompileGuard gate: zero jit compiles after warmup
on either side.  Results go to stdout and ``BENCH_obs.json`` (schema in
benchmarks/README.md); exits non-zero on any non-finite number.

    PYTHONPATH=src python -m benchmarks.perf_obs [--quick]
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import tempfile
import time

import jax
import numpy as np

from repro.core.sweep import run_sweep
from repro.lint.runtime import RecompileGuard
from repro.memo import ScheduleMemo
from repro.obs import LIFECYCLE_STAGES, read_trace, summarize
from repro.stream import (StreamConfig, StreamingScheduler, TraceConfig,
                          analyze_serial, generate_trace)

SCENARIO_STAGES = ("analyze", "admit", "queue_wait", "dispatch",
                   "device", "route")


def _median(side_metrics) -> dict:
    keys = side_metrics[0].keys()
    return {k: float(np.median([m[k] for m in side_metrics])) for k in keys}


def _service(budget, batch_rows, workers, obs=None):
    return StreamingScheduler(
        budget=budget,
        stream=StreamConfig(batch_rows=batch_rows,
                            analysis_workers=workers, obs=obs))


def _check_span_trees(spans, uids) -> None:
    """Every scenario has exactly one span per lifecycle stage, and the
    stages nest in causal order."""
    by_uid = collections.defaultdict(dict)
    for s in spans:
        if s.scope is not None and s.name in SCENARIO_STAGES:
            assert s.name not in by_uid[s.scope], \
                f"duplicate {s.name} span for uid {s.scope}"
            by_uid[s.scope][s.name] = s
    for uid in uids:
        tree = by_uid.get(uid)
        assert tree is not None, f"uid {uid}: no spans at all"
        missing = [n for n in SCENARIO_STAGES if n not in tree]
        assert not missing, f"uid {uid}: missing spans {missing}"
        # causal order: each stage starts no earlier than the previous
        # one (analyze/admit overlap the queue, so compare starts)
        for a, b in zip(SCENARIO_STAGES, SCENARIO_STAGES[1:]):
            assert tree[b].start_s >= tree[a].start_s - 1e-9, \
                (uid, a, b, tree[a], tree[b])
        assert tree["device"].end_s <= tree["route"].end_s + 1e-9, uid


def _check_bit_identical(results, budget: int) -> None:
    for r in results:
        fit = analyze_serial([r.request])[0].fit
        ref = run_sweep([fit], budget=budget, seeds=[r.request.seed])
        assert r.best_fitness == ref.best_fitness[0, 0], r.request
        np.testing.assert_array_equal(r.best_accel, ref.best_accel[0, 0])
        np.testing.assert_array_equal(r.history_best,
                                      ref.history_best[0, 0])


def run_overhead(num_scenarios, group_size, budget, batch_rows, workers,
                 reps, seed, gate) -> dict:
    trace = generate_trace(TraceConfig(
        num_scenarios=num_scenarios, group_size=group_size,
        mixes=("Heavy", "Light"), settings=("S2",),
        bw_ladder_gb=(1.0, 4.0, 16.0), seed=seed))
    # no memo on either side: memo work would differ between runs and
    # the comparison must isolate the tracing itself
    off = _service(budget, batch_rows, workers)
    on = _service(budget, batch_rows, workers, obs={"enabled": True})

    print(f"== perf: obs overhead ({num_scenarios} scenarios, "
          f"G={group_size}, budget={budget}, batch_rows={batch_rows}, "
          f"{len(jax.devices())} device(s)) ==")
    guard = RecompileGuard(label="perf_obs")
    with guard:
        off.warmup(trace)
        on.warmup(trace)      # same compat keys — cache already warm
        guard.warmup()
        sides = {"off": [], "on": []}
        results_on = None
        for r in range(reps):
            off.pool.reset()          # symmetric analysis caches
            on.pool.reset()
            # alternate which side goes first: whatever systematic bias
            # the container has (cache residency, scheduler placement)
            # lands on both sides equally across reps
            order = ("off", "on") if r % 2 == 0 else ("on", "off")
            for side in order:
                if side == "off":
                    off.run(trace)
                    sides["off"].append(off.last_metrics.summary())
                else:
                    results_on = on.run(trace)
                    sides["on"].append(on.last_metrics.summary())
    print(f"recompiles after warmup: {len(guard.post_warmup)} (guarded)")

    m_off, m_on = _median(sides["off"]), _median(sides["on"])
    # median of PAIRED per-rep ratios: each rep's sides ran back to
    # back, so slow container drift cancels inside the pair instead of
    # desyncing the two side-medians
    ratio = float(np.median([
        on_m["scenarios_per_sec"] / max(off_m["scenarios_per_sec"], 1e-12)
        for off_m, on_m in zip(sides["off"], sides["on"])]))
    for tag, m in (("obs-off", m_off), ("obs-on", m_on)):
        print(f"{tag:8s} wall {m['wall_s']:7.2f} s   "
              f"{m['scenarios_per_sec']:6.2f} scen/s   "
              f"latency p50/p99 {m['latency_p50_s']:.2f}/"
              f"{m['latency_p99_s']:.2f} s")
    print(f"instrumented throughput: {ratio:.3f}x of plain "
          f"(gate: >= {gate:.2f})")
    assert ratio >= gate, \
        f"tracing overhead too high: on/off throughput ratio {ratio:.3f}"

    # completeness on the traced side (spans are from the LAST rep —
    # clear_per_run keeps exactly one run in the ring)
    spans = on.tracer.spans()
    _check_span_trees(spans, [r.uid for r in trace])
    print(f"span trees complete: {len(trace)} scenarios x "
          f"{len(SCENARIO_STAGES)} stages ({len(spans)} spans)")

    # export: write, re-read, summarize
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "trace.json")
        on.export_trace(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["traceEvents"], "empty Chrome trace"
        kinds = {e["ph"] for e in doc["traceEvents"]}
        assert kinds <= {"X", "M"}, kinds
        back = read_trace(path)
        assert len(back) == len(spans), (len(back), len(spans))
        summ = summarize(back)
    assert summ["span_count"] == len(spans)
    assert set(SCENARIO_STAGES) <= set(summ["stages"]), summ["stages"]
    print(f"chrome export round-trips: {summ['span_count']} spans, "
          f"e2e p50 {summ['end_to_end_p50_ms']:.1f} ms, "
          f"p99 {summ['end_to_end_p99_ms']:.1f} ms")

    _check_bit_identical(results_on, budget)
    print(f"all {len(results_on)} instrumented schedules bit-identical "
          f"to standalone run_sweep rows")

    return {
        "off": m_off, "on": m_on,
        "throughput_ratio_on_over_off": ratio,
        "overhead_frac": max(0.0, 1.0 - ratio),
        "span_count": len(spans),
        "stages": {k: v for k, v in summ["stages"].items()
                   if k in SCENARIO_STAGES},
        "end_to_end_p50_ms": summ["end_to_end_p50_ms"],
        "end_to_end_p99_ms": summ["end_to_end_p99_ms"],
        "critical_path": summ["critical_path"],
        "recompiles_post_warmup": len(guard.post_warmup),
        "bit_identical": True,
    }


def run_memo_spans(num_scenarios, group_size, budget, batch_rows,
                   workers, seed) -> dict:
    """Functional (untimed) section: memo spans on misses and hits."""
    trace = generate_trace(TraceConfig(
        num_scenarios=num_scenarios, group_size=group_size,
        mixes=("Light",), settings=("S2",), bw_ladder_gb=(4.0,),
        seed=seed))
    svc = StreamingScheduler(
        budget=budget, memo=ScheduleMemo(),
        stream=StreamConfig(batch_rows=batch_rows,
                            analysis_workers=workers,
                            obs={"enabled": True}))
    svc.warmup(trace)
    svc.run(trace)                        # cold: all misses, all recorded
    cold = svc.tracer.spans()
    lookups = [s for s in cold if s.name == "memo.lookup"]
    records = [s for s in cold if s.name == "memo.record"]
    assert len(lookups) == len(trace), (len(lookups), len(trace))
    assert all(s.args.get("outcome") == "miss" for s in lookups)
    assert len(records) == len(trace), (len(records), len(trace))

    svc.run(trace)                        # replay: every lookup hits
    hot = svc.tracer.spans()
    hits = [s for s in hot if s.name == "memo.lookup"]
    assert len(hits) == len(trace)
    assert all(s.args.get("outcome") == "hit" for s in hits), \
        collections.Counter(s.args.get("outcome") for s in hits)
    assert svc.last_metrics.memo_exact_hits == len(trace)
    print(f"memo spans: {len(lookups)} misses + {len(records)} records "
          f"cold, {len(hits)} exact-hit lookups on replay")
    return {"cold_lookup_misses": len(lookups),
            "cold_records": len(records),
            "replay_exact_hits": len(hits)}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenarios", type=int, default=32)
    ap.add_argument("--group-size", type=int, default=48)
    ap.add_argument("--budget", type=int, default=800)
    ap.add_argument("--batch-rows", type=int, default=8)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5,
                    help="interleaved repetitions per side (median of "
                         "paired per-rep ratios; raise on noisy hosts)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small trace/budget, extra reps")
    ap.add_argument("--out", default="BENCH_obs.json")
    args = ap.parse_args()
    if args.quick:
        args.scenarios, args.group_size, args.budget = 64, 24, 240
        args.reps = max(args.reps, 5)
    # the <3% contract holds at default scale; quick walls (~0.15 s) sit
    # inside the shared CI container's ±5% scheduling noise, so the
    # smoke gate is loosened to 10% — still catching real regressions
    # (a per-span cost would show up 10x over) without flaking
    gate = 0.90 if args.quick else 0.97

    report = {
        "bench": "perf_obs",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
        "num_scenarios": args.scenarios,
        "group_size": args.group_size,
        "budget": args.budget,
        "batch_rows": args.batch_rows,
        "analysis_workers": args.workers,
        "reps": args.reps,
        "trace_seed": args.seed,
        "lifecycle_stages": list(LIFECYCLE_STAGES),
        "unix_time": time.time(),
    }
    report["overhead_gate"] = gate
    report.update(run_overhead(args.scenarios, args.group_size,
                               args.budget, args.batch_rows, args.workers,
                               args.reps, args.seed, gate))
    report["memo_spans"] = run_memo_spans(
        max(4, args.scenarios // 4), args.group_size, args.budget,
        args.batch_rows, args.workers, args.seed + 1)

    flat = [report["throughput_ratio_on_over_off"],
            report["overhead_frac"], report["end_to_end_p50_ms"],
            report["end_to_end_p99_ms"]]
    for side in ("off", "on"):
        flat += list(report[side].values())
    for st in report["stages"].values():
        flat += list(st.values())
    if not np.isfinite(flat).all():
        print("NON-FINITE RESULTS", file=sys.stderr)
        sys.exit(1)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, default=float)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
