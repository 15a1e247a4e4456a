"""Streaming multi-tenant scheduling: scenarios arrive, schedules stream out.

    PYTHONPATH=src python examples/streaming_service.py

Generates a bursty arrival trace over the paper's heavy/light DNN mixes
(AlphaGoZero/FasterRCNN/ResNet50 vs DeepSpeech2/NCF/Transformer) against
a heterogeneous accelerator, replays it through the streaming scheduler
(async analysis pool -> admission batching -> device sweep -> result
router), prints each schedule as it would stream out, and ends with the
service metrics.  Every schedule is bit-identical to a standalone
``magma_search`` with that (scenario, seed) — the demo checks one.

The second half replays an SLO-tagged trace (urgent/normal/batch
priority classes with per-class deadlines) through an anytime-mode
service: urgent deadline-carrying misses get an immediate short-budget
interim schedule while the full-budget refinement lands in the memo for
the next arrival.

Pass ``--trace-out trace.json`` to run the first service with the obs
layer on and drop a Chrome trace of every scenario's lifecycle spans —
open it at https://ui.perfetto.dev (schedules stay bit-identical; the
standalone re-check below still passes).
"""
import argparse

import numpy as np

from repro.core.magma import magma_search
from repro.memo import ScheduleMemo
from repro.obs import format_summary, read_trace, summarize
from repro.stream import (StreamConfig, StreamingScheduler, TraceConfig,
                          analyze_serial, generate_trace)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="enable tracing and write a Perfetto-loadable "
                         "Chrome trace of the first service run here")
    args = ap.parse_args(argv)
    trace_cfg = TraceConfig(
        num_scenarios=12, arrival="bursty", rate_hz=4.0, burst_size=3.0,
        mixes=("Heavy", "Light", "HeavyLight"), settings=("S2",),
        bw_ladder_gb=(4.0, 16.0, 64.0), group_size=32, batch_scale_max=8,
        seed=0)
    trace = generate_trace(trace_cfg)
    print(f"trace: {len(trace)} scenarios, bursty arrivals over "
          f"{trace[-1].arrival_s:.2f} s")
    for r in trace[:4]:
        print(f"  t={r.arrival_s:5.2f}s  uid={r.uid}  {r.mix:10s} "
              f"on {r.setting}  BW={r.bw_gb:g} GB/s  "
              f"batch x{r.batch_scale}")
    print("  ...")

    obs = {"enabled": True} if args.trace_out else None
    svc = StreamingScheduler(
        budget=1_000,
        stream=StreamConfig(batch_rows=4, analysis_workers=2, obs=obs))
    print("\nwarming executables (a long-lived service does this once)...")
    svc.warmup(trace)

    results = svc.run(trace)
    print("\nstreamed schedules:")
    for r in results:
        print(f"  uid={r.request.uid:2d}  {r.request.mix:10s} "
              f"best={r.best_fitness:9.3e}  "
              f"analysis {1e3 * (r.ready_s - r.analysis_start_s):5.1f} ms  "
              f"latency {1e3 * r.latency_s:6.1f} ms")

    m = svc.last_metrics
    print(f"\nservice: {m.scenarios_per_sec:.1f} scenarios/s sustained, "
          f"latency p50 {1e3 * m.latency_p50_s:.0f} ms / "
          f"p99 {1e3 * m.latency_p99_s:.0f} ms, "
          f"device idle {100 * m.device_idle_frac:.1f}%, "
          f"{m.num_batches} device batches "
          f"(fill {100 * m.mean_batch_fill:.0f}%)")

    # the guarantee: a streamed schedule == the standalone search
    check = results[0]
    fit = analyze_serial([check.request])[0].fit
    ref = magma_search(fit, budget=1_000, seed=check.request.seed)
    assert check.best_fitness == ref.best_fitness
    np.testing.assert_array_equal(check.best_accel, ref.best_accel)
    print(f"\nuid={check.request.uid} re-run standalone: bit-identical "
          f"(best={ref.best_fitness:.3e})")

    if args.trace_out:
        svc.export_trace(args.trace_out)
        spans = read_trace(args.trace_out)
        print(f"\nwrote {args.trace_out} ({len(spans)} spans — open at "
              f"https://ui.perfetto.dev)")
        print(format_summary(summarize(spans)))

    # --- SLO-aware admission + anytime schedules -----------------------
    slo_cfg = TraceConfig(
        num_scenarios=8, arrival="bursty", rate_hz=4.0, burst_size=4.0,
        mixes=("Light",), settings=("S2",), bw_ladder_gb=(16.0,),
        group_size=32, seed=1,
        priorities=("urgent", "normal", "batch", "batch"),
        slo_by_class=(("urgent", 0.3), ("normal", 0.6)))
    slo_trace = generate_trace(slo_cfg)
    slo_svc = StreamingScheduler(
        budget=1_000, memo=ScheduleMemo(),
        stream=StreamConfig(batch_rows=4, analysis_workers=2,
                            anytime_budget=250))
    print("\nSLO trace (urgent deadline 0.30 s, normal 0.60 s, "
          "anytime interim budget 250):")
    slo_svc.warmup(slo_trace)
    for r in slo_svc.run(slo_trace):
        dl = (f"deadline {r.request.deadline_s:.2f}s "
              f"{'MET ' if r.deadline_met else 'MISS'}"
              if r.request.deadline_s is not None else "no deadline     ")
        kind = "interim" if r.anytime_interim else "full   "
        print(f"  uid={r.request.uid:2d}  {r.request.priority:6s}  {dl}  "
              f"{kind} @budget {r.budget:4d}  "
              f"latency {1e3 * r.latency_s:6.1f} ms")
    sm = slo_svc.last_metrics
    print(f"SLO attainment {100 * sm.slo_attainment:.0f}% "
          f"({sm.deadline_misses}/{sm.num_with_deadline} misses), "
          f"urgent p99 {1e3 * sm.latency_p99_urgent_s:.0f} ms, "
          f"{sm.anytime_interims} interims refined to full budget in "
          f"the memo ({sm.anytime_refinements} refinements)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
