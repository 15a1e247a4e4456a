"""Streaming scheduler service: the pipeline may reorder, batch, pad, and
overlap work arbitrarily, but every streamed scenario's schedule must stay
bit-identical to a standalone ``run_sweep`` row / ``magma_search`` with the
same (scenario, seed) — the same guarantee the sweep already carries, so
the pipeline is a pure-throughput win.  Multi-device coverage spawns a
subprocess with 8 fake devices (CI also runs this file in the
``multidevice`` job)."""
import os
import subprocess
import sys
import textwrap
import threading
import types

import numpy as np
import pytest

from repro.core.job_analyzer import JobAnalyzer, profile_key
from repro.core.magma import magma_search
from repro.core.strategies import get_strategy, run_strategy
from repro.core.sweep import run_sweep
from repro.costmodel import get_setting
from repro.memo import ScheduleMemo
from repro.stream import (AnalysisPool, PreparedScenario, PRIORITY_CLASSES,
                          ScenarioRequest, StreamConfig, StreamingScheduler,
                          TraceConfig, analyze_serial, compute_metrics,
                          generate_trace, interval_union_s, p99_s)
from repro.workloads import build_task_groups

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BUDGET = 300
QUICK = dict(group_size=12, bw_ladder_gb=(1.0, 16.0), settings=("S1", "S2"),
             mixes=("Heavy", "Light"))


# ---------------------------------------------------------------------------
# workload/trace generator
# ---------------------------------------------------------------------------
def test_trace_deterministic_and_sorted():
    cfg = TraceConfig(num_scenarios=16, seed=5, **QUICK)
    t1, t2 = generate_trace(cfg), generate_trace(cfg)
    assert t1 == t2
    arr = [r.arrival_s for r in t1]
    assert arr == sorted(arr) and len(t1) == 16
    assert {r.mix for r in t1} <= {"Heavy", "Light"}
    assert all(r.group_size == 12 for r in t1)


@pytest.mark.parametrize("arrival", ["poisson", "bursty", "batch"])
def test_arrival_processes(arrival):
    cfg = TraceConfig(num_scenarios=24, arrival=arrival, rate_hz=16.0,
                      seed=1, **QUICK)
    trace = generate_trace(cfg)
    times = np.array([r.arrival_s for r in trace])
    if arrival == "batch":
        assert (times == 0).all()
    else:
        assert times[-1] > 0
    if arrival == "bursty":
        # bursts share arrival instants: fewer distinct times than requests
        assert len(np.unique(times)) < len(times)


def test_trace_rejects_bad_config():
    with pytest.raises(ValueError, match="arrival"):
        TraceConfig(arrival="lumpy")
    with pytest.raises(ValueError, match="mix"):
        generate_trace(TraceConfig(mixes=("NoSuchMix",)))


def test_streaming_mixes_exist():
    for mix in ("Heavy", "Light", "HeavyLight"):
        group = build_task_groups(mix, group_size=8, seed=0)[0]
        assert len(group) == 8
        assert all(j.flops > 0 for j in group.jobs)


# ---------------------------------------------------------------------------
# analyzer cache digest + thread-safety (async-analysis prerequisite)
# ---------------------------------------------------------------------------
def test_profile_key_ignores_names():
    from repro.costmodel.layers import conv2d
    from repro.workloads.benchmark import Job

    accel = get_setting("S1")
    sub0, sub1 = accel.sub_accels[0], accel.sub_accels[1]
    l1 = conv2d("block0.conv", 4, 8, 8, 14, 14, 3, 3)
    l2 = conv2d("block7.conv", 4, 8, 8, 14, 14, 3, 3)   # same dims, new name
    # neither the layer's nor the sub-accelerator's name is cost-relevant
    assert profile_key(l1, sub0) == profile_key(l2, sub0)
    assert profile_key(l1, sub0) == profile_key(l1, sub1)  # S1 subs identical
    l3 = conv2d("other", 4, 8, 8, 14, 14, 3, 3, stride=2)
    assert profile_key(l1, sub0) != profile_key(l3, sub0)

    an = JobAnalyzer(accel)
    an.analyze([Job(0, "m", l1), Job(1, "m", l2), Job(2, "m", l3)])
    # 2 distinct (layer, sub) digests across 3 jobs x 4 identical subs
    assert an.cache_size == 2


def test_job_analyzer_thread_safe_shared_cache():
    accel = get_setting("S2")
    jobs = build_task_groups("Heavy", group_size=16, seed=0)[0].jobs
    shared = JobAnalyzer(accel)
    ref = JobAnalyzer(accel).analyze(jobs)
    tables, errors = [None] * 8, []

    def work(i):
        try:
            tables[i] = shared.analyze(jobs)
        except Exception as e:          # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for tab in tables:
        np.testing.assert_array_equal(tab.lat, ref.lat)
        np.testing.assert_array_equal(tab.bw, ref.bw)
        np.testing.assert_array_equal(tab.energy, ref.energy)


def test_analysis_pool_matches_serial():
    trace = generate_trace(TraceConfig(num_scenarios=6, seed=2, **QUICK))
    with AnalysisPool(workers=3) as pool:
        ready = [f.result() for f in [pool.submit(r) for r in trace]]
    serial = analyze_serial(trace)
    for a, b in zip(sorted(ready, key=lambda r: r.request.uid), serial):
        assert a.request == b.request
        np.testing.assert_array_equal(np.asarray(a.fit.params.lat),
                                      np.asarray(b.fit.params.lat))
        np.testing.assert_array_equal(np.asarray(a.fit.params.bw),
                                      np.asarray(b.fit.params.bw))


# ---------------------------------------------------------------------------
# the pipeline: bit-identity + metrics
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def streamed():
    trace = generate_trace(TraceConfig(num_scenarios=8, seed=3, **QUICK))
    svc = StreamingScheduler(
        budget=BUDGET, stream=StreamConfig(batch_rows=4, analysis_workers=2))
    results = svc.run(trace)
    return trace, svc, results


def test_stream_results_cover_trace(streamed):
    trace, _, results = streamed
    assert [r.request.uid for r in results] == [t.uid for t in trace]
    for r in results:
        assert np.isfinite(r.best_fitness)
        assert r.ready_s >= r.analysis_start_s
        assert r.done_s >= r.dispatch_s >= 0
        assert r.latency_s > 0


def test_streamed_rows_bit_identical_to_run_sweep(streamed):
    """THE guarantee: every streamed schedule == a standalone run_sweep
    row (and, for MAGMA, == magma_search) with that (scenario, seed)."""
    _, _, results = streamed
    for r in results:
        fit = analyze_serial([r.request])[0].fit
        ref = run_sweep([fit], budget=BUDGET, seeds=[r.request.seed])
        assert r.best_fitness == ref.best_fitness[0, 0]
        np.testing.assert_array_equal(r.best_accel, ref.best_accel[0, 0])
        np.testing.assert_array_equal(r.best_prio, ref.best_prio[0, 0])
        np.testing.assert_array_equal(r.history_best,
                                      ref.history_best[0, 0])
        standalone = magma_search(fit, budget=BUDGET, seed=r.request.seed)
        assert r.best_fitness == standalone.best_fitness


def test_stream_metrics_sane(streamed):
    _, svc, results = streamed
    m = svc.last_metrics
    assert m.num_scenarios == len(results)
    assert 0 < m.latency_p50_s <= m.latency_p99_s
    assert 0.0 <= m.device_idle_frac <= 1.0
    assert m.device_busy_s <= m.wall_s + 1e-9
    assert m.num_batches >= 2          # batch_rows=4 < 8 scenarios
    assert 0 < m.mean_batch_fill <= 1.0
    s = m.summary()
    assert s["scenarios_per_sec"] > 0


def test_admission_accounting_not_double_counted(streamed):
    """The extracted admission queues (repro.stream.admission) keep the
    quadruple ``enqueued == dispatched + stolen + depth``; in-process
    runs never steal, every member dispatches exactly once (an early
    flush is a reason tag on one dispatch, not a second count), and the
    metrics mirror the queue counters."""
    _, svc, results = streamed
    aq = svc.last_admission
    assert aq is not None
    aq.check()
    assert aq.enqueued == aq.dispatched == len(results)
    assert aq.stolen == 0 and aq.depth == 0
    assert aq.early_flushes <= aq.enqueued
    m = svc.last_metrics
    assert m.queue_peak_depth == aq.peak_depth > 0
    assert m.early_flushes == aq.early_flushes
    assert m.stolen_members == 0


def test_interval_union():
    assert interval_union_s([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert interval_union_s([]) == 0.0
    assert interval_union_s([(1, 2), (1, 2)]) == pytest.approx(1.0)


def test_incompatible_scenarios_batch_separately():
    """Scenarios whose tables differ in shape (group size) cannot share a
    compiled executable; the admission stage must route them to separate
    batches yet complete them all — and each still matches standalone.
    (Different *settings* with the same (G, A) may legitimately share a
    batch: the tables are traced row data, not compile-time constants.)"""
    reqs = [ScenarioRequest(uid=0, arrival_s=0.0, mix="Light", setting="S1",
                            bw_gb=4.0, group_size=8, seed=1),
            ScenarioRequest(uid=1, arrival_s=0.0, mix="Light", setting="S2",
                            bw_gb=4.0, group_size=8, seed=2),
            ScenarioRequest(uid=2, arrival_s=0.0, mix="Light", setting="S1",
                            bw_gb=4.0, group_size=10, seed=3)]
    svc = StreamingScheduler(budget=BUDGET,
                             stream=StreamConfig(batch_rows=4))
    results = svc.run(reqs)
    assert len(results) == 3
    keys = {b.compat_key for b in svc.last_batches}
    assert len(keys) == 2              # split on G=8 vs G=10, not on setting
    for r in results:
        fit = analyze_serial([r.request])[0].fit
        ref = run_sweep([fit], budget=BUDGET, seeds=[r.request.seed])
        assert r.best_fitness == ref.best_fitness[0, 0]


def test_prepared_scenarios_and_strategy_override():
    """Prepared scenarios skip analysis; per-scenario strategy overrides
    batch separately and match the standalone strategy run."""
    fit = analyze_serial(generate_trace(
        TraceConfig(num_scenarios=1, seed=4, **QUICK)))[0].fit
    svc = StreamingScheduler(budget=BUDGET)
    for name in ("magma", "stdga"):
        res = svc.schedule_prepared(fit, seed=7, strategy=name)
        ref = run_strategy(get_strategy(name), fit, budget=BUDGET, seed=7)
        assert res.best_fitness == ref.best_fitness
        np.testing.assert_array_equal(res.best_accel, ref.best_accel)
        sr = res.to_search_result()
        np.testing.assert_array_equal(sr.history_samples,
                                      ref.history_samples)
        np.testing.assert_array_equal(sr.history_best, ref.history_best)


def test_host_only_strategy_rejected():
    with pytest.raises(ValueError, match="host-only"):
        StreamingScheduler(strategy="herald_like")
    fit = analyze_serial(generate_trace(
        TraceConfig(num_scenarios=1, seed=0, **QUICK)))[0].fit
    svc = StreamingScheduler(budget=BUDGET)
    with pytest.raises(ValueError, match="host-only"):
        svc.schedule_prepared(fit, strategy="cmaes")


def test_realtime_replay_orders_arrivals():
    """Realtime mode honors arrival offsets (scaled tiny for test speed)."""
    trace = generate_trace(TraceConfig(num_scenarios=4, rate_hz=200.0,
                                       seed=6, **QUICK))
    svc = StreamingScheduler(
        budget=BUDGET,
        stream=StreamConfig(batch_rows=2, realtime=True))
    results = svc.run(trace)
    assert len(results) == 4
    for r, t in zip(results, trace):
        assert r.arrival_s == t.arrival_s       # trace offsets preserved
        assert r.done_s >= t.arrival_s


def test_loop_phases_tile_a_realtime_run(tmp_path):
    """With obs on, the main loop's ``stream.loop.*`` phases tile the run:
    they never overlap, cover its wall time, reach the profiler's trace,
    and a stretch of idle iterations is one span.  A request's
    queue_wait/dispatch/device/route spans carry its batch's id."""
    import glob

    import jax
    from jax.profiler import ProfileData

    import dataclasses

    from repro.stream.service import IDLE, LOOP_PHASES
    # the first arrival 50 ms in: an idle stretch of several 10 ms sleeps
    trace = [dataclasses.replace(r, arrival_s=r.arrival_s + 0.05)
             for r in generate_trace(TraceConfig(num_scenarios=5,
                                                 rate_hz=20.0, seed=6,
                                                 **QUICK))]
    svc = StreamingScheduler(
        budget=BUDGET,
        stream=StreamConfig(batch_rows=2, realtime=True,
                            obs={"enabled": True}))
    svc.warmup(trace)
    jax.profiler.start_trace(str(tmp_path))
    try:
        results = svc.run(trace)
    finally:
        jax.profiler.stop_trace()
    assert len(results) == len(trace)

    spans = svc.tracer.spans()
    phases = sorted((s for s in spans if s.name in LOOP_PHASES),
                    key=lambda s: s.start_s)
    for a, b in zip(phases, phases[1:]):
        assert b.start_s >= a.end_s - 1e-9, (a, b)      # never overlap
        assert not (a.name == b.name == IDLE)           # one idle stretch
    assert phases[0].name != IDLE and phases[1].name == IDLE
    assert phases[1].dur_s >= 0.04
    wall = svc.last_metrics.wall_s
    assert sum(p.dur_s for p in phases) >= 0.99 * wall

    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    seen = {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {p.name for p in phases} <= seen and "analyze" in seen

    batches = {b.batch for b in svc.last_batches}
    assert len(batches) == len(svc.last_batches)
    for r in results:
        mine = {s.name: s.args["batch"] for s in spans
                if s.scope == r.request.uid
                and s.name in ("queue_wait", "dispatch", "device", "route")}
        assert len(mine) == 4 and len(set(mine.values())) == 1, mine
        assert set(mine.values()) <= batches


# ---------------------------------------------------------------------------
# SLO-aware admission: deadlines, priority classes, anytime schedules
# ---------------------------------------------------------------------------
def _slo_req(uid, bw=16.0, mix="Light", group_size=12, seed=5,
             priority="normal", deadline_s=None):
    return ScenarioRequest(uid=uid, arrival_s=0.0, mix=mix, setting="S2",
                           bw_gb=bw, group_size=group_size, seed=seed,
                           priority=priority, deadline_s=deadline_s)


def test_trace_priorities_and_deadlines():
    cfg = TraceConfig(num_scenarios=24, seed=9,
                      priorities=("urgent", "batch", "batch"),
                      slo_by_class=(("urgent", 0.2), ("normal", 1.0)),
                      **QUICK)
    t1, t2 = generate_trace(cfg), generate_trace(cfg)
    assert t1 == t2                       # SLO fields are deterministic too
    assert {r.priority for r in t1} <= {"urgent", "batch"}
    assert any(r.priority == "urgent" for r in t1)
    for r in t1:
        # deadline comes from the class's slo_by_class entry (or nothing)
        assert r.deadline_s == (0.2 if r.priority == "urgent" else None)
    # a single-class config draws nothing extra, so the scenario content
    # (mixes/BWs/seeds) is identical whatever the one class is — pre-SLO
    # traces replay bit-identically under the default ("normal",)
    base = dict(num_scenarios=12, seed=4, **QUICK)
    a = generate_trace(TraceConfig(priorities=("urgent",),
                                   slo_by_class=(("urgent", 0.5),), **base))
    b = generate_trace(TraceConfig(**base))
    assert [(r.mix, r.bw_gb, r.seed, r.arrival_s) for r in a] == \
        [(r.mix, r.bw_gb, r.seed, r.arrival_s) for r in b]


def test_slo_config_validation():
    with pytest.raises(ValueError, match="priority"):
        TraceConfig(priorities=("gold",))
    with pytest.raises(ValueError, match="at least one"):
        TraceConfig(priorities=())
    with pytest.raises(ValueError, match="unknown class"):
        TraceConfig(slo_by_class=(("gold", 1.0),))
    with pytest.raises(ValueError, match="must be > 0"):
        TraceConfig(slo_by_class=(("urgent", 0.0),))
    with pytest.raises(ValueError, match="priority"):
        _slo_req(0, priority="gold")
    with pytest.raises(ValueError, match="deadline_s"):
        _slo_req(0, deadline_s=-1.0)
    with pytest.raises(ValueError, match="slo_margin_s"):
        StreamConfig(slo_margin_s=-0.1)
    with pytest.raises(ValueError, match="anytime_budget"):
        StreamConfig(anytime_budget=0)
    with pytest.raises(ValueError, match="slo_aware"):
        StreamConfig(anytime_budget=100, slo_aware=False)
    with pytest.raises(ValueError, match="memo"):
        StreamingScheduler(budget=BUDGET,
                           stream=StreamConfig(anytime_budget=100))


def test_deadline_ordered_dispatch():
    """All four scenarios are admitted upfront into ONE compatibility
    queue; batch_rows=2 forces two dispatches, and SLO-aware member
    selection must send the urgent pair (tightest absolute deadline
    first) before normal before batch — while every schedule stays
    bit-identical to its standalone run_sweep row."""
    fits = [analyze_serial([_slo_req(i, bw=bw, seed=20 + i)])[0].fit
            for i, bw in enumerate((1.0, 4.0, 8.0, 16.0))]
    prepared = [
        PreparedScenario(fit=fits[0], seed=20, uid=0, priority="batch"),
        PreparedScenario(fit=fits[1], seed=21, uid=1, priority="normal"),
        PreparedScenario(fit=fits[2], seed=22, uid=2, priority="urgent",
                         deadline_s=10.0),
        PreparedScenario(fit=fits[3], seed=23, uid=3, priority="urgent",
                         deadline_s=5.0)]
    svc = StreamingScheduler(budget=BUDGET,
                             stream=StreamConfig(batch_rows=2))
    results = svc.run(prepared=prepared)
    by_uid = {r.request.uid for r in results}
    assert by_uid == {0, 1, 2, 3}
    r = {res.request.uid: res for res in results}
    # the urgent pair went out in the first batch, batch-class last
    assert all(b.rows == 2 for b in svc.last_batches)
    assert max(r[2].dispatch_s, r[3].dispatch_s) \
        <= min(r[0].dispatch_s, r[1].dispatch_s)
    assert r[2].dispatch_s == r[3].dispatch_s      # same batch
    # reordering changed WHEN, never WHAT
    for res in results:
        ref = run_sweep([prepared[res.request.uid].fit], budget=BUDGET,
                        seeds=[res.request.seed])
        assert res.best_fitness == ref.best_fitness[0, 0]
        np.testing.assert_array_equal(res.best_accel, ref.best_accel[0, 0])
        np.testing.assert_array_equal(res.history_best,
                                      ref.history_best[0, 0])


def test_urgent_flush_preempts_held_partial():
    """While analyses are in flight, partials are normally HELD to fill
    the batch; an urgent member whose slack is inside slo_margin_s
    flushes the hold immediately (rows=1 dispatch).  The priority-blind
    config holds the same partial until the analyses drain, so the
    urgent schedule queues behind them."""
    trace = [_slo_req(uid, bw=bw, seed=30 + uid)
             for uid, bw in ((1, 1.0), (2, 16.0))]
    fit = analyze_serial([_slo_req(0, bw=4.0, seed=29)])[0].fit
    urgent = PreparedScenario(fit=fit, seed=29, uid=0, priority="urgent",
                              deadline_s=1e-6)

    svc = StreamingScheduler(
        budget=BUDGET, stream=StreamConfig(batch_rows=4,
                                           analysis_workers=1))
    res = {r.request.uid: r for r in svc.run(trace, prepared=[urgent])}
    first = min(svc.last_batches, key=lambda b: b.dispatch_s)
    assert first.rows == 1                      # the flushed urgent partial
    assert res[0].dispatch_s == first.dispatch_s
    assert res[0].dispatch_s < min(res[1].dispatch_s, res[2].dispatch_s)
    m = svc.last_metrics
    assert m.num_with_deadline == 1
    assert m.deadline_misses == 1               # a 1 us SLO is unmeetable
    assert m.slo_attainment == 0.0
    assert res[0].deadline_met is False
    assert res[1].deadline_met is None          # no deadline attached

    blind = StreamingScheduler(
        budget=BUDGET, stream=StreamConfig(batch_rows=4,
                                           analysis_workers=1,
                                           slo_aware=False,
                                           max_hold_s=30.0))
    bres = {r.request.uid: r for r in blind.run(trace, prepared=[urgent])}
    bfirst = min(blind.last_batches, key=lambda b: b.dispatch_s)
    assert bfirst.rows == 3                     # held until analyses drained
    # blind or aware, the urgent schedule itself is bit-identical
    assert bres[0].best_fitness == res[0].best_fitness
    np.testing.assert_array_equal(bres[0].best_accel, res[0].best_accel)


def test_anytime_interim_then_refined():
    """Anytime mode: a deadline-carrying miss returns a short-budget
    interim schedule (bit-identical to a standalone search at the
    anytime budget) while a silent full-budget twin lands in the memo
    (bit-identical to a standalone search at the full budget); the next
    arrival replays the refined schedule as an exact hit."""
    ANYTIME = 60
    fit = analyze_serial([_slo_req(0, seed=40)])[0].fit
    strat = get_strategy("magma")
    memo = ScheduleMemo(near=False)
    svc = StreamingScheduler(
        budget=BUDGET, memo=memo,
        stream=StreamConfig(anytime_budget=ANYTIME))

    res1 = svc.schedule_prepared(fit, seed=5, priority="urgent",
                                 deadline_s=2.0)
    assert res1.anytime_interim and res1.budget == ANYTIME
    interim = run_strategy(strat, fit, budget=ANYTIME, seed=5)
    assert res1.best_fitness == interim.best_fitness
    np.testing.assert_array_equal(res1.best_accel, interim.best_accel)
    np.testing.assert_array_equal(res1.history_best, interim.history_best)
    m = svc.last_metrics
    assert m.anytime_interims == 1 and m.anytime_refinements == 1

    # the silent refinement is already in the memo at the FULL budget,
    # bit-identical to the standalone full-budget search
    refined = run_strategy(strat, fit, budget=BUDGET, seed=5)
    hit = memo.lookup(fit, strat, BUDGET, 5)
    assert hit is not None and not hit.warm_seeded
    assert hit.best_fitness == refined.best_fitness
    np.testing.assert_array_equal(hit.best_accel, refined.best_accel)

    # second arrival: exact replay of the refined schedule, no dispatch
    res2 = svc.schedule_prepared(fit, seed=5, priority="urgent",
                                 deadline_s=2.0)
    assert res2.memo_exact and res2.budget == BUDGET
    assert not res2.anytime_interim
    assert res2.best_fitness == refined.best_fitness
    m2 = svc.last_metrics
    assert m2.num_batches == 0
    assert m2.anytime_interims == 0 and m2.anytime_refinements == 0

    # no deadline -> no split: one full-budget dispatch, no interim
    res3 = svc.schedule_prepared(fit, seed=7)
    assert not res3.anytime_interim and res3.budget == BUDGET
    assert res3.best_fitness == run_strategy(strat, fit, budget=BUDGET,
                                             seed=7).best_fitness


def test_memo_counters_are_disjoint():
    """The metrics partition scenarios: exact + warm + cold ==
    num_scenarios, with exact WINNING on a replayed row that was
    originally warm-seeded (the flags keep the provenance, the counters
    never double-count)."""
    ra = _slo_req(0, bw=16.0, seed=50)            # Light @ 16 GB/s
    rb = _slo_req(1, bw=8.0, seed=51)             # near sibling (d ~ 0.30)
    rh = _slo_req(2, mix="Heavy", group_size=10, seed=52)  # other family
    memo = ScheduleMemo()
    svc = StreamingScheduler(budget=BUDGET, memo=memo,
                             stream=StreamConfig(batch_rows=4))

    svc.run([ra])                                 # pass 1: cold, records pop
    m1 = svc.last_metrics
    assert m1.memo_exact_hits == 0 and m1.memo_warm_hits == 0

    res = {r.request.uid: r for r in svc.run([ra, rb, rh])}
    m2 = svc.last_metrics
    assert res[0].memo_exact and not res[0].warm_seeded   # replayed cold row
    assert res[1].warm_seeded and not res[1].memo_exact   # seeded from ra
    assert not res[2].memo_exact and not res[2].warm_seeded  # cold: no donor
    cold = sum(not r.memo_exact and not r.warm_seeded for r in res.values())
    assert m2.memo_exact_hits == 1 and m2.memo_warm_hits == 1 and cold == 1
    assert m2.memo_exact_hits + m2.memo_warm_hits + cold == m2.num_scenarios

    # replay of the warm-seeded row: exact wins, warm stays as provenance
    res3 = svc.run([rb])[0]
    m3 = svc.last_metrics
    assert res3.memo_exact and res3.warm_seeded
    assert m3.memo_exact_hits == 1 and m3.memo_warm_hits == 0
    assert res3.best_fitness == res[1].best_fitness
    np.testing.assert_array_equal(res3.best_accel, res[1].best_accel)


def test_p99_higher_and_slo_accounting():
    """p99 is tail-conservative: with 10 samples it reads the OBSERVED
    maximum, where linear interpolation would read below it."""
    lats = [(i + 1) / 10 for i in range(10)]      # 0.1 .. 1.0
    assert p99_s(lats) == 1.0
    assert float(np.percentile(lats, 99)) < 1.0   # what "linear" would say
    assert p99_s([]) == 0.0

    def fake(i, lat, prio, deadline):
        req = types.SimpleNamespace(priority=prio, deadline_s=deadline)
        return types.SimpleNamespace(
            request=req, latency_s=lat, analysis_start_s=0.0, ready_s=0.0)

    results = [fake(i, lat,
                    "urgent" if i < 3 else "batch" if i >= 8 else "normal",
                    0.55)
               for i, lat in enumerate(lats)]
    m = compute_metrics(results, [], wall_s=2.0)
    assert m.num_with_deadline == 10
    assert m.deadline_misses == 5                 # 0.6 .. 1.0 miss 0.55
    assert m.slo_attainment == 0.5
    assert m.latency_p99_urgent_s == 0.3          # max of its 3 samples
    assert m.latency_p99_normal_s == 0.8
    assert m.latency_p99_batch_s == 1.0
    # empty input stays vacuous, not NaN
    e = compute_metrics([], [], wall_s=0.0)
    assert e.slo_attainment == 1.0 and e.num_with_deadline == 0
    assert e.latency_p99_urgent_s == 0.0
    flat = list(m.summary().values())
    assert np.isfinite(np.asarray(flat, dtype=np.float64)).all()


def test_all_deadlines_expired_edge():
    """A trace whose deadlines cannot be met: attainment 0, every result
    a miss — and the schedules themselves are untouched."""
    trace = generate_trace(TraceConfig(
        num_scenarios=3, seed=11, priorities=("urgent",),
        slo_by_class=(("urgent", 1e-9),), **QUICK))
    svc = StreamingScheduler(budget=BUDGET,
                             stream=StreamConfig(batch_rows=4))
    results = svc.run(trace)
    m = svc.last_metrics
    assert m.num_with_deadline == 3 and m.deadline_misses == 3
    assert m.slo_attainment == 0.0
    assert all(r.deadline_met is False for r in results)
    assert m.latency_p99_urgent_s > 0.0
    assert m.latency_p99_normal_s == 0.0          # class has no members
    for r in results:
        fit = analyze_serial([r.request])[0].fit
        ref = run_sweep([fit], budget=BUDGET, seeds=[r.request.seed])
        assert r.best_fitness == ref.best_fitness[0, 0]

    assert svc.run([]) == []                      # empty trace stays clean
    assert svc.last_metrics.slo_attainment == 1.0
    assert svc.last_metrics.num_with_deadline == 0


# ---------------------------------------------------------------------------
# multi-device: subprocess with fake devices
# ---------------------------------------------------------------------------
def _run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_streamed_bit_identical_multidevice():
    """8 fake devices: streamed schedules (sharded batches) == forced
    single-device stream == standalone run_sweep rows."""
    out = _run_sub("""
        import jax, numpy as np
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core.sweep import SweepConfig, run_sweep
        from repro.stream import (StreamConfig, StreamingScheduler,
                                  TraceConfig, analyze_serial,
                                  generate_trace)

        trace = generate_trace(TraceConfig(
            num_scenarios=6, seed=3, group_size=12,
            bw_ladder_gb=(1.0, 16.0), settings=("S2",), mixes=("Light",)))
        svc = StreamingScheduler(budget=300, stream=StreamConfig(
            batch_rows=4, analysis_workers=2))
        res = svc.run(trace)
        assert any(b.num_devices > 1 for b in svc.last_batches), \\
            [b.num_devices for b in svc.last_batches]

        one = StreamingScheduler(budget=300, stream=StreamConfig(
            batch_rows=4, analysis_workers=2, max_devices=1))
        res1 = one.run(trace)
        for a, b in zip(res, res1):
            assert a.best_fitness == b.best_fitness
            np.testing.assert_array_equal(a.best_accel, b.best_accel)
            np.testing.assert_array_equal(a.history_best, b.history_best)

        for r in res:
            fit = analyze_serial([r.request])[0].fit
            ref = run_sweep([fit], budget=300, seeds=[r.request.seed],
                            sweep=SweepConfig(max_devices=1))
            assert r.best_fitness == ref.best_fitness[0, 0]
            np.testing.assert_array_equal(r.best_accel,
                                          ref.best_accel[0, 0])
        print('STREAM-SHARDED-OK')
    """)
    assert "STREAM-SHARDED-OK" in out


def test_slo_admission_multidevice():
    """8 fake devices: SLO-aware admission (priorities + deadlines on
    the trace, anytime split on a prepared scenario) reorders dispatch
    but every routed schedule still equals its standalone run_sweep /
    run_strategy row."""
    out = _run_sub("""
        import jax, numpy as np
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core.strategies import get_strategy, run_strategy
        from repro.core.sweep import SweepConfig, run_sweep
        from repro.memo import ScheduleMemo
        from repro.stream import (PreparedScenario, StreamConfig,
                                  StreamingScheduler, TraceConfig,
                                  analyze_serial, generate_trace)

        trace = generate_trace(TraceConfig(
            num_scenarios=6, seed=3, group_size=12,
            bw_ladder_gb=(1.0, 16.0), settings=("S2",), mixes=("Light",),
            priorities=("urgent", "batch", "batch"),
            slo_by_class=(("urgent", 0.5),)))
        memo = ScheduleMemo(near=False)
        svc = StreamingScheduler(budget=300, memo=memo,
                                 stream=StreamConfig(
                                     batch_rows=4, analysis_workers=2,
                                     anytime_budget=60))
        fit = analyze_serial(trace[:1])[0].fit
        res = svc.run(trace, prepared=[PreparedScenario(
            fit=fit, seed=999, uid=100, priority="urgent",
            deadline_s=2.0)])
        m = svc.last_metrics
        assert m.num_with_deadline >= 2, m
        # EVERY deadline-carrying miss splits: interim out, silent twin
        # refined into the memo
        assert m.anytime_interims >= 1, m
        assert m.anytime_refinements == m.anytime_interims, m
        assert any(b.num_devices > 1 for b in svc.last_batches), \\
            [b.num_devices for b in svc.last_batches]

        strat = get_strategy("magma")
        for r in res:
            # r.budget is what the row was computed at (60 for an
            # anytime interim, 300 otherwise): the row equals the
            # standalone search at THAT budget
            assert r.anytime_interim == (r.budget == 60), r
            if r.request.uid == 100:
                assert r.anytime_interim
                ref = run_strategy(strat, fit, budget=60, seed=999)
                assert r.best_fitness == ref.best_fitness
                np.testing.assert_array_equal(r.best_accel, ref.best_accel)
            else:
                f = analyze_serial([r.request])[0].fit
                ref = run_sweep([f], budget=r.budget,
                                seeds=[r.request.seed],
                                sweep=SweepConfig(max_devices=1))
                assert r.best_fitness == ref.best_fitness[0, 0]
                np.testing.assert_array_equal(r.best_accel,
                                              ref.best_accel[0, 0])
        hit = memo.lookup(fit, strat, 300, 999)
        assert hit is not None           # the silent refinement landed
        ref = run_strategy(strat, fit, budget=300, seed=999)
        assert hit.best_fitness == ref.best_fitness
        print('STREAM-SLO-OK')
    """)
    assert "STREAM-SLO-OK" in out
