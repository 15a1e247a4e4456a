"""repro.obs: tracer, registry, export, flight recorder — and the
end-to-end contract: with observability ON every streamed scenario gets
a complete span tree while schedules stay bit-identical to the
uninstrumented run.  CI also runs this file in the multidevice job."""
import collections
import json
import os
import threading

import numpy as np
import pytest

import repro.obs.__main__ as obs_cli
from repro.core.sweep import SweepConfig, run_sweep
from repro.lint.runtime import RecompileGuard
from repro.memo import ScheduleMemo
from repro.obs import (FlightRecorder, NULL_SPAN, NULL_TRACER, ObsConfig,
                       RunClock, Span, Tracer, as_obs_config, get_registry,
                       get_tracer, interval_union_s, p50_s, p99_s,
                       read_trace, summarize, to_chrome_trace,
                       write_chrome_trace, write_jsonl)
from repro.obs.registry import MetricsRegistry
from repro.stream import (AnalysisPool, StreamConfig, StreamingScheduler,
                          TraceConfig, generate_trace)
from repro.stream.metrics import compute_metrics
from repro.stream.metrics import interval_union_s as stream_union
from repro.stream.metrics import p99_s as stream_p99

HERE = os.path.dirname(os.path.abspath(__file__))

QUICK = dict(group_size=12, bw_ladder_gb=(1.0, 16.0), settings=("S1",),
             mixes=("Light",))
STAGES = ("analyze", "admit", "queue_wait", "dispatch", "device", "route")


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
def test_tracer_basics_and_clock():
    tr = Tracer(clock=lambda: 0.0)
    tr.emit("a", 1.0, 2.0, scope=3, rows=4)
    (s,) = tr.spans()
    assert (s.name, s.start_s, s.end_s, s.scope) == ("a", 1.0, 2.0, 3)
    assert s.args == {"rows": 4} and s.dur_s == 1.0
    with tr.span("b", scope=1) as sp:
        sp.set(outcome="hit")
    (_, s2) = tr.spans()
    assert s2.name == "b" and s2.args == {"outcome": "hit"}
    assert tr.drain() and not tr.spans()


def test_disabled_tracer_records_nothing_and_shares_null_span():
    tr = Tracer(enabled=False)
    tr.emit("a", 0.0, 1.0)
    assert tr.span("x") is NULL_SPAN
    with tr.span("x") as sp:
        sp.set(whatever=1)
        sp.finish()
    assert tr.spans() == [] and tr.dropped == 0
    assert NULL_TRACER.enabled is False


def _profiled(log_dir, body):
    """Run ``body()`` under the CPU profiler; the host events of the trace
    as ``(name, start_ns, dur_ns, stats)``."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(log_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_live_span_reaches_the_profiler_on_its_run_clock(tmp_path):
    """An enabled tracer's live span is a host event of the same name
    carrying its run-clock start as ``run_s``; every such event gives the
    same trace-minus-run offset, so one fixes the two clocks."""
    import time
    clock = RunClock()
    tr = Tracer(clock=clock)

    def body():
        for i in range(4):
            with tr.span(f"obs.test.{i}", scope=i, batch=10 + i):
                time.sleep(0.002)
            time.sleep(0.003)
        h = tr.span("obs.test.explicit")
        time.sleep(0.002)
        h.finish()

    events = {n: (s, d, st) for n, s, d, st in _profiled(tmp_path, body)
              if n.startswith("obs.test.")}
    assert sorted(events) == [f"obs.test.{i}" for i in range(4)] + \
        ["obs.test.explicit"]
    spans = {s.name: s for s in tr.spans()}
    offsets = []
    for name, (start_ns, dur_ns, stats) in events.items():
        assert float(stats["run_s"]) == pytest.approx(spans[name].start_s,
                                                      abs=1e-6)
        assert dur_ns * 1e-9 == pytest.approx(spans[name].dur_s, abs=1e-3)
        offsets.append(start_ns * 1e-9 - float(stats["run_s"]))
    assert max(offsets) - min(offsets) < 1e-3
    assert int(events["obs.test.2"][2]["scope"]) == 2
    assert int(events["obs.test.2"][2]["batch"]) == 12


def test_span_open_before_the_profiler_reannotates(tmp_path):
    """A span opened before the profiler started records nothing there;
    ``reannotate`` after the start shows it from then on."""
    import time
    tr = Tracer(clock=RunClock())
    early = tr.span("obs.test.early")
    late = tr.span("obs.test.late")

    def body():
        time.sleep(0.002)
        t = tr.now()
        early.reannotate()
        early.reannotate()                  # once is enough
        time.sleep(0.002)
        early.finish()
        late.finish()
        assert tr.spans()[0].start_s < t

    events = [(n, st) for n, _, _, st in _profiled(tmp_path, body)
              if n.startswith("obs.test.")]
    assert [n for n, _ in events] == ["obs.test.early"]
    assert float(events[0][1]["run_s"]) > tr.spans()[0].start_s


def test_disabled_tracer_leaves_no_host_event(tmp_path):
    tr = Tracer(enabled=False)

    def body():
        with tr.span("obs.test.disabled", scope=1):
            pass
        tr.span("obs.test.disabled.explicit").finish()

    names = {n for n, *_ in _profiled(tmp_path, body)}
    assert not any(n.startswith("obs.test.disabled") for n in names)


def test_ring_eviction_oldest_first():
    tr = Tracer(capacity=4)
    for i in range(6):
        tr.emit(f"s{i}", float(i), float(i) + 0.5)
    spans = tr.spans()
    assert [s.name for s in spans] == ["s2", "s3", "s4", "s5"]
    assert tr.dropped == 2
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_tracer_thread_safety_under_analysis_pool():
    """Analyze spans are emitted from pool worker threads; the buffer
    must hold one uncorrupted span per scenario."""
    tr = Tracer()
    clock = RunClock()
    trace = generate_trace(TraceConfig(num_scenarios=12, seed=3, **QUICK))
    with AnalysisPool(workers=4, clock=clock, tracer=tr) as pool:
        ready = [f.result() for f in [pool.submit(r) for r in trace]]
    assert len(ready) == 12
    spans = tr.spans()
    assert len(spans) == 12
    assert {s.scope for s in spans} == {r.uid for r in trace}
    for s in spans:
        assert s.name == "analyze" and s.end_s >= s.start_s
        assert s.args["mix"] == "Light"


def test_tracer_concurrent_emit_no_torn_records():
    tr = Tracer(capacity=64)

    def hammer(tid):
        for i in range(50):
            tr.emit("hit", float(i), i + 1.0, scope=tid, thread=tid)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 64 and tr.dropped == 4 * 50 - 64
    for s in spans:
        assert s.args["thread"] == s.scope     # whole records only


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------
def test_obs_config_coercion_and_validation():
    assert as_obs_config(None) == ObsConfig()
    assert as_obs_config({"enabled": True}).enabled
    cfg = ObsConfig(worker="w3")
    assert as_obs_config(cfg) is cfg
    with pytest.raises(TypeError):
        as_obs_config("yes")
    with pytest.raises(ValueError):
        ObsConfig(trace_capacity=0)
    with pytest.raises(ValueError):
        ObsConfig(flight_events=0)


# ---------------------------------------------------------------------------
# stats (satellite b: one tail-math implementation, re-exported)
# ---------------------------------------------------------------------------
def test_stats_reexported_through_stream_metrics():
    assert stream_p99 is p99_s
    assert stream_union is interval_union_s
    from repro.fleet.metrics import p99_s as fleet_p99
    assert fleet_p99 is p99_s


def test_quantile_conventions():
    lats = list(range(1, 11))
    assert p99_s(lats) == 10.0          # method="higher": observed max
    assert p99_s([]) == 0.0
    assert p50_s([1.0, 2.0, 3.0, 4.0]) == 2.5   # p50 stays linear
    assert interval_union_s([(0, 2), (1, 3), (5, 6)]) == 4.0


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "things")
    c.inc()
    c.inc(2, worker="w0")
    assert c.value() == 1.0 and c.value(worker="w0") == 2.0
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("repro_test_depth", "depth")
    g.set(5, queue="a")
    g.inc(2.5, queue="a")
    assert g.value(queue="a") == 7.5
    h = reg.histogram("repro_test_seconds", "lat",
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    hist = snap["repro_test_seconds"]["series"][0]["value"]
    assert hist["count"] == 3 and hist["sum"] == pytest.approx(5.55)
    # same name must keep the same kind
    with pytest.raises(TypeError):
        reg.gauge("repro_test_total", "things")
    # get-or-create returns the same object
    assert reg.counter("repro_test_total", "things") is c


def test_registry_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("repro_x_total", "help text").inc(3, kind="exact")
    reg.histogram("repro_y_seconds", "lat", buckets=(1.0,)).observe(0.5)
    text = reg.prometheus_text()
    assert "# HELP repro_x_total help text" in text
    assert "# TYPE repro_x_total counter" in text
    assert 'repro_x_total{kind="exact"} 3' in text
    assert 'repro_y_seconds_bucket{le="1"} 1' in text
    assert 'repro_y_seconds_bucket{le="+Inf"} 1' in text
    assert "repro_y_seconds_count 1" in text
    json.loads(reg.json())              # snapshot serializes


def test_compute_metrics_publishes_to_registry():
    reg = get_registry()
    before = reg.counter("repro_stream_scenarios_total", "").value()

    class _R:                      # result duck type
        def __init__(self, uid, lat):
            self.request = type("Q", (), {"uid": uid, "priority": "normal",
                                          "deadline_s": None})()
            self.latency_s = lat
            self.analysis_start_s, self.ready_s = 0.0, 0.1

    class _B:                      # batch duck type
        dispatch_s, done_s, rows, padded_rows = 0.1, 0.4, 2, 2

    m = compute_metrics([_R(0, 0.3), _R(1, 0.4)], [_B()], wall_s=0.5)
    assert m.num_scenarios == 2
    after = reg.counter("repro_stream_scenarios_total", "").value()
    assert after == before + 2
    assert reg.gauge("repro_stream_latency_p99_seconds",
                     "").value() == m.latency_p99_s


def test_recompile_guard_publishes_compile_counter():
    reg = get_registry()
    guard = RecompileGuard(label="obs-test")
    seen = []
    guard.add_listener(lambda name, post: seen.append((name, post)))
    before = reg.counter("repro_jit_compiles_total", "").value(
        phase="warmup", guard="obs-test")
    guard._record_compile("jit_fn_a")
    guard.warmup()
    guard._record_compile("jit_fn_b")
    assert seen == [("jit_fn_a", False), ("jit_fn_b", True)]
    after = reg.counter("repro_jit_compiles_total", "").value(
        phase="warmup", guard="obs-test")
    assert after == before + 1
    assert reg.counter("repro_jit_compiles_total", "").value(
        phase="post_warmup", guard="obs-test") >= 1


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------
_FIXTURE_SPANS = [
    Span("analyze", 0.0, 0.5, scope=0, worker="main",
         args={"mix": "Light"}),
    Span("sweep.chunk", 0.125, 0.25, scope=None, worker="main",
         args={"chunk": 0}),
    Span("device", 0.5, 1.25, scope=0, worker="w1"),
    Span("route", 1.25, 1.5, scope=1, worker="w1"),
]


def test_chrome_trace_golden_file():
    doc = to_chrome_trace(_FIXTURE_SPANS, meta={"service": "test"})
    with open(os.path.join(HERE, "golden_obs_trace.json")) as f:
        golden = json.load(f)
    assert doc == golden


def test_chrome_trace_roundtrip(tmp_path):
    path = str(tmp_path / "t.json")
    write_chrome_trace(path, _FIXTURE_SPANS, meta={"k": "v"})
    back = read_trace(path)
    assert len(back) == len(_FIXTURE_SPANS)
    by_name = {s.name: s for s in back}
    assert by_name["analyze"].scope == 0
    assert by_name["analyze"].args == {"mix": "Light"}
    assert by_name["sweep.chunk"].scope is None
    assert by_name["device"].worker == "w1"
    assert by_name["device"].dur_s == pytest.approx(0.75)


def test_jsonl_roundtrip_summarize_and_cli(tmp_path, capsys):
    path = str(tmp_path / "t.jsonl")
    write_jsonl(path, _FIXTURE_SPANS)
    back = read_trace(path)
    assert [s.name for s in back] == [s.name for s in _FIXTURE_SPANS]
    summ = summarize(back)
    assert summ["span_count"] == 4 and summ["scenarios"] == 2
    assert summ["workers"] == ["main", "w1"]
    assert summ["stages"]["analyze"]["count"] == 1
    # scenario 0 spans [0, 1.25], scenario 1 spans [1.25, 1.5]
    assert summ["end_to_end_p99_ms"] == pytest.approx(1250.0)
    assert obs_cli.main([path]) == 0
    assert "critical path" in capsys.readouterr().out
    assert obs_cli.main(["--json", path]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["span_count"] == 4


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_capture_dumps_on_exception(tmp_path):
    fr = FlightRecorder(max_events=8, dump_dir=str(tmp_path))
    fr.note("dispatch", rows=4)
    with pytest.raises(RuntimeError):
        with fr.capture("unit"):
            raise RuntimeError("boom")
    assert len(fr.dumps) == 1
    with open(fr.dumps[0]) as f:
        payload = json.load(f)
    assert payload["reason"] == "exception"
    events = [e["event"] for e in payload["events"]["main"]]
    assert events == ["dispatch", "exception"]


def test_flight_ring_bounded_and_guard_hook(tmp_path):
    fr = FlightRecorder(max_events=3, dump_dir=str(tmp_path))
    for i in range(5):
        fr.note("e", i=i)
    snap = fr.snapshot()["main"]
    assert [e["i"] for e in snap] == [2, 3, 4]     # oldest evicted
    guard = RecompileGuard(label="flight-test")
    fr.attach_guard(guard)
    guard._record_compile("jit_warm")              # pre-boundary: no dump
    assert fr.dumps == []
    guard.warmup()
    guard._record_compile("jit_bad")               # post-boundary: dump
    assert len(fr.dumps) == 1
    with open(fr.dumps[0]) as f:
        payload = json.load(f)
    assert payload["reason"] == "post_warmup_recompile"
    assert payload["context"]["executable"] == "jit_bad"


def test_flight_dump_on_deadline_miss_in_stream(tmp_path):
    """Regression: a deadline-carrying scenario that lands late must
    leave a flight dump behind."""
    import dataclasses
    trace = generate_trace(TraceConfig(num_scenarios=2, seed=7, **QUICK))
    trace = [dataclasses.replace(r, deadline_s=1e-4) for r in trace]
    svc = StreamingScheduler(
        budget=64,
        stream=StreamConfig(batch_rows=2, analysis_workers=1,
                            obs={"enabled": True,
                                 "flight_dir": str(tmp_path)}))
    results = svc.run(trace)
    assert all(r.deadline_met is False for r in results)
    dumps = [p for p in os.listdir(tmp_path) if p.startswith("flight_")]
    assert len(dumps) == len(results)
    with open(tmp_path / sorted(dumps)[0]) as f:
        payload = json.load(f)
    assert payload["reason"] == "deadline_miss"
    assert "dispatch" in [e["event"]
                          for e in payload["events"]["main"]]


# ---------------------------------------------------------------------------
# stream integration: complete trees + bit-identity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_run():
    trace = generate_trace(TraceConfig(num_scenarios=6, seed=11, **QUICK))
    svc = StreamingScheduler(
        budget=96, stream=StreamConfig(batch_rows=2, analysis_workers=2,
                                       obs={"enabled": True}))
    results = svc.run(trace)
    return trace, svc, results


def test_stream_span_trees_complete(traced_run):
    trace, svc, results = traced_run
    by_uid = collections.defaultdict(dict)
    for s in svc.tracer.spans():
        if s.scope is not None and s.name in STAGES:
            assert s.name not in by_uid[s.scope], (s.scope, s.name)
            by_uid[s.scope][s.name] = s
    for r in trace:
        tree = by_uid[r.uid]
        assert sorted(tree) == sorted(STAGES), (r.uid, sorted(tree))
        for a, b in zip(STAGES, STAGES[1:]):
            assert tree[b].start_s >= tree[a].start_s - 1e-9, (r.uid, a, b)
        # span timestamps line up with the result's own clock: the
        # device span ends when the result's batch finished
        res = next(x for x in results if x.request.uid == r.uid)
        assert tree["device"].end_s == pytest.approx(res.done_s, abs=1e-6)


def test_stream_bit_identical_with_obs_on(traced_run):
    trace, _, results = traced_run
    plain = StreamingScheduler(
        budget=96, stream=StreamConfig(batch_rows=2, analysis_workers=2))
    base = plain.run(trace)
    for a, b in zip(results, base):
        assert a.request.uid == b.request.uid
        assert a.best_fitness == b.best_fitness
        np.testing.assert_array_equal(a.best_accel, b.best_accel)
        np.testing.assert_array_equal(a.history_best, b.history_best)


def test_stream_memo_spans(traced_run):
    trace, *_ = traced_run
    svc = StreamingScheduler(
        budget=96, memo=ScheduleMemo(),
        stream=StreamConfig(batch_rows=2, analysis_workers=1,
                            obs={"enabled": True}))
    svc.run(trace)
    names = collections.Counter(s.name for s in svc.tracer.spans())
    assert names["memo.lookup"] == len(trace)
    assert names["memo.record"] == len(trace)
    svc.run(trace)                         # replay: exact hits
    hits = [s for s in svc.tracer.spans() if s.name == "memo.lookup"]
    assert all(s.args.get("outcome") == "hit" for s in hits)


def test_export_trace_method(traced_run, tmp_path):
    _, svc, _ = traced_run
    path = str(tmp_path / "stream.json")
    svc.export_trace(path)
    spans = read_trace(path)
    assert len(spans) == len(svc.tracer.spans())
    assert summarize(spans)["scenarios"] == 6


def test_obs_disabled_run_stays_clean():
    trace = generate_trace(TraceConfig(num_scenarios=2, seed=13, **QUICK))
    svc = StreamingScheduler(
        budget=64, stream=StreamConfig(batch_rows=2, analysis_workers=1))
    svc.run(trace)
    assert svc.tracer is NULL_TRACER and svc.flight is None
    assert svc.tracer.spans() == []


# ---------------------------------------------------------------------------
# sweep chunk spans
# ---------------------------------------------------------------------------
def test_sweep_chunk_spans_on_default_tracer():
    from repro.core.fitness import FitnessFn
    from repro.core.job_analyzer import JobAnalyzer
    from repro.costmodel import get_setting
    from repro.workloads import build_task_groups

    GB = 1024 ** 3
    group = build_task_groups("Light", group_size=8, seed=0)[0]
    table = JobAnalyzer(get_setting("S1")).analyze(group.jobs)
    fits = [FitnessFn(table, bw_sys=16 * GB) for _ in range(4)]
    tr = get_tracer()
    tr.clear()
    run_sweep(fits, budget=64, sweep=SweepConfig(chunk_rows=2,
                                                 obs={"enabled": True}))
    chunks = [s for s in tr.spans() if s.name == "sweep.chunk"]
    # chunking depends on the device count (multi-device runs widen
    # chunks to fill the mesh): one span per compiled call, contiguous
    # indices, all 4 rows covered — not a fixed chunk count
    assert len(chunks) >= 1
    assert [s.args["chunk"] for s in chunks] == list(range(len(chunks)))
    assert sum(s.args["rows"] for s in chunks) >= len(fits)
    assert all(s.args["devices"] >= 1 for s in chunks)
    tr.clear()
    run_sweep(fits, budget=64, sweep=SweepConfig(chunk_rows=2))
    assert tr.spans() == []                # disabled: nothing recorded
