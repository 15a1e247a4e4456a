"""Fleet tests: admission-queue accounting + steal semantics, the
sharded shared memo (v1 -> v2 in-place migration, versioned errors for
old readers), router partitioning/stealing over fake in-process worker
handles, and the end-to-end subprocess fleet — 2 workers x 4 fake
devices each — where every fleet-served schedule must be bit-identical
to a standalone single-host ``run_sweep`` row and a rerun must replay
cross-worker memo hits (CI runs this file in the ``fleet`` job)."""
import dataclasses
import os
import queue
from typing import Optional

import numpy as np
import pytest

from repro.fleet import (FleetConfig, NUM_SHARDS, ShardedMemoStore,
                         launch_fleet, shard_of)
from repro.fleet.router import FleetRouter
from repro.fleet.worker import encode_array
from repro.memo import MemoLayoutError, MemoRecord, MemoStore, read_layout
from repro.stream import TraceConfig, analyze_serial, generate_trace
from repro.stream.admission import (AdmissionQueues, member_rank,
                                    member_slack)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BUDGET = 120


# ---------------------------------------------------------------------------
# admission queues: the accounting quadruple + steal semantics
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Req:
    uid: int
    arrival_s: float = 0.0
    priority: str = "normal"
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class _Member:
    request: _Req
    ready_s: float = 0.0
    silent: bool = False


def _m(uid, priority="normal", deadline_s=None, ready_s=0.0, silent=False):
    return _Member(_Req(uid=uid, priority=priority, deadline_s=deadline_s),
                   ready_s=ready_s, silent=silent)


def test_member_rank_and_slack():
    assert member_rank(_m(0, "urgent")) == 0
    assert member_rank(_m(0, "normal")) == 1
    assert member_rank(_m(0, "batch")) == 2
    assert member_rank(_m(0, "urgent", silent=True)) == 3
    assert member_slack(_m(0), now=5.0) == np.inf
    assert member_slack(_m(0, deadline_s=8.0), now=5.0) == pytest.approx(3.0)
    assert member_slack(_m(0, deadline_s=8.0, silent=True), 5.0) == np.inf


def test_push_take_accounting_invariant():
    q = AdmissionQueues(batch_rows=4)
    for i in range(10):
        q.push("a" if i % 2 else "b", _m(i))
        q.check()
    assert q.enqueued == len(q) == q.depth == q.peak_depth == 10
    taken = 0
    while q:
        key = q.select(0.0, analyses_pending=False)
        taken += len(q.take(key))
        q.check()
    assert taken == q.dispatched == 10
    assert q.depth == 0 and q.stolen == 0 and q.peak_depth == 10
    assert q.select(0.0, analyses_pending=False) is None


def test_full_batch_goes_partial_holds():
    q = AdmissionQueues(batch_rows=4)
    for i in range(3):
        q.push("k", _m(i))
    assert q.select(0.0, analyses_pending=True) is None   # partial: hold
    assert q.select(0.0, analyses_pending=False) == "k"   # drain: go
    q.push("k", _m(3))
    assert q.select(0.0, analyses_pending=True) == "k"    # full: go now
    assert len(q.take("k")) == 4
    assert q.early_flushes == 0                           # full != flush
    q.check()


def test_early_flush_counted_once_as_reason_tag():
    q = AdmissionQueues(batch_rows=4, max_hold_s=0.25)
    q.push("k", _m(0, ready_s=0.0))
    q.push("k", _m(1, ready_s=0.0))
    assert q.select(0.1, analyses_pending=True) is None   # within hold
    key = q.select(1.0, analyses_pending=True)            # held too long
    assert key == "k"
    assert len(q.take(key)) == 2
    assert q.early_flushes == 1 and q.dispatched == 2     # one event, not 2
    q.check()
    # a later non-flush take never re-counts
    q.push("k", _m(2))
    q.take(q.select(0.0, analyses_pending=False))
    assert q.early_flushes == 1
    q.check()


def test_urgent_slack_preempts_hold():
    q = AdmissionQueues(batch_rows=8, max_hold_s=10.0, slo_margin_s=0.05)
    q.push("k", _m(0, "urgent", deadline_s=1.0))
    assert q.select(0.5, analyses_pending=True) is None   # slack left
    assert q.select(0.97, analyses_pending=True) == "k"   # margin hit


def test_take_order_slo_vs_fifo():
    slo = AdmissionQueues(batch_rows=3, slo_aware=True)
    for uid, prio, dl in [(0, "batch", None), (1, "urgent", 5.0),
                          (2, "normal", 2.0), (3, "urgent", 1.0)]:
        slo.push("k", _m(uid, prio, dl))
    # (class rank, absolute deadline, uid): urgent dl=1, urgent dl=5,
    # then normal — the batch member waits
    assert [m.request.uid for m in slo.take("k")] == [3, 1, 2]

    fifo = AdmissionQueues(batch_rows=3, slo_aware=False)
    for uid in (7, 8, 9, 10):
        fifo.push("k", _m(uid, "urgent" if uid == 10 else "batch"))
    assert [m.request.uid for m in fifo.take("k")] == [7, 8, 9]


def test_steal_least_urgent_first_whole_partials():
    q = AdmissionQueues(batch_rows=4)
    for i in range(6):                                   # relaxed queue
        q.push("a", _m(i, "normal", deadline_s=10.0 + i))
    for i in (90, 91):                                   # urgent queue
        q.push("b", _m(i, "urgent", deadline_s=1.0))
    moved = q.steal(4, now=0.0)
    # one whole partial from the LEAST urgent queue ("a"), and within it
    # the members the victim would have dispatched last
    assert [(k, sorted(m.request.uid for m in ms)) for k, ms in moved] \
        == [("a", [2, 3, 4, 5])]
    assert q.stolen == 4 and q.depth == 4
    q.check()
    # the urgent queue is only surrendered once the relaxed one is gone
    moved = q.steal(100, now=0.0)
    assert [k for k, _ in moved] == ["a", "b"]
    assert q.stolen == 8 and q.depth == 0
    q.check()


def test_steal_never_splits_below_batch_size():
    q = AdmissionQueues(batch_rows=4)
    for i in range(6):
        q.push("a", _m(i))
    assert q.steal(3, now=0.0) == []                     # 4 > allowance
    assert q.stolen == 0 and q.depth == 6
    q.check()


def test_steal_never_touches_dispatched_work():
    q = AdmissionQueues(batch_rows=4)
    for i in range(6):
        q.push("a", _m(i))
    inflight = q.take("a")                               # 4 now on device
    moved = q.steal(100, now=0.0)
    stolen_uids = {m.request.uid for _, ms in moved for m in ms}
    assert stolen_uids.isdisjoint({m.request.uid for m in inflight})
    assert q.enqueued == 6 == q.dispatched + q.stolen + q.depth
    assert q.dispatched == 4 and q.stolen == 2
    q.check()


def test_steal_fifo_victim_gives_up_tail():
    q = AdmissionQueues(batch_rows=2, slo_aware=False)
    for i in range(4):
        q.push("a", _m(i))
    moved = q.steal(2, now=0.0)
    assert [m.request.uid for m in moved[0][1]] == [2, 3]  # newest leave
    assert [m.request.uid for m in q.take("a")] == [0, 1]  # FIFO intact
    q.check()


# ---------------------------------------------------------------------------
# sharded shared memo: layout, migration, old readers
# ---------------------------------------------------------------------------
def _rec(fp, family=("fam",), n=16):
    rng = np.random.default_rng(abs(hash(fp)) % (2 ** 31))
    return MemoRecord(fingerprint=fp, family=family,
                      arrays={"best_fitness": np.float32(rng.uniform()),
                              "best_accel": rng.integers(
                                  0, 4, size=n).astype(np.int32)},
                      meta={"seed": 1})


def _fps(n):
    """n fingerprints spread across shards (first char = hex prefix)."""
    return [f"{i % 16:x}deadbeef{i:04d}" for i in range(n)]


def test_shard_of_covers_all_prefixes():
    assert [shard_of(f"{h:x}00") for h in range(16)] == list(range(16))
    assert NUM_SHARDS == 16


def test_sharded_roundtrip_refresh_discard(tmp_path):
    path = str(tmp_path / "memo")
    a = ShardedMemoStore(path)
    fps = _fps(32)
    for fp in fps:
        a.put(_rec(fp, family=("fam", shard_of(fp) % 2)))
    assert len(a) == 32
    assert read_layout(path) == {"version": 2, "shards": NUM_SHARDS}

    b = ShardedMemoStore(path)                 # second worker, same dir
    assert len(b) == 32
    for fp in fps:
        np.testing.assert_array_equal(b.get(fp).arrays["best_accel"],
                                      a.get(fp).arrays["best_accel"])
    assert sorted(r.fingerprint for r in b.family(("fam", 0))) \
        == sorted(fp for fp in fps if shard_of(fp) % 2 == 0)

    b.put(_rec("0feed0001"))                   # b appends, a refreshes
    assert "0feed0001" not in a
    assert a.refresh() >= 1
    assert "0feed0001" in a
    assert a.refresh() == 0                    # cursors: second stat free

    a.discard(fps[0])
    c = ShardedMemoStore(path)
    assert fps[0] not in c and len(c) == 32    # 32 = 31 live + b's append


def test_v1_index_migrates_in_place_once(tmp_path):
    path = str(tmp_path / "memo")
    v1 = MemoStore(path)
    fps = _fps(24)
    for fp in fps:
        v1.put(_rec(fp))
    v1.discard(fps[3])                         # tombstone must survive
    expect = {fp: v1.get(fp).arrays["best_accel"]
              for fp in fps if fp != fps[3]}

    v2 = ShardedMemoStore(path)                # migrates on open
    assert not os.path.exists(os.path.join(path, "index.jsonl"))
    assert os.path.exists(os.path.join(path, "index.jsonl.v1"))
    assert read_layout(path)["version"] == 2
    assert len(v2) == 23 and fps[3] not in v2
    for fp, accel in expect.items():           # bit-identical round-trip
        np.testing.assert_array_equal(v2.get(fp).arrays["best_accel"],
                                      accel)

    again = ShardedMemoStore(path)             # reopen: no second split
    assert len(again) == 23
    shard_files = [f for f in os.listdir(path) if f.startswith("index-")]
    assert 0 < len(shard_files) <= NUM_SHARDS


def test_old_reader_gets_versioned_error(tmp_path):
    path = str(tmp_path / "memo")
    ShardedMemoStore(path).put(_rec("0abc"))
    with pytest.raises(MemoLayoutError, match="v2.*ShardedMemoStore"):
        MemoStore(path)


def test_sharded_rejects_memory_store_and_bad_layout(tmp_path):
    with pytest.raises(ValueError, match="directory path"):
        ShardedMemoStore("")
    path = str(tmp_path / "memo")
    os.makedirs(path)
    with open(os.path.join(path, "memo_layout.json"), "w") as f:
        f.write('{"version": 3, "shards": 2}')
    with pytest.raises(MemoLayoutError, match="version.*3"):
        ShardedMemoStore(path)


def test_shard_budget_split(tmp_path):
    st = ShardedMemoStore(str(tmp_path / "memo"),
                          byte_budget=NUM_SHARDS * 1024)
    assert all(s.byte_budget == 1024 for s in st._shards)
    st.put(_rec("0aa"))
    assert st.total_bytes > 0
    st.compact()                               # per-shard locks: no clash
    assert "0aa" in ShardedMemoStore(str(tmp_path / "memo"),
                                     byte_budget=None)


# ---------------------------------------------------------------------------
# router over fake in-process worker handles
# ---------------------------------------------------------------------------
class _FakeHandle:
    """Worker-handle stand-in: answers every chunk synchronously with
    per-uid sentinel rows, so routing/steal logic is testable without
    subprocesses or devices."""

    def __init__(self, worker_id, inbox):
        self.worker_id = worker_id
        self._inbox = inbox
        self.outstanding = 0
        self.stats_snapshot = None
        self.scenarios = 0

    def send(self, msg):
        if msg["cmd"] == "run":
            rows = []
            for p in msg["requests"] + msg["prepared"]:
                self.scenarios += 1
                rows.append({
                    "uid": p["uid"], "best_fitness": float(p["uid"]),
                    "best_accel": encode_array(
                        np.full(3, p["uid"], np.int32)),
                    "best_prio": encode_array(np.arange(3, dtype=np.int32)),
                    "history_best": encode_array(np.zeros(2)),
                    "n_samples": 8, "budget": BUDGET, "memo_exact": False,
                    "warm_seeded": False, "anytime_interim": False})
            self._inbox.put((self.worker_id,
                             {"ok": "done", "chunk": msg["chunk"],
                              "results": rows}))
        elif msg["cmd"] == "stats":
            self._inbox.put((self.worker_id,
                             {"ok": "stats",
                              "stats": {"scenarios": self.scenarios}}))


def _trace(n, group_size=8, setting="S1", uid0=0):
    from repro.stream import ScenarioRequest
    return [ScenarioRequest(uid=uid0 + i, arrival_s=0.0, mix="Light",
                            setting=setting, bw_gb=4.0,
                            group_size=group_size, seed=uid0 + i)
            for i in range(n)]


def _fake_router(steal=True, chunk_rows=4):
    inbox = queue.Queue()
    handles = [_FakeHandle("w0", inbox), _FakeHandle("w1", inbox)]
    return FleetRouter(handles, inbox, chunk_rows=chunk_rows,
                       max_outstanding=1, steal=steal,
                       default_budget=BUDGET,
                       stream={"batch_rows": 4}), handles


def test_router_skewed_signature_steals_to_idle_worker():
    router, _ = _fake_router(steal=True)
    results = router.run(_trace(16))           # one signature: all -> w0
    assert [r.request.uid for r in results] == list(range(16))
    assert [r.best_fitness for r in results] == [float(i) for i in range(16)]
    m = router.last_metrics
    assert m.steals >= 1 and m.stolen_members >= 4
    assert set(m.per_worker_scenarios) != {0}  # both ends served work
    assert {r.worker_id for r in results} == {"w0", "w1"}
    assert m.num_scenarios == 16 and m.scenarios_per_sec > 0


def test_router_static_partition_without_steal():
    router, _ = _fake_router(steal=False)
    results = router.run(_trace(6, group_size=8)
                         + _trace(6, group_size=10, uid0=100))
    # two signatures, greedy least-loaded homes: one per worker, sticky
    by_worker = {r.request.uid: r.worker_id for r in results}
    assert len({by_worker[u] for u in range(6)}) == 1
    assert len({by_worker[u] for u in range(100, 106)}) == 1
    assert by_worker[0] != by_worker[100]
    m = router.last_metrics
    assert m.steals == 0 and m.stolen_members == 0
    assert sorted(m.per_worker_scenarios) == [6, 6]


def test_router_steal_rehomes_signature():
    router, handles = _fake_router(steal=True)
    router.run(_trace(16))
    sig = router._signature(_trace(1)[0])
    # after stealing, future arrivals of the signature follow the thief
    assert router._home[sig] == 1
    assert handles[1].scenarios > 0


# ---------------------------------------------------------------------------
# end to end: a real 2-worker x 4-device fleet (subprocess workers)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def fleet_runs(tmp_path_factory):
    """One fleet brought up once (startup dominates): a skewed trace
    routed twice — run 1 with stealing, run 2 steal-free so every
    scenario lands on its home worker and replays the shared memo."""
    memo = str(tmp_path_factory.mktemp("fleet") / "memo")
    trace = generate_trace(TraceConfig(
        num_scenarios=12, group_size=8, seed=5, settings=("S1", "S2"),
        mixes=("Light",), bw_ladder_gb=(1.0, 4.0)))
    cfg = FleetConfig(num_workers=2, devices_per_worker=4, budget=BUDGET,
                      stream={"batch_rows": 4}, memo_path=memo,
                      chunk_rows=4)
    with launch_fleet(cfg) as fleet:
        r1 = fleet.run(trace)
        m1 = fleet.last_metrics
        r2 = fleet.run(trace, steal=False)
        m2 = fleet.last_metrics
    return trace, memo, (r1, m1), (r2, m2)


def test_fleet_covers_trace_and_steals(fleet_runs):
    trace, _, (r1, m1), _ = fleet_runs
    assert [r.request.uid for r in r1] == [t.uid for t in trace]
    assert m1.num_workers == 2 and m1.num_scenarios == len(trace)
    assert m1.steals >= 1 and m1.stolen_members >= 1
    assert all(n > 0 for n in m1.per_worker_scenarios)
    assert m1.scenarios_per_sec > 0 and m1.wall_s > 0
    assert 0 < m1.latency_p50_s <= m1.latency_p99_s


def test_fleet_bit_identical_to_standalone_rows(fleet_runs):
    """THE fleet guarantee: regardless of which worker served a
    scenario (or whether it was stolen there), the schedule equals the
    standalone single-host run_sweep row for that (scenario, seed)."""
    from repro.core.sweep import run_sweep
    _, _, (r1, _), _ = fleet_runs
    # memo_near defaults off: no warm seeding, so the COLD standalone
    # row is the reference for every result
    assert not any(r.warm_seeded for r in r1)
    for r in r1:
        fit = analyze_serial([r.request])[0].fit
        ref = run_sweep([fit], budget=BUDGET, seeds=[r.request.seed])
        assert r.best_fitness == ref.best_fitness[0, 0]
        np.testing.assert_array_equal(r.best_accel, ref.best_accel[0, 0])
        np.testing.assert_array_equal(r.best_prio, ref.best_prio[0, 0])
        np.testing.assert_array_equal(r.history_best,
                                      ref.history_best[0, 0])
        sr = r.to_search_result()
        assert sr.best_fitness == r.best_fitness
        assert sr.n_samples == r.n_samples


def test_fleet_rerun_replays_cross_worker_memo_hits(fleet_runs):
    """Run 2 (steal off) routes every scenario to its home worker; the
    ones run 1 stole were SOLVED elsewhere, so their exact hits cross a
    worker boundary — the shared store's raison d'etre."""
    _, _, (r1, _), (r2, m2) = fleet_runs
    for a, b in zip(r1, r2):
        assert a.best_fitness == b.best_fitness
        np.testing.assert_array_equal(a.best_accel, b.best_accel)
        np.testing.assert_array_equal(a.history_best, b.history_best)
    assert m2.memo_exact_hits == len(r2)
    assert all(r.memo_exact for r in r2)
    assert m2.memo_foreign_hits >= 1
    assert 0.0 < m2.cross_worker_hit_rate <= 1.0


def test_fleet_shared_store_is_sharded_v2(fleet_runs):
    _, memo, (r1, _), _ = fleet_runs
    assert read_layout(memo) == {"version": 2, "shards": NUM_SHARDS}
    store = ShardedMemoStore(memo)
    assert len(store) == len(r1)               # one record per scenario
    with pytest.raises(MemoLayoutError):
        MemoStore(memo)                        # old readers stay honest


def test_warm_starts_cross_worker_boundaries(tmp_path):
    """The shared store's other half: a population one worker's memo
    recorded seeds another worker's near-hit warm start (opt-in via
    ``memo_near=True`` — warm-seeded rows match the memoized warm
    search, not the cold standalone row)."""
    from repro.core.strategies import get_strategy
    from repro.memo import ScheduleMemo
    from repro.stream import StreamConfig, StreamingScheduler
    path = str(tmp_path / "memo")
    trace = generate_trace(TraceConfig(
        num_scenarios=3, group_size=8, seed=7, settings=("S1",),
        mixes=("Light",), bw_ladder_gb=(1.0, 2.0)))
    memo_a = ScheduleMemo(ShardedMemoStore(path), origin="wA")
    svc = StreamingScheduler(budget=BUDGET, memo=memo_a,
                             stream=StreamConfig(batch_rows=2))
    svc.run(trace[:2])                         # wA solves + records pops
    memo_b = ScheduleMemo(ShardedMemoStore(path), origin="wB",
                          max_donor_dist=None)
    fit = analyze_serial(trace[2:])[0].fit
    ws = memo_b.warm_start(fit, get_strategy("magma"),
                           family=trace[2].mix)
    assert ws is not None                      # wA's population donated
    assert memo_b.stats.near_hits == 1


def test_fleet_config_validation():
    with pytest.raises(ValueError, match="num_workers"):
        FleetConfig(num_workers=0)
    with pytest.raises(ValueError, match="devices_per_worker"):
        FleetConfig(devices_per_worker=0)
    with pytest.raises(ValueError, match="chunk_rows"):
        FleetConfig(chunk_rows=0)


def test_chip_env_pins_one_chip_per_worker(monkeypatch):
    """On a TPU host every worker sees exactly its own chip; off one (or
    under JAX_PLATFORMS=cpu) the launcher pins nothing."""
    from repro.fleet.launch import chip_env, tpu_chip_count
    envs = [chip_env(i, 9000 + i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert tpu_chip_count() == 0


def test_worker_failure_carries_its_log(tmp_path):
    """A worker that fails at init: the parent's error names the worker
    and carries the tail of its stderr log."""
    cfg = FleetConfig(num_workers=1, devices_per_worker=1, budget=BUDGET,
                      stream={"no_such_field": 1}, log_dir=str(tmp_path))
    with pytest.raises(RuntimeError) as err:
        launch_fleet(cfg)
    msg = str(err.value)
    assert "fleet worker w0 failed" in msg and "no_such_field" in msg
    assert str(tmp_path / "w0.log") in msg
    assert (tmp_path / "w0.log").exists()

