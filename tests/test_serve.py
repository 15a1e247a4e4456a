"""Multi-tenant serving engine with the MAGMA scheduler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import module
from repro.models.registry import get_model
from repro.serve.engine import (MultiTenantEngine, Submesh, Tenant,
                                TenantSLO, default_submeshes, job_costs)


@pytest.fixture(scope="module")
def engine():
    tenants = []
    for i, arch in enumerate(["granite-3-2b", "falcon-mamba-7b"]):
        cfg = get_smoke_config(arch).replace(dtype="float32")
        model = get_model(cfg)
        values, _ = module.split(model.init(jax.random.PRNGKey(i)))
        tenants.append(Tenant(arch, cfg, values, model))
    return MultiTenantEngine(tenants, default_submeshes(), budget=400,
                             group_size=32, decode_window=4, seed=0)


def test_jobs_for_requests_structure(engine):
    reqs = [("granite-3-2b", 128, 8), ("falcon-mamba-7b", 64, 4)]
    jobs = engine.jobs_for_requests(reqs)
    prefills = [j for j in jobs if j.phase == "prefill"]
    decodes = [j for j in jobs if j.phase == "decode"]
    assert len(prefills) == 2
    assert sum(j.tokens for j in decodes) == 12
    assert all(j.flops > 0 and j.hbm_bytes > 0 for j in jobs)


def test_schedule_covers_all_jobs(engine):
    reqs = [("granite-3-2b", 128, 8)] * 4 + [("falcon-mamba-7b", 64, 8)] * 4
    jobs = engine.jobs_for_requests(reqs)
    out = engine.schedule(jobs)
    scheduled = sorted(uid for q in out["queues"] for uid in q)
    assert scheduled == sorted(j.uid for j in jobs)
    assert out["makespan_s"] > 0 and np.isfinite(out["makespan_s"])


def test_tenant_slo_strictest_and_forwarded(engine):
    """A job group is scheduled at the STRICTEST member tenant's SLO
    (highest class, smallest deadline), and that SLO rides the prepared
    scenario into the stream's admission."""
    with pytest.raises(ValueError, match="priority"):
        TenantSLO(priority="gold")
    with pytest.raises(ValueError, match="deadline_s"):
        TenantSLO(deadline_s=0.0)

    names = list(engine.tenants)
    jobs = engine.jobs_for_requests([(names[0], 64, 4), (names[1], 64, 4)])
    # default: no tenant carries an SLO -> (normal, no deadline)
    slo = engine.slo_for(jobs)
    assert slo.priority == "normal" and slo.deadline_s is None
    try:
        engine.tenants[names[0]].slo = TenantSLO("batch", 9.0)
        engine.tenants[names[1]].slo = TenantSLO("urgent", 2.5)
        slo = engine.slo_for(jobs)
        assert slo.priority == "urgent" and slo.deadline_s == 2.5
        # a group touching only the batch tenant keeps that tenant's SLO
        only = [j for j in jobs if j.tenant == names[0]]
        slo0 = engine.slo_for(only)
        assert slo0.priority == "batch" and slo0.deadline_s == 9.0
        # the stream sees the strictest SLO on the scheduled request
        sr = engine.schedule(jobs)["stream"]
        assert sr is not None
        assert sr.request.priority == "urgent"
        assert sr.request.deadline_s == 2.5
        assert sr.deadline_met is not None
    finally:
        for n in names:
            engine.tenants[n].slo = None


def test_magma_not_worse_than_naive_round_robin(engine):
    reqs = [("granite-3-2b", 256, 16)] * 3 + [("falcon-mamba-7b", 128, 16)] * 3
    jobs = engine.jobs_for_requests(reqs)
    table = engine.analyze(jobs)
    out = engine.schedule(jobs, method="magma")
    # naive round robin baseline
    from repro.core.bw_allocator import simulate_numpy
    A = len(engine.submeshes)
    rr = [[] for _ in range(A)]
    for i, j in enumerate(jobs):
        rr[i % A].append(j.uid - jobs[0].uid)
    naive = simulate_numpy(rr, table.lat, table.bw, engine.system_bw)
    assert out["makespan_s"] <= naive * 1.02


def test_bigger_submesh_is_faster_per_job():
    cfg = get_smoke_config("granite-3-2b")
    f, h, p = job_costs(cfg, "prefill", 1, 256, 256)
    big = Submesh("tp16", 16).cost.profile(f, h, p)
    small = Submesh("tp4", 4).cost.profile(f, h, p)
    assert big[0] < small[0]          # faster
    assert big[1] > small[1]          # but more BW-hungry


def test_schedule_execute_under_registry_strategies(engine):
    """schedule(execute=True) under registry strategies (device-resident
    AND host-only): every method's schedule covers all jobs, executed
    outputs cover the scheduled decode queue, and the greedy tokens are
    schedule-invariant (queue order only changes inter-chain
    interleaving, never per-chain results).  Device-resident methods
    route through the stream service and must match the direct
    run_strategy result bit-for-bit."""
    from repro.core.fitness import FitnessFn
    from repro.core.strategies import get_strategy, run_strategy

    reqs = [("granite-3-2b", 12, 4), ("falcon-mamba-7b", 16, 4)]
    jobs = engine.jobs_for_requests(reqs)
    rng = np.random.default_rng(1)
    prompts = {j.uid: rng.integers(0, 128, (1, j.seq))
               for j in jobs if j.phase == "prefill"}
    decode_uids = sorted(j.uid for j in jobs if j.phase == "decode")

    with pytest.raises(ValueError, match="prompts"):
        engine.schedule(jobs, execute=True)

    fit = FitnessFn(engine.analyze(jobs), bw_sys=engine.system_bw)
    ref_tokens = None
    for method in ("magma", "stdga", "random", "herald_like"):
        out = engine.schedule(jobs, method=method, execute=True,
                              prompts=prompts)
        scheduled = sorted(uid for q in out["queues"] for uid in q)
        assert scheduled == sorted(j.uid for j in jobs)
        assert sorted(out["outputs"]) == decode_uids
        toks = np.concatenate([out["outputs"][u] for u in decode_uids],
                              axis=1)
        if ref_tokens is None:
            ref_tokens = toks
        else:
            np.testing.assert_array_equal(toks, ref_tokens)

        strategy = get_strategy(method)
        if strategy.device_resident:
            assert out["stream"] is not None
            ref = run_strategy(strategy, fit, budget=engine.budget,
                               seed=engine.seed)
            assert out["result"].best_fitness == ref.best_fitness
            np.testing.assert_array_equal(out["result"].best_accel,
                                          ref.best_accel)
            np.testing.assert_array_equal(out["result"].best_prio,
                                          ref.best_prio)
        else:
            assert out["stream"] is None


def test_execute_runs_schedule_and_matches_reference(engine):
    """Scheduled execution produces the same tokens as a plain decode."""
    reqs = [("granite-3-2b", 12, 6)]
    jobs = engine.jobs_for_requests(reqs)
    out = engine.schedule(jobs)
    rng = np.random.default_rng(0)
    prompts = {j.uid: rng.integers(0, 128, (1, j.seq))
               for j in jobs if j.phase == "prefill"}
    gen = engine.execute(jobs, out["queues"], prompts)
    toks = np.concatenate([gen[j.uid] for j in jobs if j.phase == "decode"],
                          axis=1)

    # reference: greedy decode without the engine
    tenant = engine.tenants["granite-3-2b"]
    prompt = jnp.asarray(prompts[jobs[0].uid])
    logits, cache = tenant.model.prefill(tenant.params, {"tokens": prompt},
                                         12 + 6)
    cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    want = []
    pos = 12
    for _ in range(6):
        lg, cache = tenant.model.decode_step(tenant.params, cache, cur,
                                             jnp.int32(pos))
        cur = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
        want.append(int(cur[0, 0]))
        pos += 1
    np.testing.assert_array_equal(toks[0], np.array(want))


def test_schedule_front_serves_the_frontier(engine):
    """The multi-objective serving tier: the profile table carries a real
    energy column (whole-slice board power), and ``schedule_front``
    returns a non-dominated set of complete schedules."""
    from repro.core.pareto import non_dominated_mask

    reqs = [("granite-3-2b", 128, 8)] * 3 + [("falcon-mamba-7b", 64, 8)] * 3
    jobs = engine.jobs_for_requests(reqs)
    table = engine.analyze(jobs)
    assert table.energy is not None and (table.energy > 0).all()
    # a tp16 slice is faster but costs more energy than tp4 on every job
    subs = [s.name for s in engine.submeshes]
    tp16, tp4 = subs.index("tp16_a"), subs.index("tp4_a")
    assert (table.lat[:, tp16] < table.lat[:, tp4]).all()
    assert (table.energy[:, tp16] > table.energy[:, tp4]).all()

    out = engine.schedule_front(jobs)
    front = out["front"]
    assert front.names == ("latency", "energy", "edp")
    assert len(front) >= 1 and len(out["points"]) == len(front)
    assert non_dominated_mask(front.objectives).all()
    all_uids = sorted(j.uid for j in jobs)
    for pt in out["points"]:
        assert sorted(u for q in pt["queues"] for u in q) == all_uids
        assert pt["makespan_s"] > 0 and np.isfinite(pt["makespan_s"])
        assert set(pt["objectives"]) == {"latency", "energy", "edp"}


def test_fleet_path_ships_host_tables(engine):
    """With a fleet attached the engine builds no device array: it ships
    host numpy tables equal to the FitnessFn's, and the schedule equals
    the in-process stream's."""
    from repro.core.fitness import FitnessFn
    from repro.stream import StreamConfig, StreamingScheduler

    class InProcessFleet:
        """Stands in for repro.fleet.Fleet: serves prepared scenarios the
        way a worker does, after checking what crossed the wire."""
        def __init__(self):
            self.svc = StreamingScheduler(budget=engine.budget,
                                          stream=StreamConfig())
            self.seen = []

        def run(self, prepared=()):
            self.seen.extend(prepared)
            return self.svc.run(prepared=prepared)

    reqs = [("granite-3-2b", 64, 4)] * 3
    jobs = engine.jobs_for_requests(reqs)
    fleet = InProcessFleet()
    with MultiTenantEngine(list(engine.tenants.values()),
                           default_submeshes(), budget=engine.budget,
                           group_size=engine.group_size,
                           decode_window=engine.decode_window,
                           seed=engine.seed, fleet=fleet) as fleet_engine:
        out = fleet_engine.schedule(jobs)
    fleet.svc.close()
    (p,) = fleet.seen
    ref_fit = FitnessFn(out["table"], bw_sys=engine.system_bw)
    for leaf, ref in zip(p.fit.params, ref_fit.params):
        assert type(leaf).__module__.startswith("numpy")
        np.testing.assert_array_equal(leaf, np.asarray(ref))
    ref = engine.schedule(jobs)
    assert out["queues"] == ref["queues"]
    assert out["makespan_s"] == ref["makespan_s"]

