"""Multi-tenant serving engine whose job->submesh scheduler is MAGMA.

This is the paper's technique integrated as a first-class framework
feature, hardware-adapted to TPU pods (DESIGN.md §3):

  sub-accelerator  ->  TPU submesh (tp x dp slice of the pod)
  job              ->  (tenant, phase) unit: a prefill of a request batch,
                       or a decode window of T tokens
  system BW        ->  shared host->pod ingress (PCIe/DCN) that all
                       submeshes contend for
  job analysis     ->  TPU roofline cost model (costmodel.tpu): no-stall
                       latency = max(compute, HBM) term; required BW =
                       host-visible bytes / latency

The engine batches queued requests into dependency-free job groups,
profiles them against every submesh, runs MAGMA over the (selection x
priority) encoding, and returns the mapping + the BW-allocator timeline.
``schedule(..., execute=True)`` additionally runs the scheduled jobs for
real (smoke-size models on CPU; the same code path drives TPU submeshes
via jit) so tests can check output correctness, not just schedule quality.

Since the ``repro.stream`` service landed, the engine is a *client* of the
stream rather than a standalone code path: every device-resident method
is scheduled via ``StreamingScheduler.schedule_prepared`` (the engine's
TPU-roofline tables enter the admission queue as prepared scenarios and
ride the same compiled row executables as every sweep), which is
bit-identical to the old direct ``run_strategy`` call with the same seed
and budget.  Host-only methods (heuristics, RL) keep the host loop.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import M3E  # noqa: F401  (re-export convenience)
from repro.core.fitness import FitnessFn
from repro.core.job_analyzer import table_from_arrays
from repro.core.magma import SearchResult
from repro.core.bw_allocator import simulate_numpy
from repro.core.encoding import decode_to_lists
from repro.costmodel.tpu import TPUSubmesh, V5E
from repro.models import module
from repro.models.config import ModelConfig
from repro.models.registry import get_model, count_active_params


@dataclasses.dataclass
class Submesh:
    """One schedulable slice of the pod."""
    name: str
    tp: int
    dp: int = 1

    @property
    def cost(self) -> TPUSubmesh:
        return TPUSubmesh(self.name, tp=self.tp, dp=self.dp)


def default_submeshes() -> List[Submesh]:
    """A heterogeneous carving of one 256-chip pod: big TP slices for
    latency-critical prefill, small slices for decode — the TPU analogue of
    the paper's HB/LB heterogeneous cores."""
    return [Submesh("tp16_a", 16), Submesh("tp16_b", 16),
            Submesh("tp8_a", 8), Submesh("tp8_b", 8),
            Submesh("tp4_a", 4), Submesh("tp4_b", 4),
            Submesh("tp4_c", 4), Submesh("tp4_d", 4)]


@dataclasses.dataclass(frozen=True)
class TenantSLO:
    """Per-tenant service-level objective, forwarded to the stream's
    SLO-aware admission: ``priority`` is one of
    ``repro.stream.workloads.PRIORITY_CLASSES`` and ``deadline_s`` the
    scheduling-latency budget (admission -> schedule routed).  A job
    group spanning several tenants is scheduled at the STRICTEST member
    SLO — an urgent tenant's jobs must not wait because a batch tenant
    shares the group."""
    priority: str = "normal"
    deadline_s: Optional[float] = None

    def __post_init__(self):
        from repro.stream.workloads import PRIORITY_CLASSES
        if self.priority not in PRIORITY_CLASSES:
            raise ValueError(f"unknown priority {self.priority!r}; "
                             f"expected one of {PRIORITY_CLASSES}")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0 or None, got "
                             f"{self.deadline_s}")


@dataclasses.dataclass
class Tenant:
    name: str
    cfg: ModelConfig
    params: object                  # value tree
    model: object = None
    slo: Optional[TenantSLO] = None  # None: normal priority, no deadline

    def __post_init__(self):
        if self.model is None:
            self.model = get_model(self.cfg)


@dataclasses.dataclass
class ServeJob:
    uid: int
    tenant: str
    phase: str                      # 'prefill' | 'decode'
    batch: int                      # requests in the job
    seq: int                        # prompt len (prefill) / ctx len (decode)
    tokens: int                     # tokens produced/processed
    flops: float = 0.0
    hbm_bytes: float = 0.0
    host_bytes: float = 0.0


def job_costs(cfg: ModelConfig, phase: str, batch: int, seq: int,
              tokens: int) -> Tuple[float, float, float]:
    """(flops, hbm_bytes, host_bytes) for one job, from the model config."""
    n_active = count_active_params(cfg)
    bpe = 2  # bf16
    if phase == "prefill":
        flops = 2.0 * n_active * batch * seq
        hbm = n_active * bpe + batch * seq * cfg.d_model * bpe
        host = batch * seq * 4 + batch * seq * cfg.d_model * bpe * 0.0 \
            + batch * 4  # token ids in, last-logit ids out
        if cfg.family in ("vlm", "encdec"):
            host += batch * seq * cfg.d_model * bpe  # embeddings cross PCIe
    else:
        flops = 2.0 * n_active * batch * tokens
        kv_heads = max(cfg.n_kv_heads, 1)
        kv = (2 * cfg.num_layers * batch * seq * kv_heads * cfg.hd * bpe
              if cfg.n_heads else
              cfg.num_layers * batch * cfg.inner * cfg.ssm_state * 4)
        hbm = tokens * (n_active * bpe + kv)
        host = batch * tokens * 2 * 4
    return float(flops), float(hbm), float(host)


class MultiTenantEngine:
    def __init__(self, tenants: Sequence[Tenant],
                 submeshes: Optional[Sequence[Submesh]] = None,
                 system_bw: float = 64e9, group_size: int = 64,
                 decode_window: int = 32, budget: int = 2_000,
                 method: str = "magma", seed: int = 0,
                 stream=None, memo=None, fleet=None):
        self.tenants = {t.name: t for t in tenants}
        self.submeshes = list(submeshes or default_submeshes())
        self.system_bw = float(system_bw)
        self.group_size = group_size
        self.decode_window = decode_window
        self.budget = budget
        self.method = method
        self.seed = seed
        self._uid = 0
        # the stream service this engine schedules through (shared so many
        # engines can feed one admission queue); lazily built when the
        # first device-resident method is scheduled
        self._stream = stream
        self._owns_stream = False
        # schedule memo (repro.memo.ScheduleMemo) consulted by the stream
        # at admission: a re-seen job group replays its stored mapping
        # bit-for-bit with no search; near-same groups warm-start.  Only
        # applies to the service this engine creates — an injected
        # ``stream`` keeps whatever memo it was built with.
        self.memo = memo
        # fleet-backed option: an injected ``repro.fleet.Fleet`` serves
        # device-resident methods instead of an in-process stream — the
        # prepared tables cross to a worker bit-exactly and the returned
        # schedule is bit-identical to the in-process path (the fleet
        # contract).  The fleet is the injector's to launch and close.
        self.fleet = fleet

    def stream_service(self):
        """The ``repro.stream.StreamingScheduler`` this engine is a client
        of (created on first use unless one was injected)."""
        if self._stream is None:
            from repro.stream import StreamConfig, StreamingScheduler
            # no trace analysis happens on this path (scenarios arrive
            # prepared), so a minimal analysis pool suffices
            self._stream = StreamingScheduler(
                budget=self.budget,
                stream=StreamConfig(analysis_workers=1),
                memo=self.memo)
            self._owns_stream = True
        return self._stream

    def close(self) -> None:
        """Shut down the stream service this engine created (an injected,
        shared service is the injector's to close)."""
        if self._owns_stream and self._stream is not None:
            self._stream.close()
            self._stream = None
            self._owns_stream = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- job construction -----------------------------------------------------
    def jobs_for_requests(self, requests: Sequence[Tuple[str, int, int]]
                          ) -> List[ServeJob]:
        """requests: (tenant, prompt_len, gen_len) -> prefill + decode jobs."""
        jobs: List[ServeJob] = []
        for tenant, prompt, gen in requests:
            cfg = self.tenants[tenant].cfg
            f, h, p = job_costs(cfg, "prefill", 1, prompt, prompt)
            jobs.append(ServeJob(self._uid, tenant, "prefill", 1, prompt,
                                 prompt, f, h, p))
            self._uid += 1
            done = 0
            while done < gen:
                w = min(self.decode_window, gen - done)
                ctx = prompt + done + w
                f, h, p = job_costs(cfg, "decode", 1, ctx, w)
                jobs.append(ServeJob(self._uid, tenant, "decode", 1, ctx, w,
                                     f, h, p))
                self._uid += 1
                done += w
        return jobs

    def slo_for(self, jobs: Sequence[ServeJob]) -> TenantSLO:
        """The strictest SLO across the tenants appearing in ``jobs``:
        highest priority class, smallest deadline.  Tenants without an
        SLO contribute the (normal, no-deadline) default."""
        from repro.stream.workloads import PRIORITY_CLASSES
        slos = [self.tenants[j.tenant].slo or TenantSLO()
                for j in jobs] or [TenantSLO()]
        priority = min((s.priority for s in slos),
                       key=PRIORITY_CLASSES.index)
        deadlines = [s.deadline_s for s in slos if s.deadline_s is not None]
        return TenantSLO(priority=priority,
                         deadline_s=min(deadlines) if deadlines else None)

    # -- analysis + scheduling --------------------------------------------------
    def analyze(self, jobs: Sequence[ServeJob]):
        """Job-analysis table over (job x submesh) from the TPU cost model.

        Carries an energy column (``TPUSubmesh.energy_j``: whole-slice
        board power x duration) so the serving tier can search energy and
        EDP objectives — a tp16 slice finishes a job ~4x faster than tp4
        but holds 4x the chips, a real latency/energy frontier.
        """
        G, A = len(jobs), len(self.submeshes)
        lat = np.zeros((G, A))
        bw = np.zeros((G, A))
        en = np.zeros((G, A))
        for g, job in enumerate(jobs):
            for a, sm in enumerate(self.submeshes):
                l, b = sm.cost.profile(job.flops, job.hbm_bytes,
                                       job.host_bytes)
                lat[g, a] = l
                bw[g, a] = b
                en[g, a] = sm.cost.energy_j(l)
        flops = np.array([j.flops for j in jobs])
        return table_from_arrays(lat, bw, flops, energy=en)

    def schedule(self, jobs: Sequence[ServeJob],
                 method: Optional[str] = None,
                 execute: bool = False,
                 prompts: Optional[Dict[int, np.ndarray]] = None) -> Dict:
        """Profile, search, and map ``jobs`` onto the submeshes.

        Device-resident methods go through the stream service (prepared
        scenario -> admission queue -> compiled row executable), which is
        bit-identical to a direct ``run_strategy`` with the same seed and
        budget; host-only methods run their own loops.  With
        ``execute=True`` the scheduled jobs also run for real in queue
        order (``prompts`` maps prefill-job uid -> token array) and the
        generated tokens come back under ``"outputs"``.
        """
        from repro.core.strategies import get_strategy, run_strategy
        if execute and prompts is None:
            raise ValueError("execute=True needs prompts "
                             "(prefill-job uid -> token array)")
        table = self.analyze(jobs)
        method = method or self.method
        strategy = get_strategy(method)
        stream_res = None
        if not strategy.device_resident:
            res: SearchResult = run_strategy(
                strategy, FitnessFn(table, bw_sys=self.system_bw),
                budget=self.budget, seed=self.seed)
        else:
            slo = self.slo_for(jobs)
            if self.fleet is not None:
                # the fleet's workers own the chips: ship host tables and
                # never place an array on a device from this process
                from repro.fleet.worker import HostFit
                from repro.stream.service import PreparedScenario
                stream_res = self.fleet.run(prepared=[PreparedScenario(
                    fit=HostFit.from_table(table, self.system_bw),
                    seed=self.seed, budget=self.budget, strategy=strategy,
                    priority=slo.priority, deadline_s=slo.deadline_s)])[0]
            else:
                stream_res = self.stream_service().schedule_prepared(
                    FitnessFn(table, bw_sys=self.system_bw),
                    seed=self.seed, budget=self.budget, strategy=strategy,
                    priority=slo.priority, deadline_s=slo.deadline_s)
            res = stream_res.to_search_result()
        local = decode_to_lists(res.best_accel, res.best_prio,
                                len(self.submeshes))
        makespan = simulate_numpy(local, table.lat, table.bw, self.system_bw)
        # map group-local job indices back to engine-global job uids
        queues = [[int(jobs[i].uid) for i in q] for q in local]
        out = {
            "result": res,
            "queues": queues,
            "local_queues": local,
            "makespan_s": float(makespan),
            "throughput_flops": table.total_flops / max(makespan, 1e-30),
            "table": table,
            "stream": stream_res,
        }
        if execute:
            out["outputs"] = self.execute(jobs, queues, prompts)
        return out

    def schedule_front(self, jobs: Sequence[ServeJob],
                       objectives: Sequence[str] = ("latency", "energy",
                                                    "edp"),
                       method: str = "nsga2") -> Dict:
        """Co-search several serving objectives at once -> the frontier.

        Same profile tables as :meth:`schedule` (the energy column comes
        from whole-slice board power), a vector ``ObjectiveSpec``, routed
        through ``stream_service().schedule_front`` under the job group's
        strictest tenant SLO.  Returns the ``ParetoFront`` plus, for each
        front point, the decoded queues and simulated makespan — the
        operator picks the latency/energy trade-off, every candidate
        already a complete schedule.
        """
        table = self.analyze(jobs)
        fit = FitnessFn(table, bw_sys=self.system_bw,
                        objective=tuple(objectives))
        slo = self.slo_for(jobs)
        front = self.stream_service().schedule_front(
            fit, seed=self.seed, budget=self.budget, strategy=method,
            priority=slo.priority, deadline_s=slo.deadline_s)
        points = []
        for k in range(len(front)):
            pt = front.point(k)
            local = decode_to_lists(pt["accel"], pt["prio"],
                                    len(self.submeshes))
            makespan = simulate_numpy(local, table.lat, table.bw,
                                      self.system_bw)
            points.append({
                "objectives": {n: pt[n] for n in front.names},
                "queues": [[int(jobs[i].uid) for i in q] for q in local],
                "makespan_s": float(makespan),
            })
        return {"front": front, "points": points, "table": table}

    # -- execution (functional correctness on the scheduled order) -------------
    def execute(self, jobs: Sequence[ServeJob], queues: List[List[int]],
                prompts: Dict[int, np.ndarray]) -> Dict[int, np.ndarray]:
        """Run the scheduled jobs for real, in per-submesh queue order.

        ``prompts``: prefill-job uid -> (1, prompt_len) token array.
        Returns uid -> generated token ids (greedy) for decode jobs.
        State (cache) is keyed per tenant-request chain."""
        outputs: Dict[int, np.ndarray] = {}
        chains: Dict[str, Dict] = {}
        by_uid = {j.uid: j for j in jobs}
        order = [uid for q in queues for uid in q]
        # execution must respect per-chain phase order; queue order decides
        # inter-chain interleaving (the scheduler's freedom)
        for uid in sorted(order, key=lambda u: u):
            job = by_uid[uid]
            tenant = self.tenants[job.tenant]
            model, cfg = tenant.model, tenant.cfg
            chain = chains.setdefault(job.tenant, {})
            if job.phase == "prefill":
                toks = jnp.asarray(prompts[uid])
                total = job.seq + sum(
                    j.tokens for j in jobs
                    if j.tenant == job.tenant and j.phase == "decode")
                logits, cache = model.prefill(tenant.params,
                                              {"tokens": toks}, total)
                chain["cache"] = cache
                chain["pos"] = job.seq
                chain["last"] = jnp.argmax(logits[:, -1], axis=-1)
            else:
                cache, pos = chain["cache"], chain["pos"]
                cur = chain["last"][:, None].astype(jnp.int32)
                outs = []
                for _ in range(job.tokens):
                    logits, cache = model.decode_step(tenant.params, cache,
                                                      cur, jnp.int32(pos))
                    cur = jnp.argmax(logits[:, -1], axis=-1)[:, None]
                    cur = cur.astype(jnp.int32)
                    outs.append(np.asarray(cur[:, 0]))
                    pos += 1
                chain.update(cache=cache, pos=pos, last=cur[:, 0])
                outputs[uid] = np.stack(outs, axis=1)
        return outputs
