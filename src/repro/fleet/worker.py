"""Fleet worker — one scheduler process serving chunks over stdio.

``python -m repro.fleet.worker`` is the subprocess the launcher spawns:
it builds ONE long-lived :class:`~repro.stream.StreamingScheduler` over
this process's local devices and serves "run" commands — each a chunk
of held partials the router assembled — returning every schedule over
the same pipe.  The worker is deliberately dumb: all placement policy
(partitioning, stealing) lives in the router; the worker just runs the
unchanged stream pipeline, which is what makes every fleet schedule
bit-identical to a standalone single-host row.

Wire protocol (JSON lines)
--------------------------
Parent -> worker (stdin): ``{"cmd": "init"|"run"|"warmup"|"stats"|
"warm_boundary"|"stop", ...}``.
Worker -> parent (stdout): lines prefixed ``@fleet `` — anything else
on stdout (library prints, banners) is ignored by the parent, so a
chatty dependency cannot corrupt the protocol.  Arrays cross as
``{"dtype", "shape", "b64"}`` (raw little-endian bytes, base64): bit
-exact by construction, no text round-off.  ``best_fitness`` crosses as
a Python float — f32 widens to f64 exactly and ``json`` round-trips
f64 exactly (repr shortest-round-trip), so equality survives the pipe.

Memo: with a shared store configured the worker opens the SAME
:class:`~repro.fleet.shared_memo.ShardedMemoStore` directory as every
other worker and stamps its records ``origin=<worker_id>``; it calls
``store.refresh()`` before each chunk, so schedules solved by one
worker replay as exact hits on any other (counted in
``MemoStats.foreign_hits``).
"""
from __future__ import annotations

import base64
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

PREFIX = "@fleet "


# -- array / scenario codec (also imported by the router side) ----------------
def encode_array(x) -> Dict:
    x = np.ascontiguousarray(x)
    return {"dtype": x.dtype.str, "shape": list(x.shape),
            "b64": base64.b64encode(x.tobytes()).decode("ascii")}


def decode_array(d: Dict) -> np.ndarray:
    buf = base64.b64decode(d["b64"])
    return np.frombuffer(buf, dtype=np.dtype(d["dtype"])) \
             .reshape(d["shape"]).copy()


def encode_request(req) -> Dict:
    return dataclasses.asdict(req)


def decode_request(d: Dict):
    from repro.stream.workloads import ScenarioRequest
    return ScenarioRequest(**d)


def encode_prepared(p) -> Dict:
    """A :class:`~repro.stream.service.PreparedScenario` on the wire:
    the analyzed tables (FitnessParams leaves, bit-exact) + executable
    statics.  Strategy overrides cross by NAME only — a custom strategy
    instance is not portable across processes."""
    fit = p.fit
    strategy = p.strategy
    if strategy is not None and not isinstance(strategy, str):
        strategy = strategy.name
    spec = fit.objective_spec
    return {
        "params": {k: encode_array(v)
                   for k, v in fit.params._asdict().items()},
        "num_accels": int(fit.num_accels),
        "use_kernel": fit.use_kernel,
        "objective": None if spec is None else list(spec.names),
        "seed": int(p.seed), "uid": int(p.uid),
        "budget": p.budget, "strategy": strategy,
        "priority": p.priority, "deadline_s": p.deadline_s,
    }


class HostFit:
    """A scenario held as host numpy tables: exactly the attribute surface
    admission/dispatch/memo touch (``FitnessFn`` duck type — tables +
    executable statics).  Workers schedule decoded prepared scenarios as
    these; a fleet parent builds them with :meth:`from_table` so that it
    never places an array on a chip its workers own."""

    def __init__(self, params, num_accels: int, use_kernel,
                 objective_names: Optional[List[str]]):
        from repro.core.fitness import FitnessParams, ObjectiveSpec
        self.params = FitnessParams(**params)
        self.num_accels = int(num_accels)
        self.use_kernel = use_kernel
        self.objective_spec = (None if objective_names is None
                               else ObjectiveSpec(tuple(objective_names)))
        self.objective = self.objective_spec
        self.group_size = int(np.asarray(self.params.lat).shape[-2])
        self.bw_sys = float(np.asarray(self.params.bw_sys))

    @classmethod
    def from_table(cls, table, bw_sys: float) -> "HostFit":
        """The throughput-objective scenario ``FitnessFn(table, bw_sys)``
        would place on the device."""
        from repro.core.fitness import as_objective_spec, host_params
        spec = as_objective_spec("throughput")
        params = host_params(table, bw_sys, spec)
        return cls(params._asdict(), table.num_accels, False,
                   list(spec.names))


def decode_prepared(d: Dict):
    from repro.stream.service import PreparedScenario
    fit = HostFit({k: decode_array(v) for k, v in d["params"].items()},
                  d["num_accels"], d["use_kernel"], d["objective"])
    return PreparedScenario(fit=fit, seed=d["seed"], uid=d["uid"],
                            budget=d["budget"], strategy=d["strategy"],
                            priority=d["priority"],
                            deadline_s=d["deadline_s"])


def encode_result(r) -> Dict:
    return {
        "uid": int(r.request.uid),
        "best_fitness": float(r.best_fitness),
        "best_accel": encode_array(r.best_accel),
        "best_prio": encode_array(r.best_prio),
        "history_best": encode_array(r.history_best),
        "n_samples": int(r.n_samples),
        "budget": int(r.budget),
        "memo_exact": bool(r.memo_exact),
        "warm_seeded": bool(r.warm_seeded),
        "anytime_interim": bool(r.anytime_interim),
    }


# -- the worker process -------------------------------------------------------
def _emit(msg: Dict) -> None:
    sys.stdout.write(PREFIX + json.dumps(msg) + "\n")
    sys.stdout.flush()


class _Worker:
    def __init__(self, init: Dict):
        self.worker_id = str(init.get("worker_id", "w?"))
        dist = init.get("distributed")
        import jax
        if dist:
            # multi-controller mode: one global runtime across workers.
            # Scheduling still uses jax.local_devices() everywhere
            # (sweep/stream were audited for it), so each worker's
            # dispatches stay process-local and bit-identical.
            jax.distributed.initialize(
                coordinator_address=dist["coordinator_address"],
                num_processes=int(dist["num_processes"]),
                process_id=int(dist["process_id"]))
        from repro.stream.service import StreamConfig, StreamingScheduler
        self.memo = None
        memo_path = init.get("memo_path")
        if memo_path:
            from repro.fleet.shared_memo import ShardedMemoStore
            from repro.memo import ScheduleMemo
            # near=False by default: near-hit warm seeding searches from
            # a transferred population, which is bit-identical to the
            # memoized WARM search but not to the cold standalone row —
            # the fleet's hard guarantee.  memo_near=True opts into
            # cross-worker warm starts where convergence matters more.
            self.memo = ScheduleMemo(ShardedMemoStore(memo_path),
                                     near=bool(init.get("memo_near", False)),
                                     origin=self.worker_id)
        stream_d = dict(init.get("stream") or {})
        obs = init.get("obs")
        if obs:
            # the fleet's ObsConfig rides the init message as a dict;
            # per-worker defaults: spans carry THIS worker's id, and the
            # ring accumulates across chunks (each chunk is one service
            # run — clearing per run would keep only the last chunk)
            obs = dict(obs)
            obs.setdefault("worker", self.worker_id)
            obs["clear_per_run"] = bool(obs.get("clear_per_run", False))
            stream_d["obs"] = obs
        stream = StreamConfig(**stream_d)
        self.svc = StreamingScheduler(strategy=init.get("strategy"),
                                      budget=int(init.get("budget", 2000)),
                                      stream=stream, memo=self.memo)
        self.guard = None
        if init.get("recompile_guard"):
            # process-lifetime observer: entered once, never exited (the
            # process exit tears the logging handler down with it); the
            # router marks the warmup boundary via the "warm_boundary"
            # command, after which stats report any violations
            from repro.lint.runtime import RecompileGuard
            self.guard = RecompileGuard(label=self.worker_id).__enter__()
            if self.svc.flight is not None:
                self.svc.flight.attach_guard(self.guard)
        self.chunks = 0
        self.scenarios = 0
        self.run_wall_s = 0.0
        self.peak_depth = 0
        self.early_flushes = 0
        self.refinements = 0
        dev = jax.local_devices()[0]
        # a pinned worker's chip is a 1x1x1 slice of its own, so its
        # device coords are (0, 0, 0) on every chip: report the chip the
        # launcher pinned it to instead (None when unpinned)
        _emit({"ok": "ready", "worker": self.worker_id,
               "devices": len(jax.local_devices()),
               "platform": dev.platform, "device_kind": dev.device_kind,
               "chip": os.environ.get("TPU_VISIBLE_CHIPS")})

    def handle_run(self, msg: Dict) -> None:
        requests = [decode_request(d) for d in msg.get("requests", ())]
        prepared = [decode_prepared(d) for d in msg.get("prepared", ())]
        if self.memo is not None:
            # fold in every record other workers landed since our last
            # chunk — this is the moment a foreign schedule becomes an
            # exact hit here (one stat per unchanged shard)
            self.memo.store.refresh()
        t0 = time.perf_counter()
        results = self.svc.run(requests, prepared=prepared)
        wall = time.perf_counter() - t0
        self.chunks += 1
        self.scenarios += len(results)
        self.run_wall_s += wall
        aq = self.svc.last_admission
        if aq is not None:
            self.peak_depth = max(self.peak_depth, aq.peak_depth)
            self.early_flushes += aq.early_flushes
        self.refinements += self.svc._refined
        _emit({"ok": "done", "chunk": msg.get("chunk"),
               "results": [encode_result(r) for r in results],
               "wall_s": wall})

    def handle_warmup(self, msg: Dict) -> None:
        """Exhaustive precompilation: the service's own ``warmup`` over a
        decoded trace compiles EVERY bucket size greedy admission could
        hit — a plain warm run only compiles the buckets its own dynamic
        batching happened to produce."""
        self.svc.warmup([decode_request(d)
                         for d in msg.get("requests", ())])
        _emit({"ok": "warmed"})

    def warm_boundary(self) -> None:
        """Everything compiled so far was deliberate warmup; from here a
        compile is a violation the stats will report."""
        if self.guard is not None:
            self.guard.warmup()

    def stats(self) -> Dict:
        memo = (self.memo.stats.summary() if self.memo is not None else {})
        d = {"worker": self.worker_id, "chunks": self.chunks,
             "scenarios": self.scenarios, "run_wall_s": self.run_wall_s,
             "peak_depth": self.peak_depth,
             "early_flushes": self.early_flushes,
             "refinements": self.refinements, "memo": memo}
        if self.guard is not None:
            d["compiles"] = len(self.guard.compiles)
            d["recompiles_post_warmup"] = len(self.guard.post_warmup)
        return d


def main() -> int:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    worker: Optional[_Worker] = None
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        msg = json.loads(line)
        cmd = msg.get("cmd")
        try:
            if cmd == "init":
                worker = _Worker(msg)
            elif cmd == "run":
                worker.handle_run(msg)
            elif cmd == "stats":
                _emit({"ok": "stats", "stats": worker.stats()
                       if worker is not None else {}})
            elif cmd == "warmup":
                worker.handle_warmup(msg)
            elif cmd == "warm_boundary":
                if worker is not None:
                    worker.warm_boundary()
                _emit({"ok": "warm"})
            elif cmd == "stop":
                _emit({"ok": "stopped", "stats": worker.stats()
                       if worker is not None else {}})
                break
            else:
                _emit({"ok": "error", "error": f"unknown cmd {cmd!r}"})
        except Exception as e:                    # protocol-visible failure
            _emit({"ok": "error", "cmd": cmd, "error": repr(e)})
            if cmd == "init":
                return 1
    if worker is not None:
        worker.svc.close()
    return 0


if __name__ == "__main__":
    # line-buffer stdout even when piped, so protocol lines flush promptly
    os.environ.setdefault("PYTHONUNBUFFERED", "1")
    sys.exit(main())
