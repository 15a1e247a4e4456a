"""Fleet launcher — N scheduler workers as subprocesses (or one
``jax.distributed`` multi-controller group).

CPU-testable end to end: each worker subprocess gets
``--xla_force_host_platform_device_count=<devices_per_worker>`` in its
``XLA_FLAGS``, so a laptop CI job brings up a genuine 2-worker x
4-device fleet.  On a TPU host, ``devices_per_worker=None`` gives every
worker ONE chip of its own: a chip belongs to one process, so worker
``i`` sees only chip ``i`` through libtpu's per-process visibility
variables (:func:`chip_env`).  ``distributed=True`` runs one global JAX
runtime via ``jax.distributed.initialize`` instead; scheduling stays
process-local because the whole stack dispatches over
``jax.local_devices()``.

Each worker's stderr goes to ``<log_dir>/<worker id>.log``; a worker
that fails or dies takes the tail of its log into the parent's error.

    cfg = FleetConfig(num_workers=2, devices_per_worker=4, budget=300)
    with launch_fleet(cfg) as fleet:
        results = fleet.run(generate_trace(TraceConfig(...)))
        print(fleet.last_metrics.summary())

``launch_fleet`` blocks until every worker reports ready (compiled
imports + device init), so ``run`` measures scheduling, not startup.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import queue
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.fleet.worker import PREFIX


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Fleet shape + the per-worker service knobs.

    num_workers         scheduler processes
    devices_per_worker  fake host-platform devices per worker (None:
                        real accelerators — on a TPU host each worker is
                        pinned to a chip of its own)
    budget / strategy   the per-worker StreamingScheduler defaults
    stream              StreamConfig field overrides for every worker
                        (dict, e.g. {"batch_rows": 4})
    memo_path           shared ShardedMemoStore directory (None: no memo)
    memo_near           near-hit warm seeding from the shared store.
                        OFF by default: a warm-seeded row searches from
                        a transferred population and is bit-identical to
                        the memoized warm search, NOT to the cold
                        standalone row — the fleet's hard guarantee.
                        Turn on when convergence matters more (records
                        keep their warm_seeded provenance either way)
    chunk_rows          max scenarios the router sends a worker per chunk
    max_outstanding     chunks in flight per worker (2 = the pipe's
                        double buffering: the next chunk rides the wire
                        while the current one computes)
    steal               work-stealing on (False: static partition only)
    distributed         one global JAX runtime via jax.distributed
                        (coordinator on localhost; workers barrier at
                        init) instead of independent runtimes
    ready_timeout_s     max wait for worker startup (imports + devices)
    obs                 repro.obs.ObsConfig (or field dict) shipped to
                        every worker's StreamingScheduler AND used by
                        the router itself (None: observability off).
                        Workers default ``worker`` to their id and keep
                        the span ring across chunks
    recompile_guard     arm a process-lifetime RecompileGuard in every
                        worker; ``mark_warm()`` sets the boundary and
                        ``worker_stats()`` reports
                        compiles / recompiles_post_warmup
    log_dir             directory of the per-worker stderr logs (None: a
                        new temporary directory)
    """
    num_workers: int = 2
    devices_per_worker: Optional[int] = None
    budget: int = 2_000
    strategy: Optional[str] = None
    stream: Optional[Dict] = None
    memo_path: Optional[str] = None
    memo_near: bool = False
    chunk_rows: int = 16
    max_outstanding: int = 2
    steal: bool = True
    distributed: bool = False
    ready_timeout_s: float = 120.0
    obs: Optional[Dict] = None
    recompile_guard: bool = False
    log_dir: Optional[str] = None

    def __post_init__(self):
        if self.num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got "
                             f"{self.num_workers}")
        if self.devices_per_worker is not None \
                and self.devices_per_worker < 1:
            raise ValueError("devices_per_worker must be >= 1 or None")
        if self.chunk_rows < 1 or self.max_outstanding < 1:
            raise ValueError("chunk_rows and max_outstanding must be >= 1")
        from repro.obs import as_obs_config
        as_obs_config(self.obs)       # validate shape/values early


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tpu_chip_count() -> int:
    """TPU chips on this host, counted from their device files, so the
    launcher learns it without loading libtpu (which would take every
    chip from the workers).  0 when ``JAX_PLATFORMS`` rules the TPU out."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return len(glob.glob("/dev/accel[0-9]*") + glob.glob("/dev/vfio/[0-9]*"))


def chip_env(chip: int, port: int) -> Dict[str, str]:
    """libtpu's per-process visibility: the process sees chip ``chip``
    alone, as a 1x1x1 slice of its own with its own port.  Each pinned
    process loads libtpu for a different chip, which its host-wide load
    lock would otherwise refuse."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


class WorkerHandle:
    """One worker subprocess: stdin for commands, a reader thread
    draining stdout protocol lines into the fleet's shared inbox."""

    def __init__(self, worker_id: str, proc: subprocess.Popen,
                 inbox: "queue.Queue[Tuple[str, Dict]]", log_path: str):
        self.worker_id = worker_id
        self.proc = proc
        self.log_path = log_path
        self._inbox = inbox
        self.info: Dict = {}          # the worker's "ready" message
        self.outstanding = 0          # chunks sent, not yet done
        self.stats: Dict = {}         # final worker-side rollup (on stop)
        self.stats_snapshot: Optional[Dict] = None   # router delta base
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            if line.startswith(PREFIX):
                try:
                    self._inbox.put((self.worker_id,
                                     json.loads(line[len(PREFIX):])))
                except json.JSONDecodeError:
                    pass              # torn line at kill time
        self._inbox.put((self.worker_id, {"ok": "eof"}))

    def failure(self, msg, lines: int = 40) -> str:
        """An error message for this worker: what it reported (or the
        parent saw) plus the tail of its stderr log."""
        try:
            with open(self.log_path, errors="replace") as f:
                tail = "".join(f.readlines()[-lines:])
        except OSError as e:
            tail = f"<no log: {e}>"
        return (f"fleet worker {self.worker_id} failed: {msg}\n"
                f"--- {self.log_path} (last {lines} lines) ---\n{tail}")

    def send(self, msg: Dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def close(self, timeout: float = 10.0) -> None:
        try:
            if self.proc.poll() is None:
                self.send({"cmd": "stop"})
                self.proc.stdin.close()
                self.proc.wait(timeout=timeout)
        except (BrokenPipeError, OSError, subprocess.TimeoutExpired):
            self.proc.kill()
        finally:
            if self.proc.poll() is None:
                self.proc.kill()


class Fleet:
    """A running fleet: worker handles + the router front door.

    ``run`` routes a trace through the fleet and returns
    :class:`~repro.fleet.router.FleetResult`s ordered by uid;
    ``last_metrics`` holds the run's
    :class:`~repro.fleet.metrics.FleetMetrics`.
    """

    def __init__(self, cfg: FleetConfig):
        self.cfg = cfg
        self.inbox: "queue.Queue[Tuple[str, Dict]]" = queue.Queue()
        self.workers: List[WorkerHandle] = []
        self.last_metrics = None
        self.log_dir = cfg.log_dir or tempfile.mkdtemp(prefix="fleet-logs-")
        os.makedirs(self.log_dir, exist_ok=True)
        self.chips = (tpu_chip_count() if cfg.devices_per_worker is None
                      and not cfg.distributed else 0)
        if self.chips and cfg.num_workers > self.chips:
            raise ValueError(
                f"{cfg.num_workers} workers need a TPU chip each; this "
                f"host has {self.chips}")
        coordinator = (f"127.0.0.1:{_free_port()}"
                       if cfg.distributed else None)
        for i in range(cfg.num_workers):
            self.workers.append(self._spawn(i, coordinator))
        # send every init BEFORE waiting: distributed workers barrier
        # inside jax.distributed.initialize, so a send-then-wait loop
        # would deadlock on the first worker
        for i, w in enumerate(self.workers):
            w.send(self._init_msg(i, coordinator))
        self._await_ready()

    # -- startup --------------------------------------------------------------
    def _spawn(self, i: int, coordinator: Optional[str]) -> WorkerHandle:
        env = dict(os.environ)
        # the worker must import the SAME repro the parent runs,
        # regardless of the parent's cwd-relative PYTHONPATH (repro is
        # a namespace package: locate it via __path__, not __file__)
        import repro
        root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
        env["PYTHONPATH"] = (root + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else root)
        if self.cfg.devices_per_worker is not None:
            flags = env.get("XLA_FLAGS", "")
            flags = " ".join(f for f in flags.split()
                             if "host_platform_device_count" not in f)
            env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_"
                                f"device_count={self.cfg.devices_per_worker}"
                                ).strip()
        elif self.chips:
            env.update(chip_env(i, _free_port()))
        wid = f"w{i}"
        log_path = os.path.join(self.log_dir, f"{wid}.log")
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                [sys.executable, "-u", "-m", "repro.fleet.worker"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=log, env=env, text=True)
        return WorkerHandle(wid, proc, self.inbox, log_path)

    def _init_msg(self, i: int, coordinator: Optional[str]) -> Dict:
        cfg = self.cfg
        obs = None
        if cfg.obs is not None:
            from repro.obs import as_obs_config
            obs = dataclasses.asdict(as_obs_config(cfg.obs))
        return {"cmd": "init", "worker_id": f"w{i}",
                "budget": cfg.budget, "strategy": cfg.strategy,
                "stream": cfg.stream or {}, "memo_path": cfg.memo_path,
                "memo_near": cfg.memo_near, "obs": obs,
                "recompile_guard": cfg.recompile_guard,
                "distributed": (None if coordinator is None else
                                {"coordinator_address": coordinator,
                                 "num_processes": cfg.num_workers,
                                 "process_id": i})}

    def _await_ready(self) -> None:
        deadline = time.monotonic() + self.cfg.ready_timeout_s
        pending = {w.worker_id for w in self.workers}
        while pending:
            try:
                wid, msg = self.inbox.get(
                    timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                self.close()
                raise TimeoutError("\n".join(
                    self._handle(wid).failure(
                        f"not ready within {self.cfg.ready_timeout_s:.0f}s")
                    for wid in sorted(pending)))
            if msg.get("ok") == "ready":
                self._handle(wid).info = msg
                pending.discard(wid)
            elif msg.get("ok") in ("error", "eof"):
                self.close()
                raise RuntimeError(self._handle(wid).failure(
                    f"at init: {msg}"))

    def _handle(self, wid: str) -> WorkerHandle:
        return next(w for w in self.workers if w.worker_id == wid)

    # -- serving --------------------------------------------------------------
    def run(self, requests: Sequence = (), prepared: Sequence = (),
            steal: Optional[bool] = None):
        """Route one trace (and/or prepared scenarios) through the
        fleet; results come back uid-ordered, metrics land in
        ``last_metrics``.  ``steal`` overrides the config's
        work-stealing flag for this run only."""
        from repro.fleet.router import FleetRouter
        router = FleetRouter(self.workers, self.inbox,
                             chunk_rows=self.cfg.chunk_rows,
                             max_outstanding=self.cfg.max_outstanding,
                             steal=(self.cfg.steal if steal is None
                                    else bool(steal)),
                             default_budget=self.cfg.budget,
                             stream=self.cfg.stream or {},
                             obs=self.cfg.obs)
        results = router.run(requests, prepared=prepared)
        self.last_metrics = router.last_metrics
        return results

    def warmup(self, requests: Sequence) -> None:
        """Precompile every worker over a trace: each worker runs its
        service's exhaustive ``warmup`` (all admission bucket sizes), so
        a following ``mark_warm()`` boundary is airtight — no bucket is
        left for the measured runs to compile."""
        from repro.fleet.worker import encode_request
        for w in self.workers:
            w.send({"cmd": "warmup",
                    "requests": [encode_request(r) for r in requests]})
        pending = {w.worker_id for w in self.workers}
        deadline = time.monotonic() + self.cfg.ready_timeout_s
        while pending:
            wid, msg = self.inbox.get(
                timeout=max(0.0, deadline - time.monotonic()))
            if msg.get("ok") == "warmed":
                pending.discard(wid)
            elif msg.get("ok") in ("error", "eof"):
                raise RuntimeError(self._handle(wid).failure(msg))

    def mark_warm(self) -> None:
        """Tell every worker its RecompileGuard warmup is over: compiles
        so far were deliberate precompilation, any later one shows up in
        ``worker_stats()`` as ``recompiles_post_warmup``.  No-op for
        workers launched without ``recompile_guard``."""
        for w in self.workers:
            w.send({"cmd": "warm_boundary"})
        pending = {w.worker_id for w in self.workers}
        while pending:
            wid, msg = self.inbox.get(timeout=60.0)
            if msg.get("ok") == "warm":
                pending.discard(wid)
            elif msg.get("ok") in ("error", "eof"):
                raise RuntimeError(self._handle(wid).failure(msg))

    def worker_stats(self) -> Dict[str, Dict]:
        """Raw lifetime worker rollups (a 'stats' round trip to every
        worker; unlike the router's per-run deltas these are the
        process-lifetime counters, including ``compiles`` /
        ``recompiles_post_warmup`` when the guard is armed)."""
        for w in self.workers:
            w.send({"cmd": "stats"})
        stats: Dict[str, Dict] = {}
        pending = {w.worker_id for w in self.workers}
        while pending:
            wid, msg = self.inbox.get(timeout=60.0)
            if msg.get("ok") == "stats":
                stats[wid] = msg.get("stats", {})
                pending.discard(wid)
            elif msg.get("ok") in ("error", "eof"):
                raise RuntimeError(self._handle(wid).failure(msg))
        return stats

    def close(self) -> None:
        for w in self.workers:
            w.close()
        # collect final worker rollups (already enqueued by stop replies)
        while True:
            try:
                wid, msg = self.inbox.get_nowait()
            except queue.Empty:
                break
            if msg.get("ok") == "stopped":
                for w in self.workers:
                    if w.worker_id == wid:
                        w.stats = msg.get("stats", {})

    def __enter__(self) -> "Fleet":
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def launch_fleet(cfg: Optional[FleetConfig] = None, **overrides) -> Fleet:
    """Bring up a fleet (blocking until every worker is ready).  Keyword
    overrides patch ``cfg`` (or a default one): ``launch_fleet(
    num_workers=4, devices_per_worker=2)``."""
    if cfg is None:
        cfg = FleetConfig(**overrides)
    elif overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return Fleet(cfg)
