"""Runtime sanitizers — recompilation and transfer discipline, enforced.

Two invariants the static pass cannot see end-to-end:

* **No silent recompilation.**  The stream scheduler and the sweep
  engine promise that after ``warmup()`` every dispatch reuses a cached
  executable; an unexpected static-argument change (a strategy rebuilt
  non-identically, a new bucket shape, an objective spec that stopped
  hashing equal) silently recompiles mid-run and turns a
  milliseconds-scale dispatch into a seconds-scale stall.
  :class:`RecompileGuard` counts compilations and raises — naming the
  offending executables — when any happen after ``warmup()``.

* **No implicit host<->device transfers on the hot path.**  Every
  intended transfer in ``run_rows``/stream dispatch is an explicit
  ``jax.device_put``/``jax.device_get``; anything else (a numpy array
  leaking into a jitted call, a stray ``float()``) is a hidden sync.
  :func:`transfer_sanitizer` scopes ``jax.transfer_guard("disallow")``
  over a region behind a config flag (``SweepConfig.transfer_guard`` /
  ``StreamConfig.transfer_guard``).

The guard counts compilations by listening to jax's own compilation
logging (the ``Compiling <name> ...`` records ``jax``'s internal pxla
module emits at DEBUG level).  That channel names the executable —
``jax.monitoring`` compile events carry no names — and attaching a
logging handler is read-only with respect to jax internals.  The logger
name is pinned per jax version; :func:`_compile_loggers` probes the
known spellings so a jax upgrade degrades to an explicit error, not
silent non-counting.
"""
from __future__ import annotations

import contextlib
import logging
import re
import threading
from typing import Callable, List, Optional

__all__ = ["RecompileError", "RecompileGuard", "transfer_sanitizer"]


def _count_compile(label: str, post_warmup: bool) -> None:
    """Publish every observed compile to the obs registry.  Lazy import
    (``repro.obs`` imports nothing from here at module scope, but this
    module must stay importable without obs) and never raises: the
    guard runs inside a logging handler."""
    try:
        from repro.obs.registry import get_registry
        get_registry().counter(
            "repro_jit_compiles_total",
            "jit compilations observed by RecompileGuard",
        ).inc(phase="post_warmup" if post_warmup else "warmup",
              guard=label or "unlabeled")
    except Exception:
        pass


class RecompileError(RuntimeError):
    """A jit compilation happened inside a region that promised none."""


# jax 0.9.0 emits "Compiling <fn> with global shapes and types ..." from
# jax._src.interpreters.pxla at DEBUG, once per compile.
_COMPILE_LOGGER_NAMES = (
    "jax._src.interpreters.pxla",
)
_COMPILE_RE = re.compile(r"^Compiling (\S+)")


class _CompileListener(logging.Handler):
    """Never raises from emit (logging would swallow it into stderr and
    the guard would silently undercount) — parse, record, move on."""

    def __init__(self, guard: "RecompileGuard"):
        super().__init__(level=logging.DEBUG)
        self._guard = guard

    def emit(self, record: logging.LogRecord) -> None:  # pragma: no cover
        try:
            m = _COMPILE_RE.match(record.getMessage())
            if m:
                self._guard._record_compile(m.group(1))
        except Exception:
            pass


class RecompileGuard:
    """Context manager asserting zero jit compilations after warmup.

        with RecompileGuard(label="stream") as guard:
            svc.warmup(trace)
            guard.warmup()          # compiles so far were expected
            svc.run(trace)          # any compile past here raises
        # __exit__ re-checks; guard.post_warmup lists offenders

    ``warmup()`` marks the boundary: everything compiled before it was
    the deliberate precompilation pass, anything after is a violation.
    Without a ``warmup()`` call the guard only observes (``compiles``
    holds every executable name) and never raises — useful for
    reporting.  Thread-safe: compilations on pool threads are counted.
    """

    def __init__(self, label: str = ""):
        self.label = label
        self.compiles: List[str] = []
        self._boundary: Optional[int] = None
        self._lock = threading.Lock()
        self._listener: Optional[_CompileListener] = None
        self._saved: List = []
        self._callbacks: List[Callable[[str, bool], None]] = []

    def add_listener(self, fn: Callable[[str, bool], None]) -> None:
        """Register ``fn(executable_name, post_warmup)`` to run on every
        recorded compile (the obs flight recorder hooks in here)."""
        with self._lock:
            self._callbacks.append(fn)

    # -- listener plumbing ----------------------------------------------------
    def _record_compile(self, name: str) -> None:
        with self._lock:
            self.compiles.append(name)
            post = self._boundary is not None
            callbacks = list(self._callbacks)
        _count_compile(self.label, post)
        for fn in callbacks:
            try:
                fn(name, post)
            except Exception:       # never raise from the log handler
                pass

    def __enter__(self) -> "RecompileGuard":
        self._listener = _CompileListener(self)
        for lname in _COMPILE_LOGGER_NAMES:
            lg = logging.getLogger(lname)
            opened = not lg.isEnabledFor(logging.DEBUG)
            self._saved.append((lg, lg.level, lg.propagate, opened))
            if opened:
                # WE opened the level just to hear the compile records:
                # stop propagation so they reach only our handler and
                # never hit the user's (or jax's own) stderr handlers.
                # A logger already at DEBUG keeps propagating — the user
                # asked for those logs and the guard must not eat them.
                lg.setLevel(logging.DEBUG)
                lg.propagate = False
            lg.addHandler(self._listener)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        for lg, level, propagate, opened in self._saved:
            lg.removeHandler(self._listener)
            if opened:
                lg.setLevel(level)
                lg.propagate = propagate
        self._saved.clear()
        self._listener = None
        if exc_type is None:
            self.check()
        return False

    # -- the contract ---------------------------------------------------------
    def warmup(self) -> "RecompileGuard":
        """Mark the boundary: compilations so far were the warmup."""
        with self._lock:
            self._boundary = len(self.compiles)
        return self

    @property
    def warmup_compiles(self) -> List[str]:
        with self._lock:
            cut = (len(self.compiles) if self._boundary is None
                   else self._boundary)
            return list(self.compiles[:cut])

    @property
    def post_warmup(self) -> List[str]:
        """Executables compiled after ``warmup()`` (the violations)."""
        with self._lock:
            if self._boundary is None:
                return []
            return list(self.compiles[self._boundary:])

    def check(self) -> None:
        """Raise :class:`RecompileError` naming every executable
        compiled after ``warmup()`` (no-op before ``warmup()``)."""
        bad = self.post_warmup
        if bad:
            label = f" [{self.label}]" if self.label else ""
            names = ", ".join(sorted(set(bad)))
            raise RecompileError(
                f"{len(bad)} jit compilation(s) after warmup{label}: "
                f"{names} — a static argument changed (strategy/objective "
                f"not hashing equal, or an unwarmed bucket shape)")


@contextlib.contextmanager
def transfer_sanitizer(enabled: bool = True):
    """Scoped ``jax.transfer_guard("disallow")`` (no-op when disabled).

    Inside the scope every implicit host<->device transfer raises;
    ``jax.device_put`` / ``jax.device_get`` / ``jnp.asarray`` are
    explicit and stay allowed — which is exactly the discipline the hot
    paths follow.  Intentional implicit transfers inside the scope (none
    on the hot paths today) would wrap themselves in
    ``jax.transfer_guard("allow")``.
    """
    if not enabled:
        yield
        return
    import jax
    with jax.transfer_guard("disallow"):
        yield
