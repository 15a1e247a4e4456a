"""Faults planted underneath the timed path.

The benchmark's own runs never plant one.  The tests drive a run with
each fault the cells can have and see ``correct`` come out false; the
calibration script (``bench/limits.py``) reads a fault's numbers on the
chip, which sets the upper reading of the number it fails.

``unchanged``   the search's generation step returns its state unchanged
                (MAGMA's ``tell``), so a search returns the best of its
                random initial population;
``half_batch``  only the first half of a dispatched batch's rows is
                kept: the other rows get copies of them;
``altered``     the first row's reported fitness is altered by 1% where
                the executable produces it.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "altered")


def _wrap(fn, fault):
    import jax.numpy as jnp

    def faulty(*args):
        outs = fn(*args)
        n = outs[0].shape[0]
        if fault == "half_batch":
            keep = jnp.arange(n) % max(1, (n + 1) // 2)
            return tuple(o[keep] for o in outs)
        return (outs[0].at[0].multiply(1.01),) + tuple(outs[1:])

    return faulty


def _clear():
    import jax
    from repro.core import sweep
    sweep._chunk_fn.cache_clear()
    jax.clear_caches()


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` for the duration of the block."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; expected one of {FAULTS}")
    from repro.core import sweep
    from repro.core.strategies.magma_strategy import MagmaStrategy
    from repro.stream import service
    saved = (MagmaStrategy.tell, sweep.row_executable,
             service.row_executable)
    if fault == "unchanged":
        MagmaStrategy.tell = lambda self, state, fitness: state
    else:
        def row_executable(*args, **kwargs):
            fn, target = saved[1](*args, **kwargs)
            return _wrap(fn, fault), target
        sweep.row_executable = service.row_executable = row_executable
    _clear()
    try:
        yield
    finally:
        (MagmaStrategy.tell, sweep.row_executable,
         service.row_executable) = saved
        _clear()
