"""Share of its roofline that the fitness reaches as the search runs it:
the algorithm's operations and bytes (``benchlib.counts``) for every
population evaluated in the traced calls, over the leaf-operation device
time under ``search.eval``.  The vmapped, scanned fitness inside the row
executable, not a probe."""
from benchlib import phases


def read(run):
    if run.trace is None:
        return None
    reading = phases.search_scopes(run.trace)
    return None if reading is None else reading.fitness_roofline(
        run.device_kind)
