"""Share of the row executable's leaf-operation device time in the traced
calls that runs under the search's ``search.eval`` scope (the fitness:
decode, queue tables, event scan).  Operations are attributed by the
named scopes of the compiled module (``repro.core.sweep``)."""
from benchlib import phases


def read(run):
    if run.trace is None:
        return None
    reading = phases.search_scopes(run.trace)
    return None if reading is None else reading.fitness_share
