"""Share of the traced window in which no operation ran on the device,
from the profiler trace."""
from benchlib import profile


def read(run):
    return None if run.trace is None else profile.idle_share(run.trace)
