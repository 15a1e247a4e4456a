"""Share of the traced window in which no operation ran on the device
while the service's main loop was not in its ``stream.loop.idle`` phase:
work pending, the loop busy or waiting on analysis.  From the profiler
trace and the loop's phase spans, put on the trace's clock by the
phases' host annotations."""
from benchlib import phases


def read(run):
    if run.trace is None:
        return None
    return phases.idle_host_share(run.trace, run.spans)
