"""90th percentile schedule latency over every request due in the window
(never routed: infinitely late).  A per-layer reading: at 0.8 x knee one
queue that forms from a clump of arrivals moves it by half from run to
run, wider than any end-to-end bound may be."""
from benchlib.stats import latencies, percentile


def read(run):
    return percentile(latencies(run.requests), 90) if run.requests else None
