"""90th percentile schedule latency over every request due in the window
(never routed: infinitely late).  A per-layer reading: at 0.8 x knee its
runs on one TPU v5e spread 6.7% over seeds, and five times that is past
the largest bound an end-to-end metric may have."""
from benchlib.stats import latencies, percentile


def read(run):
    return percentile(latencies(run.requests), 90) if run.requests else None
