"""Median schedule latency: routed minus due, over every request due in
the window (never routed: infinitely late).  A per-layer reading: at 0.8 x
knee whether a clump of arrivals tips the service into padded multi-row
batches moves it by 13-18% from run to run, wider than half of any
end-to-end bound may be."""
from benchlib.stats import latencies, percentile


def read(run):
    return percentile(latencies(run.requests), 50) if run.requests else None
