"""Median schedule latency: routed minus due, over every request due in
the window (never routed: infinitely late)."""
from benchlib.stats import latencies, percentile


def read(run):
    return percentile(latencies(run.requests), 50) if run.requests else None
