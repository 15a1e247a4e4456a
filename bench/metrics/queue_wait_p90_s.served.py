"""90th percentile of the service's ``queue_wait`` spans: admission to
dispatch of each request."""
from benchlib.stats import percentile


def read(run):
    waits = [e - s for n, s, e in run.spans or () if n == "queue_wait"]
    return percentile(waits, 90) if waits else None
