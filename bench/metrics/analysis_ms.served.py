"""Mean host analysis time per request (the service's ``analyze``
spans), in milliseconds."""
from benchlib.stats import mean


def read(run):
    spans = [e - s for n, s, e in run.spans or () if n == "analyze"]
    return 1e3 * mean(spans) if spans else None
