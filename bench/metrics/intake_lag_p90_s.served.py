"""How late the service takes requests in: 90th percentile of (analysis
start - due) over the requests routed.  In realtime mode the pipeline
loop only submits due arrivals between its other steps."""
from benchlib.stats import percentile


def read(run):
    lags = [r["analysis_s"] - r["due_s"] for r in run.requests or ()
            if r["analysis_s"] is not None]
    return percentile(lags, 90) if lags else None
