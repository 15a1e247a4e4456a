"""Schedules routed per second: requests routed over the time from the
window's start to the last routing, drain included."""


def read(run):
    if not run.requests:
        return None
    done = [r["done_s"] for r in run.requests if r["done_s"] is not None]
    return len(done) / max(done) if done else None
