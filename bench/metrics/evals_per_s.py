"""Fitness evaluations per second: rows x samples of every grid call,
over the time from the window's start to the end of the last call."""


def read(run):
    if not run.calls:
        return None
    return sum(c["evals"] for c in run.calls) / run.calls[-1]["end_s"]
