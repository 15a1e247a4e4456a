"""Mean real rows per device dispatch (padding excluded)."""
from benchlib.stats import mean


def read(run):
    return mean(run.batches) if run.batches else None
