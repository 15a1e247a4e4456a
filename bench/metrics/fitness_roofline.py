"""Share of its roofline that the fitness entry point reaches: the least
time the chip could take for the algorithm's operations and bytes
(``benchlib.counts``) over the device time of one population evaluation,
timed alone after the window.  Memory bounds it at the paper's sizes."""
from benchlib import counts


def read(run):
    reading = run.probe() if run.probe is not None else None
    if reading is None:
        return None
    seconds, flops, nbytes = reading
    share, _ = counts.roofline_share(flops, nbytes, seconds, run.device_kind)
    return share
