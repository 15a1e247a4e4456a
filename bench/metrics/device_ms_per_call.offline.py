"""Device time per grid call of the search's row executable (the program
with the most device time in the traced window), in milliseconds."""
from benchlib import profile


def read(run):
    return None if run.trace is None else profile.busiest_program_ms(run.trace)
