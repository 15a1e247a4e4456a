"""Set-up: process start to the window's start (imports, analysis,
compilation or loading from the persistent cache, warm-up)."""


def read(run):
    return run.setup_s
