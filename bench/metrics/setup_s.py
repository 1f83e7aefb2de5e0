"""Set-up: interpreter start to the window (imports, device, engine and
weights, warm-up, compiles or cache reads)."""


def read(run):
    return run.setup_s
