"""generate calls finished in the window, per second of the window."""


def read(run):
    return len(run.calls) / run.window_s if run.calls else None
