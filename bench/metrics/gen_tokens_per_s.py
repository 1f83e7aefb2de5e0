"""Tokens returned by generate in the window, per second of the window."""


def read(run):
    n = sum(len(c.tokens) for c in run.calls if c.tokens is not None)
    return n / run.window_s if n else None
