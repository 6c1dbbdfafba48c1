"""Share of the measured window in which no op ran on the device:
1 - (union of the device's op intervals) / (the window's length)."""

from bench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    busy, win = tr.busy_and_window_s(ctx.trace)
    return 100.0 * (1.0 - busy / win)
