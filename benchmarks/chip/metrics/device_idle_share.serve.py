"""1 - device busy time (the union of device-op intervals) over the traced
window, mean over the cell's chips, in %: serving cells."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.busy_s:
        return None
    return 100.0 * (1.0 - ctx.trace.mean_busy_s() / ctx.trace.window_s)
