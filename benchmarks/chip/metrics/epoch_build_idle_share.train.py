"""Device-idle time (outside the union of device-op intervals, mean over
chips) while a ``repro.map.epoch_build`` span of the program is open, over
the traced window, in %: the part of the window the host's epoch build
holds the chip."""
from chipbench import oppaths


def read(ctx):
    t = oppaths.for_reader(__file__, ctx)
    if t is None:
        return None
    window = ctx.trace.window_ns
    builds = t.named("repro.map.epoch_build", window)
    if not builds:
        return None
    return 100.0 * t.idle_inside(builds, window) / ctx.trace.window_s
