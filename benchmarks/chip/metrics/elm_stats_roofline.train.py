"""The least time the chip needs for U = HᵀH and V = HᵀT at unpadded n, L
and C (``work.stats``) over the device time of the ``_elm_stats``
kernel calls, in %."""
from chipbench.work import roofline_s


def read(ctx):
    w = ctx.counters.get("work")
    if ctx.trace is None or not w:
        return None
    t = ctx.trace.total_layer_s("elm_stats")
    if t <= 0:
        return None
    return 100.0 * roofline_s(w["stats_flops"], w["stats_bytes"],
                              ctx.peak)[0] / t
