"""The least time the chip needs for the conv forward work of the window's
jobs (``work.job``: conv_flops and conv_bytes, unpadded) over the device
time of the ops whose op-name path lies under the program's ``conv2d``
scope (im2col, the GEMM kernel, reshapes and pads), in %."""
from chipbench import oppaths
from chipbench.work import roofline_s


def read(ctx):
    w = ctx.counters.get("work")
    t = oppaths.for_reader(__file__, ctx)
    if t is None or not w:
        return None
    busy = sum(t.scope_s("conv2d", ctx.trace.window_ns).values())
    if busy <= 0:
        return None
    return 100.0 * roofline_s(w["conv_flops"], w["conv_bytes"],
                              ctx.peak)[0] / busy
