"""The least time the chip needs for the gradient of the window's SGD
steps (``chipbench.sgd_work``: the features again, the conv backward and
the ELM loss gradient's 4·B·L·C products, unpadded) over the device time
of the ops under the program's ``sgd_update`` scope, in %."""
from chipbench import oppaths
from chipbench.work import roofline_s


def read(ctx):
    w = ctx.counters.get("work") or {}
    t = oppaths.for_reader(__file__, ctx)
    if t is None or "sgd_update_flops" not in w:
        return None
    busy = sum(t.scope_s("sgd_update", ctx.trace.window_ns).values())
    if busy <= 0:
        return None
    return 100.0 * roofline_s(w["sgd_update_flops"], w["sgd_update_bytes"],
                              ctx.peak)[0] / busy
