"""Useful FLOPs of the jobs completed in the traced window (``work.job``:
conv forward once, the backward products SGD needs, ELM statistics, β
solves) over window x chips x the chip's bf16 peak, in %."""


def read(ctx):
    w = ctx.counters.get("work")
    if ctx.trace is None or not w or ctx.trace.window_s <= 0:
        return None
    return 100.0 * w["useful_flops"] / (
        ctx.trace.window_s * ctx.chips * ctx.peak["bf16_flops_per_s"])
