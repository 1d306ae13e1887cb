"""Mean time a request waits between submission and the start of its
flush: Σ ``wait_us`` / Σ ``n`` over the program's ``repro.serve.flush``
spans that start in the traced window, in ms."""
from chipbench import oppaths


def read(ctx):
    t = oppaths.for_reader(__file__, ctx)
    if t is None:
        return None
    flushes = [s.args for s in t.named("repro.serve.flush",
                                       ctx.trace.window_ns)
               if "n" in s.args and "wait_us" in s.args]
    n = sum(int(a["n"]) for a in flushes)
    if n == 0:
        return None
    return sum(float(a["wait_us"]) for a in flushes) / n / 1e3
