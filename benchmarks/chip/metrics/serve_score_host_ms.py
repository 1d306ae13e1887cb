"""Host time of a scoring call: the mean, over the program's
``repro.serve.score`` spans that start in the traced window, of the span's
duration less the device-busy time inside it (mean over chips), in ms."""
from chipbench import oppaths


def read(ctx):
    t = oppaths.for_reader(__file__, ctx)
    if t is None:
        return None
    window = ctx.trace.window_ns
    scores = t.named("repro.serve.score", window)
    if not scores:
        return None
    spans = [(s.event.start_ns, s.event.end_ns) for s in scores]
    busy = t.busy_inside(spans, window)
    return sum(b - a - x for (a, b), x in zip(spans, busy)) \
        / len(spans) / 1e6
