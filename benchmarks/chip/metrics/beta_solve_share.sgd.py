"""Device time of the ops under the program's ``beta_solve`` scope (the
Cholesky factor and both triangular solves of each β: one per batch and
member inside the SGD scan) over the traced window, mean over chips, in
%."""
from chipbench import oppaths


def read(ctx):
    t = oppaths.for_reader(__file__, ctx)
    if t is None:
        return None
    per_chip = t.scope_s("beta_solve", ctx.trace.window_ns)
    busy = sum(per_chip.values())
    if busy <= 0:
        return None
    return 100.0 * busy / (len(per_chip) * ctx.trace.window_s)
