"""Requests per scoring dispatch in the window (``ServerStats``:
completed over batches, window deltas)."""


def read(ctx):
    return ctx.counters.get("occupancy")
