"""Host wall time per ``BucketedScorer.score_block`` call in the traced
window, timed by the benchmark's proxy around the scorer, in ms."""


def read(ctx):
    return ctx.counters.get("score_ms")
