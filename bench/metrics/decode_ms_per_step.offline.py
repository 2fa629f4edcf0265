"""Device milliseconds per greedy decode step: the decode programs' time
in the traced window over their launches (one launch is one step of the
whole batch).  Source: device trace."""
from bench.harness import trace as tracing


def read(ctx):
    if ctx.trace is None:
        return None
    sec, n = tracing.step_time(ctx.trace, "decode")
    return sec / n * 1e3 if n else None
