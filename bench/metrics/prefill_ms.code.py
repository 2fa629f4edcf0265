"""Device milliseconds per prefill call: the prefill programs' time in the
traced window over their launches.  Source: device trace."""
from bench.harness import trace as tracing


def read(ctx):
    if ctx.trace is None:
        return None
    sec, n = tracing.step_time(ctx.trace, "prefill")
    return sec / n * 1e3 if n else None
