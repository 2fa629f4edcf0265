"""Share of the traced window in which no operation ran on the device:
1 minus the union of the XLA ops' intervals over the window.  Source:
device trace."""
from bench.harness import trace as tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    busy, window = tracing.busy_ns(ctx.trace)
    return (1.0 - busy / window) * 100.0
