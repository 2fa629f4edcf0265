"""Share of the traced window in which the device idled while the
service's flusher held a partial batch for its ``max_wait_ms`` deadline
(inside ``ecore.hold``, and inside no other program span): work the
dispatch layer held back.  Source: device trace, with the program's
spans."""
from bench.harness import spans


def read(ctx):
    program = spans.for_trace(ctx.trace)
    if program is None or not ctx.trace.ops:
        return None
    return spans.idle_shares(ctx.trace, program)["held"]
