"""Share of the traced window in which the device idled while the
engine's host loop ran: idle time inside an ``ecore.serve_batch`` span
(building the batch, dispatching prefill and each decode step, waiting,
copying the tokens back).  With ``idle_partition``'s other three classes
it sums to ``device_idle``.  Source: device trace, with the program's
spans."""
from bench.harness import spans


def read(ctx):
    program = spans.for_trace(ctx.trace)
    if program is None or not ctx.trace.ops:
        return None
    return spans.idle_shares(ctx.trace, program)["engine"]
