"""Share of the decode slots run that some request asked for: each
request's ``max_new_tokens`` summed, over batch x the batch's largest
``max_new_tokens``, of the ``serve_batch`` calls that began in the traced
window.  A batch decodes every row to its largest request, so the rest is
device time no token counts.  The engine's own counts, read off its
``ecore.serve_batch`` spans.  Source: program counter."""
from bench.harness import spans


def read(ctx):
    program = spans.for_trace(ctx.trace)
    if program is None:
        return None
    return spans.decode_slots_used(ctx.trace, program)
