"""Requests per ``serve_batch`` call in the window, from the service's own
counters (``EcoreService.stats``: served over serve_calls).  Source:
program counter."""


def read(ctx):
    calls = ctx.counters.get("serve_calls", 0)
    return ctx.counters["served"] / calls if calls else None
