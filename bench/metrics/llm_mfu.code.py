"""Whole-step model FLOP utilization: FLOPs of the prompt and output
tokens the window completed (``counts.request_flops``), times the rate,
over the bf16 peak.  Source: host clock."""
from bench.metrics._mfu import mfu


def read(ctx):
    return mfu(ctx)
