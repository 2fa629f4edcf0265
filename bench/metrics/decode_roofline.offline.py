"""Share of the memory roofline the decode step reaches: the bytes a step
must move (every bf16 weight once, plus each sequence's cache at its
length) over the chip's HBM bandwidth, against the decode programs'
device time per step.  The traced window holds whole batches, so the
cache lengths of its steps are known.  Source: device trace."""
from bench.harness import trace as tracing


def read(ctx):
    lengths = ctx.counters.get("traced_decode_lengths") or []
    if ctx.trace is None or ctx.peaks is None or not lengths:
        return None
    sec, n = tracing.step_time(ctx.trace, "decode")
    if n != len(lengths):
        return None
    need = sum(ctx.counts.decode_step_bytes(
        ctx.shape, ctx.counters["decode_batch"], ln) for ln in lengths)
    return need / ctx.peaks["hbm_bytes_per_s"] / sec * 100.0
