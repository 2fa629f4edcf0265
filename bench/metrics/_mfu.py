"""Shared by the ``*mfu*`` readers: model FLOPs of the work the window
completed, over the window and the chips' bf16 peak."""


def mfu(ctx):
    if ctx.peaks is None or not ctx.counters.get("model_flops"):
        return None
    return (ctx.counters["model_flops"] / ctx.counters["window_s"]
            / (ctx.peaks["bf16_flops"] * ctx.device["count"]) * 100.0)
