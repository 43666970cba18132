"""Model FLOP utilization of the whole step over the traced steps: model
FLOPs per token (``flops.py``) times tokens per second, over the chips'
bf16 peak (``peaks.json``)."""


def read(ctx):
    if not ctx.seconds:
        return None
    rate = ctx.flops_per_token * ctx.tokens_per_step * ctx.steps / ctx.seconds
    return 100.0 * rate / (ctx.chips * ctx.peak["bf16_flops_per_s"])
