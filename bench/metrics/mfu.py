"""Model FLOP utilisation of the whole step over the traced window:
model FLOPs per token (``counts.model_flops_per_token``, recompute not
counted) x tokens per second, over chips x the chip's bf16 peak."""

LAYER = "whole step"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    lo, hi = ctx.window
    rate = ctx.tokens_per_step * ctx.steps / ((hi - lo) / 1e9)
    return 100.0 * ctx.flops_per_token * rate / (
        ctx.chips * ctx.peaks["bf16_flops"])
