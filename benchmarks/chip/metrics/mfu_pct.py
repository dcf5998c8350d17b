"""Whole-round model FLOP utilisation: local training's model FLOPs per round
(``flops/<family>.py``) times the rounds in the traced window, over the
window's seconds, the chips and the chip's bf16 peak."""


def read(ctx):
    flops = ctx.train_flops_per_round * ctx.rounds
    peak = ctx.window_s * ctx.chips * ctx.peak["bf16_flops_per_s"]
    return 100.0 * flops / peak
