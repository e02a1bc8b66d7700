"""The whole round's share of the chip's peak, in %: model FLOPs of every
client's forward and backward passes in the traced window (no recomputation
counted) over the window's seconds, over peak bf16 FLOP/s."""


def read(ctx):
    w = ctx.reduction.window_s
    if ctx.rounds <= 0 or w <= 0:
        return None
    rate = ctx.model_flops_per_round * ctx.rounds / w
    return 100.0 * rate / ctx.peaks["bf16_flops_per_s"]
