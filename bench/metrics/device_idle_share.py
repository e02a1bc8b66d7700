"""The share of the traced window, in %, in which no op ran on the chip:
1 - (union of the chip's op intervals / window)."""


def read(ctx):
    r = ctx.reduction
    if r.window_s <= 0 or r.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
