"""Device seconds per FL round of the round body's other ops: Top-K
selection, the merge with its Mosaic custom calls, the server step."""


def read(ctx):
    t = ctx.reduction.layer_s.get("merge")
    if not t or ctx.rounds <= 0:
        return None
    return t / ctx.rounds
