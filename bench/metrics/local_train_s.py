"""Device seconds per FL round of local training: the ops under the vmapped
local trainer or under value_and_grad's name stack (trace_reduce)."""


def read(ctx):
    t = ctx.reduction.layer_s.get("local_train")
    if not t or ctx.rounds <= 0:
        return None
    return t / ctx.rounds
