"""The merge's share of its roofline, in %: the least time its work allows
(the least HBM bytes of one merge at the chip's peak bandwidth) over the
device seconds it took per round. Memory bounds the merge; its FLOPs are a
few per element."""


def read(ctx):
    t = ctx.reduction.layer_s.get("merge")
    if not t or ctx.rounds <= 0:
        return None
    least = ctx.merge_bytes_per_round / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (t / ctx.rounds)
