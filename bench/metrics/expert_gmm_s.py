"""Device seconds per FL round of the expert layer's grouped matmuls
(``kernels/expert_gmm.py``): the forward products and both gradients, the
ops whose HLO instruction carries a kernel's ``name=`` (``expert_gmm.<n>``,
``expert_tgmm.<n>``, as the chip's compiler names a Mosaic call)."""
import re

KERNEL = re.compile(r"expert_t?gmm(\.\d+)?")


def read(ctx):
    # an op's key is "<layer>: <instruction> <opcode> <shape>"
    t = sum(s for key, s in ctx.reduction.ops
            if KERNEL.fullmatch(key.split(": ", 1)[-1].split(" ", 1)[0]))
    if not t or ctx.rounds <= 0:
        return None
    return t / ctx.rounds
