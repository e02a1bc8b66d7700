"""Device seconds per FL round of the ``fused_merge`` Pallas kernel
(``kernels/fused_merge.py``), every leaf's masked, overlap-weighted merge:
the ops whose HLO instruction carries the kernel's ``name=``
(``fused_merge.<n>``, as the chip's compiler names a Mosaic call)."""
import re

KERNEL = re.compile(r"fused_merge(\.\d+)?")


def read(ctx):
    # an op's key is "<layer>: <instruction> <opcode> <shape>"
    t = sum(s for key, s in ctx.reduction.ops
            if KERNEL.fullmatch(key.split(": ", 1)[-1].split(" ", 1)[0]))
    if not t or ctx.rounds <= 0:
        return None
    return t / ctx.rounds
