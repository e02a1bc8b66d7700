"""Device seconds per FL round of the ``threshold_find`` Pallas kernel
(``kernels/threshold_find.py``), every leaf's per-client k-th-magnitude
search: the ops whose HLO instruction carries the kernel's ``name=``
(``threshold_find.<n>``, as the chip's compiler names a Mosaic call)."""
import re

KERNEL = re.compile(r"threshold_find(\.\d+)?")


def read(ctx):
    # an op's key is "<layer>: <instruction> <opcode> <shape>"
    t = sum(s for key, s in ctx.reduction.ops
            if KERNEL.fullmatch(key.split(": ", 1)[-1].split(" ", 1)[0]))
    if not t or ctx.rounds <= 0:
        return None
    return t / ctx.rounds
