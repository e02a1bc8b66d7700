"""The grouped expert matmuls' share of their roofline, in %: the least
time of one round's grouped products (``expert_gmm_work`` of the
configuration's reference module: FLOPs at the chip's bf16 peak or HBM
bytes at its bandwidth, whichever is longer) over ``expert_gmm_s``. The
work is counted at the expected routed rows and without remat's
recomputation, which the measured time includes."""
import os

import cells

CELL = "deepseek-v2-lite.silo"
HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx):
    seconds = cells.load_module(os.path.join(HERE, "expert_gmm_s.py"),
                                "bench_metric_expert_gmm_s").read(ctx)
    if seconds is None:
        return None
    cell = cells.resolve(CELL)
    work = cell.model_ref.expert_gmm_work(cell.config["model"], cell.mix)
    least = max(work["flops"] / ctx.peaks["bf16_flops_per_s"],
                work["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
