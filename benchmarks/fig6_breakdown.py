"""Paper Fig. 6: per-round time breakdown — compress/decompress, training,
uncompressed communication vs BCRS communication.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bcrs as bcrs_mod
from repro.core import compression as C
from repro.core import cost_model
from repro.core.aggregation import AggregationConfig
from repro.fed.simulation import FLSimConfig, mlp_init, mlp_loss
from repro.fed.client import make_local_trainer
from repro.core.compression import flatten_tree


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.time() - t0) / reps


def run(verbose: bool = True):
    rng = np.random.default_rng(0)
    params = mlp_init(jax.random.PRNGKey(0), 64, 10)
    flat, _ = flatten_tree(params)
    n = flat.shape[0]
    v_bytes = 4.0 * n
    links = cost_model.sample_links(5, rng)

    # training time (one client, E=1 epoch equivalent: 8 steps of bs=64)
    local = jax.jit(make_local_trainer(mlp_loss, 0.05))
    batches = {"x": jnp.asarray(rng.normal(0, 1, (8, 64, 64)), jnp.float32),
               "y": jnp.asarray(rng.integers(0, 10, (8, 64)), jnp.int32)}
    t_train = _time(lambda: local(params, batches))

    # compression time
    u = jnp.asarray(rng.normal(0, 1, (n,)), jnp.float32)
    t_topk = _time(lambda: C.topk_compress(u, 0.01).values)

    # communication: uncompressed vs uniform top-k vs BCRS
    t_dense = cost_model.uncompressed_round(links, v_bytes).actual
    t_topk_comm = cost_model.round_times(links, v_bytes, [0.01] * 5).actual
    crs = bcrs_mod.schedule_crs(links, v_bytes, 0.01)
    t_bcrs_comm = cost_model.round_times(links, v_bytes, crs).actual

    rows = {
        "train_s": t_train, "compress_topk_s": t_topk,
        "comm_dense_s": t_dense,
        "comm_topk_s": t_topk_comm, "comm_bcrs_s": t_bcrs_comm,
    }
    if verbose:
        print(f"fig6 train={t_train * 1e3:.1f}ms "
              f"compress(topk)={t_topk * 1e3:.1f}ms")
        print(f"fig6 comm: dense={t_dense:.2f}s topk={t_topk_comm:.3f}s "
              f"bcrs={t_bcrs_comm:.3f}s "
              f"(bcrs == topk benchmark time, by construction)")
    return rows


def main():
    rows = run()
    print("\nname,us_per_call,derived")
    for k, v in rows.items():
        print(f"fig6/{k},{v * 1e6:.1f},")
    return rows


if __name__ == "__main__":
    main()
