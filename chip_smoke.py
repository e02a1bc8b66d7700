"""Bring-up smoke test: the federated round on one TPU chip, at full width.

    python chip_smoke.py

One process, no children. Phases, in order; any failure raises and the
exit code is non-zero:

  (a) device check: the first JAX device must be a TPU (no CPU fallback);
  (b) kernel parity at a real stablelm-1.6b leaf shape, [2, 2048*5632] f32:
      the Pallas pipeline (``threshold_find`` + ``fused_merge``) against the
      jnp path of ``compress_merge_leaf``, bit for bit, for ``bcrs_opwa`` and
      ``eftopk`` at traced per-client retained counts, with the compiled
      Mosaic call (``tpu_custom_call``) present in the lowered program;
  (c) the main path: ``fl_train.run`` on stablelm-1.6b at published widths
      (24 layers, d_model 2048, vocab 100352, bf16 weights from a seed),
      2 clients, ``bcrs_opwa``, 3 rounds; every loss and every final weight
      must be finite.

The numbers it prints are from a bring-up run, not a benchmark. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

#: stablelm-1.6b MLP weight [d_model, d_ff], the leaf shape of phase (b)
PARITY_LEAF = (2048, 5632)


def check_device():
    """Phase (a): the TPU, or SystemExit."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


def kernel_parity() -> None:
    """Phase (b): megakernel vs jnp route of ``compress_merge_leaf``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import strategies
    from repro.core.compression import k_for_ratio_traced
    from repro.fed.engine import compress_merge_leaf
    from repro.kernels import ops as kops

    leaf = PARITY_LEAF
    c, n = 2, int(np.prod(leaf))
    ku, ke = jax.random.split(jax.random.PRNGKey(0))
    updates = jax.random.normal(ku, (c, n), jnp.float32) * 1e-3
    residuals = jax.random.normal(ke, (c, n), jnp.float32) * 3e-4
    # BCRS-style per-client ratios -> traced retained counts
    ks = k_for_ratio_traced(n, jnp.asarray([0.05, 0.0125], jnp.float32))
    weights = jnp.asarray([0.625, 0.375], jnp.float32)
    active = jnp.ones((c,), bool)
    for strategy in ("bcrs_opwa", "eftopk"):
        strat = strategies.get(strategy)
        res = residuals if strat.needs_residuals else None
        kw = dict(opwa=strat.overlap_weighted, gamma=3.0, d=1)
        text = kops.megakernel_aggregate.lower(
            updates, ks, weights, res, active, **kw).as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{strategy}: no Mosaic kernel in the "
                                 "lowered megakernel program")
        agg_k, res_k = kops.megakernel_aggregate(updates, ks, weights, res,
                                                 active, **kw)
        ref = jax.jit(functools.partial(
            compress_merge_leaf, strategy=strat, gamma=3.0, overlap_d=1,
            use_kernel=False))
        agg_r, res_r = ref(updates.reshape((c,) + leaf), weights, ks,
                           residuals=(res.reshape((c,) + leaf)
                                      if res is not None else None),
                           active=active)
        np.testing.assert_array_equal(np.asarray(agg_k),
                                      np.asarray(agg_r).reshape(n),
                                      err_msg=f"{strategy}: aggregate")
        if res is not None:
            np.testing.assert_array_equal(
                np.asarray(res_k), np.asarray(res_r).reshape(c, n),
                err_msg=f"{strategy}: new residuals")
        print(f"[smoke] kernel parity {strategy} [{c}, {n}] ks "
              f"{np.asarray(ks).tolist()}: bit-exact, tpu_custom_call")


def main_path(cfg) -> dict:
    """Phase (c): ``fl_train.run``; asserts finite losses and weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import fl_train

    out = fl_train.run(cfg)
    losses = out["losses"]
    if len(losses) != cfg.rounds or not np.all(np.isfinite(losses)):
        raise AssertionError(f"losses {losses} over {cfg.rounds} rounds")
    for path, leaf in jax.tree_util.tree_leaves_with_path(out["params"]):
        if not bool(jnp.all(jnp.isfinite(leaf))):
            raise AssertionError(
                f"non-finite weights in {jax.tree_util.keystr(path)}")
    return out


def main() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no source tree at {SRC}")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    dev = check_device()
    import jax
    print(f"[smoke] device {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache}")

    t0 = time.perf_counter()
    kernel_parity()
    print(f"[smoke] phase (b) {time.perf_counter() - t0:.1f}s")

    from repro.launch.fl_train import FLTrainConfig
    cfg = FLTrainConfig(arch="stablelm-1.6b", reduced=False,
                        strategy="bcrs_opwa", engine="scan", clients=2,
                        local_steps=1, batch=2, seq=512, rounds=3)
    t0 = time.perf_counter()
    out = main_path(cfg)
    stats = dev.memory_stats() or {}
    print("[smoke] bring-up run, not a benchmark: stablelm-1.6b full width, "
          f"C={cfg.clients} batch={cfg.batch} seq={cfg.seq} "
          f"strategy={cfg.strategy}")
    print(f"[smoke] losses {out['losses']}")
    print(f"[smoke] wall s/round after compile {out['wall_per_round']}")
    print(f"[smoke] compile s {out['compile_s']}")
    print(f"[smoke] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(f"[smoke] phase (c) {time.perf_counter() - t0:.1f}s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
