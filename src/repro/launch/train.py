"""Distributed training driver (single process; multi-host launch uses the
same entry point via jax.distributed — see README).

Fault tolerance: resumes from the latest checkpoint automatically; atomic
writes make crash-mid-save safe; ``--compressed-pods`` turns on the
hierarchical BCRS/OPWA gradient sync over the pod axis (the paper's
technique applied to multi-pod DP — docs/DESIGN.md §2).

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --steps 100 --batch 8 --seq 256 --reduced --checkpoint-dir ckpt/
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import ARCH_IDS, get_config
from repro.core.bcrs import pod_link_schedule
from repro.data import synthetic_lm_tokens
from repro.dist.grad_sync import (init_compressed_state,
                                  make_compressed_train_step, make_train_step)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.optim import make_optimizer


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adamw"])
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--compressed-pods", type=int, default=0,
                    help="N>=2: hierarchical BCRS sync across N virtual pods")
    ap.add_argument("--wire-cr", type=float, default=0.05)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.compressed_pods and not args.compressed_pods >= 2:
        ap.error(f"--compressed-pods must be >= 2 (got {args.compressed_pods})")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    rng = np.random.default_rng(args.seed)
    opt = make_optimizer(args.optimizer, args.lr)

    params = model.init(jax.random.PRNGKey(args.seed))
    # compressed sync carries per-pod error-feedback residuals in opt_state
    opt_state = (init_compressed_state(opt, params, n_pods=args.compressed_pods)
                 if args.compressed_pods else opt.init(params))
    start_step = 0
    if args.checkpoint_dir and ckpt.latest_step(args.checkpoint_dir) is not None:
        try:
            (params, opt_state), start_step, extra = ckpt.restore(
                args.checkpoint_dir, (params, opt_state))
        except KeyError as e:
            raise SystemExit(
                f"[train] checkpoint in {args.checkpoint_dir} does not match "
                f"the current optimizer-state structure (missing {e}); it was "
                f"likely written with a different --compressed-pods / "
                f"--optimizer setting") from e
        print(f"[train] resumed from step {start_step}")

    if args.compressed_pods:
        n_pods = args.compressed_pods
        step_fn = jax.jit(make_compressed_train_step(
            model, opt, n_pods=n_pods, wire_cr=args.wire_cr, gamma=2.0))
        # heterogeneous virtual DCN links -> BCRS per-pod CRs
        n_flat = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        crs = pod_link_schedule([100.0 / (i + 1) for i in range(n_pods)],
                                v_bytes=4 * n_flat, cr_star=args.wire_cr / 2,
                                cr_max=args.wire_cr)
        pod_crs = jnp.asarray(crs, jnp.float32)
        pod_coeffs = jnp.full((n_pods,), 1.0 / n_pods, jnp.float32)
        print(f"[train] compressed pod sync: CRs={np.round(crs, 4)}")
    else:
        step_fn = jax.jit(make_train_step(model, opt))

    t0 = time.time()
    for step in range(start_step, args.steps):
        toks = synthetic_lm_tokens(args.batch, args.seq + 1, cfg.vocab_size, rng)
        batch = {"tokens": jnp.asarray(toks[:, :-1]),
                 "labels": jnp.asarray(toks[:, 1:])}
        if cfg.family == "encdec":
            batch["frames"] = jnp.asarray(
                rng.normal(0, 1, (args.batch, args.seq, cfg.d_model)), jnp.float32)
        if cfg.family == "vlm":
            v = cfg.vision
            batch["patches"] = jnp.asarray(
                rng.normal(0, 1, (args.batch, v.n_patches, v.d_vision)), jnp.float32)
        if args.compressed_pods:
            params, opt_state, metrics = step_fn(params, opt_state, batch,
                                                 pod_crs, pod_coeffs)
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"[train] step {step} loss {float(metrics['loss']):.4f} "
                  f"({time.time() - t0:.1f}s)")
        if (args.checkpoint_dir and args.checkpoint_every
                and (step + 1) % args.checkpoint_every == 0):
            ckpt.save(args.checkpoint_dir, step + 1, (params, opt_state),
                      extra={"arch": args.arch})
    print("[train] done")


if __name__ == "__main__":
    main()
