"""Train-step builders: dense DP sync and hierarchical BCRS/OPWA compressed
pod sync (the paper's technique applied to multi-pod data parallelism).

``make_train_step`` is the plain jit-able ``(params, opt_state, batch) ->
(params, opt_state, metrics)`` step with optional gradient-accumulation
microbatching and explicit grad shardings (FSDP: grads land on the param
layout instead of whatever the partitioner guesses).

``make_compressed_train_step`` splits the global batch over ``n_pods``
virtual pods, gives every pod its own gradient, and replaces the dense
all-reduce with the paper's compressed exchange: per-pod error-feedback
Top-K at the BCRS-scheduled traced ratios (``pod_crs``, clipped to the
``wire_cr`` budget; ``repro.core.bcrs.pod_link_schedule`` produces them from
heterogeneous DCN links), merged with overlap-weighted averaging
(``repro.core.opwa`` — coords kept by <= ``overlap_d`` pods are amplified by
``gamma``). Compression + EF + merge run through the shared substrate
(``repro.fed.engine.compress_merge_leaf`` -> the one
``topk_compress_dynamic`` bisection), the same pipeline the FL round
engines use. At ``wire_cr=1.0`` every pod keeps everything, overlap saturates,
and the step reproduces ``make_train_step`` exactly (strict generalization —
see tests/test_dist.py).

Error-feedback residuals live in the optimizer-state pytree: init with
``init_compressed_state(opt, params, n_pods=N)`` and the step threads
``{"opt": <inner>, "ef": <[n_pods, ...] residuals>}``. A bare ``opt.init``
state is also accepted (residuals start at zero and are dropped on return,
keeping the in/out structure identical for ahead-of-time lowering in
``launch/specs.py``); only the wrapped form carries EF across steps.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import strategies as strat_mod
from repro.core.compression import k_for_ratio_traced
from repro.fed.engine import compress_merge_leaf

Metrics = Dict[str, jax.Array]


def _grad_fn(model) -> Callable:
    return jax.value_and_grad(model.loss_fn, has_aux=True)


# ------------------------------------------------------------------ dense step
def make_train_step(model, opt, *, n_micro: int = 1,
                    grad_shardings: Any = None) -> Callable:
    """Dense DP train step. ``n_micro`` > 1 scans fwd+bwd over microbatches
    (bounded activation memory; grads/metrics averaged in f32).
    ``grad_shardings``: optional sharding pytree (matching params) pinned on
    the accumulated grads before the optimizer update."""
    grad_fn = _grad_fn(model)

    def step(params, opt_state, batch):
        if n_micro == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                    + x.shape[1:]), batch)
            mb0 = jax.tree.map(lambda x: x[0], micro)
            (l_abs, m_abs), _ = jax.eval_shape(grad_fn, params, mb0)

            def body(carry, mb):
                g_acc, l_acc, m_acc = carry
                (l, m), g = grad_fn(params, mb)
                g_acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32) / n_micro,
                    g_acc, g)
                m_acc = jax.tree.map(lambda a, v: a + v / n_micro, m_acc, m)
                return (g_acc, l_acc + l / n_micro, m_acc), None

            init = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params),
                    jnp.zeros(l_abs.shape, jnp.float32),
                    jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                                 m_abs))
            (grads, loss, metrics), _ = jax.lax.scan(body, init, micro)
        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        new_params, new_state = opt.update(grads, opt_state, params)
        out = dict(metrics)
        out["loss"] = loss
        return new_params, new_state, out

    return step


# ------------------------------------------------------ compressed-state init
def _zero_ef(params, n_pods: int):
    return jax.tree.map(
        lambda p: jnp.zeros((n_pods,) + tuple(p.shape), jnp.float32), params)


def init_compressed_state(opt, params, *, n_pods: int):
    """Optimizer state + per-pod f32 error-feedback residuals."""
    return {"opt": opt.init(params), "ef": _zero_ef(params, n_pods)}


def _is_wrapped(opt_state) -> bool:
    return (isinstance(opt_state, dict) and len(opt_state) == 2
            and "opt" in opt_state and "ef" in opt_state)


# ------------------------------------------------------------- compressed step
def make_compressed_train_step(model, opt, *, n_pods: int,
                               wire_cr: float = 0.05, gamma: float = 1.0,
                               min_leaf_size: int = 4096, overlap_d: int = 1,
                               use_kernel="auto",
                               strategy: str = "bcrs_opwa") -> Callable:
    """Returns jittable
    ``step(params, opt_state, batch, pod_crs, pod_coeffs)``.

    pod_crs: f32 [n_pods] traced BCRS compression ratios (one compiled step
    serves any per-round schedule); pod_coeffs: f32 [n_pods] averaging
    coefficients p'_i (1/n_pods reproduces the dense mean). Leaves smaller
    than ``min_leaf_size`` are exchanged dense (their index overhead would
    exceed the savings — same cutoff the byte model uses).

    ``strategy`` names a registered compressing strategy; its capabilities
    pick the merge (``overlap_weighted`` -> OPWA vs plain coefficient sum)
    and the optional ``value_codec`` (e.g. ``qtopk``'s int8 quantizer —
    EF absorbs its quantization error, same contract as the FL engines).
    Pod sync always runs error feedback: residuals are structural in the
    wrapped optimizer state, so ``carry`` here only affects the codec's EF
    interplay, not whether residuals exist.
    """
    if n_pods < 2:
        # with a single pod every kept coordinate has overlap 1 <= overlap_d,
        # so OPWA would silently scale all gradients by gamma (an LR change,
        # not a sync strategy) — use make_train_step instead
        raise ValueError(f"n_pods must be >= 2, got {n_pods}")
    strat = strat_mod.get(strategy)
    if not strat.compresses:
        raise ValueError(
            f"strategy {strategy!r} does not compress; use make_train_step "
            f"for dense sync")
    grad_fn = _grad_fn(model)

    def step(params, opt_state, batch, pod_crs, pod_coeffs):
        b = jax.tree.leaves(batch)[0].shape[0]
        if b % n_pods:
            raise ValueError(
                f"global batch {b} not divisible by n_pods={n_pods}")
        wrapped = _is_wrapped(opt_state)
        if wrapped:
            lead = jax.tree.leaves(opt_state["ef"])[0].shape[0]
            if lead != n_pods:
                raise ValueError(
                    f"opt_state carries EF residuals for {lead} pods but the "
                    f"step was built with n_pods={n_pods} (checkpoint / "
                    f"--compressed-pods mismatch)")
        inner = opt_state["opt"] if wrapped else opt_state
        ef = opt_state["ef"] if wrapped else _zero_ef(params, n_pods)

        pod_batch = jax.tree.map(
            lambda x: x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:]),
            batch)
        (losses, metrics), grads = jax.vmap(
            grad_fn, in_axes=(None, 0))(params, pod_batch)

        crs = jnp.clip(pod_crs.astype(jnp.float32), 0.0, wire_cr)
        coeffs = pod_coeffs.astype(jnp.float32)

        def sync_leaf(g, e):
            """g: [n_pods, *shape] pod grads; e: matching EF residuals."""
            n = int(np.prod(g.shape[1:]))
            gf = g.reshape(n_pods, n).astype(jnp.float32)
            if n < min_leaf_size:  # dense exchange, no EF
                return (jnp.tensordot(coeffs, gf, axes=(0, 0))
                        .reshape(g.shape[1:]), e)
            ks = k_for_ratio_traced(n, crs)
            agg, new_e = compress_merge_leaf(
                gf, coeffs, ks, strat, gamma=gamma, overlap_d=overlap_d,
                use_kernel=use_kernel, residuals=e.reshape(n_pods, n))
            return agg.reshape(g.shape[1:]), new_e.reshape(e.shape)

        pairs = jax.tree.map(sync_leaf, grads, ef)
        is_pair = lambda x: isinstance(x, tuple) and len(x) == 2
        agg_grads = jax.tree.map(lambda t: t[0], pairs, is_leaf=is_pair)
        new_ef = jax.tree.map(lambda t: t[1], pairs, is_leaf=is_pair)

        new_params, new_inner = opt.update(agg_grads, inner, params)
        out = jax.tree.map(jnp.mean, dict(metrics))
        out["loss"] = jnp.mean(losses)
        out["wire_cr"] = jnp.mean(crs)
        new_state = ({"opt": new_inner, "ef": new_ef} if wrapped
                     else new_inner)
        return new_params, new_state, out

    return step
