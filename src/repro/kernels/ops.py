"""jit'd public wrappers for the Pallas kernels: padding/reshaping to tile
boundaries, interpret mode on the CPU backend only, flat-vector interfaces
used by repro.core."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.core.strategies import CODEC_LEVELS, quantization_scale
from repro.kernels.expert_gmm import expert_gmm_pallas, expert_tgmm_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_merge import fused_merge_pallas
from repro.kernels.fused_merge import TILE_N as MERGE_TILE
from repro.kernels.threshold_find import threshold_find_pallas
from repro.kernels.threshold_find import TILE_N as THRESH_TILE


def _interpret() -> bool:
    """Compiled on a TPU, interpreted on the CPU backend (the test suite);
    no other backend can run these kernels, so it is refused outright."""
    platform = jax.devices()[0].platform
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas TPU kernels cannot run on platform {platform!r}")
    return platform == "cpu"


# ------------------------------------------------- traced-k megakernel pipeline
@jax.jit
def topk_thresholds(updates: jax.Array, ks: jax.Array,
                    residuals: jax.Array | None = None) -> jax.Array:
    """[C, n] updates + traced [C] retained counts -> exact per-client
    k-th-|.| bit-pattern thresholds u32 [C] (of ``residuals + updates`` when
    residuals are given). The Top-K mask is
    ``bitcast(|x|, u32) >= thresholds[:, None]`` — bit-identical to
    ``topk_compress_dynamic`` in 8 streamed HBM sweeps instead of 32."""
    c, n = updates.shape
    n_pad = (-n) % THRESH_TILE
    up = jnp.pad(updates.astype(jnp.float32), ((0, 0), (0, n_pad)))
    ep = (jnp.pad(residuals.astype(jnp.float32), ((0, 0), (0, n_pad)))
          if residuals is not None else None)
    th = threshold_find_pallas(up, ks.reshape(c, 1), ep,
                               interpret=_interpret())
    return th[:, 0]


@functools.partial(jax.jit, static_argnames=("opwa", "gamma", "d", "codec"))
def megakernel_aggregate(updates: jax.Array, ks: jax.Array,
                         weights: jax.Array,
                         residuals: jax.Array | None = None,
                         active: jax.Array | None = None,
                         *, opwa: bool = False, gamma: float = 1.0,
                         d: int = 1, codec: str = "none"):
    """Whole flat-space client merge through the two-kernel pipeline:
    threshold-find (8 HBM sweeps) + fused apply/merge (1 pass) — vs the
    ~35 passes of the unfused XLA lowering (see repro.roofline.kernel_bytes).

    updates [C, n] f32; ks [C] i32 traced; weights [C] f32; residuals
    optional [C, n] (switches on EF arithmetic and the new-residual output);
    active optional bool [C] (padded-cohort gating, engine semantics);
    codec: "none" | "int8" | "int4" — quantize/dequantize the survivors
    inside the merge tile pass (requires residuals: EF absorbs the
    quantization error). The per-client scale is the row absmax emitted by
    threshold-find on its already-streamed sweep, fed through the identical
    ``strategies.quantization_scale`` the jnp ``value_codec`` uses, so the
    scales (and everything downstream) match bit for bit.

    Returns (agg [n] f32, new_residuals [C, n] | None) — bit-exact with the
    jnp route of ``fed.engine.compress_merge_leaf``.
    """
    c, n = updates.shape
    n_pad = (-n) % MERGE_TILE
    up = jnp.pad(updates.astype(jnp.float32), ((0, 0), (0, n_pad)))
    ep = (jnp.pad(residuals.astype(jnp.float32), ((0, 0), (0, n_pad)))
          if residuals is not None else None)
    # MERGE_TILE is a multiple of THRESH_TILE: one padding serves both
    if codec == "none":
        th = threshold_find_pallas(up, ks.reshape(c, 1), ep,
                                   interpret=_interpret())
        scales = None
    else:
        th, absmax = threshold_find_pallas(up, ks.reshape(c, 1), ep,
                                           emit_scale=True,
                                           interpret=_interpret())
        scales = quantization_scale(absmax, CODEC_LEVELS[codec])
    act = (active.astype(jnp.float32).reshape(c, 1)
           if active is not None else None)
    out = fused_merge_pallas(up, th, weights.astype(jnp.float32)
                             .reshape(c, 1), ep, act, opwa=opwa,
                             gamma=gamma, d=d, codec=codec, scales=scales,
                             interpret=_interpret())
    if residuals is None:
        return out[0, :n], None
    agg, new_res = out
    return agg[0, :n], new_res[:, :n]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128) -> jax.Array:
    """Model-layout wrapper: q [B,S,H,D], k/v [B,S,H,D] (equal heads; GQA
    callers broadcast kv first). Pads Sq/Sk to block multiples."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    pq, pk = (-sq) % blk_q, (-sk) % blk_k
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, pq), (0, 0)))
    if pk:
        # padded keys sit at positions >= Sk: causal-masked away for every
        # real query position (non-causal callers must pad Sk themselves)
        assert causal, "non-causal flash with Sk % blk_k != 0 unsupported"
        kt = jnp.pad(kt, ((0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pk), (0, 0)))
    out = flash_attention_pallas(qt, kt, vt, causal=causal, blk_q=blk_q,
                                 blk_k=blk_k, interpret=_interpret())
    out = out[:, :sq].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out


def _client_starts(group_sizes: jax.Array, m: int) -> jax.Array:
    """[C * G] first row of each client's groups, clients' rows stacked in
    blocks of ``m``."""
    c = group_sizes.shape[0]
    starts = (jnp.arange(c, dtype=jnp.int32)[:, None] * m
              + jnp.cumsum(group_sizes, axis=1) - group_sizes)
    return starts.reshape(-1)


def _clients_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                 transpose_rhs: bool) -> jax.Array:
    """x [C, m, k] (each client's rows sorted by group), group_sizes [C, G],
    w [G, k, n] shared or [C, G, k, n] per client -> [C, m, n], zero in the
    rows past each client's last group: one kernel call over all clients."""
    c, m, k = x.shape
    if w.ndim == 4:
        w = w.reshape((-1,) + w.shape[2:])
    out = expert_gmm_pallas(x.reshape(c * m, k), w,
                            _client_starts(group_sizes, m),
                            group_sizes.reshape(-1),
                            transpose_rhs=transpose_rhs,
                            interpret=_interpret())
    out = out.reshape(c, m, -1)
    keep = jnp.arange(m) < jnp.sum(group_sizes, axis=1, keepdims=True)
    return jnp.where(keep[..., None], out, jnp.zeros((), out.dtype))


def _clients_tgmm(x: jax.Array, dy: jax.Array,
                  group_sizes: jax.Array) -> jax.Array:
    """x [C, m, k], dy [C, m, n], group_sizes [C, G] -> [C, G, k, n]."""
    c, m, k = x.shape
    out = expert_tgmm_pallas(x.reshape(c * m, k), dy.reshape(c * m, -1),
                             _client_starts(group_sizes, m),
                             group_sizes.reshape(-1), interpret=_interpret())
    return out.reshape(group_sizes.shape + out.shape[1:])


def _per_client(fn, batched_w: bool):
    """``fn`` on one client's operands, and its rule under ``vmap``: the
    clients' rows go through one kernel call (FL trains the cohort under a
    vmap whose members have their own group sizes; the weights are shared
    in a round's first local step and per client after it)."""
    @custom_batching.custom_vmap
    def one(x, w, group_sizes):
        return fn(x[None], w, group_sizes[None])[0]

    @one.def_vmap
    def rule(axis_size, in_batched, x, w, group_sizes):
        x_b, w_b, gs_b = in_batched
        if not x_b:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        if not gs_b:
            group_sizes = jnp.broadcast_to(group_sizes,
                                           (axis_size,) + group_sizes.shape)
        if batched_w and not w_b:
            w = jnp.broadcast_to(w, (axis_size,) + w.shape)
        return fn(x, w, group_sizes), True

    return one


_gmm = _per_client(functools.partial(_clients_gmm, transpose_rhs=False),
                   batched_w=False)
_gmm_t = _per_client(functools.partial(_clients_gmm, transpose_rhs=True),
                     batched_w=False)
_tgmm = _per_client(_clients_tgmm, batched_w=True)


@jax.custom_vjp
def expert_matmul(x: jax.Array, w: jax.Array,
                  group_sizes: jax.Array) -> jax.Array:
    """Rows sorted by expert [m, k] x the experts' weights [G, k, n] ->
    [m, n]: ``x[rows of g] @ w[g]`` for each group, zero in the rows past
    the last group. ``group_sizes`` [G] int32. Differentiable in ``x`` and
    ``w`` (``expert_gmm`` for the rows' gradient, ``expert_tgmm`` for the
    weights'); under ``vmap`` the batch's rows go through one kernel call
    per product."""
    return _gmm(x, w, group_sizes)


def _expert_matmul_fwd(x, w, group_sizes):
    return _gmm(x, w, group_sizes), (x, w, group_sizes)


def _expert_matmul_bwd(res, dy):
    x, w, group_sizes = res
    return _gmm_t(dy, w, group_sizes), _tgmm(x, dy, group_sizes), None


expert_matmul.defvjp(_expert_matmul_fwd, _expert_matmul_bwd)
