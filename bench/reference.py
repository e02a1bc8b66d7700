"""The plain reference of the federated round, and the comparison that
decides ``correct``.

Independent of the program: nothing here imports it or takes anything it
has made. The weights are the benchmark's own (``weights.py``), the inputs
come from ``traffic.py``, and each configuration's forward pass and loss is
its own plain module beside its sizes (``configs/<name>.py``).

The reference computes in float32 at ``highest`` matmul precision. The
parameters are stored in their leaf dtype (bfloat16 for the matrices), and
each gradient is taken with respect to the stored leaf, in its dtype. One
round:

1. local training: each client runs its local SGD steps from the round's
   parameters on its own batches. A client of one step has the delta lr *
   gradient in float32. A client of several steps rounds its local
   parameters to the leaf dtype after each step, and its delta is the
   round's parameters less its last local ones. That is the precision the
   program's compiled round has on the TPU: the compiler keeps a single
   step's update in float32 and rounds the loop carry of several. The
   client's loss is the pre-update loss of its last step;
2. compression and merge: per leaf, each client keeps the k = round(cr * n)
   coordinates of largest magnitude (ties at the threshold kept), and the
   merge is the weighted sum over clients, with OPWA's enlarge rate gamma on
   coordinates that 1..D clients kept (paper Alg. 3);
3. server step: parameters minus eta times the merged update, stored in the
   leaf dtype.

The control (``mm=matmul_fp8``) is the same reference with every parameter
matmul's operands rounded to float8 e4m3 with a per-tensor scale, the
nearest precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


# ------------------------------------------------------------- params
def val(w):
    """A parameter's value in float32."""
    return w.astype(F32)


def rows(table, ids):
    """Embedding rows ``table[ids]`` in float32."""
    return jnp.take(table, ids, axis=0).astype(F32)


# ------------------------------------------------------------- matmuls
def matmul_f32(x, w):
    """x @ w in float32 at highest precision."""
    return jnp.matmul(x.astype(F32), val(w), precision=HIGHEST)


def _fp8(a):
    """Round to float8 e4m3 with a per-tensor scale; the backward pass goes
    straight through in float32."""
    a = a.astype(F32)
    amax = jnp.max(jnp.abs(a))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return a + jax.lax.stop_gradient(q - a)


def matmul_fp8(x, w):
    """The control's matmul: both operands in float8 e4m3."""
    return jnp.matmul(_fp8(x), _fp8(val(w)), precision=HIGHEST)


MATMULS = {"f32": matmul_f32, "fp8": matmul_fp8}


# -------------------------------------------------------------- layers
def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * val(scale)


def cross_entropy(logits, labels, vocab: int):
    """Mean next-token cross entropy over the first ``vocab`` logits."""
    lf = logits[..., :vocab].astype(F32)
    lse = jax.nn.logsumexp(lf, -1)
    label = jnp.take_along_axis(lf, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - label)


def layer_stack(params: Dict[str, jax.Array], prefix: str):
    """The ``prefix``-ed leaves with their prefix removed (stacked [L, ...])."""
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


# ------------------------------------------------------- Top-K selection
def kth_largest(mag: jax.Array, k: jax.Array) -> jax.Array:
    """The k-th largest value of the non-negative float32 ``mag``, found by
    halving the interval of its bit patterns (non-negative floats order as
    their int32 bit patterns do)."""
    bits = jax.lax.bitcast_convert_type(mag.reshape(-1), jnp.int32)
    lo = jnp.int32(0)                  # count(bits >= lo) >= k
    hi = jnp.max(bits) + 1             # count(bits >= hi) < k

    def halve(_, c):
        lo, hi = c
        mid = lo + (hi - lo) // 2
        ok = jnp.sum(bits >= mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 32, halve, (lo, hi))
    return jax.lax.bitcast_convert_type(lo, F32)


def retained_count(n: int, cr: jax.Array) -> jax.Array:
    """round(cr * n) clipped to [1, n], in float32."""
    return jnp.clip(jnp.round(cr.astype(F32) * n), 1, n).astype(jnp.int32)


# --------------------------------------------------------- one FL round
class RoundReference:
    """The reference's FL round for one cell: ``run(params, inputs)``.

    ``loss`` is the configuration's plain loss, ``loss(params, tokens,
    labels, mm)``; ``mix`` the traffic file's content. ``half_batch`` plants
    a fault for the readings: each client's loss leaves the second half of
    every sequence out and takes the mean over the rest.
    """

    def __init__(self, loss: Callable, mix: dict, mm: Callable,
                 half_batch: bool = False):
        self.mix = mix
        compress = mix["compress"] == "topk"
        opwa = bool(mix["overlap_weighted"])
        gamma, d = float(mix["gamma"]), int(mix["overlap_d"])
        lr, eta = float(mix["lr"]), float(mix["eta"])
        if mix.get("error_feedback"):
            raise NotImplementedError("the reference carries no EF residuals")

        def client_loss(p, tokens, labels):
            if half_batch:
                t = tokens.shape[-1] // 2
                tokens, labels = tokens[..., :t], labels[..., :t]
            with jax.default_matmul_precision("highest"):
                return loss(p, tokens, labels, mm)

        self._grad = jax.jit(jax.value_and_grad(client_loss))

        @functools.partial(jax.jit, donate_argnums=(1,))
        def carry(p, g):
            """A local step carried on to the next: rounded to the leaf's
            dtype."""
            return {k: (p[k].astype(F32) - lr * g[k].astype(F32))
                    .astype(p[k].dtype) for k in p}

        def merge_in(acc, cnt, delta, w, cr):
            new_acc, new_cnt = {}, {}
            for k in acc:
                dk = delta[k]
                if compress:
                    mag = jnp.abs(dk)
                    thr = kth_largest(mag, retained_count(dk.size, cr))
                    mask = mag >= thr
                    dk = jnp.where(mask, dk, 0.0)
                    new_cnt[k] = cnt[k] + mask.astype(cnt[k].dtype)
                new_acc[k] = acc[k] + w * dk
            return new_acc, new_cnt

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def merge_step(acc, cnt, g, w, cr):
            """A one-step client: its delta is lr * gradient in float32."""
            return merge_in(acc, cnt, {k: lr * g[k].astype(F32) for k in g},
                            w, cr)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def merge_local(acc, cnt, p, local, w, cr):
            """A client of several steps: its delta is the round's
            parameters less its last local ones."""
            return merge_in(acc, cnt, {k: p[k].astype(F32)
                                       - local[k].astype(F32) for k in p},
                            w, cr)

        @jax.jit
        def server(p, acc, cnt):
            out = {}
            for k in p:
                agg = acc[k]
                if opwa:
                    agg = jnp.where((cnt[k] > 0) & (cnt[k] <= d), gamma,
                                    1.0) * agg
                out[k] = (p[k].astype(F32) - eta * agg).astype(p[k].dtype)
            return out

        self._carry, self._server = carry, server
        self._merge_step, self._merge_local = merge_step, merge_local
        self._opwa = opwa

    def run(self, p: Dict[str, jax.Array], inputs: Dict[str, np.ndarray]):
        """One round from ``p`` (path -> leaf). Returns (new params, loss),
        the loss being the mean over active clients of each client's last
        step's pre-update loss."""
        acc = {k: jnp.zeros(v.shape, F32) for k, v in p.items()}
        cnt = ({k: jnp.zeros(v.shape, jnp.int8) for k, v in p.items()}
               if self._opwa else {})
        losses = []
        for i in np.flatnonzero(inputs["active"]):
            steps = np.flatnonzero(inputs["step_mask"][i])
            w = jnp.float32(inputs["weights"][i])
            cr = jnp.float32(inputs["crs"][i])
            if len(steps) == 1:
                loss_i, g = self._grad(p, inputs["tokens"][i, steps[0]],
                                       inputs["labels"][i, steps[0]])
                acc, cnt = self._merge_step(acc, cnt, g, w, cr)
            else:
                local = p
                for s in steps:
                    loss_i, g = self._grad(local, inputs["tokens"][i, s],
                                           inputs["labels"][i, s])
                    local = self._carry(local, g)
                acc, cnt = self._merge_local(acc, cnt, p, local, w, cr)
            del g
            losses.append(float(loss_i))
        new = self._server(p, acc, cnt)
        return new, float(np.mean(losses))


# ------------------------------------------------------------ comparison
@jax.jit
def leaf_stats(a: jax.Array, b: jax.Array, p0: jax.Array) -> jax.Array:
    """Of two states ``a`` (under test) and ``b`` (reference) of one leaf
    that started at ``p0``: ‖a - p0‖, ‖b - p0‖, ‖a - b‖, the coordinates
    moved in one state and not in the other, and those moved in ``b``."""
    a, b, p0 = a.astype(F32), b.astype(F32), p0.astype(F32)
    moved_a, moved_b = a != p0, b != p0
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
    return jnp.stack([norm(a - p0), norm(b - p0), norm(a - b),
                      jnp.sum(moved_a ^ moved_b).astype(F32),
                      jnp.sum(moved_b).astype(F32)])


def counted_leaves(ref_norms: Dict[str, float]) -> List[str]:
    """Leaves the reference moves: a norm of at least a thousandth of the
    median leaf's. A leaf whose update is nought to rounding in the
    reference is left out by this rule, never by name."""
    med = float(np.median(list(ref_norms.values())))
    return sorted(k for k, v in ref_norms.items() if v >= 1e-3 * med)


def worst_leaf(num: Dict[str, float], den: Dict[str, float],
               leaves: List[str]):
    """max over leaves of num / max(den, median den). Returns (value,
    leaf)."""
    med = float(np.median([den[k] for k in leaves]))
    gaps = {k: num[k] / max(den[k], med) for k in leaves}
    worst = max(gaps, key=gaps.get)
    return float(gaps[worst]), worst


def reading_numbers(stats: Dict[str, np.ndarray]) -> Dict[str, tuple]:
    """The numbers of one reading of the state, from ``leaf_stats`` per
    leaf, each by the worst counted leaf: ``gap``, the gap between the
    norms of the two changes; ``diff``, the norm of their difference;
    ``kept``, the coordinates moved on one side only, over those the
    reference moved. Each is taken against the reference leaf's own
    measure or the median leaf's, whichever is larger."""
    na = {k: float(s[0]) for k, s in stats.items()}
    nb = {k: float(s[1]) for k, s in stats.items()}
    leaves = counted_leaves(nb)
    gap = {k: abs(na[k] - nb[k]) for k in leaves}
    diff = {k: float(stats[k][2]) for k in leaves}
    odd = {k: float(stats[k][3]) for k in leaves}
    moved = {k: float(stats[k][4]) for k in leaves}
    return {"gap": worst_leaf(gap, nb, leaves),
            "diff": worst_leaf(diff, nb, leaves),
            "kept": worst_leaf(odd, moved, leaves)}


def compare(prog: dict, ref: dict, p0: Dict[str, np.ndarray]) -> dict:
    """The numbers of a run under test against the reference's run of the
    same cell and seed.

    A run is ``losses`` (one per checked round) and ``states``: a list of
    (round, {leaf: host array}) at the rounds the state was read. A reading
    after round 1 gives the ``first_update_*`` numbers, the first update as
    the server applied it; the last reading gives the ``change_*`` numbers,
    the change over all checked rounds. ``loss_gap`` is the largest
    relative gap of a round's loss. Returns {name: {"value", "leaf"?}}.
    """
    out = {}
    n_read = len(ref["states"])
    for i, ((rnd, a), (rnd_r, b)) in enumerate(zip(prog["states"],
                                                   ref["states"])):
        if rnd != rnd_r:
            raise ValueError(f"state read after rounds {rnd} and {rnd_r}")
        stats = {k: np.asarray(leaf_stats(a[k], b[k], p0[k])) for k in b}
        prefix = []
        if rnd == 1:
            prefix.append("first_update")
        if i == n_read - 1:
            prefix.append("change")
        for kind, (value, leaf) in reading_numbers(stats).items():
            for pre in prefix:
                out[f"{pre}_{kind}"] = {"value": value, "leaf": leaf,
                                        "round": rnd}
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out["loss_gap"] = {"value": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    return out
