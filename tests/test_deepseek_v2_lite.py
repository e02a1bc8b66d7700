"""DeepSeek-V2-Lite as the program runs it, against the benchmark's plain
reference (``bench/configs/deepseek-v2-lite.py``) at a small size: MLA
without q-LoRA and with YaRN, the dropless expert layer over a chip's share
of the routed experts, and a whole FL round of the scan engine."""
import dataclasses
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.fed import engine as engine_mod
from repro.models import Model, mla
from repro.models import moe as moe_mod
from repro.models.layers import yarn_correction_range, yarn_inv_freq

_BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                      "bench"))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import cells  # noqa: E402
from reference import MATMULS, RoundReference, compare  # noqa: E402
from traffic import Traffic  # noqa: E402
from weights import check_layout, make_weights  # noqa: E402

REF = cells.load_module(os.path.join(_BENCH, "configs", "deepseek-v2-lite.py"),
                        "bench_model_deepseek_v2_lite")
MM = MATMULS["f32"]

#: the published model's sections at a small size: 16 routed experts of
#: which 8 held, top 3, 2 shared, 1 dense + 2 MoE layers, YaRN as published
SMALL = {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 96, "vocab_size": 256, "rope_theta": 10000.0,
         "norm_eps": 1e-6, "tie_embeddings": False, "act": "swiglu",
         "dtype": "float32", "remat": "full",
         "moe": {"n_experts": 16, "top_k": 3, "d_expert": 32, "n_shared": 2,
                 "d_shared": 32, "first_dense_layers": 1, "n_held": 8,
                 "norm_topk_prob": False, "routed_scaling_factor": 1.0,
                 "router_noise": 0.0, "aux_loss_weight": 0.0},
         "mla": {"q_lora_rank": None, "kv_lora_rank": 16,
                 "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                 "v_head_dim": 16, "rope_factor": 40.0,
                 "rope_original_max": 4096, "beta_fast": 32.0,
                 "beta_slow": 1.0, "mscale": 0.707, "mscale_all_dim": 0.707}}


def small_model(m=SMALL):
    """The program's model for the sizes ``m``, built as the benchmark
    builds it (the registry's config replaced by the file's sections)."""
    return Model(dataclasses.replace(get_config("deepseek-v2-lite"), **m))


def seeded_params(model, m=SMALL, seed=123):
    """The benchmark's weights for ``m``: the reference's {path: leaf} and
    the same arrays in the program's pytree."""
    specs = REF.leaf_specs(m)
    treedef, paths = check_layout(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), specs)
    flat = make_weights(specs, seed)
    return flat, jax.tree_util.tree_unflatten(treedef,
                                              [flat[p] for p in paths]), paths


def tokens(seed=0, batch=2, seq=64, vocab=256):
    t = np.random.default_rng(seed).integers(0, vocab, (batch, seq + 1))
    t = jnp.asarray(t, jnp.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


# ------------------------------------------------------- loss and gradient
def test_loss_and_gradients_match_the_reference():
    model = small_model()
    flat, params, paths = seeded_params(model)
    batch = tokens()
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, batch)[0]))(params)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: REF.loss(p, batch["tokens"], batch["labels"], SMALL,
                               MM)))(flat)
    # float32 on both sides; the two differ in summation order only
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    gp = dict(zip(paths, jax.tree.leaves(gp)))
    for k in gr:
        err = float(jnp.linalg.norm(gp[k] - gr[k]))
        assert err <= 1e-4 * float(jnp.linalg.norm(gr[k])) + 1e-7, k


# ------------------------------------------------------------ expert layer
def _layer(n_experts=8, n_held=8, top_k=3, seed=0):
    """One MoE layer's reference leaves {moe/...} and the program's config."""
    m = dict(SMALL, n_layers=2,
             moe=dict(SMALL["moe"], n_experts=n_experts, n_held=n_held,
                      top_k=top_k))
    flat = make_weights(REF.leaf_specs(m), seed)
    p = {k[len("moe_layers/"):]: v[0] for k, v in flat.items()
         if k.startswith("moe_layers/moe/")}
    return m, p


def _program_layer(p: dict, mo: dict) -> dict:
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), moe=mo).moe
    tree = {k: p["moe/" + k] for k in ("router", "w_gate", "w_up", "w_down")}
    tree["shared"] = {k: p["moe/shared/" + k]
                      for k in ("w_gate", "w_up", "w_down")}
    return tree, cfg


def _apply(tree, cfg, y):
    with jax.default_matmul_precision("highest"):
        out, _ = moe_mod.apply_moe(tree, y[None], mo=cfg)
    return out[0]


def test_chip_shares_add_up_to_the_whole_layer():
    """Two chips of four experts each: their routed parts, with the shared
    experts counted once, give what the uncut reference gives for the
    layer."""
    m, p = _layer(n_experts=8, n_held=8)
    y = jax.random.normal(jax.random.PRNGKey(1), (64, m["d_model"]))
    with jax.default_matmul_precision("highest"):
        whole = REF.moe_ffn(y, p, m["moe"], MM)
        shared = REF._swiglu(y, p["moe/shared/w_gate"], p["moe/shared/w_up"],
                             p["moe/shared/w_down"], MM)
    share = dict(m["moe"], n_held=4)
    parts = []
    for first in (0, 4):
        # the chip holding experts first..first+3 sees them as its first
        # four: its router's columns rolled, its expert leaves sliced
        held = {k: v for k, v in p.items()}
        held["moe/router"] = jnp.roll(p["moe/router"], -first, axis=1)
        for k in ("w_gate", "w_up", "w_down"):
            held["moe/" + k] = p["moe/" + k][first:first + 4]
        tree, cfg = _program_layer(held, share)
        parts.append(_apply(tree, cfg, y))
    total = parts[0] + parts[1] - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    # and neither share alone is the whole layer
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3


def test_dropless_when_every_token_picks_one_expert():
    """All 64 tokens route to held expert 2 (and two others each): no
    capacity drops any of them, so the layer is the reference's."""
    m, p = _layer(n_experts=16, n_held=8)
    p = dict(p)
    y = jax.random.normal(jax.random.PRNGKey(2), (64, m["d_model"]))
    # a large constant column: expert 2's logit tops every token's
    p["moe/router"] = p["moe/router"].at[:, 2].set(0.0)
    y = y.at[:, 0].set(8.0)
    p["moe/router"] = p["moe/router"].at[0, 2].set(4.0)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(y @ p["moe/router"], -1)
        assert bool(jnp.all(jnp.argmax(probs, -1) == 2))
        want = REF.moe_ffn(y, p, m["moe"], MM)
    tree, cfg = _program_layer(p, m["moe"])
    got = _apply(tree, cfg, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------- grouped matmul kernels
def _dense_groups(x, w, group_sizes):
    """Each group's rows times its expert, in plain jnp; rows past the last
    group are zero."""
    ends = np.cumsum(group_sizes)
    row = jnp.arange(x.shape[0])[:, None]
    return sum(jnp.where((row >= e - n) & (row < e), x @ w[g], 0.0)
               for g, (n, e) in enumerate(zip(group_sizes, ends)))


@pytest.mark.parametrize("sizes", [
    [[0, 100, 3, 0], [128, 0, 0, 128], [0, 0, 0, 0]],   # empty, full, none
    [[7, 1, 200, 40], [1, 1, 1, 1], [60, 60, 60, 60]]],
    ids=["edges", "ragged"])
@pytest.mark.parametrize("per_client_w", [False, True],
                         ids=["shared_w", "client_w"])
def test_expert_matmul_in_a_cohort(sizes, per_client_w):
    """Three clients' sorted rows through one ``expert_matmul`` under
    ``vmap``, against each client's dense per-group products, forward and
    both gradients; the weights shared (a round's first local step) or each
    client's own."""
    from repro.kernels.ops import expert_matmul
    gs = jnp.asarray(sizes, jnp.int32)
    c, g, m, k, n = 3, 4, 256, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (c, m, k))
    w = jax.random.normal(ks[1], ((c,) if per_client_w else ()) + (g, k, n))
    dy = jax.random.normal(ks[2], (c, m, n))
    w_axis = 0 if per_client_w else None

    def loss(x, w, gs, dy, fn):
        return jnp.sum(fn(x, w, gs) * dy)

    got = jax.vmap(expert_matmul, (0, w_axis, 0))(x, w, gs)
    grads = jax.vmap(jax.grad(loss, (0, 1)), (0, w_axis, 0, 0, None))(
        x, w, gs, dy, expert_matmul)
    for i in range(c):
        wi = w[i] if per_client_w else w
        with jax.default_matmul_precision("highest"):
            want = _dense_groups(x[i], wi, sizes[i])
            dx, dw = jax.grad(loss, (0, 1))(
                x[i], wi, sizes[i], dy[i],
                lambda a, b, s: _dense_groups(a, b, s))
        np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(grads[0][i], dx, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(grads[1][i], dw, rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------------- YaRN
def test_yarn_constants_at_the_published_values():
    a = get_config("deepseek-v2-lite").mla
    assert yarn_correction_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    inv = yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, extra / 40 * ramp + extra * (1 - ramp),
                               rtol=1e-6)
    assert inv[9] == pytest.approx(extra[9]) and \
        inv[23] == pytest.approx(extra[23] / 40)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert mla.softmax_scale(a) == pytest.approx(0.114721, abs=1e-6)
    assert mla.softmax_scale(a) == pytest.approx(192 ** -0.5 * m * m)
    assert mla.rope_scale(a) == 1.0
    np.testing.assert_allclose(mla.rope_inv_freq(a, 10000.0), inv)
    ref_mla = dict(SMALL["mla"], qk_nope_head_dim=128, qk_rope_head_dim=64)
    np.testing.assert_allclose(REF.yarn_inv_freq(64, 10000.0, ref_mla), inv,
                               rtol=1e-6)
    assert REF.softmax_scale(ref_mla) == pytest.approx(0.114721, abs=1e-6)


# -------------------------------------------------------- names in the HLO
SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "mla.attention", "expert_gmm", "expert_tgmm")


def test_scopes_and_kernel_names_reach_the_compiled_program():
    model = small_model()
    _, params, _ = seeded_params(model)
    batch = tokens(seq=32)
    hlo = jax.jit(jax.grad(lambda p: model.loss_fn(p, batch)[0])).lower(
        params).compile().as_text()
    parts = set()
    for op in re.findall(r'op_name="([^"]*)"', hlo):
        parts.update(op.split("/"))
    for scope in SCOPES:
        assert scope in parts, scope


# ---------------------------------------------------------------- FL round
def test_fl_round_matches_the_reference_round():
    """Two rounds of the fl_train scan chunk (bcrs_opwa, 2 clients) against
    ``bench/reference.RoundReference`` from the same weights and inputs."""
    mix = {"strategy": "bcrs_opwa", "weighting": "bcrs", "compress": "topk",
           "overlap_weighted": True, "error_feedback": False, "cr": 0.05,
           "alpha": 1.0, "gamma": 3.0, "overlap_d": 1, "clients": 2,
           "population": 2, "local_steps": 1, "batch": 1, "seq": 16,
           "lr": 0.01, "eta": 1.0,
           "links": {"bw_mean_mbps": 1000.0, "bw_sd_mbps": 400.0,
                     "lat_lo_s": 0.001, "lat_hi_s": 0.005}}
    model = small_model()
    flat, params, paths = seeded_params(model, seed=7)
    n = sum(math.prod(s) for s, _, _ in REF.leaf_specs(SMALL).values())
    traffic = Traffic(mix, SMALL["vocab_size"], n, seed=7)
    sim = engine_mod.make_mesh_sim_scan(
        model.loss_fn, params, lr=mix["lr"], strategy=mix["strategy"],
        eta=mix["eta"], gamma=mix["gamma"], overlap_d=mix["overlap_d"],
        use_kernel="auto")
    x = traffic.chunk(0, 2)
    xs = {"batches": {"tokens": jnp.asarray(x["tokens"]),
                      "labels": jnp.asarray(x["labels"])},
          **{k: jnp.asarray(x[k]) for k in ("step_mask", "active", "weights",
                                            "crs")}}
    p0 = jax.device_get(flat)
    with jax.default_matmul_precision("highest"):
        out = sim(params, jnp.zeros((0,), jnp.float32), xs)
    prog = {"losses": np.asarray(out["ys"]["loss"]).tolist(),
            "states": [(2, dict(zip(paths, jax.device_get(
                jax.tree.leaves(out["params"])))))]}
    ref = RoundReference(
        lambda p, t, lab, mmf: REF.loss(p, t, lab, SMALL, mmf), mix, MM)
    p, losses = flat, []
    for r in range(2):
        p, loss = ref.run(p, traffic.round(r))
        losses.append(loss)
    numbers = compare(prog, {"losses": losses,
                             "states": [(2, jax.device_get(p))]}, p0)
    # float32 on both sides: summation order only
    assert numbers["change_gap"]["value"] < 1e-3
    assert numbers["change_kept"]["value"] < 0.01
    assert numbers["loss_gap"]["value"] < 1e-5


# ------------------------------------------------ the benchmark's readers
def _trace(gmm: str, tgmm: str):
    """Two rounds of a trace: per round local training 30 ms, of which the
    grouped matmuls 8 + 4 ms under the given instruction names."""
    import trace_reduce
    ms = 1_000_000
    ops, cats = [], {"fusion.2": "local_train", gmm: "local_train",
                     tgmm: "local_train"}
    for r0 in (10, 110):
        ops += [("%fusion.2 = bf16[4]{0} fusion(bf16[4]{0} %a)", r0 * ms,
                 (r0 + 18) * ms),
                (f"%{gmm} = bf16[8,4]{{1,0}} custom-call(bf16[4]{{0}} %x)",
                 (r0 + 18) * ms, (r0 + 26) * ms),
                (f"%{tgmm} = bf16[2,4]{{1,0}} custom-call(bf16[4]{{0}} %x)",
                 (r0 + 26) * ms, (r0 + 30) * ms)]
    host = [("bench.stage", 0, 10 * ms), ("bench.dispatch", 10 * ms, 100 * ms),
            ("bench.stage", 100 * ms, 110 * ms),
            ("bench.dispatch", 110 * ms, 200 * ms)]
    red = trace_reduce.reduce({"chips": [ops], "host": host}, cats)
    return trace_reduce.Context(
        reduction=red, rounds=2,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        model_flops_per_round=1e12, merge_bytes_per_round=1e9)


def test_expert_gmm_readers():
    cell = cells.resolve("deepseek-v2-lite.silo")
    readers = cell.readers
    ctx = _trace("expert_gmm.12", "expert_tgmm.7")
    assert readers["expert_gmm_s"].read(ctx) == pytest.approx(0.012)
    work = REF.expert_gmm_work(cell.config["model"], cell.mix)
    # the routed rows of a round: 4 clients x 2 x 2048 tokens x 6 of 64
    # experts x 8 held, per MoE layer
    assert work["rows"] == 6 * 4 * 2 * 2048 * 6 * 8 / 64
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert least == work["flops"] / 197e12         # bound by the MXU
    assert readers["expert_gmm_roofline"].read(ctx) == pytest.approx(
        100 * least / 0.012)
    # the parent's program has no such kernels: nothing is read
    ctx = _trace("fusion.12", "custom-call.7")
    assert readers["expert_gmm_s"].read(ctx) is None
    assert readers["expert_gmm_roofline"].read(ctx) is None
    assert readers["local_train_s"].read(ctx) == pytest.approx(0.030)
