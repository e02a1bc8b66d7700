"""The merge kernels compile for a TPU v5e chip, at a real leaf width.

The TPU compiler is installed with jax, and compiles for a chip that is
described rather than attached, so these tests need no accelerator: they
catch what Mosaic refuses (and interpret mode accepts) before any chip run.
The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_merge import fused_merge_pallas
from repro.kernels.threshold_find import threshold_find_pallas

#: 8 clients x the stablelm-1.6b MLP weight (d_model 2048 x d_ff 5632)
C, N = 8, 2048 * 5632
#: the merge cell's cohort: its largest leaf (the 100352 x 2048 embedding,
#: many blocks of either kernel) and a norm-sized leaf (2048, one block,
#: already a multiple of megakernel_aggregate's 1024 padding)
C_MERGE, N_EMBED, N_NORM = 4, 100352 * 2048, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _tf_plain(x, ks):
    return threshold_find_pallas(x, ks, interpret=False)


def _tf_ef_scale(x, ks, e):
    return threshold_find_pallas(x, ks, e, emit_scale=True, interpret=False)


def _fm_opwa(x, th, w, act):
    return fused_merge_pallas(x, th, w, None, act, opwa=True, gamma=3.0, d=1,
                              interpret=False)


def _fm_ef_int8(x, th, w, e, act, scales):
    return fused_merge_pallas(x, th, w, e, act, codec="int8", scales=scales,
                              interpret=False)


CASES = {
    "threshold_find": (_tf_plain, ("x", "ks"), (C, N)),
    "threshold_find_ef_scale": (_tf_ef_scale, ("x", "ks", "x"), (C, N)),
    "threshold_find_embedding": (_tf_plain, ("x", "ks"), (C_MERGE, N_EMBED)),
    "threshold_find_ef_scale_embedding": (_tf_ef_scale, ("x", "ks", "x"),
                                          (C_MERGE, N_EMBED)),
    "threshold_find_norm": (_tf_plain, ("x", "ks"), (C_MERGE, N_NORM)),
    "threshold_find_ef_scale_norm": (_tf_ef_scale, ("x", "ks", "x"),
                                     (C_MERGE, N_NORM)),
    "fused_merge_opwa": (_fm_opwa, ("x", "th", "col", "col"), (C, N)),
    "fused_merge_opwa_embedding": (_fm_opwa, ("x", "th", "col", "col"),
                                   (C_MERGE, N_EMBED)),
    "fused_merge_opwa_norm": (_fm_opwa, ("x", "th", "col", "col"),
                              (C_MERGE, N_NORM)),
    "fused_merge_ef_int8": (_fm_ef_int8, ("x", "th", "col", "x", "col",
                                          "col"), (C, N)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiles_for_v5e(case, one_chip):
    fn, kinds, (c, n) = CASES[case]
    shapes = {"x": ((c, n), jnp.float32), "ks": ((c, 1), jnp.int32),
              "th": ((c, 1), jnp.uint32), "col": ((c, 1), jnp.float32)}
    args = [jax.ShapeDtypeStruct(*shapes[k], sharding=one_chip)
            for k in kinds]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_round_kernels_are_named_in_the_v5e_program(one_chip, monkeypatch):
    """In the fl_train scan chunk compiled for the chip, each Mosaic call is
    named after its kernel (the label a profile shows), and its op_name
    holds the kernel's name inside the round's merge scope."""
    import re

    from repro.kernels import ops as kops

    # the kernels' jitted wrappers keep their traces: drop the CPU ones
    # (interpret mode) before, and the chip's (Mosaic) ones after
    jax.clear_caches()
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    try:
        hlo, leaves = _compile_round_for(one_chip)
    finally:
        jax.clear_caches()
    calls = [re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"",
                      line).groups()
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2 * leaves
    for kernel in ("threshold_find", "fused_merge"):
        mine = [(n, op) for n, op in calls if n.startswith(kernel + ".")]
        assert len(mine) == leaves, kernel
        for n, op in mine:
            parts = op.split("/")
            assert "fl.merge" in parts and kernel in parts, (n, op)


def _compile_round_for(one_chip, arch="stablelm-1.6b"):
    """The HLO of a one-round fl_train scan chunk of a tiny model of
    ``arch``, compiled for the described chip with the Pallas kernels on the
    merge path, and the model's number of parameter leaves."""
    import dataclasses

    from repro.configs import get_config
    from repro.fed import engine
    from repro.models import Model

    cfg = get_config(arch).reduced()
    if arch == "stablelm-1.6b":
        cfg = dataclasses.replace(cfg, n_layers=1, d_model=32, n_heads=2,
                                  n_kv_heads=2, head_dim=16, d_ff=64,
                                  vocab_size=256)
    else:
        cfg = dataclasses.replace(cfg, dtype="bfloat16")
    model = Model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    place = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    params = jax.tree.map(lambda s: place(s.shape, s.dtype), shapes)
    t, c, s, b, seq = 1, 2, 1, 1, 16
    xs = {"batches": {"tokens": place((t, c, s, b, seq), jnp.int32),
                      "labels": place((t, c, s, b, seq), jnp.int32)},
          "step_mask": place((t, c, s), jnp.bool_),
          "active": place((t, c), jnp.bool_),
          "weights": place((t, c), jnp.float32),
          "crs": place((t, c), jnp.float32)}
    sim = engine.make_mesh_sim_scan(model.loss_fn, shapes, lr=0.01,
                                    strategy="bcrs_opwa", use_kernel=True)
    hlo = sim.compile(params, place((0,), jnp.float32), xs).as_text()
    return hlo, len(jax.tree.leaves(shapes))


#: the deepseek-v2-lite.silo cell's expert layer: each of 4 clients'
#: dispatch buffer (batch 2 x seq 2048 tokens x top 6 rows) against the 8
#: held experts' fused gate-up [2048, 2 x 1408] and down [1408, 2048]
EXPERT_CLIENTS, EXPERT_ROWS, HELD = 4, 2 * 2048 * 6, 8
EXPERT_CASES = {"gate_up": (2048, 2 * 1408), "down": (1408, 2048)}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("case", list(EXPERT_CASES))
def test_expert_gmm_compiles_for_v5e(case, grad, one_chip, monkeypatch):
    """The grouped matmuls as the cohort's vmapped local step runs them:
    the forward product, or its backward (``expert_gmm`` for the rows'
    gradient, ``expert_tgmm`` for the weights'), one Mosaic call each."""
    import re

    from repro.kernels import ops as kops

    k, n = EXPERT_CASES[case]
    cohort = jax.vmap(kops.expert_matmul, in_axes=(0, None, 0))

    def step(x, w, group_sizes, dy):
        if not grad:
            return cohort(x, w, group_sizes)
        _, vjp = jax.vjp(lambda x, w: cohort(x, w, group_sizes), x, w)
        return vjp(dy)

    c, m = EXPERT_CLIENTS, EXPERT_ROWS
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((c, m, k), jnp.bfloat16), ((HELD, k, n), jnp.bfloat16),
        ((c, HELD), jnp.int32), ((c, m, n), jnp.bfloat16))]
    jax.clear_caches()
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    try:
        hlo = jax.jit(step).lower(*args).compile().as_text()
    finally:
        jax.clear_caches()
    calls = re.findall(r"%[\w.\-]*?(expert_t?gmm)[\w.\-]* = .*tpu_custom_call",
                       hlo)
    want = ["expert_gmm", "expert_tgmm"] if grad else ["expert_gmm"]
    assert sorted(calls) == want


def test_expert_kernels_are_named_in_the_v5e_program(one_chip, monkeypatch):
    """In a deepseek-v2-lite fl_train scan chunk compiled for the chip, the
    grouped matmuls of every local step are Mosaic calls named after their
    kernels, as the benchmark's ``expert_gmm_s`` reads them, under the
    round's local-training scope and the expert layer's."""
    import re

    from repro.kernels import ops as kops

    jax.clear_caches()
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    try:
        hlo, _ = _compile_round_for(one_chip, "deepseek-v2-lite")
    finally:
        jax.clear_caches()
    calls = [re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"",
                      line).groups()
             for line in hlo.splitlines()
             if "tpu_custom_call" in line and "expert_" in line]
    names = sorted(re.fullmatch(r"(expert_t?gmm)\.\d+", n).group(1)
                   for n, _ in calls)
    # one MoE layer, no remat: gate-up and down forward and their rows'
    # gradients; the two weight gradients
    assert names == ["expert_gmm"] * 4 + ["expert_tgmm"] * 2
    for n, op in calls:
        parts = op.split("/")
        assert "fl.local_train" in parts and "moe.experts" in parts, (n, op)
