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
    "threshold_find": (_tf_plain, ("x", "ks")),
    "threshold_find_ef_scale": (_tf_ef_scale, ("x", "ks", "x")),
    "fused_merge_opwa": (_fm_opwa, ("x", "th", "col", "col")),
    "fused_merge_ef_int8": (_fm_ef_int8, ("x", "th", "col", "x", "col",
                                          "col")),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiles_for_v5e(case, one_chip):
    fn, kinds = CASES[case]
    shapes = {"x": ((C, N), jnp.float32), "ks": ((C, 1), jnp.int32),
              "th": ((C, 1), jnp.uint32), "col": ((C, 1), jnp.float32)}
    args = [jax.ShapeDtypeStruct(*shapes[k], sharding=one_chip)
            for k in kinds]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
