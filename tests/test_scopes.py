"""The compiled FL round names its own phases and kernels.

``fed/mesh_round.make_round_body`` wraps local training, the merge and the
server step in ``jax.named_scope``s (``fl.local_train``, ``fl.merge``,
``fl.server_step``), and each Pallas kernel has a ``name=``, which the
compiled ``op_name`` carries as a component too. These tests check that
the scopes and names reach the compiled program of the fl_train scan chunk
and agree with the name-stack marks the benchmark's trace reducer
(``bench/trace_reduce.py``) splits the round by, and that the benchmark's
kernel readers find the named kernels in a trace.
"""
import dataclasses
import os
import re
import sys
from typing import Dict

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.fed import engine as engine_mod
from repro.models import Model

_BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                      "bench"))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import cells  # noqa: E402
import trace_reduce  # noqa: E402

SCOPES = ("fl.local_train", "fl.merge", "fl.server_step", "threshold_find",
          "fused_merge")
KERNELS = ("threshold_find", "fused_merge")
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_APPLIED = re.compile(r"\bto_apply=%?([\w.\-]+)")


# ------------------------------------------------------------ HLO reading
def _instructions(hlo_text: str):
    """(instruction, its computation, its line) for every instruction, read
    with the trace reducer's own patterns."""
    comp = None
    for line in hlo_text.splitlines():
        m = trace_reduce._COMP.match(line)
        if m and " = " not in line.split("{")[0]:
            comp = m.group(1)
            continue
        m = trace_reduce._INSTR.match(line)
        if m and comp is not None:
            yield m.group(1), comp, line


def hlo_scopes(hlo_text: str) -> Dict[str, frozenset]:
    """{instruction: the ``SCOPES`` that are whole components of its name
    stack}, the op_names of the instructions calling its computation (a
    loop body, a fusion, a branch) included, as ``hlo_categories`` walks
    them."""
    own, where, callers = {}, {}, {}
    for name, comp, line in _instructions(hlo_text):
        where[name] = comp
        op = trace_reduce._OP_NAME.search(line)
        own[name] = set(op.group(1).split("/")) if op else set()
        called = trace_reduce._CALLS.findall(line)
        for b in trace_reduce._BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in b.split(",")]
        for c in called:
            callers.setdefault(c, name)
    cache: Dict[str, set] = {}

    def parts(name):
        if name not in cache:
            cache[name] = own[name]                # guards a cycle
            parent = callers.get(where[name])
            cache[name] = own[name] | (parts(parent) if parent else set())
        return cache[name]

    return {n: frozenset(k for k in SCOPES if k in parts(n)) for n in own}


def device_ops(hlo_text: str) -> set:
    """Instructions that run as ops of their own, the ones a profiler
    trace shows: all but those inside a fusion or a reducer."""
    where, inner = {}, set()
    for name, comp, line in _instructions(hlo_text):
        where[name] = comp
        if " fusion(" in line:
            inner.update(_FUSED.findall(line))
        inner.update(_APPLIED.findall(line))
    return {n for n, c in where.items() if c not in inner}


def test_hlo_scopes_are_whole_components_of_the_name_stack():
    hlo = """HloModule m

%body.1 (a: f32[4]) -> f32[4] {
  %dot.3 = f32[4]{0} dot(f32[4]{0} %a), metadata={op_name="jit(f)/while/body/fl.local_train/vmap()/jvp()/dot_general"}
  %fusion.4 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused.9
}

%fused.9 (p: f32[4]) -> f32[4] {
  %add.10 = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %p), metadata={op_name="jit(f)/fl.merge/add"}
}

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  %while.5 = f32[4]{0} while(f32[4]{0} %x), condition=%c.1, body=%body.1, metadata={op_name="jit(f)/while"}
  %custom-call.6 = u32[4,1]{1,0} custom-call(f32[4]{0} %x), metadata={op_name="jit(f)/fl.merge/jit(k)/threshold_find/pallas_call"}
  %copy.7 = f32[4]{0} copy(f32[4]{0} %x), metadata={op_name="jit(f)/fl.merged/threshold_finder"}
}
"""
    sc = hlo_scopes(hlo)
    assert sc["dot.3"] == {"fl.local_train"}
    assert sc["fusion.4"] == frozenset()          # its op_name names none
    assert sc["add.10"] == {"fl.merge"}
    assert sc["custom-call.6"] == {"fl.merge", "threshold_find"}
    assert sc["copy.7"] == frozenset()            # no whole-name match
    assert device_ops(hlo) == {"dot.3", "fusion.4", "while.5",
                               "custom-call.6", "copy.7"}


# ----------------------------------------------------- compiled round
def tiny_model() -> Model:
    cfg = dataclasses.replace(
        get_config("stablelm-1.6b").reduced(), n_layers=1, d_model=32,
        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=256)
    return Model(cfg)


def compiled_chunk_hlo(strategy: str, use_kernel) -> str:
    """The optimised HLO of a one-round fl_train scan chunk at tiny size."""
    model = tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    t, c, s, b, seq = 1, 2, 1, 1, 16
    xs = {"batches": {"tokens": jnp.zeros((t, c, s, b, seq), jnp.int32),
                      "labels": jnp.zeros((t, c, s, b, seq), jnp.int32)},
          "step_mask": jnp.ones((t, c, s), bool),
          "active": jnp.ones((t, c), bool),
          "weights": jnp.full((t, c), 1.0 / c, jnp.float32),
          "crs": jnp.full((t, c), 0.1, jnp.float32)}
    sim = engine_mod.make_mesh_sim_scan(model.loss_fn, params, lr=0.01,
                                        strategy=strategy,
                                        use_kernel=use_kernel)
    return sim.compile(params, jnp.zeros((0,), jnp.float32), xs).as_text()


@pytest.fixture(scope="module", params=[("bcrs_opwa", True),
                                        ("fedavg", "auto")],
                ids=["bcrs_opwa-kernels", "fedavg-dense"])
def chunk(request):
    strategy, use_kernel = request.param
    hlo = compiled_chunk_hlo(strategy, use_kernel)
    return {"strategy": strategy, "ops": device_ops(hlo),
            "categories": trace_reduce.hlo_categories(hlo),
            "scopes": hlo_scopes(hlo)}


def test_local_training_carries_its_scope(chunk):
    cat, sc = chunk["categories"], chunk["scopes"]
    local = [n for n in chunk["ops"] if cat[n] == "local_train"]
    assert local
    assert all("fl.local_train" in sc[n] for n in local)


def test_scopes_agree_with_the_name_stack_marks(chunk):
    cat, sc = chunk["categories"], chunk["scopes"]
    for n in chunk["ops"]:
        scopes = sc[n]
        if scopes & {"fl.merge", "fl.server_step"}:
            assert cat[n] == "merge", (n, sorted(scopes))
        if cat[n] == "local_train":
            assert "fl.local_train" in scopes, n
        if "fl.local_train" in scopes:
            assert cat[n] == "local_train", n
    assert any("fl.server_step" in sc[n] for n in chunk["ops"])
    assert any("fl.merge" in sc[n] for n in chunk["ops"])


def test_kernels_run_under_the_merge_scope(chunk):
    sc = chunk["scopes"]
    kernel_ops = [n for n in chunk["ops"] if sc[n] & set(KERNELS)]
    if chunk["strategy"] == "fedavg":
        # the dense merge bypasses both kernels
        assert not kernel_ops
        return
    for k in KERNELS:
        assert any(k in sc[n] for n in kernel_ops), k
    for n in kernel_ops:
        assert "fl.merge" in sc[n], n
        assert len(sc[n] & set(KERNELS)) == 1, n


# ----------------------------------------------- the benchmark's readers
def _reduced(kernel_names):
    """A two-round trace: per round, local training 20 ms, a
    ``threshold_find`` call 40 ms, a ``fused_merge`` call 5 ms and the
    server step's fusion 2 ms, with the kernels' instructions named as
    given (the program's names, or the parent's anonymous ones)."""
    ms = 1_000_000
    tf, fm = kernel_names
    ops = []
    for r0 in (10, 110):
        ops += [(f"%dot.3 = f32[4]{{0}} dot(f32[4]{{0}} %a)", r0 * ms,
                 (r0 + 20) * ms),
                (f"%{tf} = u32[4,1]{{1,0}} custom-call(f32[4]{{0}} %x)",
                 (r0 + 20) * ms, (r0 + 60) * ms),
                (f"%{fm} = f32[1,4]{{1,0}} custom-call(f32[4]{{0}} %x)",
                 (r0 + 60) * ms, (r0 + 65) * ms),
                ("%fusion.8 = bf16[4]{0} fusion(f32[4]{0} %x)",
                 (r0 + 65) * ms, (r0 + 67) * ms)]
    host = [("bench.stage", 0, 10 * ms), ("bench.dispatch", 10 * ms, 100 * ms),
            ("bench.stage", 100 * ms, 110 * ms),
            ("bench.dispatch", 110 * ms, 200 * ms)]
    categories = {"dot.3": "local_train", tf: "merge", fm: "merge",
                  "fusion.8": "merge"}
    red = trace_reduce.reduce({"chips": [ops], "host": host}, categories)
    return trace_reduce.Context(
        reduction=red, rounds=2,
        peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
        model_flops_per_round=1e9, merge_bytes_per_round=1e6)


def _merge_readers():
    readers = cells.resolve("stablelm-1.6b.merge").readers
    return {k: readers[k] for k in ("threshold_find_s", "fused_merge_s",
                                    "merge_s")}


def test_kernel_readers_on_a_synthetic_trace():
    ctx = _reduced(("threshold_find.24", "fused_merge.24"))
    got = {k: r.read(ctx) for k, r in _merge_readers().items()}
    assert got["threshold_find_s"] == pytest.approx(0.040)
    assert got["fused_merge_s"] == pytest.approx(0.005)
    assert got["merge_s"] == pytest.approx(0.047)
    assert got["threshold_find_s"] + got["fused_merge_s"] <= got["merge_s"]


@pytest.mark.parametrize("names", [
    ("megakernel_aggregate.70", "megakernel_aggregate.49"),
    ("threshold_finder.2", "fused_merge_x.3")])
def test_kernel_readers_read_nothing_without_the_kernels_names(names):
    """Kernels the compiler did not name after the program's ``name=`` (as
    before the kernels were named) read nothing, not a zero."""
    ctx = _reduced(names)
    readers = _merge_readers()
    assert readers["threshold_find_s"].read(ctx) is None
    assert readers["fused_merge_s"].read(ctx) is None
    assert readers["merge_s"].read(ctx) == pytest.approx(0.047)


def test_kernel_readers_are_listed_for_the_kernel_cell_only():
    for w in cells.benchmark()["workloads"]:
        readers = cells.resolve(w["name"]).readers
        has = {"threshold_find_s", "fused_merge_s"} <= set(readers)
        assert has == (w["name"] == "stablelm-1.6b.merge"), w["name"]
