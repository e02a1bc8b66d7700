"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; their files are found by name (``cells.py``). The system under
test is the program's compiled FL scan chunk, the one ``launch.fl_train
--engine scan`` runs: ``fed.engine.make_mesh_sim_scan`` over
``models.Model(cfg).loss_fn`` with ``use_kernel="auto"`` (local SGD of every
cohort member, the per-leaf compress-and-merge, the server step).

Set-up (``setup_s``, from process start): imports, the weights made on the
device from the seed, the chunk program compiled or loaded from the
persistent cache, and the first rounds of the trajectory, dispatched
through the same compiled program and feed as the window. The state after
those rounds is copied to the host for the check.

Window: chunks of ``rounds_per_dispatch`` rounds dispatched back to back,
each staged on the host and ended with ``block_until_ready``, until the
first chunk boundary after ``--seconds``. ``round_s`` is the window's wall
time over its rounds. With ``--trace 1`` the window is traced and the
per-layer metrics are read from the trace instead.

Check: once the window has closed, the peak memory is read and the
program's state freed, the plain reference (``reference.py``) runs the same
first rounds from the same weights and inputs, and ``correct`` holds when
every number compared is within its limit (``limits/<cell>.json``).

The last line of standard output is one JSON object. A run on anything but
a TPU, or on fewer chips than the cell asks for, exits non-zero with no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import cells  # noqa: E402


class NoChip(SystemExit):
    """Raised when the device is not the one the benchmark measures."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ device
def check_device(chips: int):
    """The first JAX device must be a TPU, with at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[0]


class CompileCounter:
    """Counts XLA backend compilations while active (jax.monitoring)."""

    def __init__(self):
        import jax
        self.n = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._cb)

    def _cb(self, name, duration, **kwargs):
        if self.active and "backend_compile" in name:
            self.n += 1


# ----------------------------------------------------------------- program
def program_config(cell: cells.Cell):
    """The program's ModelConfig with the configuration file's sizes."""
    from repro.configs import get_config
    from repro.configs import base as cfg_base
    model = dict(cell.config["model"])
    if "rwkv" in model:
        model["rwkv"] = cfg_base.RWKVConfig(**model["rwkv"])
    cfg = dataclasses.replace(get_config(cell.config["program_arch"]),
                              **model)
    return cfg


def make_program(cell: cells.Cell, model, params):
    """The system under test: the fl_train scan chunk program."""
    from repro.fed import engine
    mix = cell.mix
    return engine.make_mesh_sim_scan(
        model.loss_fn, params, lr=mix["lr"], strategy=mix["strategy"],
        eta=mix["eta"], gamma=mix["gamma"], overlap_d=mix["overlap_d"],
        use_kernel="auto")


def stage(traffic, r0: int, n: int):
    """Rounds r0 .. r0+n-1 as the program's xs, on the device."""
    import jax.numpy as jnp
    x = traffic.chunk(r0, n)
    return {"batches": {"tokens": jnp.asarray(x["tokens"]),
                        "labels": jnp.asarray(x["labels"])},
            "step_mask": jnp.asarray(x["step_mask"]),
            "active": jnp.asarray(x["active"]),
            "weights": jnp.asarray(x["weights"]),
            "crs": jnp.asarray(x["crs"])}


@dataclasses.dataclass
class Setup:
    cell: cells.Cell
    seed: int
    specs: dict
    paths: List[str]
    traffic: object
    compiled: object
    params: object
    residuals: object
    rounds: int                      # rounds dispatched so far
    losses: List[float]
    snapshots: List[tuple]           # (round, {path: host array})
    compile_s: float


def host_params(params, paths) -> Dict[str, np.ndarray]:
    import jax
    return dict(zip(paths, jax.device_get(jax.tree.leaves(params))))


def set_up(cell: cells.Cell, seed: int, program: Callable = make_program,
           compiled=None) -> Setup:
    """Weights, traffic, the compiled chunk program (``compiled``, where a
    process runs several seeds of one cell), and the first rounds."""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from traffic import Traffic
    from weights import check_layout, make_weights

    cfg = program_config(cell)
    model = Model(cfg)
    specs = cell.model_ref.leaf_specs(cell.config["model"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    treedef, paths = check_layout(shapes, specs)
    w = make_weights(specs, seed)
    params = jax.tree_util.tree_unflatten(treedef, [w[p] for p in paths])
    del w
    n_params = sum(math.prod(s) for s, _, _ in specs.values())
    traffic = Traffic(cell.mix, cell.config["model"]["vocab_size"], n_params,
                      seed)
    residuals = jnp.zeros((0,), jnp.float32)
    rpd = int(cell.mix["rounds_per_dispatch"])
    xs = stage(traffic, 0, rpd)
    t0 = time.perf_counter()
    if compiled is None:
        compiled = program(cell, model, params).compile(params, residuals, xs)
    compile_s = time.perf_counter() - t0
    st = Setup(cell=cell, seed=seed, specs=specs, paths=paths, traffic=traffic, compiled=compiled,
               params=params, residuals=residuals, rounds=0, losses=[],
               snapshots=[], compile_s=compile_s)
    # the first rounds, through the window's own call and feed
    check = int(cell.mix["check_rounds"])
    read_at = observed_rounds(cell.mix)
    while st.rounds < check:
        out = dispatch(st, xs)
        st.losses.extend(np.asarray(out).tolist())
        if st.rounds in read_at:
            st.snapshots.append((st.rounds, host_params(st.params, paths)))
        xs = stage(traffic, st.rounds, rpd) if st.rounds < check else None
    return st


def dispatch(st: Setup, xs):
    """One chunk through the compiled program; returns its [T] losses."""
    import jax
    out = st.compiled(st.params, st.residuals, xs)
    jax.block_until_ready(out["params"])
    st.params, st.residuals = out["params"], out["residuals"]
    st.rounds += int(out["ys"]["loss"].shape[0])
    return out["ys"]["loss"]


# ------------------------------------------------------------------ window
def window(st: Setup, seconds: float, annotate: bool = False) -> dict:
    """Dispatch chunks back to back until the first chunk boundary after
    ``seconds``. Returns wall seconds, rounds, losses, compiles."""
    import jax
    rpd = int(st.cell.mix["rounds_per_dispatch"])
    span = (jax.profiler.TraceAnnotation if annotate
            else lambda name: contextlib.nullcontext())
    counter = CompileCounter()
    counter.active = True
    losses = []
    t0 = time.perf_counter()
    r0 = st.rounds
    while True:
        with span("bench.stage"):
            xs = stage(st.traffic, st.rounds, rpd)
        with span("bench.dispatch"):
            losses.append(dispatch(st, xs))
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    counter.active = False
    loss = np.concatenate([np.asarray(x) for x in losses])
    return {"wall_s": wall, "rounds": st.rounds - r0, "losses": loss,
            "compiles": counter.n}


# ------------------------------------------------------------------- check
def reference_summary(cell: cells.Cell, seed: int, mm: str = "f32",
                      half_batch: bool = False) -> dict:
    """The plain reference over the cell's checked rounds: the start, the
    losses, and the state on the host at the rounds the program's state is
    read. ``mm`` and ``half_batch`` make the control and a planted fault."""
    import jax
    from reference import MATMULS, RoundReference
    from traffic import Traffic
    from weights import make_weights

    specs = cell.model_ref.leaf_specs(cell.config["model"])
    m = cell.config["model"]
    ref = RoundReference(
        lambda p, t, l, mmf: cell.model_ref.loss(p, t, l, m, mmf),
        cell.mix, MATMULS[mm], half_batch=half_batch)
    n_params = sum(math.prod(s) for s, _, _ in specs.values())
    traffic = Traffic(cell.mix, m["vocab_size"], n_params, seed)
    p = make_weights(specs, seed)
    p0 = jax.device_get(p)
    read_at = observed_rounds(cell.mix)
    losses, states = [], []
    for r in range(int(cell.mix["check_rounds"])):
        p, loss = ref.run(p, traffic.round(r))
        losses.append(loss)
        if r + 1 in read_at:
            states.append((r + 1, jax.device_get(p)))
    return {"losses": losses, "states": states, "p0": p0}


def observed_rounds(mix: dict) -> List[int]:
    """Rounds after which the program's state is read: round 1 where a
    chunk ends there, and the last checked round."""
    rpd, check = int(mix["rounds_per_dispatch"]), int(mix["check_rounds"])
    ends = set(range(rpd, check + rpd, rpd))
    return sorted(r for r in {1, check} if r in ends)


def program_run(st: Setup) -> dict:
    """The program's checked rounds as ``reference.compare`` takes them."""
    return {"losses": st.losses[:int(st.cell.mix["check_rounds"])],
            "states": st.snapshots}


def check(st: Setup, window_losses: np.ndarray) -> Dict[str, dict]:
    """Run the reference and compare; returns {name: {value, limit}} for
    the numbers the cell's limits file names. The others are logged as
    readings."""
    from reference import compare
    ref = reference_summary(st.cell, st.seed)
    numbers = compare(program_run(st), ref, ref.pop("p0"))
    st.snapshots = []
    out = {}
    for name, v in numbers.items():
        if name not in st.cell.limits:
            log(f"reading {name} {v['value']!r} (not compared)")
    for name, lim in st.cell.limits.items():
        value = numbers.get(name, {"value": math.inf})["value"]
        out[name] = {"value": value, "limit": lim["limit"]}
    out["nonfinite_losses"] = {
        "value": int(np.sum(~np.isfinite(window_losses))), "limit": 0}
    return out


# ------------------------------------------------------------------- trace
def traced_window(st: Setup, seconds: float) -> tuple:
    """The window under the profiler; returns (window result, events)."""
    import jax
    import trace_reduce
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            res = window(st, seconds, annotate=True)
        finally:
            jax.profiler.stop_trace()
        events = trace_reduce.load(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return res, events


def per_layer_metrics(st: Setup, res: dict, events, categories: dict,
                      peaks: dict) -> tuple:
    """Reduce the trace; each reader takes its metric from the reduction."""
    import trace_reduce
    m = st.cell.config["model"]
    mix = st.cell.mix
    red = trace_reduce.reduce(events, categories)
    specs = st.specs
    tokens = mix["clients"] * mix["local_steps"] * mix["batch"] * mix["seq"]
    ctx = trace_reduce.Context(
        reduction=red, rounds=res["rounds"], peaks=peaks,
        model_flops_per_round=tokens * st.cell.model_ref.flops_per_token(
            m, mix["seq"]),
        merge_bytes_per_round=least_merge_bytes(specs, mix))
    metrics = {}
    for spec in st.cell.per_layer:
        v = st.cell.readers[spec["name"]].read(ctx)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    return metrics, red


def least_merge_bytes(specs: dict, mix: dict) -> float:
    """The least HBM bytes one round's merge needs, whatever implements it:
    each client's delta read once in its dtype, the parameters read and
    written once, EF residuals (float32) read and written once where they
    are carried."""
    import jax.numpy as jnp
    c = int(mix["clients"])
    total = 0.0
    for shape, dtype, _ in specs.values():
        n = math.prod(shape)
        size = jnp.dtype(dtype).itemsize
        total += c * n * size + 2 * n * size
        if mix.get("error_feedback"):
            total += 2 * c * n * 4
    return total


# -------------------------------------------------------------------- main
def peak_bytes(dev) -> int:
    """The chip's peak HBM footprint: buffers in use plus the memory the
    runtime reserved for the executables' temporaries. On the TPU runtime
    the two are separate counts of one HBM (``bytes_reservable_limit`` is
    ``bytes_limit`` less ``bytes_in_use``), and ``peak_bytes_in_use``
    alone leaves the program's temporaries out."""
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def device_info(dev, count: int) -> dict:
    return {"platform": dev.platform, "kind": dev.device_kind, "count": count,
            "memory_peak_bytes": peak_bytes(dev)}


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             dev, count: int, peaks: dict,
             program: Callable = make_program) -> dict:
    """Set-up, window, check; returns the result line's object."""
    st = set_up(cell, seed, program)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s (compile or cache load {st.compile_s:.3f} "
        f"s), {st.rounds} rounds")
    if trace:
        res, events = traced_window(st, seconds)
    else:
        res, events = window(st, seconds), None
    log(f"window {res['wall_s']:.3f} s, {res['rounds']} rounds, "
        f"compilations inside the window: {res['compiles']}")
    device = device_info(dev, count)
    log(f"memory {dev.memory_stats()}")
    log(f"first-round losses {st.losses}")
    log(f"window losses {res['losses'].tolist()}")
    if trace:
        import trace_reduce
        categories = trace_reduce.hlo_categories(st.compiled.as_text())
    # the program's state goes before any reference runs
    st.params = st.residuals = st.compiled = None
    gc.collect()
    result = {"correct": False, "attempted": int(res["rounds"]),
              "failed": int(np.sum(~np.isfinite(res["losses"]))),
              "metrics": {}, "device": device}
    if trace:
        metrics, red = per_layer_metrics(st, res, events, categories, peaks)
        result["metrics"] = metrics
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    else:
        measured = {"round_s": res["wall_s"] / res["rounds"],
                    "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
                    "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    numbers = check(st, res["losses"])
    result["correct"] = all(v["value"] <= v["limit"]
                            for v in numbers.values())
    result["check"] = numbers
    for name, v in numbers.items():
        log(f"check {name} {v['value']!r} limit {v['limit']!r}")
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    cell = cells.resolve(args.workload)
    try:
        dev = check_device(cell.chips)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache}")
    peaks = cells.peaks(dev.device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), dev,
                      cell.chips, peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
