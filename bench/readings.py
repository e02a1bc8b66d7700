"""The readings a cell's limits are set from, on the chip, at the cell's
size: the numbers ``correct`` compares, for sound runs of the program on
many seeds and for the control and the faults on a few.

    python3 bench/readings.py --workload <name> --seeds 11,12,13 \
        [--control-seeds 11,12,13] [--half-batch-seeds 11,12,13] \
        [--half-cohort-seeds 11,12,13] [--out readings.jsonl]

One process, no window. For each seed the float32 reference runs once;
then each run under test drives the cell's first rounds through
``run.set_up`` exactly as a benchmark run does, and is compared with it:

* ``program``: the program's compiled chunk;
* ``control``: the reference in the program's place, its matmul operands
  in float8 e4m3 (``InPlace("fp8")``), the precision below bfloat16;
* ``half_batch``: the reference in the program's place with half of every
  client's batch left out and the mean taken over the rest;
* ``half_cohort``: the program with half of the cohort left out and the
  mean taken over the rest (``Faulty``).

One JSON line per reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run  # noqa: F401  (puts the program and bench/ on sys.path)

import cells
from reference import compare


class Faulty:
    """The program's compiled chunk with a fault planted underneath:
    ``unchanged`` returns the state it was given; ``half_cohort`` leaves
    half of the cohort out and takes the mean over the rest."""

    def __init__(self, fault: str):
        self.fault = fault

    def __call__(self, cell, model, params):
        sim = run.make_program(cell, model, params)
        fault = self.fault

        def broken(compiled):
            def call(params, residuals, xs):
                import jax
                import jax.numpy as jnp
                if fault == "unchanged":
                    keep = jax.tree.map(jnp.copy, params)
                    return {**compiled(params, residuals, xs),
                            "params": keep}
                c = xs["active"].shape[-1]
                half = jnp.arange(c) < max(c // 2, 1)
                xs = dict(xs, active=xs["active"] & half,
                          weights=jnp.where(half, xs["weights"] * 2.0, 0.0))
                return compiled(params, residuals, xs)
            return call

        class Program:
            def compile(self, params, residuals, xs):
                return broken(sim.compile(params, residuals, xs))

        return Program()


class InPlace:
    """The reference put in the program's place, with the matmuls ``mm``
    (``"fp8"``: the control) and, where ``half_batch``, half of every
    client's batch left out."""

    def __init__(self, mm: str = "fp8", half_batch: bool = False):
        self.mm, self.half_batch = mm, half_batch

    def __call__(self, cell, model, params):
        import jax
        import jax.numpy as jnp
        from reference import MATMULS, RoundReference
        from weights import leaf_path
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        paths = [leaf_path(p) for p, _ in flat]
        m = cell.config["model"]
        ref = RoundReference(
            lambda p, t, l, mmf: cell.model_ref.loss(p, t, l, m, mmf),
            cell.mix, MATMULS[self.mm], half_batch=self.half_batch)

        def call(params, residuals, xs):
            p = dict(zip(paths, jax.tree.leaves(params)))
            host = jax.device_get(xs)
            losses = []
            for r in range(host["active"].shape[0]):
                inputs = {k: v[r] for k, v in host.items()
                          if k != "batches"}
                inputs.update(tokens=host["batches"]["tokens"][r],
                              labels=host["batches"]["labels"][r])
                p, loss = ref.run(p, inputs)
                losses.append(loss)
            new = jax.tree_util.tree_unflatten(treedef,
                                               [p[k] for k in paths])
            return {"params": new, "residuals": residuals,
                    "ys": {"loss": jnp.asarray(losses, jnp.float32)}}

        class Program:
            def compile(self, params, residuals, xs):
                return call

        return Program()


KINDS = {"control": InPlace("fp8"),
         "half_batch": InPlace("f32", half_batch=True),
         "half_cohort": Faulty("half_cohort")}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    for kind in KINDS:
        ap.add_argument(f"--{kind.replace('_', '-')}-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    def seeds(s: str):
        return {int(x) for x in s.split(",") if x}

    wanted = {"program": seeds(args.seeds)}
    for kind in KINDS:
        wanted[kind] = seeds(getattr(args, f"{kind}_seeds"))
    cell = cells.resolve(args.workload)
    dev = run.check_device(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = open(args.out, "a") if args.out else None

    def emit(rec: dict) -> None:
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    compiled = None
    for seed in sorted(set().union(*wanted.values())):
        t0 = time.perf_counter()
        ref = run.reference_summary(cell, seed)
        p0 = ref.pop("p0")
        t_ref = time.perf_counter() - t0
        for kind in ["program", *KINDS]:
            if seed not in wanted[kind]:
                continue
            t0 = time.perf_counter()
            if kind == "program":
                st = run.set_up(cell, seed, compiled=compiled)
                compiled = st.compiled
            else:
                st = run.set_up(cell, seed, program=KINDS[kind])
                st.compiled = None
            st.params = st.residuals = None
            gc.collect()
            numbers = compare(run.program_run(st), ref, p0)
            emit({"workload": cell.name, "kind": kind, "seed": seed,
                  "numbers": numbers, "losses": st.losses,
                  "ref_losses": ref["losses"], "ref_s": t_ref,
                  "run_s": time.perf_counter() - t0,
                  "device": dev.device_kind})
            del st
            gc.collect()
        del p0, ref
        gc.collect()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
