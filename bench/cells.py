"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, so a later change adds a cell by adding files:

* ``bench/configs/<config>.json``: the sizes as run, their source and cut;
  ``bench/configs/<config>.py`` beside it: the plain reference;
* ``bench/traffic/<mix>.json``: the traffic's parameters;
* ``bench/limits/<cell>.json``: the limit of each number ``correct``
  compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
* ``bench/peaks.json``: the chips' peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    """Import a benchmark file whose name need not be a Python identifier."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config: dict                 # configs/<config>.json
    model_ref: ModuleType        # configs/<config>.py
    mix: dict                    # traffic/<mix>.json
    limits: dict                 # limits/<cell>.json
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    readers: Dict[str, ModuleType] = field(default_factory=dict)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, limits and the
    readers of the per-layer metrics reported in it."""
    bench = benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_file = os.path.join(root, cfg_entry["file"])
    config = load_json(cfg_file)
    model_ref = load_module(cfg_file[:-len(".json")] + ".py",
                            "bench_model_" + w["config"].replace("-", "_")
                            .replace(".", "_"))
    mix = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH, "limits", name + ".json"))

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if reported(m)]
    per_layer = [m for m in bench["per_layer"] if reported(m)]
    readers = {m["name"]: load_module(
        os.path.join(BENCH, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"]) for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                model_ref=model_ref, mix=mix, limits=limits, end_to_end=e2e,
                per_layer=per_layer, readers=readers)


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
