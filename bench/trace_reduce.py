"""From a profiler trace of the window to per-layer numbers.

The trace (``jax.profiler``, an ``.xplane.pb``) holds, per chip, the line
"XLA Ops": one event per executed HLO instruction, named by the
instruction's text (``%fusion.12 = bf16[...] fusion(...)``), nested (a
``while`` event spans its body's events). The host plane's main-thread line
holds the benchmark's own spans, ``bench.stage`` and ``bench.dispatch``.

What each op did is read from the compiled program's HLO, not from the
trace: each instruction's ``op_name`` metadata is its JAX name stack, and an
instruction inside a called computation (a loop body, a fusion) inherits
the name stacks of the instructions that call it. The layers:

* ``local_train``: under the vmapped local trainer (``vmap(``), or under
  ``value_and_grad`` (``jvp(`` forward, ``transpose(`` backward): local SGD
  of every cohort member, its update and its delta;
* ``merge``: the rest of the round body (``while/body``): Top-K selection,
  the merge and its Mosaic custom calls, the server step;
* ``other``: outside the round body (the chunk program's prologue).

Device seconds are self times: an event's duration less its children's.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

LOCAL_MARKS = ("vmap(", "jvp(", "transpose(")
ROUND_MARK = "while/body"
SPANS = ("bench.stage", "bench.dispatch")
#: shorter gaps between adjacent ops are the trace's rounding, not idle time
MIN_GAP_NS = 1000

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:condition|body|calls|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_LABEL = re.compile(r"^%?([\w.\-]+) = (\(?[\w\[\],]+)\S* ([\w\-]+)\(")


# ------------------------------------------------------------------- HLO
def hlo_categories(hlo_text: str) -> Dict[str, str]:
    """{instruction name: layer} for every instruction of the module."""
    comp = None
    own: Dict[str, str] = {}           # instruction -> its op_name
    where: Dict[str, str] = {}         # instruction -> its computation
    callers: Dict[str, str] = {}       # computation -> calling instruction
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and " = " not in line.split("{")[0]:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        where[name] = comp
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        called = _CALLS.findall(line)
        for b in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in b.split(",")]
        for c in called:
            callers.setdefault(c, name)

    cache: Dict[str, str] = {}

    def stack(name: str) -> str:
        """The instruction's op_name with its callers' op_names prepended."""
        if name in cache:
            return cache[name]
        cache[name] = own.get(name, "")       # guards a cycle
        parent = callers.get(where.get(name, ""))
        full = (stack(parent) + " | " if parent else "") + own.get(name, "")
        cache[name] = full
        return full

    out = {}
    for name in own:
        s = stack(name)
        if any(k in s for k in LOCAL_MARKS):
            out[name] = "local_train"
        elif ROUND_MARK in s:
            out[name] = "merge"
        else:
            out[name] = "other"
    return out


def op_label(event_name: str) -> Tuple[str, str]:
    """(instruction name, short label) from an "XLA Ops" event's name."""
    m = _LABEL.match(event_name)
    if not m:
        name = event_name.lstrip("%").split(" ")[0]
        return name, name[:80]
    name, shape, opcode = m.groups()
    return name, f"{name} {opcode} {shape}"[:100]


# ----------------------------------------------------------------- trace
def load(trace_dir: str) -> dict:
    """The events of one trace: per chip, the "XLA Ops" events as (event
    name, start ns, end ns); the host's benchmark spans likewise."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    pd = ProfileData.from_file(files[0])
    chips, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips.append([(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:         # the main thread's line
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name in SPANS]
    return {"chips": chips, "host": sorted(host, key=lambda e: e[1])}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events: List[Tuple[str, float, float]]) -> List[float]:
    """Each event's duration less the durations of the events it contains
    (its direct children on the same line)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] - e[1] for e in events]
    stack: List[int] = []
    for i in order:
        s, e = events[i][1], events[i][2]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # averaged over chips
    layer_s: Dict[str, float]           # device self seconds, chip average
    ops: List[Tuple[str, float]]        # label -> seconds, largest first
    gaps: List[Tuple[str, float]]       # host span -> idle seconds

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.ops[:10]],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def reduce(trace: dict, categories: Dict[str, str]) -> Reduction:
    """The window is the host's first ``bench.stage`` start to its last
    ``bench.dispatch`` end; busy is the union of the chip's op intervals
    inside it; each idle gap of a microsecond or more is named by the
    benchmark span the host had open at its middle."""
    host = trace["host"]
    if not host or not trace["chips"]:
        raise RuntimeError(f"the trace holds {len(host)} benchmark spans "
                           f"and {len(trace['chips'])} chips")
    w0, w1 = host[0][1], max(e[2] for e in host)
    window_s = (w1 - w0) / 1e9
    busy, layers, ops, gaps = [], {}, {}, []
    n = len(trace["chips"])
    for events in trace["chips"]:
        busy_iv = [(max(a, w0), min(b, w1)) for _, a, b in events
                   if b > w0 and a < w1]
        merged = union(busy_iv)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for (name, _, _), t in zip(events, self_times(events)):
            instr, label = op_label(name)
            layer = categories.get(instr, "other")
            layers[layer] = layers.get(layer, 0.0) + t / 1e9 / n
            key = f"{layer}: {label}"
            ops[key] = ops.get(key, 0.0) + t / 1e9 / n
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= MIN_GAP_NS:
                mid = (a + b) / 2
                span = next((s for s, x, y in host if x <= mid < y), "none")
                gaps.append((span, (b - a) / 1e9))
    return Reduction(window_s=window_s, busy_s=sum(busy) / n,
                     layer_s=layers,
                     ops=sorted(ops.items(), key=lambda kv: -kv[1]),
                     gaps=sorted(gaps, key=lambda g: -g[1]))


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    reduction: Reduction
    rounds: int                         # FL rounds in the traced window
    peaks: dict                         # bench/peaks.json, this chip
    model_flops_per_round: float        # forward + backward, all clients
    merge_bytes_per_round: float        # least HBM bytes of one merge
