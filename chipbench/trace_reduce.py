"""From a profiler trace to numbers: the benchmark's own reducer.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler wrote into plain
lists; everything after it is pure Python on those lists, checked in
``chipbench/tests`` on a small recorded trace.

What a TPU trace holds (looked at on a v5e, jax 0.9.0): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per executed
operation (stats ``hlo_module``, ``program_id``) and whose line
``XLA Modules`` carries one event per executed program; and ``/host:CPU``,
whose python thread's line carries the worker's ``TraceAnnotation`` spans.
All start times are nanoseconds of one clock.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]  # start, end (seconds)


def short_name(op: str) -> str:
    """``fusion.12`` of ``%fusion.12 = f32[...] fusion(...)``: a TPU trace
    names an operation by its whole HLO line."""
    return op.split(" = ", 1)[0].lstrip("%").strip()[:64]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, span_names: Iterable[str]) -> dict:
    """``{"devices": {plane: {"ops": [[name, module, start_s, dur_s], ...],
    "modules": [[name, start_s, dur_s], ...]}}, "spans": [[name, start_s,
    dur_s], ...]}`` with ``spans`` the host events named in ``span_names``."""
    from jax.profiler import ProfileData

    wanted = set(span_names)
    out = {"devices": {}, "spans": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = out["devices"].setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        module = ""
                        for key, value in ev.stats:
                            if key == "hlo_module":
                                module = str(value)
                                break
                        dev["ops"].append([short_name(ev.name), module,
                                           ev.start_ns / 1e9, ev.duration_ns / 1e9])
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        dev["modules"].append(
                            [ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        out["spans"].append(
                            [ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9])
    out["spans"].sort(key=lambda s: s[1])
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def busy_seconds(ops: Sequence[Sequence], lo: float, hi: float) -> float:
    """Seconds of [lo, hi) in which some operation ran on the device."""
    return sum(b - a for a, b in union(clip(
        ((op[2], op[2] + op[3]) for op in ops), lo, hi)))


def idle_gaps(ops: Sequence[Sequence], lo: float, hi: float,
              spans: Sequence[Sequence], top: int = 10) -> List[List]:
    """The longest idle gaps of [lo, hi), each named after the innermost
    (latest-started) host span that covers the gap's start, or ``"(none)"``
    where the worker was in no span of its own."""
    busy = union(clip(((op[2], op[2] + op[3]) for op in ops), lo, hi))
    edges = [lo, *[t for iv in busy for t in iv], hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for start, end in gaps[:top]:
        covering = [s for s in spans if s[1] <= start < s[1] + s[2]]
        name = max(covering, key=lambda s: s[1])[0] if covering else "(none)"
        named.append([name, end - start])
    return named


def op_seconds(ops: Sequence[Sequence], lo: float, hi: float,
               top: Optional[int] = 10) -> List[List]:
    """Device seconds by operation name inside [lo, hi), largest first."""
    total: Dict[str, float] = {}
    for name, _module, start, dur in ops:
        if lo <= start < hi:
            total[name] = total.get(name, 0.0) + dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in (ranked if top is None else ranked[:top])]


def module_runs(modules: Sequence[Sequence], lo: float, hi: float,
                contains: str) -> List[Interval]:
    """Executions inside [lo, hi) of the programs whose name has ``contains``."""
    return [(start, start + dur) for name, start, dur in modules
            if contains in name and lo <= start < hi]


def ops_within(ops: Sequence[Sequence], runs: Sequence[Interval]) -> List[float]:
    """Device seconds of the operations inside each of ``runs`` (busy union,
    so that overlapping lanes are not counted twice)."""
    return [busy_seconds(ops, a, b) for a, b in runs]


def span_intervals(spans: Sequence[Sequence], name: str) -> List[Interval]:
    return [(s[1], s[1] + s[2]) for s in spans if s[0] == name]
