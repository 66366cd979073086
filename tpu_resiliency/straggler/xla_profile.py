"""XLA-profile timer backend: per-op device durations from JAX traces.

The reference's CUPTI extension records per-kernel durations on every
detection section (``cupti_src/``); the XLA analog captures a JAX profiler
trace and aggregates the device-lane op events.  The emitted Chrome-trace
JSON is parsed with the stdlib (the xplane protobuf bindings in this image
are version-broken, and a hard dependency on them would be fragile anyway).

Profiling a step costs more than the reference's always-on CUPTI buffers
(trace start/stop ≈ tens of ms), so the collector is designed for **sampled**
capture — wrap one step every N report rounds:

    collector = XlaProfileCollector(detector.device)
    with collector.capture():
        step_fn(...)   # one profiled step
    # per-op durations now in the detector's device DurationStore ("xla:...")

Op-name durations feed the same relative/individual scoring as section and
callable timings — per-op granularity pinpoints WHICH op is slow on a
straggling rank (the CUPTI per-kernel capability).
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import shutil
import tempfile
from typing import Dict, List, Tuple

from ..utils.logging import get_logger
from .timers import DurationStore

log = get_logger("straggler.xla")


# A trace taken on an accelerator has one process per device
# ("/device:TPU:0") whose "XLA Ops" lane carries the ops; its other lanes
# ("XLA Modules", "Async XLA Ops", "TC Overlay") aggregate or overlap them.
# The host process of such a trace ("/host:CPU": the PJRT execute thread, the
# sync-flag poller, python) is runtime time, never op time (looked at on a
# v5e, jax 0.9.0: PERF.md, PR 21).
_DEVICE_PROCESS_PREFIX = "/device:"
_DEVICE_OP_LANE = "XLA Ops"

# On the CPU backend there is no device process and the ops run on the
# client's execution threads, next to runtime bookkeeping spans that are not
# op time.  "end: <op>" markers would double-count ops; executor/listener
# spans cover whole executions and would dilute per-op weighting;
# "XLA Modules"/"Steps" lane aggregates likewise.
_NON_OP_PREFIXES = ("end: ", "$")
_NON_OP_SUBSTRINGS = (
    "ThunkExecutor", "ThreadpoolListener", "ExecuteThunks", "BufferAllocations",
)
_NON_OP_LANE_SUBSTRINGS = ("python", "Steps", "XLA Modules", "tf_Compile", "Framework")


def _is_op_event(name: str, lane: str) -> bool:
    if any(s in lane for s in _NON_OP_LANE_SUBSTRINGS):
        return False
    if name.startswith(_NON_OP_PREFIXES):
        return False
    if any(s in name for s in _NON_OP_SUBSTRINGS):
        return False
    return True


def parse_trace_dir(trace_dir: str) -> Dict[str, List[float]]:
    """Aggregate op durations (seconds) from a profiler dump directory."""
    return parse_trace_events(trace_dir)[0]


def parse_trace_events(trace_dir: str) -> Tuple[Dict[str, List[float]], str]:
    """``(per_op_durations_s, source)`` from a profiler dump directory.

    Takes complete ('X') events keyed by op name.  ``source`` is "device"
    when the trace has accelerator processes — then only their "XLA Ops"
    lanes count — and "host" on the CPU backend, where the ops sit on the
    PjRt client's execution threads with runtime bookkeeping spans filtered
    (see ``_is_op_event``)."""
    out: Dict[str, List[float]] = {}
    source = "host"
    for path in glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    ):
        try:
            with gzip.open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as exc:
            log.warning("unparseable trace file %s: %s", path, exc)
            continue
        events = data.get("traceEvents", [])
        lanes: Dict[tuple, str] = {}
        device_pids = set()
        for e in events:
            if e.get("ph") != "M":
                continue
            label = e.get("args", {}).get("name", "")
            if e.get("name") == "thread_name":
                lanes[(e.get("pid"), e.get("tid"))] = label
            elif (e.get("name") == "process_name"
                  and label.startswith(_DEVICE_PROCESS_PREFIX)):
                device_pids.add(e.get("pid"))
        if device_pids:
            source = "device"
        for e in events:
            if e.get("ph") != "X" or not e.get("dur"):
                continue
            lane = lanes.get((e.get("pid"), e.get("tid")), "")
            name = e.get("name", "?")
            if device_pids:
                if e.get("pid") not in device_pids or lane != _DEVICE_OP_LANE:
                    continue
            elif not _is_op_event(name, lane):
                continue
            out.setdefault(name, []).append(float(e["dur"]) / 1e6)  # µs → s
    return out, source


class XlaProfileCollector:
    def __init__(self, store: DurationStore, prefix: str = "xla:", top_k: int = 64):
        self.store = store
        self.prefix = prefix
        self.top_k = top_k
        self.last_capture: Dict[str, List[float]] = {}
        self.last_source = ""  # "device" | "host" lanes of the last capture

    @contextlib.contextmanager
    def capture(self):
        """Profile the enclosed step(s); record per-op durations on exit."""
        import jax

        trace_dir = tempfile.mkdtemp(prefix="tpurx-xlaprof-")
        try:
            with jax.profiler.trace(trace_dir):
                yield
            per_op, self.last_source = parse_trace_events(trace_dir)
            # keep the top_k ops by total time: straggler scores weight by
            # total anyway, and unbounded op-name cardinality would bloat
            # every report
            ranked = sorted(
                per_op.items(), key=lambda kv: -sum(kv[1])
            )[: self.top_k]
            self.last_capture = dict(ranked)
            for name, durs in ranked:
                for d in durs:
                    self.store.record(self.prefix + name, d)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
