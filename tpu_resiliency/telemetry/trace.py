"""Profiling/flight JSONL → Chrome-trace / Perfetto JSON, cross-host aligned.

``utils/profiling.py`` records the restart pipeline as flat JSONL events
(``rendezvous_started`` … ``inprocess_restart_completed``) and
``telemetry/flight.py`` dumps the flight-recorder ring in the same shape.
This module pairs the start/end events into complete spans ("ph": "X") and
emits the Chrome trace-event format both ``chrome://tracing`` and Perfetto
load directly — one track (pid) per rank, unpaired events as instants,
fault-episode phases as spans connected across ranks by flow arrows.

CLI::

    python -m tpu_resiliency.telemetry.trace profiling.jsonl -o cycle.trace.json

Multiple input files merge (e.g. one JSONL per rank collected off a shared
mount); each record's ``rank`` (fallback: ``pid``) selects its track.

Timestamps are the recorder's ``mono_ns``.  Monotonic clocks are per-host
domains, so each file's ``_flight_meta`` header (written by both recorders)
carries the producing process's estimated offset to the job's reference
clock (``telemetry/clock.py``); :func:`load_aligned` applies it per file so
multi-host dumps land on ONE aligned timeline.  When two or more hosts
contribute files with no offset, their clocks cannot be related and a
stderr warning names them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import zlib
from typing import Any, Dict, Iterable, List, Optional, Tuple

META_EVENT = "_flight_meta"

# start event -> (end event, span name, category)
SPAN_PAIRS: Dict[str, Tuple[str, str, str]] = {
    "rendezvous_started": ("rendezvous_completed", "rendezvous", "fault_tolerance"),
    "worker_start_requested": ("worker_started", "worker_start", "fault_tolerance"),
    "worker_stop_requested": ("worker_stopped", "worker_stop", "fault_tolerance"),
    "checkpoint_save_started": (
        "checkpoint_save_finalized", "checkpoint_save", "checkpointing",
    ),
    "checkpoint_load_started": (
        "checkpoint_load_completed", "checkpoint_load", "checkpointing",
    ),
    "inprocess_restart_started": (
        "inprocess_restart_completed", "inprocess_restart", "inprocess",
    ),
    "health_check_started": ("health_check_completed", "health_check", "health"),
    # flight-recorder events (dotted namespace, see telemetry/flight.py)
    "monitor.section_begin": ("monitor.section_end", "section", "monitor"),
    "collective.dispatch": ("collective.settle", "collective", "collective"),
    "ckpt.drain_begin": ("ckpt.drain_end", "ckpt_drain", "checkpointing"),
    "ckpt.restore_begin": ("ckpt.restore_end", "ckpt_restore", "checkpointing"),
    # flight intervals (flight.interval): one save is ckpt.save (prepare,
    # snapshot, handoff) + ckpt.stage (d2h, d2h.first, and a fresh staging's
    # populate) + ckpt.drain, sharing the save ticket as ``ident``; one
    # restore is ckpt.load and its children, sharing a load number
    "ckpt.save_begin": ("ckpt.save_end", "ckpt.save", "checkpointing"),
    "ckpt.save.prepare_begin": (
        "ckpt.save.prepare_end", "ckpt.save.prepare", "checkpointing",
    ),
    "ckpt.save.snapshot_begin": (
        "ckpt.save.snapshot_end", "ckpt.save.snapshot", "checkpointing",
    ),
    "ckpt.save.handoff_begin": (
        "ckpt.save.handoff_end", "ckpt.save.handoff", "checkpointing",
    ),
    "ckpt.stage_begin": ("ckpt.stage_end", "ckpt.stage", "checkpointing"),
    "ckpt.stage.d2h_begin": (
        "ckpt.stage.d2h_end", "ckpt.stage.d2h", "checkpointing",
    ),
    "ckpt.stage.d2h.first_begin": (
        "ckpt.stage.d2h.first_end", "ckpt.stage.d2h.first", "checkpointing",
    ),
    "ckpt.stage.populate_begin": (
        "ckpt.stage.populate_end", "ckpt.stage.populate", "checkpointing",
    ),
    "ckpt.load_begin": ("ckpt.load_end", "ckpt.load", "checkpointing"),
    "ckpt.load.plan_begin": (
        "ckpt.load.plan_end", "ckpt.load.plan", "checkpointing",
    ),
    "ckpt.load.start_begin": (
        "ckpt.load.start_end", "ckpt.load.start", "checkpointing",
    ),
    "ckpt.load.wait_begin": (
        "ckpt.load.wait_end", "ckpt.load.wait", "checkpointing",
    ),
    "ckpt.load.place_begin": (
        "ckpt.load.place_end", "ckpt.load.place", "checkpointing",
    ),
    "ckpt.load.release_begin": (
        "ckpt.load.release_end", "ckpt.load.release", "checkpointing",
    ),
    # the monitor thread's coalescing window; ident = the wrapper iteration
    "inproc.coalesce_begin": (
        "inproc.coalesce_end", "inproc.coalesce", "inprocess",
    ),
    # predict-and-evacuate: risk crossing → replacement's warm join is
    # the planned-handoff MTTR span (evac.ckpt_ahead / evac.promote
    # render as instants inside it)
    "evac.risk_cross": ("evac.join", "evacuation", "evac"),
}
# from the trip to the wrapped fn's re-entry (docs/observability.md): the
# monitor thread's abort, the two dumps, the raise's delivery, and the main
# thread's restart path with its eight phases; ident = the faulted iteration
# (a dump's: its sequence number)
for _name, _cat in (
    ("inproc.abort", "inprocess"),
    ("inproc.abort.on_trip", "inprocess"),
    ("inproc.abort.ladder", "inprocess"),
    ("inproc.abort.stage", "inprocess"),
    ("inproc.raise", "inprocess"),
    ("inproc.restart", "inprocess"),
    ("inproc.restart.abort_wait", "inprocess"),
    ("inproc.restart.finalize", "inprocess"),
    ("inproc.restart.health_check", "inprocess"),
    ("inproc.restart.iteration_barrier", "inprocess"),
    ("inproc.restart.reassign", "inprocess"),
    ("inproc.restart.collect", "inprocess"),
    ("inproc.restart.rearm", "inprocess"),
    ("inproc.restart.initialize", "inprocess"),
    ("flight.dump.capture", "flight"),
    ("flight.dump.write", "flight"),
    ("flight.dump.hooks", "flight"),
):
    SPAN_PAIRS[f"{_name}_begin"] = (f"{_name}_end", _name, _cat)
_END_TO_START = {end: start for start, (end, _, _) in SPAN_PAIRS.items()}

INSTANT_CATEGORIES = {
    "failure_detected": "fault_tolerance",
    "hang_detected": "fault_tolerance",
    "straggler_detected": "straggler",
    "inprocess_interrupted": "inprocess",
    "health_failure": "health",
    "node_exclude_requested": "health",
    "worker_started": "fault_tolerance",  # only when its start was never seen
}

_META_KEYS = ("ts", "mono_ns", "event", "pid")

# fault-episode phase events become per-rank phase spans + cross-rank flows
_EP_BEGIN, _EP_PHASE, _EP_CLOSE = (
    "episode.begin", "episode.phase", "episode.close",
)


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail line from a killed process
            if isinstance(rec, dict) and "event" in rec and "mono_ns" in rec:
                events.append(rec)
    return events


def load_aligned(
    paths: List[str], warn: bool = True
) -> List[Dict[str, Any]]:
    """Read files and shift each into the reference clock domain.

    A file's offset is the last ``clock_offset_ns`` among its meta records
    (re-emitted after calibration, so last wins).  Files without one stay
    unshifted — correct when they ARE the reference domain (rank 0 serves
    the reference and never calibrates); when files from two or more hosts
    all lack offsets, their relative placement is meaningless and the
    warning below names them.
    """
    all_events: List[Dict[str, Any]] = []
    host_aligned: Dict[str, bool] = {}
    for path in paths:
        events = read_jsonl(path)
        offset: Optional[int] = None
        host = None
        for rec in events:
            if rec["event"] != META_EVENT:
                continue
            host = rec.get("host") or host
            if rec.get("clock_offset_ns") is not None:
                offset = int(rec["clock_offset_ns"])
        host = host or os.path.basename(path)
        host_aligned[host] = host_aligned.get(host, False) or offset is not None
        for rec in events:
            if rec["event"] == META_EVENT:
                continue
            if offset:
                rec = dict(rec, mono_ns=int(rec["mono_ns"]) + offset)
            all_events.append(rec)
    unaligned = sorted(h for h, ok in host_aligned.items() if not ok)
    if warn and len(host_aligned) >= 2 and len(unaligned) >= 2:
        print(
            "warning: no clock offset recorded for hosts "
            f"{', '.join(unaligned)}; their tracks share no reference "
            "clock and only line up by accident (run "
            "telemetry.clock.calibrate, or expect skew)",
            file=sys.stderr,
        )
    return all_events


def _track(rec: Dict[str, Any]) -> int:
    rank = rec.get("rank")
    if rank is not None:
        return int(rank)
    return int(rec.get("pid", 0))


def _span_key(rec: Dict[str, Any], start_event: str) -> Tuple:
    # health checks of different names legitimately nest/overlap — keep them
    # on separate matching stacks; everything else matches LIFO per track
    if start_event == "health_check_started":
        return (start_event, rec.get("check", ""))
    if start_event == "monitor.section_begin":
        return (start_event, rec.get("section", ""))
    if start_event == "collective.dispatch":
        return (start_event, rec.get("op", ""), rec.get("axis", ""))
    if "ident" in rec:
        # flight intervals: two saves' drains overlap, and a begin on one
        # thread ends on another — the shared ident pairs them, not LIFO
        return (start_event, rec["ident"])
    return (start_event,)


def _flow_id(episode: str) -> int:
    return zlib.crc32(episode.encode()) or 1


def _episode_flows(
    anchors: Dict[str, List[Tuple[float, int]]],
) -> List[Dict[str, Any]]:
    """One flow per episode: arrow from the first rank that saw the fault
    (the detection instant) to every other rank's episode activity."""
    out: List[Dict[str, Any]] = []
    for episode, sightings in anchors.items():
        sightings.sort()
        first_per_track: Dict[int, float] = {}
        for ts, track in sightings:
            first_per_track.setdefault(track, ts)
        if len(first_per_track) < 2:
            continue
        ordered = sorted(first_per_track.items(), key=lambda kv: kv[1])
        fid = _flow_id(episode)
        (t0_track, t0_ts) = ordered[0]
        out.append({
            "name": "episode", "cat": "episode", "ph": "s", "id": fid,
            "ts": t0_ts, "pid": t0_track, "tid": 0,
            "args": {"episode": episode},
        })
        for i, (track, ts) in enumerate(ordered[1:], start=1):
            ph = "f" if i == len(ordered) - 1 else "t"
            ev = {
                "name": "episode", "cat": "episode", "ph": ph, "id": fid,
                "ts": ts, "pid": track, "tid": 0,
                "args": {"episode": episode},
            }
            if ph == "f":
                ev["bp"] = "e"
            out.append(ev)
    return out


def to_chrome_trace(events: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Pair start/end events into complete spans; returns the trace dict."""
    events = sorted(
        (r for r in events if r["event"] != META_EVENT),
        key=lambda r: r["mono_ns"],
    )
    if not events:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(r["mono_ns"] for r in events)
    out: List[Dict[str, Any]] = []
    tracks = set()
    # (track, span_key) -> stack of pending start records
    pending: Dict[Tuple, List[Dict[str, Any]]] = {}
    # (track, episode) -> (phase name, start ts_us) of the running phase
    ep_phase: Dict[Tuple[int, str], Tuple[str, float]] = {}
    # episode -> [(ts_us, track)] of every episode event sighting
    ep_anchors: Dict[str, List[Tuple[float, int]]] = {}

    def args_of(rec: Dict[str, Any]) -> Dict[str, Any]:
        return {k: v for k, v in rec.items() if k not in _META_KEYS}

    def end_phase(track: int, episode: str, ts_us: float) -> None:
        running = ep_phase.pop((track, episode), None)
        if running is not None:
            name, start_us = running
            out.append({
                "name": name, "cat": "episode", "ph": "X",
                "ts": start_us, "dur": max(0.0, ts_us - start_us),
                "pid": track, "tid": 0, "args": {"episode": episode},
            })

    for rec in events:
        event = rec["event"]
        track = _track(rec)
        tracks.add(track)
        ts_us = (rec["mono_ns"] - t0) / 1e3
        if event in (_EP_BEGIN, _EP_PHASE, _EP_CLOSE):
            episode = str(rec.get("episode", ""))
            ep_anchors.setdefault(episode, []).append((ts_us, track))
            if event == _EP_PHASE:
                phase = str(rec.get("phase", ""))
                running = ep_phase.get((track, episode))
                if running is not None and running[0] == phase:
                    continue
                end_phase(track, episode, ts_us)
                ep_phase[(track, episode)] = (phase, ts_us)
                continue
            if event == _EP_CLOSE:
                end_phase(track, episode, ts_us)
            # begin/close also render as instants below
        if event in SPAN_PAIRS:
            key = (track, _span_key(rec, event))
            pending.setdefault(key, []).append(rec)
            continue
        start_event = _END_TO_START.get(event)
        if start_event is not None:
            key = (track, _span_key(rec, start_event))
            stack = pending.get(key)
            if stack:
                start = stack.pop()
                _, name, cat = SPAN_PAIRS[start_event]
                out.append(
                    {
                        "name": name,
                        "cat": cat,
                        "ph": "X",
                        "ts": (start["mono_ns"] - t0) / 1e3,
                        "dur": (rec["mono_ns"] - start["mono_ns"]) / 1e3,
                        "pid": track,
                        "tid": 0,
                        "args": {**args_of(start), **args_of(rec)},
                    }
                )
                continue
            # end without a start (file truncated at the front): instant
        out.append(
            {
                "name": event,
                "cat": INSTANT_CATEGORIES.get(
                    event,
                    event.split(".", 1)[0] if "." in event else "events",
                ),
                "ph": "i",
                "s": "t",
                "ts": ts_us,
                "pid": track,
                "tid": 0,
                "args": args_of(rec),
            }
        )
    # dangling starts (crash before the end event): zero-length instants so
    # the abandoned phase is still visible on the timeline
    for (track, key), stack in pending.items():
        for start in stack:
            _, name, cat = SPAN_PAIRS[key[0]]
            out.append(
                {
                    "name": f"{name} (unfinished)",
                    "cat": cat,
                    "ph": "i",
                    "s": "t",
                    "ts": (start["mono_ns"] - t0) / 1e3,
                    "pid": track,
                    "tid": 0,
                    "args": args_of(start),
                }
            )
    # a phase still running at the end of the stream extends to the last
    # event — visible, and marked unfinished
    end_us = (events[-1]["mono_ns"] - t0) / 1e3
    for (track, episode), (name, start_us) in list(ep_phase.items()):
        out.append({
            "name": f"{name} (unfinished)", "cat": "episode", "ph": "X",
            "ts": start_us, "dur": max(0.0, end_us - start_us),
            "pid": track, "tid": 0, "args": {"episode": episode},
        })
    out.extend(_episode_flows(ep_anchors))
    for track in sorted(tracks):
        out.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": track,
                "args": {"name": f"rank {track}"},
            }
        )
    out.sort(key=lambda e: (e.get("ts", 0.0), e.get("ph") != "M"))
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def convert(paths: List[str], output: Optional[str] = None) -> Dict[str, Any]:
    trace = to_chrome_trace(load_aligned(paths))
    if output:
        with open(output, "w") as f:
            json.dump(trace, f)
    return trace


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tpu_resiliency.telemetry.trace",
        description="Convert ProfilingRecorder/flight-recorder JSONL into "
        "Chrome-trace JSON (open in chrome://tracing or ui.perfetto.dev)",
    )
    parser.add_argument("inputs", nargs="+", help="JSONL file(s), one per rank")
    parser.add_argument(
        "-o", "--output",
        help="output path (default: stdout)",
    )
    args = parser.parse_args(argv)
    trace = convert(args.inputs, args.output)
    n_spans = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
    n_flows = sum(
        1 for e in trace["traceEvents"] if e.get("ph") in ("s", "t", "f")
    )
    if args.output:
        print(
            f"wrote {args.output}: {n_spans} spans, {n_flows} flow events, "
            f"{len(trace['traceEvents'])} events",
            file=sys.stderr,
        )
    else:
        json.dump(trace, sys.stdout)
        print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
