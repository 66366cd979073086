"""Readers of the product's own intervals: the flight recorder's dumps of a
run, paired into intervals and put on the profiler trace's clock.

The product records ``<name>_begin`` / ``<name>_end`` events with an ``ident``
(the save ticket, a load number) and the ``parent`` interval's name
(``tpu_resiliency/telemetry/flight.py``: ``declare_interval``, ``span``), and a worker
started with ``TPURX_FLIGHT_DIR`` set dumps its ring when it exits.
``chipbench/run.py`` sets that to the run's own directory, so the dumps lie
beside ``readings.json``: ``chipbench/out/<config>.<traffic>.<seed>.t<trace>``.
The worker keeps none of the product's ``TraceAnnotation`` spans from the
profiler's host plane, so the dumps are the only way here.

Clocks.  A flight event carries ``mono_ns``, the clock of ``time.monotonic()``
and so of every stamp in the readings.  The trace's clock starts at the
profiler session.  The worker takes a stamp and then enters the span of its
own that the trace keeps (``saves[i].call`` then ``save.call``; an episode's
``freeze``, ``reenter``, ``restore_start`` then ``stall``, ``reenter``,
``restore``), so span start less stamp is the offset plus the entry of a
context manager or two, and never less than the offset: the smallest such
difference is taken.  ``clock_bracket`` bounds it from the other side with the
``hooks`` span that ends just before every ``step_ends`` stamp.

A reader returns None where there is no dump, and where the ring had already
dropped events of the window (a full ring whose oldest event is younger than
the window's opening): never a number from part of the window.
"""

import glob
import json
import os

from chipbench import cycles, trace_reduce
from chipbench.readers import BENCH
from chipbench.readers import episode as episode_readers

OUT = os.path.join(BENCH, "out")  # the tests point this at their data
META = "_flight_meta"
# (list in the readings, the stamp's key, the worker's span that follows it)
STAMPED_SPANS = (("saves", "call", "save.call"), ("episodes", "freeze", "stall"),
                 ("episodes", "reenter", "reenter"),
                 ("episodes", "restore_start", "restore"))


def run_dir(R):
    """The run's directory, as ``run.py`` names it, or None for readings
    that are no run's.  (A traced run's ``trace`` is the reduced trace: the
    worker puts it where the flag was.)"""
    if any(R.get(key) is None for key in ("config", "traffic", "seed", "trace")):
        return None
    traced = 1 if isinstance(R["trace"], dict) else int(R["trace"])
    return os.path.join(OUT, f"{R['config']}.{R['traffic']}.{R['seed']}.t{traced}")


# -- from dump files to intervals ---------------------------------------------

def read_dump(path):
    """(meta, events) of one dump; a torn last line is left out."""
    meta, events = None, []
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("event") == META:
                meta = rec
            elif "mono_ns" in rec and "event" in rec:
                events.append(rec)
    return meta, events


def load_processes(directory):
    """``{pid: {"events": [...], "covered_from_ns": n}}`` from every
    ``flight-*.jsonl`` of ``directory``.  A process may have left several
    dumps (one at each trip, one at its exit), each the ring as it then was:
    their events are merged, each once.  ``covered_from_ns`` is the time from
    which nothing is missing: a dump that is not full holds everything since
    the process started (-inf); a full one holds what is younger than its
    oldest event, and an earlier dump extends that only if it was taken
    after that oldest event."""
    dumps = {}
    for path in sorted(glob.glob(os.path.join(directory, "flight-*.jsonl"))):
        meta, events = read_dump(path)
        if meta is not None:
            dumps.setdefault(meta["pid"], []).append((meta, events))
    out = {}
    for pid, found in dumps.items():
        found.sort(key=lambda d: d[0]["mono_ns"], reverse=True)  # newest first
        covered, seen = None, {}
        for meta, events in found:
            if covered is not None and meta["mono_ns"] < covered:
                break  # a hole between this dump and the younger ones
            for ev in events:
                seen.setdefault((ev["mono_ns"], ev["event"], ev.get("ident")), ev)
            full = meta.get("events", 0) >= meta.get("capacity", 0) > 0
            oldest = min((ev["mono_ns"] for ev in events), default=meta["mono_ns"])
            covered = oldest if full else float("-inf")
            if not full:
                break
        out[pid] = {"events": sorted(seen.values(), key=lambda ev: ev["mono_ns"]),
                    "covered_from_ns": covered}
    return out


def pair_intervals(events):
    """``[{"name", "ident", "parent", "begin", "end"}]`` (seconds of the
    monotonic clock), by begin: each ``<name>_end`` closes the latest open
    ``<name>_begin`` of the same ident.  A begin that never ended is left out."""
    open_, out = {}, []
    for ev in events:
        if "ident" not in ev:
            continue
        name, _, edge = ev["event"].rpartition("_")
        key = (name, ev["ident"])
        if edge == "begin":
            open_.setdefault(key, []).append(ev)
        elif edge == "end" and open_.get(key):
            start = open_[key].pop()
            out.append({"name": name, "ident": ev["ident"],
                        "parent": start.get("parent"),
                        "begin": start["mono_ns"] / 1e9, "end": ev["mono_ns"] / 1e9})
    out.sort(key=lambda iv: iv["begin"])
    return out


def product_intervals(R):
    """The intervals of the run's processes that recorded checkpoint
    intervals (the worker), or None: no dump, or events of the window
    already dropped from the ring."""
    found, covered = [], float("-inf")
    directory = run_dir(R)
    for proc in load_processes(directory).values() if directory else ():
        intervals = pair_intervals(proc["events"])
        if any(iv["name"].startswith("ckpt.") for iv in intervals):
            found += intervals
            covered = max(covered, proc["covered_from_ns"])
    if not found or R.get("window_open") is None:
        return None
    if covered > R["window_open"] * 1e9:
        return None
    return sorted(found, key=lambda iv: iv["begin"])


def _seconds(iv):
    return iv["end"] - iv["begin"]


def named(intervals, name, ident=None):
    return [iv for iv in intervals if iv["name"] == name
            and (ident is None or iv["ident"] == ident)]


def coverage(intervals, parent):
    """Share of ``parent`` that its children (same ident, ``parent`` as their
    parent's name) cover."""
    children = [(iv["begin"], iv["end"]) for iv in intervals
                if iv["parent"] == parent["name"] and iv["ident"] == parent["ident"]]
    covered = trace_reduce.union(
        trace_reduce.clip(children, parent["begin"], parent["end"]))
    return sum(b - a for a, b in covered) / _seconds(parent)


# -- the window's saves and restores ------------------------------------------

def window_tickets(R):
    return [s["ticket"] for s in R.get("saves", []) if s["in_window"]]


def save_interval(R, interval, scale=1.0):
    """Median ``interval`` over the window's saves, times ``scale``."""
    intervals = product_intervals(R)
    if intervals is None:
        return None
    tickets = set(window_tickets(R))
    value = cycles.median(_seconds(iv) for iv in named(intervals, interval)
                          if iv["ident"] in tickets)
    return None if value is None else value * scale


def window_loads(R, intervals):
    """The ``ckpt.load`` of each of the window's whole episodes (those the
    profiler did not touch, where any are left): the one that began between
    the episode's ``restore_start`` and ``restore_end``."""
    loads = named(intervals, "ckpt.load")
    return [iv for e in episode_readers._episodes(R) for iv in loads
            if e["restore_start"] <= iv["begin"] <= e["restore_end"]]


def restore_interval(R, interval, scale=1.0):
    """Median per restore of the summed ``interval``, times ``scale``."""
    intervals = product_intervals(R)
    if intervals is None:
        return None
    sums = [sum(_seconds(iv) for iv in named(intervals, interval, load["ident"]))
            for load in window_loads(R, intervals)]
    value = cycles.median(sums)
    return None if value is None else value * scale


# -- onto the trace's clock ---------------------------------------------------

def _traced_stamps(R):
    """(stamp, span name) of what the profiler saw: the traced cycle's save,
    the traced episode."""
    inside = [s for s in R.get("saves", []) if s["in_window"]]
    traced = R.get("traced_cycles") or []
    rows = {"saves": inside[traced[0]:traced[0] + 1] if traced else [],
            "episodes": [e for e in R.get("episodes", []) if e.get("traced")][:1]}
    return [(row[key], span) for rows_of, key, span in STAMPED_SPANS
            for row in rows[rows_of] if row.get(key) is not None]


def clock_offset(R):
    """Seconds to add to a monotonic time to get the trace's: the smallest
    (span start - stamp) over the traced stamps, or None without a trace."""
    spans = (R.get("trace") or {}).get("spans") or []
    diffs = [found[0][0] - stamp for stamp, name in _traced_stamps(R)
             if (found := trace_reduce.span_intervals(spans, name))]
    return min(diffs) if diffs else None


def clock_bracket(R):
    """(lower, upper) bound of the offset: ``upper`` is ``clock_offset``;
    ``lower`` is the largest (end of a ``hooks`` span - the ``step_ends``
    stamp taken right after it).  Their distance is how far the pairing can
    be off."""
    upper = clock_offset(R)
    if upper is None:
        return None
    ends = sorted(b for _, b in trace_reduce.span_intervals(R["trace"]["spans"], "hooks"))
    lower = float("-inf")
    for stamp in R.get("step_ends", []):
        before = [b for b in ends if b <= stamp + upper]
        if before and stamp + upper - before[-1] < 0.01:  # this step's own span
            lower = max(lower, before[-1] - stamp)
    return lower, upper


def on_trace_clock(R, intervals):
    """``[[name, start_s, dur_s]]`` as ``trace_reduce`` takes spans."""
    offset = clock_offset(R)
    if offset is None or intervals is None:
        return None
    return [[iv["name"], iv["begin"] + offset, _seconds(iv)] for iv in intervals]


def post_save_gap(R, step_module):
    """The traced save's after-save gap on the trace's clock: from the end
    of its ``ckpt.save`` to the start of the next steady step on the device
    (the first execution of ``step_module`` after which the next follows
    within 1.25 of the period the steps kept before the save).  Returns
    ``{"lo", "hi", "idle_s", "idle_under_d2h_s"}`` or None."""
    intervals, offset = product_intervals(R), clock_offset(R)
    devices = list(((R.get("trace") or {}).get("devices") or {}).values())
    traced = [stamp for stamp, name in _traced_stamps(R) if name == "save.call"]
    if intervals is None or offset is None or not devices or not traced:
        return None
    ticket = next(s["ticket"] for s in R["saves"] if s["call"] == traced[0])
    saves, d2h = named(intervals, "ckpt.save", ticket), named(
        intervals, "ckpt.stage.d2h", ticket)
    waits = trace_reduce.span_intervals(R["trace"]["spans"], "save.wait_device")
    if not saves or not d2h or not waits:
        return None
    lo, ops = saves[0]["end"] + offset, devices[0]["ops"]
    runs = trace_reduce.module_runs(
        devices[0]["modules"], float("-inf"), float("inf"), step_module)
    before = [r[0] for r in runs if r[1] <= waits[0][0]]
    after = [r[0] for r in runs if r[0] >= lo]
    period = cycles.median(b - a for a, b in zip(before, before[1:]))
    if period is None or not after:
        return None
    hi = next((a for a, b in zip(after, after[1:]) if b - a <= 1.25 * period),
              after[-1])

    def idle(a, b):
        return max(0.0, b - a) - trace_reduce.busy_seconds(ops, a, b)

    a, b = max(lo, d2h[0]["begin"] + offset), min(hi, d2h[0]["end"] + offset)
    return {"lo": lo, "hi": hi, "idle_s": idle(lo, hi), "idle_under_d2h_s": idle(a, b)}


def post_save_stall_under_d2h_pct(R, step_module):
    """Share of the device's idle time in the after-save gap that lies
    inside the stager's ``ckpt.stage.d2h``."""
    gap = post_save_gap(R, step_module)
    if not gap or gap["idle_s"] <= 0:
        return None
    return 100.0 * gap["idle_under_d2h_s"] / gap["idle_s"]


# -- what PERF.md quotes: python3 -m chipbench.readers.spans <readings.json> ---

def summary(R, step_module="jit_step", top=3):
    intervals = product_intervals(R)
    if intervals is None:
        return None
    out = {"intervals": len(intervals), "clock_bracket": clock_bracket(R)}
    for root in ("ckpt.save", "ckpt.load", "ckpt.stage", "ckpt.drain"):
        found = named(intervals, root)
        if found:
            out[root] = {
                "n": len(found), "median_s": cycles.median(map(_seconds, found)),
                "coverage_min": min(coverage(intervals, iv) for iv in found)}
    out["save"] = {name: save_interval(R, name) for name in (
        "ckpt.save", "ckpt.save.prepare", "ckpt.save.snapshot",
        "ckpt.save.handoff", "ckpt.stage", "ckpt.stage.d2h", "ckpt.drain")}
    out["restore"] = {name: restore_interval(R, name) for name in (
        "ckpt.load", "ckpt.load.plan", "ckpt.load.start", "ckpt.load.wait",
        "ckpt.load.place", "ckpt.load.release")}
    out["restore"]["loads"] = len(window_loads(R, intervals))
    out["post_save_gap"] = post_save_gap(R, step_module)
    spans = on_trace_clock(R, intervals)
    if spans:
        from chipbench.readers import trace as trace_readers

        # the longest idle gaps twice: named after the worker's own span and
        # after the (innermost) product interval that covers the gap's start
        window = trace_readers.traced_window(R)
        ops = next(iter(R["trace"]["devices"].values()))["ops"]
        by_worker = trace_reduce.idle_gaps(ops, *window, R["trace"]["spans"], top=top)
        by_product = trace_reduce.idle_gaps(ops, *window, spans, top=top)
        out["idle_gaps"] = [
            {"seconds": seconds, "worker_span": worker, "product_interval": product}
            for (worker, seconds), (product, _) in zip(by_worker, by_product)]
    return out


if __name__ == "__main__":
    import sys

    with open(sys.argv[1]) as f:
        readings = json.load(f)
    OUT = os.path.dirname(os.path.dirname(os.path.abspath(sys.argv[1])))
    print(json.dumps(summary(readings), indent=1))
