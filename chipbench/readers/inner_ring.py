"""Readers of the inner ring's own intervals, from the trip to the wrapped
function's re-entry (``stall_inproc``): what ``abort_reenter_ms`` is made of.

The product records, with the faulted wrapper iteration as ``ident``
(``docs/observability.md`` has the table): on the monitor thread
``inproc.coalesce``, ``inproc.abort`` and under it ``inproc.abort.on_trip`` and
``inproc.abort.ladder`` with an ``inproc.abort.stage`` a rung that ran; a
``flight.dump.write`` and a ``flight.dump.hooks`` a dump, on the thread that
asked for it; ``inproc.raise`` from the monitor thread's first raise to the main
thread's catch; and on the main thread ``inproc.restart`` with eight children
that follow one another from one stamp each.  ``spans.py`` loads the dumps, pairs
the events and knows the clocks; this module says which intervals are an
episode's and adds them up.

An interval belongs to an episode when it begins between the episode's ``trip``
and ``reenter`` stamps (as ``spans.window_loads`` does it for ``ckpt.load``).
Every value is the median over the window's whole episodes the profiler did not
touch, where any are left; an episode that holds no whole ``inproc.restart`` is
left out.  A reader returns None where ``spans.product_intervals`` does (no
dump, or a ring that had dropped events of the window), and where no episode is
left: a program older than these intervals reads nothing, not zero.

Every function a metric's file names is ``@_guarded``: whatever it raises
becomes None and one line on stderr, because ``readers.read_metric`` and
``run.metrics_of`` catch nothing and one exception would cost the run its
whole result line.  Nothing here imports the product.
"""

import functools
import json
import math
import os
import sys

from chipbench import cycles, trace_reduce
from chipbench.readers import episode as episode_readers
from chipbench.readers import spans

RESTART = "inproc.restart"
RESTART_PHASES = tuple(f"{RESTART}.{phase}" for phase in (
    "abort_wait", "finalize", "health_check", "iteration_barrier", "reassign",
    "collect", "rearm", "initialize"))
COALESCE = "inproc.coalesce"
WAKE = "trip_to_wake"  # no interval: the trip stamp -> inproc.coalesce's begin
MONITOR_THREAD = (COALESCE, "inproc.abort", "inproc.abort.on_trip",
                  "inproc.abort.ladder", "inproc.abort.stage",
                  "flight.dump.write", "flight.dump.hooks", "inproc.raise")
# what trip -> reenter is split into, beside the wake before the first of
# them; on_trip and the ladder's dump overlap the dumps' own two: a union
LEAVES = (COALESCE, "inproc.abort.on_trip", "inproc.abort.stage",
          "flight.dump.write", "flight.dump.hooks", "inproc.raise",
          *RESTART_PHASES)
LABEL_FIELDS = ("stage", "reason")  # of a rung's interval, of a dump's


def _guarded(reader):
    """``reader(R, **args)`` as a metric's file may name it: a finite float
    or None, never a raise.  ``metric`` (the file's own name, among its
    ``args``) is what the line on stderr says failed."""
    @functools.wraps(reader)
    def read(R, metric=None, **args):
        try:
            value = reader(R, **args)
            if value is None:
                return None
            value = float(value)
            if not math.isfinite(value):
                raise ValueError(f"not a finite number: {value!r}")
            return value
        except Exception as exc:  # noqa: BLE001 - the result line is not at stake
            print(f"chipbench: {metric or reader.__name__}: {exc!r}",
                  file=sys.stderr, flush=True)
            return None
    return read


def _ms(iv):
    return (iv["end"] - iv["begin"]) * 1e3


_LAST = []  # [readings, their inner-ring intervals]: seven metrics read one run


def _inner_intervals(R):
    """The run's ``inproc.*`` and ``flight.dump.*`` intervals, or None; the
    dumps of a run (dozens of whole rings) are parsed once, not once a metric."""
    if not _LAST or _LAST[0] is not R:
        _LAST[:] = [R, None]  # a parse that raises is not made again either
        intervals = spans.product_intervals(R)
        _LAST[1] = intervals and [
            iv for iv in intervals
            if iv["name"].startswith(("inproc.", "flight.dump."))]
    return _LAST[1]


def episodes_of(R, all_episodes=False):
    """``[(episode, its intervals by begin)]`` over the window's whole
    episodes that have a ``trip`` and a ``reenter`` stamp and a whole
    ``inproc.restart`` between them, or None where there is none."""
    inner = _inner_intervals(R)
    if not inner:
        return None
    found = []
    for e in episode_readers._episodes(R, all_episodes):
        if e.get("trip") is None or e.get("reenter") is None:
            continue
        ivs = [iv for iv in inner if e["trip"] <= iv["begin"] <= e["reenter"]]
        if spans.named(ivs, RESTART):
            found.append((e, ivs))
    return found or None


def _median(R, per_episode):
    found = episodes_of(R)
    if found is None:
        return None
    values = [per_episode(e, ivs) for e, ivs in found]
    return cycles.median([v for v in values if v is not None])


@_guarded
def interval_ms(R, intervals, absent=None):
    """Median per episode of the named intervals' summed milliseconds; an
    episode with none of them reads ``absent`` (0 for the dumps: a dump its
    throttle held records nothing; else None: left out)."""
    def per_episode(e, ivs):
        mine = [_ms(iv) for iv in ivs if iv["name"] in intervals]
        return sum(mine) if mine else absent
    return _median(R, per_episode)


def _wake_ms(e, ivs):
    window = spans.named(ivs, COALESCE)
    return (window[0]["begin"] - e["trip"]) * 1e3 if window else None


@_guarded
def trip_to_wake_ms(R):
    """The episode's ``trip`` stamp (the tripwire, before it writes its
    interruption record) to the begin of ``inproc.coalesce``: the record
    reaching the monitor thread through the store."""
    return _median(R, _wake_ms)


def _leaves(e, ivs):
    """``[(name, begin, end)]`` of the episode's leaves, the wake included."""
    out = [(iv["name"], iv["begin"], iv["end"]) for iv in ivs
           if iv["name"] in LEAVES]
    window = spans.named(ivs, COALESCE)
    if window:
        out.append((WAKE, e["trip"], window[0]["begin"]))
    return out


def _unattributed_pct(e, ivs):
    lo, hi = e["trip"], e["reenter"]
    covered = trace_reduce.union(trace_reduce.clip(
        [(a, b) for _, a, b in _leaves(e, ivs)], lo, hi))
    return 100.0 * (1.0 - sum(b - a for a, b in covered) / (hi - lo))


@_guarded
def reenter_unattributed_pct(R):
    """Of ``trip`` -> ``reenter``, the share in none of ``LEAVES`` nor in the
    wake before them, as a union on one clock: whether the split is whole."""
    return _median(R, _unattributed_pct)


# -- what PERF.md quotes: python3 -m chipbench.readers.inner_ring <readings.json>

def _labels(R):
    """``{(name, ident, begin): stage or reason}`` from the begin events
    themselves: ``spans.pair_intervals`` keeps no field but the parent."""
    out = {}
    for proc in spans.load_processes(spans.run_dir(R)).values():
        for ev in proc["events"]:
            label = next((ev[f] for f in LABEL_FIELDS if f in ev), None)
            if label is not None and ev["event"].endswith("_begin"):
                out[(ev["event"][:-len("_begin")], ev.get("ident"),
                     ev["mono_ns"] / 1e9)] = label
    return out


def _labelled(iv, labels):
    label = labels.get((iv["name"], iv["ident"], iv["begin"]))
    return iv["name"] + (f"[{label}]" if label else "")


def _rows(e, ivs, labels):
    """The episode's intervals in time order (a parent before the child that
    begins on its stamp): thread, name, milliseconds after the trip,
    milliseconds long."""
    return [{
        "thread": ("main" if iv["name"].startswith(RESTART)
                   else "monitor->main" if iv["name"] == "inproc.raise"
                   else "monitor"),
        "interval": _labelled(iv, labels),
        "at_ms": (iv["begin"] - e["trip"]) * 1e3, "ms": _ms(iv)}
        for iv in sorted(ivs, key=lambda iv: (iv["begin"], -iv["end"]))]


def _coverage(ivs, name):
    parents = spans.named(ivs, name)
    return spans.coverage(ivs, parents[0]) if parents else None


def _overlap(a, b, lo, hi):
    return max(0.0, min(b, hi) - max(a, lo))


def _traced_gaps(R, labels, top):
    """The device's longest idle gaps between the traced episode's ``trip``
    and ``reenter`` on the trace's clock, each apportioned by overlap: the
    seconds of the gap under each of the episode's intervals (a parent holds
    its children's seconds too) and under none of its leaves.  ``worker_span``
    is what the ledger's ``breakdown`` calls the gap."""
    offset = spans.clock_offset(R)
    found = episodes_of(R, all_episodes=True)
    devices = list(((R.get("trace") or {}).get("devices") or {}).values())
    if offset is None or found is None or not devices:
        return None
    traced = [(e, ivs) for e, ivs in found if e.get("traced")]
    if not traced:
        return None
    e, ivs = traced[0]
    ops = devices[0]["ops"]
    lo, hi = e["trip"] + offset, e["reenter"] + offset
    busy = trace_reduce.union(trace_reduce.clip(
        ((op[2], op[2] + op[3]) for op in ops), lo, hi))
    edges = [lo, *[t for iv in busy for t in iv], hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])  # as trace_reduce.idle_gaps orders them
    by_worker = trace_reduce.idle_gaps(ops, lo, hi, R["trace"]["spans"], top=top)
    leaves = [(name, x + offset, y + offset) for name, x, y in _leaves(e, ivs)]
    pieces = [(_labelled(iv, labels), iv["begin"] + offset, iv["end"] + offset)
              for iv in ivs] + [leaf for leaf in leaves if leaf[0] == WAKE]
    out = []
    for (a, b), (worker, _) in zip(gaps[:top], by_worker):
        under = {}
        for name, x, y in pieces:
            seconds = _overlap(x, y, a, b)
            if seconds > 0:
                under[name] = under.get(name, 0.0) + seconds
        in_leaves = sum(y - x for x, y in trace_reduce.union(
            trace_reduce.clip([leaf[1:] for leaf in leaves], a, b)))
        out.append({"at_ms": (a - lo) * 1e3, "seconds": b - a,
                    "worker_span": worker, "under": under,
                    "under_no_leaf": (b - a) - in_leaves})
    return out


def summary(R, top=5):
    found = episodes_of(R)
    if found is None:
        return None
    labels = _labels(R)
    names = (*MONITOR_THREAD, RESTART, *RESTART_PHASES)
    episodes = [{
        "entry": e.get("entry"),
        "trip_to_reenter_ms": (e["reenter"] - e["trip"]) * 1e3,
        "trip_to_wake_ms": _wake_ms(e, ivs),
        "unattributed_pct": _unattributed_pct(e, ivs),
        "coverage": {name: _coverage(ivs, name)
                     for name in ("inproc.abort", RESTART)},
        "events": 2 * len(ivs),
        "intervals": _rows(e, ivs, labels)} for e, ivs in found]
    by_label = {}
    for episode in episodes:
        sums = {}
        for row in episode["intervals"]:
            if "[" in row["interval"]:
                sums[row["interval"]] = sums.get(row["interval"], 0.0) + row["ms"]
        for label, ms in sums.items():
            by_label.setdefault(label, []).append(ms)
    return {
        "episodes": episodes,
        "median_ms": {name: interval_ms(R, intervals=[name], absent=(
            0.0 if name.startswith("flight.dump.") else None)) for name in names},
        "median_stage_ms": {k: cycles.median(v) for k, v in sorted(by_label.items())},
        "trip_to_wake_ms": trip_to_wake_ms(R),
        "unattributed_pct": reenter_unattributed_pct(R),
        "coverage_min": {name: min((ep["coverage"][name] for ep in episodes
                                    if ep["coverage"][name] is not None),
                                   default=None)
                         for name in ("inproc.abort", RESTART)},
        "abort_reenter_ms": episode_readers.median_ms(R, "abort_reenter_s"),
        "traced_episode_idle_gaps": _traced_gaps(R, labels, top),
    }


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        readings = json.load(f)
    spans.OUT = os.path.dirname(os.path.dirname(os.path.abspath(sys.argv[1])))
    print(json.dumps(summary(readings), indent=1))
