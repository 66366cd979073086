"""Always-on fault-episode flight recorder: a black box for the hot seams.

Reference analog: the PyTorch/NCCL Flight Recorder consumed by NVRx's
``attribution/trace_analyzer/fr_attribution.py``, and the always-on
recorder argument of the observable-collectives line (PAPERS.md,
arxiv 2510.00991): a near-zero-cost ring of structured events whose dump
at fault time reconstructs what every participant was doing.

Design:

- **Preallocated ring, lock-free append.**  One slot store per event —
  ``ring[next(counter) & mask] = (mono_ns, name, episode, args)`` — no
  allocation beyond the slot tuple, no lock (the itertools counter is
  GIL-atomic).
- **``TPURX_FLIGHT=0`` no-op** — the module-level :func:`record` becomes
  a shared no-op, same discipline as the registry's ``TPURX_TELEMETRY=0``.
  Call sites must use attribute access (``flight.record(...)``), never
  ``from ... import record``, so :func:`configure` rebinds take effect.
- **Declared event names.**  Every event name is declared exactly once at
  module scope via :func:`declare_event` with a literal string and its
  positional field names — the same single-declaration discipline
  ``tests/test_repo_hygiene.py`` enforces for metric names.
- **Dumps are the product.**  :func:`dump` snapshots the ring to a JSONL
  file (records shaped like ``utils/profiling.py`` lines, so
  ``telemetry/trace.py`` merges both streams onto one timeline), stamps
  the per-host clock offset from ``telemetry/clock.py`` into the meta
  record, announces through the log funnel (the warning below travels the
  ``utils/log_funnel.py`` forwarder when installed), and feeds registered
  hooks — the in-process wrapper installs one that runs the attribution
  engine's ``trace_analyzer`` over the dump.

- **Intervals are event pairs.**  :func:`declare_interval` declares a
  ``<name>_begin`` / ``<name>_end`` pair; :func:`span` (one thread) or
  :func:`begin` / :func:`end` (begin on one thread, end on another; with
  ``at_ns`` a stamp the caller took, so that one reading of the clock can end
  an interval, begin the next and feed a histogram) record them with the
  ``ident`` every interval of one operation shares (a save ticket, a load
  number, the faulted wrapper iteration) and the ``parent`` interval's name.
  A begin with no end in a fault dump says where the process was stuck.  A
  dump past its throttle records three of its own, ``flight.dump.capture``,
  ``flight.dump.write`` and ``flight.dump.hooks``.
- **A dump is a capture and a write.**  The capture (throttle, sequence
  number, meta record, ``snapshot()`` of the ring's immutable tuples, the
  file's name) is always made on the calling thread, where the dump was asked
  for: nothing recorded later can enter it.  The write (the dicts, the JSON
  lines, the file, the retention, the funnel's line, the hooks) follows at
  once on the same thread for a process that is ending or answering
  (:func:`dump`), and for a process that is recovering
  (:func:`dump_deferred`: the trip path's two) on one daemon writer thread,
  once the wrapper has re-entered the wrapped fn (:func:`release_deferred`)
  or :data:`DEFERRED_WRITE_BOUND_S` has passed, in slices that give the
  interpreter lock up between them.  :func:`flush` lands what is queued.

Dump triggers wired across the repo: monitor trip and abort-ladder entry
(deferred), ``CollectiveTimeout``, a restart the wrapper gave up on, unhandled
wrapper exceptions, ``GET /flight`` on the metrics exporter, SIGUSR2, and —
only where ``TPURX_FLIGHT_DIR`` names a directory — process exit (reason
``exit``).
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import dataclasses
import itertools
import json
import os
import re
import signal
import socket
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from ..utils import env
from ..utils.logging import get_logger
from .clock import mono_ns, offset
from .registry import counter, histogram

log = get_logger("telemetry.flight")

# -- event-name registry -----------------------------------------------------

_EVENT_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
_EVENT_FIELDS: Dict[str, Tuple[str, ...]] = {}


def declare_event(name: str, *fields: str) -> str:
    """Register a flight-event name with its positional field names.

    Names are dotted (``subsystem.event``); the part before the first dot
    becomes the trace category.  One declaration per name, literal string,
    at module scope — enforced by ``tests/test_repo_hygiene.py``.
    """
    if not _EVENT_NAME_RE.match(name):
        raise ValueError(f"invalid flight event name {name!r}")
    if name in _EVENT_FIELDS:
        raise ValueError(f"flight event {name!r} declared twice")
    _EVENT_FIELDS[name] = tuple(fields)
    return name


def event_names() -> List[str]:
    return sorted(_EVENT_FIELDS)


def event_fields(name: str) -> Tuple[str, ...]:
    return _EVENT_FIELDS[name]


class Interval(NamedTuple):
    """A declared begin/end event pair; ``name`` is what the two share
    (``ckpt.save`` of ``ckpt.save_begin`` / ``ckpt.save_end``)."""

    name: str
    begin_event: str
    end_event: str


_INTERVALS: Dict[str, Interval] = {}


def declare_interval(begin_event: str, end_event: str, *fields: str) -> Interval:
    """Declare ``<name>_begin`` and ``<name>_end`` (literal names, once, at
    module scope, like :func:`declare_event`) as the interval :func:`span`,
    :func:`begin` and :func:`end` record.  Both events carry ``ident`` and
    ``parent`` first, then ``fields``."""
    name = begin_event[: -len("_begin")]
    if not begin_event.endswith("_begin") or end_event != f"{name}_end":
        raise ValueError(
            f"not a <name>_begin/<name>_end pair: {begin_event!r}, {end_event!r}"
        )
    declare_event(begin_event, "ident", "parent", *fields)
    declare_event(end_event, "ident", "parent", *fields)
    _INTERVALS[name] = Interval(name, begin_event, end_event)
    return _INTERVALS[name]


def intervals() -> List[Interval]:
    return [_INTERVALS[name] for name in sorted(_INTERVALS)]


EV_DUMP = declare_event("flight.dump", "reason")
# a dump past its throttle; ident = the dump's sequence number.  capture, on
# the thread that asked for it: the ring snapshotted and the meta record
# stamped (a dump holds its own capture_begin and no end: the next one does).
# write: the snapshot's encoding begun -> the file written and the stale
# dumps unlinked.  hooks: the loop over the dump hooks.  Both on the thread
# that asked where the dump is synchronous, on the writer thread and after
# the release where it is deferred
IV_DUMP_CAPTURE = declare_interval(
    "flight.dump.capture_begin", "flight.dump.capture_end", "reason"
)
IV_DUMP_WRITE = declare_interval(
    "flight.dump.write_begin", "flight.dump.write_end", "reason"
)
IV_DUMP_HOOKS = declare_interval(
    "flight.dump.hooks_begin", "flight.dump.hooks_end", "reason"
)
# mirror of every utils/profiling.py record, so the ring alone tells the
# restart-pipeline story even when no profiling sink file is configured
EV_PROFILING = declare_event("profiling.event", "name", "cycle")

# -- current-episode cell ----------------------------------------------------
# telemetry/episode.py owns the lifecycle; the cell lives here so the hot
# append can tag every event with the live episode id in one list index.

_EPISODE_CELL: List[str] = [""]


def set_current_episode(episode_id: str) -> None:
    _EPISODE_CELL[0] = episode_id or ""


def current_episode_id() -> str:
    return _EPISODE_CELL[0]


# -- the ring ----------------------------------------------------------------


class FlightRecorder:
    """Preallocated, overwrite-oldest event ring."""

    __slots__ = ("_ring", "_mask", "_counter", "capacity")

    def __init__(self, capacity: int):
        cap = 1
        while cap < max(2, capacity):
            cap <<= 1
        self.capacity = cap
        self._ring: List[Optional[tuple]] = [None] * cap
        self._mask = cap - 1
        self._counter = itertools.count()

    def record(self, name: str, *args: Any) -> None:
        # HOT PATH: one counter bump, one tuple, one slot store.  Under
        # concurrent appends two threads may claim distinct slots out of
        # order — fine, the dump sorts by timestamp.
        self._ring[next(self._counter) & self._mask] = (
            mono_ns(), name, _EPISODE_CELL[0], args,
        )

    def record_at(self, t_ns: int, name: str, *args: Any) -> None:
        """:meth:`record` under a ``mono_ns()`` stamp the caller took."""
        self._ring[next(self._counter) & self._mask] = (
            t_ns, name, _EPISODE_CELL[0], args,
        )

    def __len__(self) -> int:
        return sum(1 for slot in self._ring if slot is not None)

    def snapshot(self) -> List[tuple]:
        """Occupied slots, oldest first (torn slots racing an in-flight
        append are simply whichever tuple won the store — never invalid)."""
        slots = [s for s in self._ring if s is not None]
        slots.sort(key=lambda s: s[0])
        return slots


class _NoopRecorder:
    capacity = 0

    def record(self, name: str, *args: Any) -> None:
        return None

    def __len__(self) -> int:
        return 0

    def snapshot(self) -> List[tuple]:
        return []


NOOP = _NoopRecorder()

_recorder: Any = NOOP
_dump_lock = threading.Lock()
_dump_seq = itertools.count()
_dump_paths: List[str] = []       # files this process wrote, oldest first
_last_path: Optional[str] = None  # the newest capture's file, written or not
_last_dump_ns: Dict[str, int] = {}  # reason -> mono_ns of last dump
_DUMP_HOOKS: List[Callable[[List[dict]], None]] = []


def flight_enabled() -> bool:
    try:
        return bool(env.FLIGHT.get())
    except ValueError:
        return True


def _parent_name(parent: Optional[Interval]) -> Optional[str]:
    return None if parent is None else parent.name


def _record_edge(event: str, at_ns: Optional[int], *args: Any) -> None:
    if at_ns is None:
        record(event, *args)
    else:
        _recorder.record_at(at_ns, event, *args)


def _begin(
    iv: Interval, ident: Any, parent: Optional[Interval] = None, *extra: Any,
    at_ns: Optional[int] = None,
) -> None:
    _record_edge(iv.begin_event, at_ns, ident, _parent_name(parent), *extra)


def _end(
    iv: Interval, ident: Any, parent: Optional[Interval] = None, *extra: Any,
    at_ns: Optional[int] = None,
) -> None:
    _record_edge(iv.end_event, at_ns, ident, _parent_name(parent), *extra)


def _annotation(iv: Interval) -> Any:
    """An entered ``TraceAnnotation`` of the interval's name where jax is
    already loaded (never imported here), else None; the caller exits it."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    entered = profiler.TraceAnnotation(iv.name)
    entered.__enter__()
    return entered


class _Span:
    """One interval on one thread: the begin/end records and, where jax is
    already loaded, a ``TraceAnnotation`` of the same name, so an operator's
    own profiler capture shows the interval above the device's timeline."""

    __slots__ = ("_iv", "_args", "_annotation")

    def __init__(
        self, iv: Interval, ident: Any, parent: Optional[Interval] = None,
        *extra: Any,
    ):
        self._iv, self._args = iv, (ident, _parent_name(parent), *extra)
        self._annotation = None

    def __enter__(self) -> "_Span":
        record(self._iv.begin_event, *self._args)
        self._annotation = _annotation(self._iv)
        return self

    def __exit__(self, *exc: Any) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        record(self._iv.end_event, *self._args)


_NOOP_SPAN = contextlib.nullcontext()


def _noop_span(*_args: Any, **_kwargs: Any) -> Any:
    return _NOOP_SPAN


def _noop(*_args: Any, **_kwargs: Any) -> None:
    return None


def configure(
    enabled: Optional[bool] = None, capacity: Optional[int] = None
) -> None:
    """(Re)build the process recorder and rebind :func:`record`,
    :func:`span`, :func:`begin` and :func:`end`."""
    global _recorder, record, span, begin, end, annotation
    if enabled is None:
        enabled = flight_enabled()
    if capacity is None:
        capacity = env.FLIGHT_RING.get()
    _recorder = FlightRecorder(capacity) if enabled else NOOP
    record = _recorder.record
    # span(iv, ident, parent=None, *extra): context manager around an
    # interval of one thread; begin/end(iv, ident, parent=None, *extra,
    # at_ns=None): the same pair for an interval that starts on one thread and
    # ends on another, or whose edges share a stamp with their neighbours';
    # annotation(iv): the TraceAnnotation a span enters, for such an interval
    span = _Span if enabled else _noop_span
    begin, end = (_begin, _end) if enabled else (_noop, _noop)
    annotation = _annotation if enabled else _noop


def get_flight() -> Any:
    return _recorder


configure()


def _host() -> str:
    return socket.gethostname().split(".")[0]


def _meta(reason: str, events: Optional[int] = None) -> Dict[str, Any]:
    off = offset()
    meta: Dict[str, Any] = {
        "event": "_flight_meta",
        "mono_ns": mono_ns(),
        # wall stamp is deliberate: it names the dump for humans grepping
        # a fleet's dump dirs, never enters duration math
        "ts": time.time(),  # tpurx: disable=TPURX016 -- dump label, not a duration operand
        "host": _host(),
        "pid": os.getpid(),
        "rank": env.RANK.get(),
        "reason": reason,
        "episode": current_episode_id(),
        "events": len(_recorder) if events is None else events,
        "capacity": getattr(_recorder, "capacity", 0),
    }
    if off is not None:
        meta["clock_offset_ns"] = off.offset_ns
        meta["clock_rtt_ns"] = off.rtt_ns
        meta["clock_ref"] = off.ref
    return meta


def _snapshot(reason: str) -> Tuple[Dict[str, Any], List[tuple]]:
    """The ring as it stands and the meta record stamped right after: no
    event of the snapshot is younger than the meta's ``mono_ns``."""
    slots = _recorder.snapshot()
    return _meta(reason, len(slots)), slots


def _encode(
    meta: Dict[str, Any], slots: List[tuple], pause_s: float = 0.0
) -> Tuple[List[Dict[str, Any]], List[str]]:
    """``(records, lines)`` of a snapshot: a dict and a JSON line an event,
    the meta first.  With ``pause_s`` the thread sleeps that long after every
    ``_SLICE_EVENTS`` events, so whoever waits for the interpreter lock gets
    it after about a millisecond and not after the switch interval."""
    host, pid, rank = meta["host"], meta["pid"], meta["rank"]
    records = [meta]
    lines = [json.dumps(meta, default=repr)]
    for i, (t_ns, name, episode, args) in enumerate(slots, 1):
        rec: Dict[str, Any] = {
            "mono_ns": t_ns, "event": name, "host": host, "pid": pid,
            "rank": rank,
        }
        if episode:
            rec["episode"] = episode
        fields = _EVENT_FIELDS.get(name, ())
        for j, val in enumerate(args):
            rec[fields[j] if j < len(fields) else f"arg{j}"] = val
        records.append(rec)
        lines.append(json.dumps(rec, default=repr))
        if pause_s and i % _SLICE_EVENTS == 0:
            time.sleep(pause_s)
    return records, lines


def _records(reason: str) -> List[Dict[str, Any]]:
    return _encode(*_snapshot(reason))[0]


def render_jsonl(reason: str = "request") -> str:
    """The ring as JSONL text (the ``GET /flight`` body)."""
    return "\n".join(_encode(*_snapshot(reason))[1]) + "\n"


def add_dump_hook(hook: Callable[[List[dict]], None]) -> None:
    """Register a consumer fed every dump's parsed records (e.g. the
    attribution trace analyzer).  Hooks must never raise into the dump."""
    if hook not in _DUMP_HOOKS:
        _DUMP_HOOKS.append(hook)


def remove_dump_hook(hook: Callable[[List[dict]], None]) -> None:
    try:
        _DUMP_HOOKS.remove(hook)
    except ValueError:
        pass


# -- a dump: the capture, then the write ---------------------------------------

# How long a deferred capture waits for its restart's re-entry before the
# writer encodes it anyway (a restart that hangs, a ladder outside any
# episode).  Chosen against two readings: the slowest trip -> re-entry a cell
# has shown is 0.36 s (the fifth cell under a 112.8 MB executable, PR 37;
# 0.05-0.08 s in every cell since PR 41), so 2 s clears it more than five
# times over and a healthy restart is always released by its re-entry
# (`tpurx_flight_dump_released_total{by="bound"}` rising in a healthy job
# says this is too short); and `Wrapper`'s soft and hard timeouts default to
# 60 and 90 s, so the box is on disk long before `monitor_process` sends its
# SIGTERM, let alone its SIGKILL.  It is also the throttle's default interval:
# no reason has two captures waiting.
DEFERRED_WRITE_BOUND_S = 2.0
# the deferred encoding's slice: about a millisecond of work at the 13 us an
# event the chip's host takes, then a sleep long enough for a waiting thread
# to be scheduled and take the interpreter lock
_SLICE_EVENTS = 64
_SLICE_PAUSE_S = 0.0005

_DUMPS = counter(
    "tpurx_flight_dump_total",
    "Dumps past their throttle, by where the write ran (deferred: the writer"
    " thread, behind the restart; sync: the thread that asked)",
    labels=("path",),
)
_DUMP_LAND_NS = histogram(
    "tpurx_flight_dump_land_ns",
    "A dump's capture to its file closed: how long the evidence was in"
    " memory only",
)
_DUMP_RELEASED = counter(
    "tpurx_flight_dump_released_total",
    "Deferred dumps, by what let the writer start: the wrapper re-entered"
    " the wrapped fn, the bound passed, or a flush (a synchronous dump, the"
    " exit)",
    labels=("by",),
)


@dataclasses.dataclass
class _Capture:
    """What a dump is made of, taken where it was asked for."""

    seq: int
    reason: str
    path: str
    meta: Dict[str, Any]
    slots: List[tuple]
    released: bool = False  # by the re-entry: the writer need not wait


def _capture(
    reason: str, path: Optional[str], min_interval_s: float
) -> Optional[_Capture]:
    """The synchronous half of every dump, None inside the throttle."""
    global _last_path
    if _recorder is NOOP:
        return None
    now = mono_ns()
    with _dump_lock:
        last = _last_dump_ns.get(reason)
        if (
            path is None and last is not None
            and now - last < min_interval_s * 1e9
        ):
            return None
        _last_dump_ns[reason] = now
    record(EV_DUMP, reason)
    seq = next(_dump_seq)
    with span(IV_DUMP_CAPTURE, seq, None, reason):
        meta, slots = _snapshot(reason)
        if path is None:
            path = os.path.join(
                env.FLIGHT_DIR.get() or tempfile.gettempdir(),
                f"flight-{meta['host']}-{meta['pid']}-{seq:04d}-{reason}.jsonl",
            )
        _last_path = path
    return _Capture(seq, reason, path, meta, slots)


def _write(cap: _Capture, pause_s: float = 0.0) -> None:
    """The other half: encode, write, retire stale files, announce, feed the
    hooks.  Never raises."""
    seq, reason, path = cap.seq, cap.reason, cap.path
    try:
        with span(IV_DUMP_WRITE, seq, None, reason):
            records, lines = _encode(cap.meta, cap.slots, pause_s)
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w") as f:
                f.write("\n".join(lines) + "\n")
            _DUMP_LAND_NS.observe(mono_ns() - cap.meta["mono_ns"])
            with _dump_lock:
                _dump_paths.append(path)
                keep = max(1, env.FLIGHT_DUMP_KEEP.get())
                stale, _dump_paths[:] = (
                    _dump_paths[:-keep], _dump_paths[-keep:]
                )
            for old in stale:
                try:
                    os.unlink(old)
                except OSError:
                    pass
        # the funnel-forwarded announcement: one line through the root
        # logger so the node's RootLogServer archive names every dump
        log.warning(
            "flight dump (%s): %s (%d events, episode=%s)",
            reason, path, len(records) - 1, cap.meta["episode"] or "-",
        )
        with span(IV_DUMP_HOOKS, seq, None, reason):
            for hook in list(_DUMP_HOOKS):
                try:
                    hook(records)
                except Exception:  # noqa: BLE001 - hooks never worsen a fault
                    log.exception("flight dump hook failed")
    except Exception:  # noqa: BLE001 - dumping must never worsen a fault
        log.exception("flight dump (%s) failed", reason)


class _DumpWriter:
    """The deferred captures in order, and the one daemon thread that writes
    them behind the restart that made them.  A capture is due once it is
    released (:meth:`release`: the re-entry) or ``bound_s`` old."""

    def __init__(self, bound_s: float = DEFERRED_WRITE_BOUND_S):
        self._bound_ns = int(bound_s * 1e9)
        self._cond = threading.Condition()  # guards _queue and _thread
        self._queue: collections.deque = collections.deque()
        # held around one capture's take-and-write, by the thread or by a
        # flush: files land in sequence order.  Reentrant for SIGUSR2's
        # handler, which may find the main thread inside a flush
        self._write_lock = threading.RLock()
        self._thread: Optional[threading.Thread] = None

    def submit(self, cap: _Capture) -> None:
        with self._cond:
            self._queue.append(cap)
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="tpurx-flight-writer", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()

    def release(self) -> None:
        """Everything queued may be written now."""
        with self._cond:
            for cap in self._queue:
                cap.released = True
            self._cond.notify_all()

    def _wait_s(self, now_ns: int) -> Optional[float]:
        """Seconds until the head capture is due: 0 now, None with none."""
        if not self._queue:
            return None
        head = self._queue[0]
        if head.released:
            return 0.0
        return max(0.0, (head.meta["mono_ns"] + self._bound_ns - now_ns) / 1e9)

    def write_next(self, now_ns: int, pause_s: float = 0.0) -> bool:
        """Write the head capture if it is due at ``now_ns``; whether one was
        written."""
        with self._write_lock:
            with self._cond:
                if self._wait_s(now_ns) != 0.0:
                    return False
                cap = self._queue.popleft()
            _DUMP_RELEASED.labels("reentry" if cap.released else "bound").inc()
            _write(cap, pause_s)
            return True

    def flush(self) -> None:
        """Write everything queued on the calling thread, without the
        slices' pauses: a process that is ending does not wait for them."""
        with self._write_lock:
            while True:
                with self._cond:
                    try:
                        # one atomic take: SIGUSR2's handler may flush on
                        # this very thread between any two bytecodes
                        cap = self._queue.popleft()
                    except IndexError:
                        return
                _DUMP_RELEASED.labels("reentry" if cap.released else "flush").inc()
                _write(cap)

    def _run(self) -> None:
        while True:
            with self._cond:
                wait_s = self._wait_s(mono_ns())
                if wait_s != 0.0:
                    self._cond.wait(wait_s)
                    continue
            self.write_next(mono_ns(), _SLICE_PAUSE_S)


_writer = _DumpWriter()


def dump(
    reason: str, path: Optional[str] = None, min_interval_s: float = 2.0
) -> Optional[str]:
    """Write the ring to a JSONL black-box file, on this thread, after
    whatever :func:`dump_deferred` left queued; returns the path.

    Per-reason throttled (``min_interval_s``) so a trip→ladder→timeout
    cascade produces one dump per distinct trigger, not one per retry.
    Never raises: a dump failing must not worsen the fault being dumped.
    """
    try:
        cap = _capture(reason, path, min_interval_s)
        if cap is None:
            return None
        _DUMPS.labels("sync").inc()
        _writer.flush()
        _write(cap)
        return cap.path
    except Exception:  # noqa: BLE001 - dumping must never worsen a fault
        log.exception("flight dump (%s) failed", reason)
        return None


def dump_deferred(reason: str, min_interval_s: float = 2.0) -> Optional[str]:
    """:func:`dump` for a thread on a restart's critical path: the capture
    here and now, the write on the writer thread once :func:`release_deferred`
    says the restart is over or ``DEFERRED_WRITE_BOUND_S`` has passed.
    Returns the path the file will have.  A kill inside that window loses the
    box; everything that ends the process in order flushes it."""
    try:
        cap = _capture(reason, None, min_interval_s)
        if cap is None:
            return None
        _DUMPS.labels("deferred").inc()
        _writer.submit(cap)
        return cap.path
    except Exception:  # noqa: BLE001 - dumping must never worsen a fault
        log.exception("flight dump (%s) failed", reason)
        return None


def release_deferred() -> None:
    """The restart is over (the wrapper is about to re-enter the wrapped fn):
    the writer may start on what :func:`dump_deferred` queued."""
    _writer.release()


def flush() -> None:
    """Land every queued capture, in sequence order, on this thread."""
    _writer.flush()


def last_dump_path() -> Optional[str]:
    """The newest dump's path: named at its capture, so a deferred dump's
    file may not be there yet (:func:`flush`)."""
    return _last_path


def _dump_at_exit() -> None:
    """One last black box with the whole ring — only where the operator
    named a directory for dumps: a job never sprays the temp directory."""
    flush()
    if env.FLIGHT_DIR.get():
        dump("exit", min_interval_s=0.0)


atexit.register(_dump_at_exit)

_signal_installed = False


def install_signal_handler() -> bool:
    """SIGUSR2 → dump.  Main-thread only (signal module constraint);
    returns whether the handler is installed."""
    global _signal_installed
    if _signal_installed:
        return True

    def _on_sigusr2(signum, frame):  # noqa: ARG001 - signal signature
        dump("sigusr2")

    try:
        signal.signal(signal.SIGUSR2, _on_sigusr2)
    except (ValueError, OSError):  # not the main thread / exotic platform
        return False
    _signal_installed = True
    return True
