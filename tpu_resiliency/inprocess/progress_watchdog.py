"""Progress watchdog: liveness timestamps for the monitor process.

Capability parity with ``inprocess/progress_watchdog.py:49-196``: a hybrid of
manual ``ping()`` calls from the training loop and **automatic** timestamps
proving the interpreter's main thread still executes bytecode even when user
code doesn't ping.  The reference injects a C callback with
``Py_AddPendingCall``; the pending call runs on the main thread at a
bytecode boundary, so a GIL-holding C extension or a wedged device wait
stops the auto-timestamps (exactly the hangs we must catch), while a
merely-slow loop keeps them flowing.

The callback itself is PURE C (``native/pending_stamp.c``) when the native
build is available: the monitor thread's async restart raise is delivered by
the same eval-breaker event that runs pending calls, so a Python-level
callback frame reliably eats the raise and corrupts the trampoline's error
state.  A ctypes Python callback remains as the no-toolchain fallback, with
the raise swallowed defensively (the monitor re-raises on a backoff).

Timestamps are written to a multiprocessing shared value read by the
MonitorProcess (no queue: a wedged consumer must not block the producer).
"""

from __future__ import annotations

import ctypes
import multiprocessing as mp
import threading

from ..ops.quorum import StampTripwire, wall_time_s
from ..utils.logging import get_logger

log = get_logger("progress_watchdog")

_PENDING_CALLBACK = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)


class _StampRefs(ctypes.Structure):
    _fields_ = [("timestamp", ctypes.c_void_p), ("consumed", ctypes.c_void_p)]


_PINNED: list = []  # shared slots a queued C pending call may still touch


def _load_native_stamper():
    """Load the pure-C pending-call stamper via the shared build-on-demand
    loader (utils/native.py); None if the toolchain or loader can't deliver
    it (fallback: ctypes callback)."""
    from ..utils.native import load_native

    lib = load_native("libtpurx-pending.so")
    if lib is not None:
        # idempotent re-assignment: load_native caches the CDLL per process
        lib.tpurx_schedule_stamp.argtypes = [ctypes.c_void_p]
        lib.tpurx_schedule_stamp.restype = ctypes.c_int
    return lib


class ProgressWatchdog:
    def __init__(self, interval: float = 1.0, timestamp_slot=None):
        self.interval = interval
        # 'd' = double epoch seconds; lock-free single-writer.  An external
        # ``timestamp_slot`` (a ctypes double over named shm, from
        # MonitorSharedState) lets the exec'd monitor process read the
        # stamps without fork/pickling; default stays process-local.
        if timestamp_slot is not None:
            self.timestamp = timestamp_slot
            self.timestamp.value = wall_time_s()
        else:
            self.timestamp = mp.Value("d", wall_time_s(), lock=False)
        # event-driven liveness feed: every stamp (manual ping or a consumed
        # pending call) sets the event, so a StampTripwire can park on it
        # instead of polling ``age()`` — see :meth:`watch_stale`
        self.beat_event = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # keep the callback object alive (ctypes would GC it)
        self._cb = _PENDING_CALLBACK(self._pending_call)
        self._pending_scheduled = threading.Event()
        # pure-C path: shared consumption counter + pinned refs struct
        self._native = _load_native_stamper()
        if self._native is not None:
            self._consumed = mp.Value("l", 0, lock=False)
            self._refs = _StampRefs(
                ctypes.cast(ctypes.addressof(self.timestamp), ctypes.c_void_p),
                ctypes.cast(ctypes.addressof(self._consumed), ctypes.c_void_p),
            )
            self._last_consumed = 0
            self._native_inflight = False
            # a queued pending call outlives this object's GC: the pointed-to
            # memory must never be freed (bounded: one pin per watchdog)
            _PINNED.append((self.timestamp, self._consumed, self._refs))

    # -- main-thread proof-of-life ----------------------------------------

    def _pending_call(self, _arg) -> int:
        # Runs on the MAIN thread at a bytecode boundary.  The monitor
        # thread's async RankShouldRestart can land HERE (it targets the
        # main thread, and this callback runs on it): swallow anything —
        # an exception escaping a ctypes pending-call callback corrupts the
        # eval loop's error state (SystemError leaks into user code).  The
        # monitor re-raises on a backoff until the raise lands in user code.
        try:
            self.timestamp.value = wall_time_s()
            self.beat_event.set()
            self._pending_scheduled.clear()
        # tpurx: disable=TPURX009 -- ctypes pending-call callback: an escaping raise corrupts the eval loop error state
        except BaseException:  # noqa: BLE001
            pass
        return 0

    def _schedule_pending(self) -> None:
        if self._native is not None:
            cur = self._consumed.value
            if self._native_inflight and cur == self._last_consumed:
                return  # previous one not consumed — main thread busy/stuck
            self._last_consumed = cur
            self._native_inflight = True
            res = self._native.tpurx_schedule_stamp(ctypes.addressof(self._refs))
            if res != 0:  # queue full — fine, we try again next tick
                self._native_inflight = False
            return
        if self._pending_scheduled.is_set():
            return  # previous one not consumed yet — main thread busy/stuck
        self._pending_scheduled.set()
        res = ctypes.pythonapi.Py_AddPendingCall(self._cb, None)
        if res != 0:  # queue full — fine, we try again next tick
            self._pending_scheduled.clear()

    # -- API ---------------------------------------------------------------

    def ping(self) -> None:
        """Manual liveness signal from the training loop."""
        self.timestamp.value = wall_time_s()
        self.beat_event.set()

    def age(self) -> float:
        return wall_time_s() - self.timestamp.value

    def watch_stale(self, budget_s: float, on_stale) -> StampTripwire:
        """Event-driven GIL-liveness tripwire on this watchdog's stamps.

        Parks a :class:`~tpu_resiliency.ops.quorum.StampTripwire` on
        ``beat_event`` — the native pending-call stamper proves the MAIN
        thread still reaches bytecode boundaries, so a timeout here is the
        GIL-wedge class the native beater deliberately cannot see.  The
        waiter observes staleness at wake latency (no polling read of
        ``age()``); ``on_stale(age_ms)`` fires from the watcher thread.
        Caller owns ``.stop()``."""
        return StampTripwire(
            on_stale=on_stale,
            budget_ms=budget_s * 1e3,
            event=self.beat_event,
            age_ns_fn=lambda: max(0, int(self.age() * 1e9)),
        ).start()

    def start(self) -> "ProgressWatchdog":
        self.ping()
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="tpurx-progress-watchdog", daemon=True
        )
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            self._schedule_pending()

    def pause(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2)
            self._thread = None

    resume = start

    def stop(self) -> None:
        self.pause()
