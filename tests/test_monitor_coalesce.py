"""The coalescing window of ``MonitorThread`` is a deadline, not a sleep: it
closes as soon as every surviving rank is named in the iteration's
interruption log (a world of one: at the first read), and lasts
``last_call_wait`` while a live rank is still unnamed, so that a second fault
inside it lands in the same restart.

No test here reads a clock: a window that ought to close early is given
``last_call_wait=30`` and the wait for its trip less than that, and what
closed it is read from ``tpurx_monitor_coalesce_total{closed_by}``.
"""

import glob
import json
import threading
import time

import pytest

from tpu_resiliency.inprocess import Wrapper
from tpu_resiliency.inprocess.attribution import (
    Interruption,
    InterruptionRecord,
)
from tpu_resiliency.inprocess.exceptions import RankShouldRestart
from tpu_resiliency.inprocess.monitor_thread import (
    EV_TRIP,
    IV_COALESCE,
    MonitorThread,
    quiesce_with_retry,
)
from tpu_resiliency.inprocess.store_ops import InprocStore
from tpu_resiliency.store import StoreClient
from tpu_resiliency.telemetry import flight, get_registry

LONG = 30.0  # a last_call_wait no test here waits out
WITHIN = 20.0  # how long a test waits for a window that must close early


@pytest.fixture(autouse=True)
def _fresh_ring():
    flight.configure(enabled=True, capacity=4096)
    flight._last_dump_ns.clear()
    yield
    flight.configure()
    flight._last_dump_ns.clear()


@pytest.fixture()
def ops(store):
    return InprocStore(store, "coalesce-test")


def _closed():
    reg = get_registry()
    return {
        by: reg.value_of("tpurx_monitor_coalesce_total", {"closed_by": by})
        for by in ("all_named", "deadline")
    }


def _rose(before):
    return {by: n - before[by] for by, n in _closed().items()}


def _record(ops, rank, origin_rank=-1, kind=Interruption.EXCEPTION):
    ops.record_interruption(
        0, InterruptionRecord(rank=rank, interruption=kind, origin_rank=origin_rank)
    )


def _run_bytecode(seconds):
    """Every iteration is a bytecode boundary: a pending async raise lands
    here (and not inside ``time.sleep``)."""
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        sum(range(50))


def _ring(event):
    return [r for r in flight._records("test") if r["event"] == event]


def _monitor(ops, survivors, last_call_wait, aborted):
    """A monitor that raises into nobody (its wrapper has "caught" already):
    what a test waits for is ``abort_done``."""
    mon = MonitorThread(
        ops, 0, threading.get_ident(), survivors, abort_fn=aborted.set,
        last_call_wait=last_call_wait, poll_interval=0.05,
    )
    mon.mark_caught()
    return mon


def test_one_survivor_trips_at_the_first_read_and_raises(ops):
    """(a) a world of one: the record that woke the thread names the only
    survivor, so the ladder runs and the raise lands long before
    ``last_call_wait``."""
    before = _closed()
    aborted = threading.Event()
    mon = MonitorThread(
        ops, 0, threading.get_ident(), [0], abort_fn=aborted.set,
        last_call_wait=LONG, poll_interval=0.05,
    ).start()
    try:
        raised = False
        try:
            _record(ops, 0)
            _run_bytecode(WITHIN)
        except RankShouldRestart:
            raised = True
        quiesce_with_retry(mon)
        assert raised, "the window did not close before last_call_wait"
        assert aborted.is_set()
        assert _rose(before) == {"all_named": 1, "deadline": 0}
    finally:
        mon.stop()


@pytest.mark.parametrize("survivors", [[0, 1], [0, 1, 2]])
def test_an_unnamed_survivor_keeps_the_window_open_and_faults_coalesce(
    ops, survivors
):
    """(b) a live rank is not named: the window lasts ``last_call_wait``, and
    a second rank's record appended inside it is in the ONE trip's list
    (with three survivors; with two, the second rank stays silent)."""
    before = _closed()
    aborted = threading.Event()
    mon = _monitor(ops, survivors, 2.0, aborted).start()
    try:
        _record(ops, 0)
        if len(survivors) == 3:
            while not _ring(IV_COALESCE.begin_event):
                assert not mon.tripped.wait(0.01), "no window before the trip"
            _record(ops, 1, kind=Interruption.SOFT_TIMEOUT)
        assert mon.abort_done.wait(WITHIN) and aborted.is_set()
    finally:
        mon.stop()
    assert _rose(before) == {"all_named": 0, "deadline": 1}
    (trip,) = _ring(EV_TRIP)
    assert trip["iteration"] == 0
    assert trip["interruptions"] == (
        "0:exception,1:soft_timeout" if len(survivors) == 3 else "0:exception"
    )


@pytest.mark.parametrize(
    "records",
    [
        pytest.param([(0, -1), (1, -1)], id="rank"),
        # the sibling monitor's and the tripwire's form: rank 0 names rank 1
        pytest.param([(1, 0)], id="origin_rank"),
    ],
)
def test_two_survivors_both_named_before_the_wake_close_early(ops, records):
    """(c) every live rank has spoken — as a record's ``rank`` or as its
    ``origin_rank`` — so nothing is left to wait for."""
    before = _closed()
    aborted = threading.Event()
    for rank, origin_rank in records:
        _record(ops, rank, origin_rank)
    mon = _monitor(ops, [0, 1], LONG, aborted).start()
    try:
        assert mon.abort_done.wait(WITHIN) and aborted.is_set()
    finally:
        mon.stop()
    assert _rose(before) == {"all_named": 1, "deadline": 0}


def _one_rank_wrapper(store_server, group):
    return Wrapper(
        store_factory=lambda: StoreClient(
            "127.0.0.1", store_server.port, timeout=10.0),
        group=group, soft_timeout=3600.0, hard_timeout=7200.0,
        enable_monitor_process=False, enable_sibling_monitor=False,
        last_call_wait=LONG,
    )


@pytest.mark.parametrize("fault", ["exception", "peer_record"])
def test_one_rank_wrapper_recovers_without_waiting_out_the_window(
    store_server, fault
):
    """(d) the whole restart loop on one rank, from a local exception (the
    main thread waits for the monitor's ``abort_done``) and from a record
    some other party wrote (the async raise has to land)."""
    before = _closed()

    def train(call_wrapper=None):
        if call_wrapper.iteration == 0:
            if fault == "exception":
                raise ValueError("injected fault")
            call_wrapper.ops.record_interruption(
                0,
                InterruptionRecord(
                    rank=0, interruption=Interruption.QUORUM_STALE,
                    origin_rank=0),
            )
            _run_bytecode(WITHIN)
            return "never interrupted"
        return "recovered"

    wrapper = _one_rank_wrapper(store_server, f"coalesce-{fault}")
    assert wrapper(train)() == "recovered"
    assert _rose(before) == {"all_named": 1, "deadline": 0}


def test_the_trip_dump_holds_one_paired_coalesce_interval(
    store_server, tmp_path, monkeypatch
):
    """(e) the black box written at the trip shows the window: one
    ``inproc.coalesce_begin``/``_end`` with the iteration as ident."""
    monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))

    def train(call_wrapper=None):
        if call_wrapper.iteration == 0:
            raise ValueError("injected fault")
        return "recovered"

    assert _one_rank_wrapper(store_server, "coalesce-dump")(train)() == "recovered"
    flight.flush()  # captured at the trip, written behind the re-entry
    (dump,) = glob.glob(str(tmp_path / "flight-*-monitor_trip.jsonl"))
    records = [json.loads(line) for line in open(dump)]
    window = [
        (r["event"], r["ident"], r["parent"]) for r in records
        if r["event"].startswith("inproc.coalesce_")
    ]
    assert window == [
        ("inproc.coalesce_begin", 0, None), ("inproc.coalesce_end", 0, None)]
    events = [r["event"] for r in records]
    assert events.index("inproc.coalesce_end") < events.index("monitor.trip")
