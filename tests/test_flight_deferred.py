"""A dump on a restart's critical path is a capture and a deferred write
(``telemetry/flight.py``: ``dump_deferred``, ``release_deferred``, ``flush``).

No test here reads a wall clock.  The bound is what the writer's own
``write_next(now_ns)`` is told the time is; the release is a call; where the
writer thread itself has to be seen at work the test waits on an event one of
the dump's hooks sets, under a timeout no run should need.
"""

import glob
import json
import os
import signal
import threading

import pytest

from tpu_resiliency.inprocess import Wrapper
from tpu_resiliency.inprocess.attribution import (
    Interruption,
    InterruptionRecord,
)
from tpu_resiliency.inprocess.exceptions import HealthCheckError, RestartAbort
from tpu_resiliency.store import StoreClient
from tpu_resiliency.telemetry import flight, get_registry
from tpu_resiliency.telemetry.clock import mono_ns

EV_TEST = flight.declare_event("test.deferred_event", "k")

LONG = 30.0  # a last_call_wait no test here waits out
WITHIN = 20.0
NEVER_NS = int(3600e9)  # a bound no test's real clock reaches


@pytest.fixture(autouse=True)
def _fresh_ring_and_a_bound_out_of_reach(tmp_path, monkeypatch):
    """The process's own writer, its bound an hour: the thread writes what a
    test releases and nothing else, and ``write_next`` is told the time."""
    monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))
    flight.flush()
    monkeypatch.setattr(flight._writer, "_bound_ns", NEVER_NS)
    flight.configure(enabled=True, capacity=256)
    flight.set_current_episode("")
    flight._last_dump_ns.clear()
    yield
    flight.flush()
    flight.configure()
    flight.set_current_episode("")
    flight._last_dump_ns.clear()


def _counter(name, **labels):
    return get_registry().value_of(name, labels)


def _released():
    return {by: _counter("tpurx_flight_dump_released_total", by=by)
            for by in ("reentry", "bound", "flush")}


def _rose(before):
    return {by: int(now - before[by]) for by, now in _released().items()}


def _read(path):
    records = [json.loads(line) for line in open(path)]
    return records[0], records[1:]


def _sequence(path):
    return int(os.path.basename(path).split("-")[-2])


def _written(tmp_path):
    return sorted(glob.glob(str(tmp_path / "flight-*.jsonl")), key=_sequence)


def _encodings(monkeypatch):
    """The sequence numbers ``_write`` was called with, as they come."""
    seen, write = [], flight._write

    def spy(cap, pause_s=0.0):
        seen.append(cap.seq)
        return write(cap, pause_s)

    monkeypatch.setattr(flight, "_write", spy)
    return seen


# ---- (a) the file is the ring as of the capture -------------------------------


def test_a_deferred_dumps_file_holds_the_ring_as_it_stood_at_the_capture():
    for k in range(5):
        flight.record(EV_TEST, k)
    flight.set_current_episode("ep-at-capture")
    before = mono_ns()
    path = flight.dump_deferred("monitor_trip")
    after = mono_ns()
    assert path is not None and path.endswith("-monitor_trip.jsonl")
    assert flight.last_dump_path() == path  # named at the capture
    assert not os.path.exists(path)
    flight.set_current_episode("ep-later")
    for k in range(100, 103):
        flight.record(EV_TEST, k)  # after the capture, before the write
    flight.flush()
    meta, rest = _read(path)
    assert meta["reason"] == "monitor_trip" and meta["episode"] == "ep-at-capture"
    assert before <= meta["mono_ns"] <= after
    assert meta["events"] == len(rest)
    assert [r["k"] for r in rest if r["event"] == EV_TEST] == [0, 1, 2, 3, 4]
    assert all(r["mono_ns"] <= meta["mono_ns"] for r in rest)
    # its own capture had begun, and nothing of its write is in it
    own = [r["event"] for r in rest if r["event"].startswith("flight.dump.")]
    assert own == ["flight.dump.capture_begin"]
    assert f"-{_sequence(path):04d}-" in path
    assert [r["ident"] for r in rest if "ident" in r] == [_sequence(path)]


# ---- (b) nothing before the release, everything after; else the bound ---------


def test_the_writer_encodes_nothing_before_the_release_and_everything_after(
        tmp_path, monkeypatch):
    encoded = _encodings(monkeypatch)
    landed = threading.Event()
    hook = lambda records: (  # noqa: E731
        records[0]["reason"] == "abort_ladder" and landed.set())
    flight.record(EV_TEST, 1)
    before = _released()
    trip = flight.dump_deferred("monitor_trip")
    ladder = flight.dump_deferred("abort_ladder")
    captured_ns = flight._writer._queue[-1].meta["mono_ns"]
    # a nanosecond short of the first capture's bound nothing is due
    first_ns = flight._writer._queue[0].meta["mono_ns"]
    assert not flight._writer.write_next(first_ns + NEVER_NS - 1)
    assert encoded == [] and _written(tmp_path) == []
    assert _rose(before) == {"reentry": 0, "bound": 0, "flush": 0}
    flight.add_dump_hook(hook)
    try:
        flight.release_deferred()
        assert landed.wait(WITHIN), "the writer thread wrote nothing"
    finally:
        flight.remove_dump_hook(hook)
    assert encoded == [_sequence(trip), _sequence(ladder)]
    assert os.path.exists(trip) and os.path.exists(ladder)
    assert _rose(before) == {"reentry": 2, "bound": 0, "flush": 0}
    assert threading.current_thread().name != "tpurx-flight-writer"
    # what the thread recorded of its work: a write and a hooks pair a dump,
    # each begun after the release
    flight.flush()
    begun = {(r["event"], r["ident"]): r["mono_ns"] for r in flight._records("test")
             if r["event"].startswith("flight.dump.")}
    for path in (trip, ladder):
        assert begun[("flight.dump.write_begin", _sequence(path))] >= captured_ns
        assert ("flight.dump.hooks_end", _sequence(path)) in begun


def test_with_no_release_the_writer_encodes_once_the_bound_has_passed():
    flight.record(EV_TEST, 1)
    before = _released()
    trip = flight.dump_deferred("monitor_trip")
    ladder = flight.dump_deferred("abort_ladder")
    first, second = (cap.meta["mono_ns"] for cap in flight._writer._queue)
    writer = flight._writer
    assert not writer.write_next(first + NEVER_NS - 1)
    assert writer.write_next(first + NEVER_NS)  # the first is due, and only it
    assert os.path.exists(trip) and not os.path.exists(ladder)
    if second > first:
        assert not writer.write_next(first + NEVER_NS)
    assert writer.write_next(second + NEVER_NS)
    assert os.path.exists(ladder)
    assert not writer.write_next(second + 2 * NEVER_NS)  # nothing is left
    assert _rose(before) == {"reentry": 0, "bound": 2, "flush": 0}


def test_the_shipped_bound_clears_a_restart_and_stays_under_the_timeouts():
    """The constant's two sides (``flight.DEFERRED_WRITE_BOUND_S``'s comment):
    several times the slowest trip -> re-entry a cell has shown (0.36 s), and
    a small share of ``Wrapper``'s default soft timeout, after which
    ``monitor_process`` starts its SIGTERM / SIGKILL ladder."""
    import inspect

    soft = inspect.signature(Wrapper.__init__).parameters["soft_timeout"].default
    assert 5 * 0.36 <= flight.DEFERRED_WRITE_BOUND_S <= soft / 10
    assert flight._DumpWriter()._bound_ns == flight.DEFERRED_WRITE_BOUND_S * 1e9


# ---- (c) a flush, and every dump that ends or answers --------------------------


def _one_rank_wrapper(store_server, group, **plugins):
    return Wrapper(
        store_factory=lambda: StoreClient(
            "127.0.0.1", store_server.port, timeout=10.0),
        group=group, soft_timeout=3600.0, hard_timeout=7200.0,
        enable_monitor_process=False, enable_sibling_monitor=False,
        last_call_wait=LONG, **plugins)


def _faults_once(call_wrapper=None):
    if call_wrapper.iteration == 0:
        raise ValueError("injected fault")
    return "recovered"


def _by_exit(store_server, tmp_path):
    flight._dump_at_exit()
    return "exit"


def _by_path(store_server, tmp_path):
    assert flight.dump("asked", path=str(tmp_path / "flight-asked-0-9999-asked.jsonl"))
    return "asked"


def _by_sigusr2(store_server, tmp_path):
    previous = signal.getsignal(signal.SIGUSR2)
    installed = flight._signal_installed
    try:
        if flight.install_signal_handler():
            os.kill(os.getpid(), signal.SIGUSR2)  # handled on this thread, now
        else:  # not the main thread: what the handler would have called
            flight.dump("sigusr2")
    finally:
        if threading.current_thread() is threading.main_thread():
            signal.signal(signal.SIGUSR2, previous)
        flight._signal_installed = installed
    return "sigusr2"


def _a_restart_that_ends_in(store_server, terminal, **plugins):
    """A one-rank wrapper whose restart path gives up after the trip."""
    wrapper = _one_rank_wrapper(store_server, f"deferred-{terminal}", **plugins)
    with pytest.raises(RestartAbort if terminal == "restart_abort" else RuntimeError):
        wrapper(_faults_once)()
    return terminal


def _by_restart_abort(store_server, tmp_path):
    def unhealthy(state):
        raise HealthCheckError("injected: this rank is not fit to go on")

    return _a_restart_that_ends_in(store_server, "restart_abort",
                                   health_check=unhealthy)


def _by_wrapper_exception(store_server, tmp_path):
    def broken(state):
        raise RuntimeError("injected: the finalize plugin failed")

    return _a_restart_that_ends_in(store_server, "wrapper_exception",
                                   finalize=broken)


@pytest.mark.parametrize("ends", [
    _by_exit, _by_path, _by_sigusr2, _by_restart_abort, _by_wrapper_exception,
], ids=lambda ends: ends.__name__[len("_by_"):])
def test_a_dump_that_ends_or_answers_is_on_disk_with_all_before_it(
        ends, store_server, tmp_path):
    """The queued captures land first, in sequence order, then the dump that
    was asked for: all of it on this thread, before the call returns."""
    flight.record(EV_TEST, 1)
    fed = []
    hook = lambda records: fed.append(  # noqa: E731
        (records[0]["reason"], threading.current_thread().name))
    before = _released()
    flight.add_dump_hook(hook)
    try:
        if ends in (_by_exit, _by_path, _by_sigusr2):
            # the two a trip would have left; the wrappers' own trip leaves them
            flight.dump_deferred("monitor_trip")
            flight.dump_deferred("abort_ladder")
        last = ends(store_server, tmp_path)
    finally:
        flight.remove_dump_hook(hook)
    here = threading.current_thread().name
    assert fed == [("monitor_trip", here), ("abort_ladder", here), (last, here)]
    written = _written(tmp_path)
    assert [os.path.basename(p).split("-")[-1] for p in written] == [
        "monitor_trip.jsonl", "abort_ladder.jsonl", f"{last}.jsonl"]
    stamps = [_read(p)[0]["mono_ns"] for p in written]
    assert stamps == sorted(stamps)
    assert _rose(before) == {"reentry": 0, "bound": 0, "flush": 2}
    assert flight._writer._queue == type(flight._writer._queue)()


def test_flush_lands_every_queued_capture_in_sequence_order(tmp_path):
    flight.record(EV_TEST, 1)
    paths = [flight.dump_deferred(f"reason{i}") for i in range(5)]
    assert _written(tmp_path) == []
    total = _counter("tpurx_flight_dump_total", path="deferred")
    flight.flush()
    assert _written(tmp_path) == paths
    assert [_sequence(p) for p in paths] == sorted(_sequence(p) for p in paths)
    assert _counter("tpurx_flight_dump_total", path="deferred") == total
    flight.flush()  # nothing left: a no-op
    assert _written(tmp_path) == paths


def test_captures_releases_and_flushes_from_many_threads_lose_no_dump(
        tmp_path, monkeypatch):
    """More threads than cores under a short switch interval, each capturing,
    releasing and flushing: every capture is written exactly once."""
    import sys

    monkeypatch.setenv("TPURX_FLIGHT_DUMP_KEEP", "10000")
    threads, each = 4 * (os.cpu_count() or 4), 12
    fed, paths, failed = [], [], []

    def work(n):
        try:
            for i in range(each):
                flight.record(EV_TEST, i)
                paths.append(flight.dump_deferred(f"t{n}", min_interval_s=0.0))
                (flight.release_deferred, flight.flush, lambda: None)[i % 3]()
        except Exception as exc:  # noqa: BLE001 - reported below
            failed.append(exc)

    hook = lambda records: fed.append(records[0]["reason"])  # noqa: E731
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    flight.add_dump_hook(hook)
    try:
        workers = [threading.Thread(target=work, args=(n,)) for n in range(threads)]
        for worker in workers:
            worker.start()
        for worker in workers:
            worker.join(WITHIN * 3)
        assert not any(worker.is_alive() for worker in workers)
        flight.flush()
    finally:
        sys.setswitchinterval(interval)
        flight.remove_dump_hook(hook)
    assert failed == []
    assert all(paths) and len(set(paths)) == threads * each
    assert sorted(_written(tmp_path)) == sorted(paths)
    assert sorted(fed) == sorted(f"t{n}" for n in range(threads) for _ in range(each))


def test_the_two_counters_and_the_landing_histogram_tell_the_paths_apart():
    flight.record(EV_TEST, 1)
    deferred = _counter("tpurx_flight_dump_total", path="deferred")
    sync = _counter("tpurx_flight_dump_total", path="sync")
    rows = lambda: get_registry().snapshot()[  # noqa: E731
        "tpurx_flight_dump_land_ns"]["samples"]
    landed = sum(row["count"] for row in rows())
    flight.dump_deferred("monitor_trip")
    assert flight.dump_deferred("monitor_trip") is None  # throttled: not counted
    assert sum(row["count"] for row in rows()) == landed  # in memory only
    flight.dump("answer")
    assert _counter("tpurx_flight_dump_total", path="deferred") == deferred + 1
    assert _counter("tpurx_flight_dump_total", path="sync") == sync + 1
    assert sum(row["count"] for row in rows()) == landed + 2


# ---- (d) throttle, retention and a raising hook, on the deferred path ----------


def test_the_deferred_path_throttles_by_reason(tmp_path):
    flight.record(EV_TEST, 1)
    assert flight.dump_deferred("trip") is not None
    assert flight.dump_deferred("trip") is None          # throttled, same reason
    assert flight.dump("trip") is None                   # one throttle for both
    assert flight.dump_deferred("other") is not None     # distinct reason passes
    assert flight.dump_deferred("trip", min_interval_s=0.0) is not None
    flight.flush()
    assert len(_written(tmp_path)) == 3


def test_the_deferred_path_keeps_the_retention(tmp_path, monkeypatch):
    monkeypatch.setenv("TPURX_FLIGHT_DUMP_KEEP", "2")
    flight.record(EV_TEST, 1)
    paths = [flight.dump_deferred(f"keep{i}") for i in range(4)]
    assert all(paths) and flight.last_dump_path() == paths[3]
    flight.flush()
    assert [os.path.exists(p) for p in paths] == [False, False, True, True]


def test_a_raising_hook_breaks_no_deferred_dump_nor_the_one_after_it(tmp_path):
    flight.record(EV_TEST, 1)
    fed = []

    def bad_hook(records):
        fed.append(records[0]["reason"])
        raise RuntimeError("hook boom")

    flight.add_dump_hook(bad_hook)
    try:
        first = flight.dump_deferred("hooked")
        second = flight.dump_deferred("hooked_too")
        flight.flush()
    finally:
        flight.remove_dump_hook(bad_hook)
    assert fed == ["hooked", "hooked_too"]
    assert os.path.exists(first) and os.path.exists(second)


def test_with_the_recorder_off_nothing_is_captured_or_queued(tmp_path):
    flight.configure(enabled=False)
    assert flight.dump_deferred("monitor_trip") is None
    flight.release_deferred()
    flight.flush()
    assert _written(tmp_path) == [] and not flight._writer._queue


# ---- (e) through a one-rank wrapper ---------------------------------------------


def _run_bytecode(seconds):
    import time

    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        sum(range(50))


def test_a_recovery_captures_inside_the_abort_and_writes_after_the_re_entry(
        store_server, tmp_path):
    """A recorded interruption, absorbed: both files land, the iteration's
    ``inproc.abort`` holds a ``flight.dump.capture`` pair a dump and no
    ``flight.dump.write``, and the re-entry released both."""
    landed = threading.Event()
    hook = lambda records: (  # noqa: E731
        records[0]["reason"] == "abort_ladder" and landed.set())
    reentered = []

    def train(call_wrapper=None):
        if call_wrapper.iteration == 1:
            reentered.append(mono_ns())
            # the writer thread's own work, while fn runs on
            assert landed.wait(WITHIN), "the re-entry released nothing"
            return "recovered"
        call_wrapper.ops.record_interruption(
            0, InterruptionRecord(rank=0, interruption=Interruption.QUORUM_STALE,
                                  origin_rank=0))
        _run_bytecode(WITHIN)
        return "never interrupted"

    before = _released()
    flight.add_dump_hook(hook)
    try:
        assert _one_rank_wrapper(store_server, "deferred-e2e")(train)() == "recovered"
    finally:
        flight.remove_dump_hook(hook)
    flight.flush()
    assert _rose(before) == {"reentry": 2, "bound": 0, "flush": 0}
    written = _written(tmp_path)
    assert [os.path.basename(p).split("-")[-1] for p in written] == [
        "monitor_trip.jsonl", "abort_ladder.jsonl"]
    for path in written:
        meta, rest = _read(path)
        assert all(r["mono_ns"] <= meta["mono_ns"] <= reentered[0] for r in rest)
        assert not any(r["event"].startswith("flight.dump.write") for r in rest)
    ring = [r for r in flight._records("test") if "ident" in r]
    stamp = lambda event: next(  # noqa: E731
        r["mono_ns"] for r in ring if r["event"] == event)
    abort_begin, abort_end = stamp("inproc.abort_begin"), stamp("inproc.abort_end")
    inside = [r["event"] for r in ring if r["event"].startswith("flight.dump.")
              and abort_begin <= r["mono_ns"] <= abort_end]
    assert inside == ["flight.dump.capture_begin", "flight.dump.capture_end"] * 2
    # the release is the restart path's last act before fn
    writes = [r["mono_ns"] for r in ring if r["event"] == "flight.dump.write_begin"]
    assert len(writes) == 2 and all(stamp("inproc.restart_end") <= w for w in writes)
