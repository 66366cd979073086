"""The intervals between a trip and the wrapped function's re-entry
(``docs/observability.md``): one injected fault on the one-rank harness of
``tests/test_monitor_coalesce.py`` records each of them once, on the monitor
thread (``inproc.coalesce``, ``inproc.abort`` and its children, the two dumps'
``flight.dump.capture``, ``inproc.raise``) and on the main thread
(``inproc.restart`` and its eight children), with the faulted iteration as
ident; the dumps' ``flight.dump.write`` / ``hooks`` follow on the recorder's
writer thread, after the wrapped fn's re-entry.

No test here compares a duration with a constant but for a rung held at its
deadline, which is compared with that deadline.
"""

import threading
import time

import pytest

from tpu_resiliency.inprocess import Wrapper
from tpu_resiliency.inprocess import wrap as wrap_mod
from tpu_resiliency.inprocess.abort import (
    IV_LADDER,
    IV_STAGE,
    AbortLadder,
    AbortStage,
    ShrinkMeshStage,
)
from tpu_resiliency.inprocess.attribution import (
    Interruption,
    InterruptionRecord,
)
from tpu_resiliency.inprocess.monitor_thread import (
    IV_ABORT,
    IV_RAISE,
    MonitorThread,
)
from tpu_resiliency.inprocess.store_ops import InprocStore
from tpu_resiliency.store import StoreClient, StoreServer
from tpu_resiliency.telemetry import flight, get_registry
from tpu_resiliency.telemetry.clock import mono_ns

LONG = 30.0  # a last_call_wait no test here waits out
WITHIN = 20.0

RESTART_PHASES = ("abort_wait", "finalize", "health_check", "iteration_barrier",
                  "reassign", "collect", "rearm", "initialize")
# interval -> (parent, how many one episode records)
EXPECTED = {
    "inproc.coalesce": (None, 1),
    "inproc.abort": (None, 1),
    "inproc.abort.on_trip": ("inproc.abort", 1),
    "inproc.abort.ladder": ("inproc.abort", 1),
    # fingerprint and the abort= callable; shrink_mesh is gated off
    "inproc.abort.stage": ("inproc.abort.ladder", 2),
    "flight.dump.capture": (None, 2),  # monitor_trip, abort_ladder
    "flight.dump.write": (None, 2),  # the writer thread's, behind the re-entry
    "flight.dump.hooks": (None, 2),
    "inproc.raise": (None, 1),
    "inproc.restart": (None, 1),
    **{f"inproc.restart.{phase}": ("inproc.restart", 1)
       for phase in RESTART_PHASES},
}


def _fresh_ring(enabled=True):
    flight.configure(enabled=enabled, capacity=4096)
    flight.set_current_episode("")
    flight._last_dump_ns.clear()


@pytest.fixture(autouse=True)
def _ring_back_to_default():
    yield
    flight.configure()
    flight.set_current_episode("")
    flight._last_dump_ns.clear()


def _interval_events():
    """The ring's interval events (those with an ident), oldest first."""
    return [r for r in flight._records("test")
            if r["event"] != "_flight_meta" and "ident" in r]


def _paired(records):
    """``{name: [(begin_ns, end_ns, begin record)]}``, each end closing the
    latest open begin of its name and ident."""
    open_, out = {}, {}
    for rec in records:
        name, _, edge = rec["event"].rpartition("_")
        key = (name, rec["ident"])
        if edge == "begin":
            open_.setdefault(key, []).append(rec)
        else:
            assert edge == "end" and open_.get(key), f"end without begin: {rec}"
            start = open_[key].pop()
            assert start["parent"] == rec["parent"]
            out.setdefault(name, []).append((start["mono_ns"], rec["mono_ns"], start))
    assert not any(open_.values()), f"never ended: {open_}"
    return out


def _run_bytecode(seconds):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        sum(range(50))


def _recover_once(port, group, fault, tmp_path, monkeypatch, **plugins):
    """One fault at iteration 0 of a one-rank wrapper; returns the stamp
    taken at the first line of the wrapped fn's second entry."""
    monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))
    reentered = []

    def train(call_wrapper=None):
        if call_wrapper.iteration == 1:
            reentered.append(mono_ns())
            return "recovered"
        if fault == "exception":
            raise ValueError("injected fault")
        call_wrapper.ops.record_interruption(
            0, InterruptionRecord(rank=0, interruption=Interruption.QUORUM_STALE,
                                  origin_rank=0))
        _run_bytecode(WITHIN)
        return "never interrupted"

    wrapper = Wrapper(
        store_factory=lambda: StoreClient("127.0.0.1", port, timeout=10.0),
        group=group, soft_timeout=3600.0, hard_timeout=7200.0,
        enable_monitor_process=False, enable_sibling_monitor=False,
        last_call_wait=LONG, **plugins,
    )
    assert wrapper(train)() == "recovered"
    flight.flush()  # the two dumps' writes, if the writer has not got there yet
    (stamp,) = reentered
    return stamp


@pytest.fixture(scope="module")
def episode(tmp_path_factory):
    """The ring after one peer-signal fault, with every plugin slot of the
    restart path taken (as the benchmark's worker takes them)."""
    _fresh_ring()
    server = StoreServer(host="127.0.0.1", port=0).start_in_thread()
    monkeypatch = pytest.MonkeyPatch()
    phases_before = {phase: _phase_observed(phase) for phase in RESTART_PHASES}
    total_before = _observed("tpurx_restart_total_latency_ns")
    try:
        reentered_ns = _recover_once(
            server.port, "inner-ring-episode", "peer_record",
            tmp_path_factory.mktemp("dumps"), monkeypatch,
            initialize=lambda state: None, abort=lambda state: None,
            finalize=lambda state: None, health_check=lambda state: None)
        records = _interval_events()
    finally:
        monkeypatch.undo()
        server.stop()
    return {"records": records, "paired": _paired(records),
            "reentered_ns": reentered_ns, "phases_before": phases_before,
            "total_before": total_before}


def _observed(name, **labels):
    """(count, sum) of a histogram's row."""
    rows = get_registry().snapshot().get(name, {}).get("samples", [])
    mine = [row for row in rows if row["labels"] == labels]
    return sum(row["count"] for row in mine), sum(row["sum"] for row in mine)


def _phase_observed(phase):
    return _observed("tpurx_restart_phase_latency_ns", phase=phase)


def _phase_count(phase):
    return _phase_observed(phase)[0]


# ---- one fault, every interval once -------------------------------------------


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_one_fault_records_the_interval_with_its_ident_and_parent(episode, name):
    parent, count = EXPECTED[name]
    found = episode["paired"].get(name, [])
    assert len(found) == count, (name, found)
    for begin, end, rec in found:
        assert begin <= end
        assert rec["parent"] == parent
        if not name.startswith("flight.dump."):
            assert rec["ident"] == 0  # the faulted iteration
    if name.startswith("flight.dump."):
        # a dump's two share the dump's sequence number
        assert [rec["ident"] for _, _, rec in found] == [
            rec["ident"] for _, _, rec in episode["paired"]["flight.dump.write"]]
        assert [rec["reason"] for _, _, rec in found] == [
            "monitor_trip", "abort_ladder"]
    if name == "inproc.abort.stage":
        assert [rec["stage"] for _, _, rec in found] == ["fingerprint", "<lambda>"]


def test_nothing_else_is_recorded_and_an_episode_stays_under_48_events(episode):
    names = {r["event"].rpartition("_")[0] for r in episode["records"]}
    assert names == set(EXPECTED)
    assert len(episode["records"]) == 2 * sum(n for _, n in EXPECTED.values())
    assert len(episode["records"]) <= 48


def test_the_monitor_threads_intervals_follow_one_another(episode):
    paired = episode["paired"]
    (_, coalesce_end, _), = paired["inproc.coalesce"]
    (abort_begin, abort_end, _), = paired["inproc.abort"]
    (on_trip_begin, on_trip_end, _), = paired["inproc.abort.on_trip"]
    (ladder_begin, ladder_end, _), = paired["inproc.abort.ladder"]
    (raise_begin, raise_end, _), = paired["inproc.raise"]
    (restart_begin, _, _), = paired["inproc.restart"]
    assert coalesce_end <= abort_begin <= on_trip_begin <= on_trip_end
    assert on_trip_end <= ladder_begin <= ladder_end <= abort_end <= raise_begin
    # the raise ends on the main thread, before it stamps the restart
    assert raise_begin <= raise_end <= restart_begin
    # the trip's capture lies in on_trip, the ladder's in the ladder, before
    # its rungs
    (trip_capture, ladder_capture) = paired["flight.dump.capture"]
    assert on_trip_begin <= trip_capture[0] <= trip_capture[1] <= on_trip_end
    assert ladder_begin <= ladder_capture[0] <= ladder_capture[1]
    stages = paired["inproc.abort.stage"]
    assert ladder_capture[1] <= stages[0][0] <= stages[0][1] <= stages[1][0]
    assert stages[1][1] <= ladder_end


def test_the_dumps_are_written_in_order_after_the_restart(episode):
    """No write inside ``inproc.abort`` nor before the restart's end: the
    monitor thread only captures."""
    paired = episode["paired"]
    (_, restart_end, _), = paired["inproc.restart"]
    (trip_write, ladder_write) = paired["flight.dump.write"]
    (trip_hooks, ladder_hooks) = paired["flight.dump.hooks"]
    assert restart_end <= trip_write[0] <= trip_write[1] <= trip_hooks[0]
    assert trip_hooks[1] <= ladder_write[0] <= ladder_write[1] <= ladder_hooks[0]


def test_the_restarts_eight_children_are_adjacent_in_order_and_cover_it(episode):
    paired = episode["paired"]
    (begin, end, _), = paired["inproc.restart"]
    children = [paired[f"inproc.restart.{phase}"][0] for phase in RESTART_PHASES]
    assert children[0][0] == begin and children[-1][1] == end
    for (_, left_end, _), (right_begin, _, _) in zip(children, children[1:]):
        assert left_end == right_begin  # one stamp ends one and begins the next
    covered = sum(c_end - c_begin for c_begin, c_end, _ in children)
    assert covered >= 0.99 * (end - begin)


def test_the_restart_ends_before_the_wrapped_fn_runs_again(episode):
    (_, end, _), = episode["paired"]["inproc.restart"]
    assert end <= episode["reentered_ns"]


@pytest.mark.parametrize("phase", RESTART_PHASES)
def test_each_child_is_one_observation_of_the_phase_histogram(episode, phase):
    """``collect``, ``rearm`` and ``initialize`` are label values of the
    histogram the first five already had, no new metric; and what the
    histogram observed is the interval's own length to the nanosecond: one
    stamp a boundary feeds both."""
    count, total = _phase_observed(phase)
    count_before, total_before = episode["phases_before"][phase]
    (begin, end, _), = episode["paired"][f"inproc.restart.{phase}"]
    assert count - count_before == 1
    assert total - total_before == end - begin


def test_the_restarts_end_is_the_total_latencys_stamp(episode):
    count, total = _observed("tpurx_restart_total_latency_ns")
    count_before, total_before = episode["total_before"]
    (begin, end, _), = episode["paired"]["inproc.restart"]
    assert count - count_before == 1
    assert total - total_before == end - begin


# ---- the other ways through ---------------------------------------------------


def test_an_exception_fault_records_the_restart_and_no_raise(
        store_server, tmp_path, monkeypatch):
    _fresh_ring()
    _recover_once(store_server.port, "inner-ring-exception", "exception",
                  tmp_path, monkeypatch)
    paired = _paired(_interval_events())
    assert len(paired["inproc.restart"]) == 1
    assert [len(paired[f"inproc.restart.{phase}"]) for phase in RESTART_PHASES] == [1] * 8
    assert len(paired["inproc.abort"]) == 1  # the monitor still runs the ladder
    assert IV_RAISE.name not in paired


def test_with_the_recorder_off_nothing_is_recorded_and_the_fault_is_absorbed(
        store_server, tmp_path, monkeypatch):
    _fresh_ring(enabled=False)
    before = _phase_count("collect")
    _recover_once(store_server.port, "inner-ring-off", "peer_record",
                  tmp_path, monkeypatch)
    assert len(flight.get_flight()) == 0
    assert list(tmp_path.iterdir()) == []
    assert _phase_count("collect") == before + 1  # the histogram needs no ring


def test_stopping_an_untripped_monitor_ends_no_raise(store):
    _fresh_ring()
    mon = MonitorThread(InprocStore(store, "inner-ring-untripped"), 0,
                        threading.get_ident(), [0], poll_interval=0.05).start()
    mon.stop()
    assert _interval_events() == []


class _Held(AbortStage):
    name = "held"
    timeout = 0.3

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()

    def release(self, state=None):
        self.gate.wait(timeout=60)


class _Quick(AbortStage):
    name = "quick"

    def release(self, state=None):
        return "done"


def test_a_rung_held_past_its_deadline_ends_there_and_the_next_follows():
    """The held rung's interval is as long as its deadline, the next rung's
    begins after it, a rung gated off records none; without a wrapper's
    state the ident is the ladder's own name and run number."""
    from tpu_resiliency.inprocess.state import State

    _fresh_ring()
    held = _Held()
    ladder = AbortLadder(held, _Quick(), ShrinkMeshStage(enabled=False))
    try:
        ladder(State(rank=0, world_size=1).freeze())
        degrade = AbortLadder(_Quick(), name="degrade")
        degrade(None)
        degrade(None)
    finally:
        held.gate.set()
    assert [r.outcome for r in ladder.last_results] == [
        "timed_out", "released", "skipped"]
    found = _paired(_interval_events())[IV_STAGE.name]
    assert [(rec["stage"], rec["ident"], rec["parent"]) for _, _, rec in found] == [
        ("held", 0, IV_LADDER.name), ("quick", 0, IV_LADDER.name),
        ("quick", "degrade.1", IV_LADDER.name),
        ("quick", "degrade.2", IV_LADDER.name)]
    (held_begin, held_end, _), (quick_begin, _, _) = found[:2]
    assert held.timeout * 1e9 <= held_end - held_begin < (held.timeout + 10.0) * 1e9
    assert held_end <= quick_begin


@pytest.mark.parametrize("throttled", [False, True])
def test_a_dump_records_its_capture_write_and_hooks_unless_throttled(
        tmp_path, monkeypatch, throttled):
    monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))
    _fresh_ring()
    seen = []
    flight.add_dump_hook(seen.append)
    try:
        if throttled:
            assert flight.dump("inner_ring_test") is not None
            before = len(flight.get_flight())
            assert flight.dump("inner_ring_test") is None  # inside 2 s
            assert len(flight.get_flight()) == before
            return
        path = flight.dump("inner_ring_test", min_interval_s=0.0)
    finally:
        flight.remove_dump_hook(seen.append)
    paired = _paired(_interval_events())
    (capture_begin, capture_end, capture), = paired["flight.dump.capture"]
    (write_begin, write_end, write), = paired["flight.dump.write"]
    (hooks_begin, hooks_end, hooks), = paired["flight.dump.hooks"]
    assert capture["ident"] == write["ident"] == hooks["ident"]
    assert isinstance(write["ident"], int)
    assert f"-{write['ident']:04d}-inner_ring_test.jsonl" in path
    assert capture["reason"] == write["reason"] == hooks["reason"] == "inner_ring_test"
    assert capture_begin <= capture_end <= write_begin <= write_end
    assert write_end <= hooks_begin <= hooks_end
    # the hook was fed the dump, which holds its own capture's begin and no end
    (records,) = seen
    own = [r["event"] for r in records if r.get("ident") == write["ident"]
           and r["event"].startswith("flight.dump.")]
    assert own == ["flight.dump.capture_begin"]


def test_the_new_intervals_are_declared_where_they_are_recorded():
    names = {iv.name for iv in flight.intervals()}
    assert set(EXPECTED) <= names
    assert wrap_mod.IV_RESTART.name == "inproc.restart"
    assert IV_ABORT.name == "inproc.abort"
    assert {iv.name for iv in wrap_mod._IV_PHASE.values()} == {
        f"inproc.restart.{phase}" for phase in RESTART_PHASES}
