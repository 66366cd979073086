"""The restart path's last leg (``docs/inprocess.md``, "Collect, then
freeze"): ``gc.collect()`` still frees whatever cyclic garbage the dead
invocation of the wrapped fn left, device arrays included, before fn is
entered again, and ``gc.freeze()`` right after it moves the survivors to the
permanent generation, so the next restart's collection walks what was
allocated since and not the heap that outlives every restart.

One wrapper on the one-rank harness of ``tests/test_inner_ring_intervals.py``
takes three faults in one process: the first restart collects with nothing
frozen, the second and third behind a freeze.  Nothing here sleeps or reads a
clock.
"""

import gc
import threading
import weakref

import jax
import jax.numpy as jnp
import pytest

from tpu_resiliency.inprocess import Wrapper
from tpu_resiliency.inprocess.attribution import (
    Interruption,
    InterruptionRecord,
)
from tpu_resiliency.store import StoreClient, StoreServer
from tpu_resiliency.telemetry import flight, get_registry

LONG = 30.0  # a last_call_wait no test here waits out
FAULTS = ("peer_record", "exception")
RESTARTS = (1, 2, 3)
# a shape no other array of the process has: how a leaked buffer is found
SHAPE = (3, 41, 7)


class _Holder:
    """One node of a reference cycle that holds a device array: only the
    collector can free it."""

    def __init__(self):
        self.array = jnp.zeros(SHAPE, jnp.float32) + 1.0
        self.me = self


def _live_marked_arrays():
    return sum(1 for a in jax.live_arrays() if a.shape == SHAPE)


def _counter(name):
    return get_registry().value_of(name)


def _spin():
    # bytecode for the async raise to land in; bounded, and no clock read
    for _ in range(50_000_000):
        sum(range(50))
    return "never interrupted"


def _three_restarts(port, fault):
    """Four entries of one wrapped fn, a fault in each of the first three;
    returns what fn saw at each entry, before it allocated anything."""
    seen = []
    dead = {}  # weakrefs into the invocation that just died

    def train(call_wrapper=None):
        entry = call_wrapper.iteration
        # the first trip's two dumps are written behind its re-entry, beside
        # fn: here, so that the writer thread's allocations start no automatic
        # collection that finds this entry's cycle before the restart path's
        flight.flush()
        seen.append({
            "entry": entry,
            "holder_dead": dead.get("holder") is None or dead["holder"]() is None,
            "array_dead": dead.get("array") is None or dead["array"]() is None,
            "marked_arrays": _live_marked_arrays(),
            # generations 0-2: the permanent generation is not listed, so
            # this is what a full collection would walk from here
            "walkable": len(gc.get_objects()),
            "frozen": gc.get_freeze_count(),
            "collected_total": _counter("tpurx_restart_gc_collected_total"),
            "frozen_gauge": _counter("tpurx_restart_gc_frozen_objects"),
            "thread": threading.get_ident(),
        })
        if entry == len(RESTARTS):
            return "recovered"
        holder = _Holder()
        dead["holder"] = weakref.ref(holder)
        dead["array"] = weakref.ref(holder.array)
        if fault == "exception":
            # holder is a local of the raising frame, as is its array: the
            # traceback of the caught exception holds both
            array = holder.array
            raise ValueError(f"injected fault {entry} {array.shape}")
        call_wrapper.ops.record_interruption(
            entry, InterruptionRecord(rank=0, interruption=Interruption.QUORUM_STALE,
                                      origin_rank=0))
        del holder  # the cycle alone keeps it and its array
        return _spin()

    wrapper = Wrapper(
        store_factory=lambda: StoreClient("127.0.0.1", port, timeout=10.0),
        group=f"restart-collect-{fault}", soft_timeout=3600.0, hard_timeout=7200.0,
        enable_monitor_process=False, enable_sibling_monitor=False,
        last_call_wait=LONG,
    )
    assert wrapper(train)() == "recovered"
    return seen


def _collect_end_events():
    return [r for r in flight._records("test")
            if r["event"] == "inproc.restart.collect_end"]


def _reset_ring(**configure):
    flight.configure(**configure)
    flight.set_current_episode("")
    flight._last_dump_ns.clear()


@pytest.fixture(scope="module", params=FAULTS)
def run(request, tmp_path_factory):
    """One process's three restarts on one of the two ways into the restart
    path, the ring's ``inproc.restart.collect_end`` events beside them."""
    fault = request.param
    _reset_ring(enabled=True, capacity=4096)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path_factory.mktemp("dumps")))
    server = StoreServer(host="127.0.0.1", port=0).start_in_thread()
    gc.unfreeze()  # whatever an earlier test's wrapper froze
    gc.collect()
    try:
        seen = _three_restarts(server.port, fault)
        ends = _collect_end_events()
    finally:
        gc.unfreeze()
        monkeypatch.undo()
        server.stop()
        _reset_ring()
    return {"seen": seen, "ends": ends}


@pytest.mark.parametrize("restart", RESTARTS)
def test_the_dead_invocations_cycle_and_its_array_are_gone_at_the_re_entry(
        run, restart):
    """(a) and (b): the first restart collects with nothing frozen, the
    second and third behind a freeze; on the exception path the array is a
    local of the raising frame, which ``fault_exc`` held until it was dropped
    before the collection."""
    entry = run["seen"][restart]
    assert entry["entry"] == restart
    assert entry["holder_dead"], "the cycle outlived the restart path's collection"
    assert entry["array_dead"], "the cycle's device array outlived the collection"
    assert entry["marked_arrays"] == 0  # its buffer is released, not just unnamed


@pytest.mark.parametrize("restart", RESTARTS)
def test_every_restarts_collection_finds_the_cycle_and_counts_it(run, restart):
    """(d): the counter rises at every restart by what that restart's
    ``gc.collect()`` returned (the ring's field), which is never zero here:
    the holder's cycle is garbage at each of them."""
    seen, end = run["seen"], run["ends"][restart - 1]
    found = seen[restart]["collected_total"] - seen[restart - 1]["collected_total"]
    assert found >= 1  # the holder, and whatever else the cycle took along
    assert found == end["collected"]
    assert end["ident"] == restart - 1  # the faulted iteration


def test_nothing_is_frozen_before_the_first_fault_and_the_heap_after_it(run):
    """(c): the first fault of a process walks the whole heap; from then on
    the survivors are permanent."""
    first, second = run["seen"][0], run["seen"][1]
    # not zero: Python 3.12's collector itself moves the immortal objects it
    # meets (a few hundred) to the permanent generation
    assert first["frozen"] * 100 < first["walkable"]
    assert second["frozen"] > 0
    # nearly all of what the first collection had to walk is now permanent
    assert second["frozen"] > 0.9 * first["walkable"]


@pytest.mark.parametrize("restart", RESTARTS[1:])
def test_a_later_restart_walks_a_tenth_of_what_the_first_did(run, restart):
    """(c): what a full collection would walk, read at fn's entry: the whole
    heap before the first fault, after a freeze only what the re-arm and
    ``initialize`` allocated since; the next restart's collection walks that
    and the dead invocation's own objects."""
    first, later = run["seen"][0], run["seen"][restart]
    assert later["walkable"] * 10 < first["walkable"]


@pytest.mark.parametrize("restart", RESTARTS)
def test_the_gauge_and_the_ring_carry_what_the_restarts_freeze_moved(run, restart):
    """(d): the gauge is the survivors the last freeze moved, and so is the
    ``frozen`` field of that restart's ``inproc.restart.collect_end``; the
    fields' sum stands for the permanent generation's size
    (``gc.get_freeze_count()``, which walks that generation and is never
    called on the restart path): more by what reference counts freed since,
    less by the few hundred immortal objects Python 3.12's collector moves
    there itself."""
    entry, ends = run["seen"][restart], run["ends"][:restart]
    assert entry["frozen_gauge"] == ends[-1]["frozen"] > 0
    assert sum(end["frozen"] for end in ends) == pytest.approx(
        entry["frozen"], rel=0.02)


def test_the_first_freeze_moves_the_heap_and_a_later_one_what_a_restart_left(run):
    first, second, third = (end["frozen"] for end in run["ends"])
    assert first > 0.9 * run["seen"][0]["walkable"]
    assert second * 10 < first and third * 10 < first


def test_the_wrapped_fn_re_enters_on_the_thread_that_froze(run):
    assert len({entry["thread"] for entry in run["seen"]}) == 1
    assert [entry["entry"] for entry in run["seen"]] == [0, 1, 2, 3]
    assert len(run["ends"]) == len(RESTARTS)


def test_a_cycle_made_after_the_freeze_is_still_collected():
    """What the mechanism rests on, without a wrapper: a frozen heap takes
    nothing from a later full collection's power over younger objects."""
    gc.collect()
    gc.freeze()
    try:
        holder = _Holder()
        array_ref = weakref.ref(holder.array)
        del holder
        assert array_ref() is not None
        assert gc.collect() >= 1
        assert array_ref() is None
        assert _live_marked_arrays() == 0
    finally:
        gc.unfreeze()
