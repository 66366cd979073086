"""KV store substrate tests (mirrors reference store/barrier unit coverage)."""

import threading
import time

import pytest

from tpu_resiliency.store import (
    BarrierOverflow,
    BarrierTimeout,
    PrefixStore,
    StoreClient,
    StoreTimeout,
    barrier,
    reentrant_barrier,
)


def test_set_get(store):
    store.set("k", b"v")
    assert store.get("k") == b"v"
    assert store.try_get("missing") is None


def test_blocking_get_waits_for_set(store, store_server):
    result = {}

    def setter():
        time.sleep(0.2)
        other = StoreClient("127.0.0.1", store_server.port)
        other.set("late", b"arrived")
        other.close()

    t = threading.Thread(target=setter)
    t.start()
    result["v"] = store.get("late", timeout=5.0)
    t.join()
    assert result["v"] == b"arrived"


def test_get_timeout(store):
    with pytest.raises(StoreTimeout):
        store.get("never", timeout=0.2)


def test_add_atomic(store, store_server):
    n_threads, n_incr = 8, 50

    def worker():
        c = StoreClient("127.0.0.1", store_server.port)
        for _ in range(n_incr):
            c.add("counter", 1)
        c.close()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert store.add("counter", 0) == n_threads * n_incr


def test_append(store):
    assert store.append("log", b"a") == 1
    assert store.append("log", b"bc") == 3
    assert store.get("log") == b"abc"


def test_compare_set(store):
    # set-if-absent
    assert store.compare_set("cas", b"", b"first") == b"first"
    # wrong expectation -> returns current
    assert store.compare_set("cas", b"nope", b"second") == b"first"
    # correct expectation -> swapped
    assert store.compare_set("cas", b"first", b"second") == b"second"


def test_wait_and_check(store, store_server):
    store.set("a", b"1")
    assert store.check(["a"]) is True
    assert store.check(["a", "b"]) is False

    def setter():
        time.sleep(0.15)
        c = StoreClient("127.0.0.1", store_server.port)
        c.set("b", b"2")
        c.close()

    t = threading.Thread(target=setter)
    t.start()
    store.wait(["a", "b"], timeout=5.0)
    t.join()

    with pytest.raises(StoreTimeout):
        store.wait(["nothere"], timeout=0.2)


def test_wait_rides_out_server_restart(tmp_path):
    """A blocked WAIT survives the store host dying and returning: the
    client's sliced waits reconnect against the journal-restored server and
    release when the key finally lands.  This is the exact contract the
    event-driven rendezvous (joiners parked on k_done/k_open/k_count) and
    the chaos-store soak rely on."""
    from tpu_resiliency.store import StoreServer

    journal = str(tmp_path / "j.log")
    srv = StoreServer(host="127.0.0.1", port=0, journal_path=journal)
    srv.start_in_thread()
    port = srv.port
    waiter = StoreClient("127.0.0.1", port, timeout=30.0)
    released = {}

    def block():
        try:
            waiter.wait(["late/key"], timeout=25.0)
            released["ok"] = True
        except Exception as exc:  # noqa: BLE001
            released["err"] = exc

    t = threading.Thread(target=block)
    t.start()
    time.sleep(0.3)          # the wait is parked server-side
    srv.stop()               # store host "dies"
    time.sleep(0.3)
    srv2 = StoreServer(host="127.0.0.1", port=port, journal_path=journal)
    srv2.start_in_thread()   # journal-restored on the SAME endpoint
    try:
        setter = StoreClient("127.0.0.1", port)
        time.sleep(0.2)
        setter.set("late/key", b"v")
        t.join(timeout=20.0)
        assert released.get("ok"), released
        setter.close()
    finally:
        waiter.close()
        srv2.stop()


def test_delete_num_keys_list(store):
    store.multi_set({"p/x": b"1", "p/y": b"2", "q/z": b"3"})
    assert store.num_keys() == 3
    assert sorted(store.list_keys("p/")) == [b"p/x", b"p/y"]
    assert store.delete("p/x") is True
    assert store.delete("p/x") is False
    assert store.num_keys() == 2
    assert store.multi_get(["p/y", "q/z"]) == [b"2", b"3"]
    # per-key miss semantics: absent keys come back as None ENTRIES (the
    # old all-or-nothing None return could not name the missing key)
    assert store.multi_get(["p/y", "gone"]) == [b"2", None]
    assert store.multi_get(["gone", "also-gone"]) == [None, None]


def test_prefix_store(store):
    ps = PrefixStore("iter/0", store)
    ps.set("k", b"v")
    assert store.get("iter/0/k") == b"v"
    assert ps.get("k") == b"v"
    assert ps.add("c", 5) == 5
    nested = PrefixStore("inner", ps)
    nested.set("deep", b"d")
    assert store.get("iter/0/inner/deep") == b"d"
    assert sorted(ps.list_keys()) == [b"iter/0/c", b"iter/0/inner/deep", b"iter/0/k"]
    assert sorted(ps.list_keys("inner/")) == [b"iter/0/inner/deep"]


def _run_threads(fn, n):
    errors = []

    def wrapped(i):
        try:
            fn(i)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=wrapped, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors


def test_counting_barrier(store_server):
    world = 4
    release_times = []

    def member(i):
        c = StoreClient("127.0.0.1", store_server.port)
        time.sleep(0.05 * i)
        barrier(c, "b1", world, timeout=10.0)
        release_times.append(time.monotonic())
        c.close()

    errors = _run_threads(member, world)
    assert not errors
    assert len(release_times) == world
    assert max(release_times) - min(release_times) < 1.0


def test_barrier_overflow(store):
    barrier_world = 1
    barrier(store, "b2", barrier_world, timeout=5.0)
    with pytest.raises(BarrierOverflow):
        barrier(store, "b2", barrier_world, timeout=5.0)


def test_barrier_timeout_reports_missing(store):
    with pytest.raises(BarrierTimeout) as exc_info:
        barrier(store, "b3", 3, timeout=0.5)
    assert exc_info.value.arrived == 1
    assert exc_info.value.world_size == 3


def test_reentrant_barrier(store_server):
    world = 3

    def member(i):
        c = StoreClient("127.0.0.1", store_server.port)
        # rank 0 "restarts" and re-enters — must not deadlock or overflow
        reentrant_barrier(c, "rb", i, world, timeout=10.0)
        if i == 0:
            reentrant_barrier(c, "rb", i, world, timeout=10.0)
        c.close()

    errors = _run_threads(member, world)
    assert not errors


def test_failover_store_client(store_server):
    from tpu_resiliency.store import FailoverStoreClient

    # first endpoint dead, second is the live server -> transparent failover
    dead_port = 1  # nothing listens there
    c = FailoverStoreClient(
        [f"127.0.0.1:{dead_port}", f"127.0.0.1:{store_server.port}"],
        timeout=5.0, connect_timeout=6.0,
    )
    c.set("k", b"v")
    assert c.get("k") == b"v"
    c.close()


# -- on-disk journal ---------------------------------------------------------


def _journal_server(tmp_path, **kw):
    from tpu_resiliency.store import StoreServer

    return StoreServer(
        host="127.0.0.1", port=0, journal_path=str(tmp_path / "store.journal"), **kw
    ).start_in_thread()


def test_journal_restart_restores_state(tmp_path):
    from tpu_resiliency.store import StoreClient

    s1 = _journal_server(tmp_path)
    c = StoreClient("127.0.0.1", s1.port)
    c.set("rdzv/active_round", b"7")
    c.set("rdzv/cycle", b"12")
    c.add("counter", 5)
    c.append("log", b"abc")
    c.append("log", b"def")
    c.compare_set("cas", b"", b"v1")
    c.set("gone", b"x")
    c.delete("gone")
    c.close()
    s1.stop()

    s2 = _journal_server(tmp_path)
    assert s2.replayed_keys == 5
    c2 = StoreClient("127.0.0.1", s2.port)
    assert c2.get("rdzv/active_round") == b"7"
    assert c2.get("rdzv/cycle") == b"12"
    assert c2.get("counter") == b"5"
    assert c2.get("log") == b"abcdef"
    assert c2.get("cas") == b"v1"
    assert c2.try_get("gone") is None
    # mutations continue journaling after a restart
    assert c2.add("counter", 1) == 6
    c2.close()
    s2.stop()
    s3 = _journal_server(tmp_path)
    c3 = StoreClient("127.0.0.1", s3.port)
    assert c3.get("counter") == b"6"
    c3.close()
    s3.stop()


def test_journal_tolerates_torn_tail(tmp_path):
    from tpu_resiliency.store import StoreClient

    s1 = _journal_server(tmp_path)
    c = StoreClient("127.0.0.1", s1.port)
    c.set("good", b"kept")
    c.close()
    s1.stop()
    # crash mid-append: a partial record at the tail
    with open(tmp_path / "store.journal", "ab") as f:
        f.write(b"S" + (123456).to_bytes(4, "little") + b"partial-key-then-noth")
    s2 = _journal_server(tmp_path)
    c2 = StoreClient("127.0.0.1", s2.port)
    assert c2.get("good") == b"kept"
    assert s2.replayed_keys == 1
    # the torn tail was truncated: new writes land on a clean boundary
    c2.set("after", b"crash")
    c2.close()
    s2.stop()
    s3 = _journal_server(tmp_path)
    c3 = StoreClient("127.0.0.1", s3.port)
    assert c3.get("after") == b"crash" and c3.get("good") == b"kept"
    c3.close()
    s3.stop()


def test_journal_compaction_bounds_size(tmp_path):
    from tpu_resiliency.store import StoreClient

    s1 = _journal_server(tmp_path, journal_max_bytes=4096)
    c = StoreClient("127.0.0.1", s1.port)
    for i in range(500):
        c.set("hot", b"x" * 64 + str(i).encode())  # same key rewritten
    c.close()
    # An ordering, not a sleep: the compaction the writes set off swaps the
    # file off the loop, and a stop() that cancels it in flight keeps the
    # old journal, every record of it, as the authority on purpose.
    journal = tmp_path / "store.journal"
    deadline = time.monotonic() + 30.0
    while journal.stat().st_size >= 8192 and time.monotonic() < deadline:
        time.sleep(0.01)
    s1.stop()
    size = journal.stat().st_size
    assert size < 8192, size  # compacted: not 500 * ~80 bytes
    s2 = _journal_server(tmp_path)
    c2 = StoreClient("127.0.0.1", s2.port)
    assert c2.get("hot").endswith(b"499")
    c2.close()
    s2.stop()


def test_journal_lock_refuses_second_instance(tmp_path):
    from tpu_resiliency.store import StoreServer

    s1 = _journal_server(tmp_path)
    try:
        with pytest.raises(RuntimeError, match="locked by another store"):
            StoreServer(
                host="127.0.0.1", port=0,
                journal_path=str(tmp_path / "store.journal"),
            ).start_in_thread()
    finally:
        s1.stop()
    # lock released on stop: a successor starts fine
    s2 = _journal_server(tmp_path)
    s2.stop()


def test_journal_strip_prefixes(tmp_path):
    from tpu_resiliency.store import StoreClient, StoreServer

    s1 = _journal_server(tmp_path)
    c = StoreClient("127.0.0.1", s1.port)
    c.set("rdzv/shutdown", b"success")
    c.set("rdzv/shutdown/ack/nodeA", b"1")
    c.set("rdzv/cycle", b"9")
    c.close()
    s1.stop()
    s2 = StoreServer(
        host="127.0.0.1", port=0,
        journal_path=str(tmp_path / "store.journal"),
        journal_strip_prefixes=[b"rdzv/shutdown"],
    ).start_in_thread()
    c2 = StoreClient("127.0.0.1", s2.port)
    assert c2.try_get("rdzv/shutdown") is None
    assert c2.try_get("rdzv/shutdown/ack/nodeA") is None
    assert c2.get("rdzv/cycle") == b"9"
    c2.close()
    s2.stop()
    # the strip is journaled as deletes: a THIRD start without strip still
    # does not resurrect the flag
    s3 = _journal_server(tmp_path)
    c3 = StoreClient("127.0.0.1", s3.port)
    assert c3.try_get("rdzv/shutdown") is None
    c3.close()
    s3.stop()


def test_control_plane_restart_keeps_cycle_numbering(tmp_path):
    """The VERDICT ask: a restarted control plane continues cycle numbers."""
    from tpu_resiliency.fault_tolerance.rendezvous import (
        K_CYCLE,
        RendezvousHost,
        k_done,
    )
    from tpu_resiliency.store import StoreClient

    s1 = _journal_server(tmp_path)
    c = StoreClient("127.0.0.1", s1.port)
    host = RendezvousHost(c, min_nodes=1)
    host.bootstrap()
    host.open_round()   # round 0, cycle 0
    assert int(c.get(K_CYCLE)) == 1
    c.set(k_done(0), b"1")  # round 0 completed before the control plane died
    c.close()
    s1.stop()

    # control plane restarts from the journal
    s2 = _journal_server(tmp_path)
    c2 = StoreClient("127.0.0.1", s2.port)
    host2 = RendezvousHost(c2, min_nodes=1)
    host2.bootstrap()  # must be a no-op on restored state
    assert host2.current_round() == 0  # round pointer survived
    n = host2.open_round()
    assert n == 1       # advances past the completed round 0
    assert int(c2.get(K_CYCLE)) == 2  # cycle numbering continued, no reset
    c2.close()
    s2.stop()

    # a mid-round restart resumes the SAME open round (no spurious advance)
    s3 = _journal_server(tmp_path)
    c3 = StoreClient("127.0.0.1", s3.port)
    host3 = RendezvousHost(c3, min_nodes=1)
    host3.bootstrap()
    assert host3.open_round() == 1  # round 1 still open: resume it
    assert int(c3.get(K_CYCLE)) == 2
    c3.close()
    s3.stop()
