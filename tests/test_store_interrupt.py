"""Interrupt-at-every-point + brownout coverage for the store client.

The tentpole contract under test: NO store code path may sit in a single
C-level wait longer than the poll quantum (``TPURX_STORE_POLL_S``), so a
pending async raise (in-process restart abort, monitor-triggered teardown,
shutdown) lands between slices — never parked behind one uninterruptible
``recv``.  Each test parks a worker thread at a different point of the I/O
state machine (connect, send, recv-mid-frame, server-held long poll,
cross-shard fan-out), injects
``PyThreadState_SetAsyncExc`` and asserts the raise lands within the
contract budget AND the client is cleanly re-usable afterwards (no
half-read frames on the wire).

Brownout coverage: a server that accepts connections but never answers
(``TPURX_STORE_TEST_BROWNOUT``) must be escaped via the per-op first-byte
deadline (:class:`StoreBrownout`), retried on a sibling endpoint by the
failover client, and ridden out by the sharded client's existing
``store_shard_failover`` episode ending in spare promotion — never a hung
caller.
"""

import ctypes
import socket
import struct
import threading
import time

import pytest

from tpu_resiliency.store import (
    FailoverStoreClient,
    ShardMap,
    ShardServerGroup,
    ShardedStoreClient,
    StoreBrownout,
    StoreClient,
    StoreServer,
    spawn_shard_subprocess,
)
from tpu_resiliency.store.client import (
    StoreError,
    _brownout_grace,
    _poll_quantum,
)
from tpu_resiliency.store.sharding import free_port

# Small quantum so landing-latency assertions are tight; the contract is
# "within 2x the poll quantum", LAND_SLACK covers CI scheduler jitter and
# the cost of the BaseException cleanup path (socket close) on top.
QUANTUM = 0.05
LAND_SLACK = 1.5


@pytest.fixture(autouse=True)
def _fast_quantum(monkeypatch):
    monkeypatch.setenv("TPURX_STORE_POLL_S", str(QUANTUM))
    yield


class _Interrupt(Exception):
    """Stand-in for the restart/abort async raise."""


def _async_raise(tid: int) -> None:
    n = ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(tid), ctypes.py_object(_Interrupt)
    )
    if n > 1:  # pragma: no cover - undo over-broad delivery
        ctypes.pythonapi.PyThreadState_SetAsyncExc(ctypes.c_ulong(tid), None)
    assert n == 1, f"async raise delivered to {n} threads"


def _interrupt_parked(target, settle: float = 0.5, join: float = 20.0):
    """Run ``target`` in a thread, async-raise once it is parked, and
    return how long the raise took to LAND (from injection to the except
    block running)."""
    box = {}

    def run():
        try:
            box["ret"] = target()
        except _Interrupt:
            box["landed"] = time.monotonic()
        except BaseException as exc:  # noqa: BLE001 - surfaced in assert
            box["err"] = exc

    th = threading.Thread(target=run, daemon=True)
    th.start()
    time.sleep(settle)  # let target reach its blocking wait
    assert th.is_alive(), f"target finished before injection: {box}"
    t0 = time.monotonic()
    _async_raise(th.ident)
    th.join(timeout=join)
    assert not th.is_alive(), "interrupt never landed; thread still parked"
    assert "landed" in box, f"interrupt swallowed or transformed: {box}"
    return box["landed"] - t0


def _assert_landed(dt: float) -> None:
    assert dt <= 2 * QUANTUM + LAND_SLACK, (
        f"async raise took {dt:.2f}s to land; contract is ~2x quantum "
        f"({2 * QUANTUM:.2f}s) plus scheduling slack"
    )


@pytest.fixture
def server():
    srv = StoreServer(host="127.0.0.1", port=0).start_in_thread()
    yield srv
    srv.stop()


# -- async raise at every point of the I/O state machine ----------------------


class TestInterruptEveryPoint:
    def test_mid_long_poll_wait_lands_and_client_reusable(self, server):
        """The documented flake: a rank parked in wait() used to sit ~30s in
        one C-level recv, so the restart raise could not land.  Now every
        recv slice is one quantum long."""
        c = StoreClient("127.0.0.1", server.port, timeout=60.0)
        dt = _interrupt_parked(lambda: c.wait(["never/set"], timeout=60.0))
        _assert_landed(dt)
        # clean re-entry: the socket was dropped mid-frame, the next op
        # reconnects and runs normally — no half-read frame parsing
        assert c._sock is None
        c.set("after/interrupt", b"ok")
        assert c.get("after/interrupt", timeout=5.0) == b"ok"
        c.close()

    def test_mid_long_poll_get_lands(self, server):
        c = StoreClient("127.0.0.1", server.port, timeout=60.0)
        dt = _interrupt_parked(lambda: c.get("never/get", timeout=60.0))
        _assert_landed(dt)
        c.set("g", b"v")
        assert c.get("g", timeout=5.0) == b"v"
        c.close()

    def test_mid_recv_partial_frame_lands_and_drops_socket(self):
        """Server sends ONE byte of the response then stalls: the client is
        mid-frame in _read_exact.  The raise must land within a slice and
        the desynced socket must be dropped (never re-parsed)."""
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        port = lst.getsockname()[1]
        stop = threading.Event()

        def stall_server():
            conn, _ = lst.accept()
            conn.recv(4096)  # the request frame
            conn.sendall(b"\x00")  # Status.OK ... and nothing else, ever
            stop.wait(30.0)
            conn.close()

        st = threading.Thread(target=stall_server, daemon=True)
        st.start()
        c = StoreClient("127.0.0.1", port, timeout=60.0, retries=0)
        try:
            dt = _interrupt_parked(
                lambda: c.get("k", timeout=60.0), settle=0.8
            )
            _assert_landed(dt)
            assert c._sock is None, "half-read frame survived the interrupt"
        finally:
            stop.set()
            c.close()
            lst.close()

    def test_mid_send_lands(self):
        """Fill the kernel buffers with a value larger than they can hold
        against a server that never reads: the client parks inside the
        sliced _send_all, where the raise must land too."""
        lst = socket.socket()
        lst.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        port = lst.getsockname()[1]
        c = StoreClient("127.0.0.1", port, timeout=60.0, retries=0)
        big = b"x" * (64 << 20)
        try:
            dt = _interrupt_parked(lambda: c.set("big", big), settle=0.8)
            _assert_landed(dt)
            # `sent` never flipped, the op was never applied, and the
            # partially-written socket is gone
            assert c._sock is None
        finally:
            c.close()
            lst.close()

    def test_mid_connect_lands(self):
        """The constructor's connect loop retries at quantum granularity
        (black-holed endpoint: a listener whose accept queue is full drops
        SYNs), so even a client that never got a socket is interruptible."""
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(0)
        port = lst.getsockname()[1]
        fillers = []
        for _ in range(4):  # saturate the accept queue; never accepted
            s = socket.socket()
            s.setblocking(False)
            try:
                s.connect(("127.0.0.1", port))
            except BlockingIOError:
                pass
            fillers.append(s)
        time.sleep(0.2)
        try:
            dt = _interrupt_parked(
                lambda: StoreClient("127.0.0.1", port, connect_timeout=60.0)
            )
            _assert_landed(dt)
        finally:
            for s in fillers:
                s.close()
            lst.close()

    def test_mid_cross_shard_fanout_lands(self, tmp_path):
        """Cross-shard wait: per-shard worker threads park server-side
        while the caller sits in the sliced join — the raise targets the
        CALLER and must land between join slices."""
        group = ShardServerGroup(
            2, journal_base=str(tmp_path / "j")
        ).start()
        c = ShardedStoreClient(group.endpoints, timeout=60.0)
        try:
            keys = [f"fan/{i}" for i in range(8)]  # spreads over both shards
            dt = _interrupt_parked(lambda: c.wait(keys, timeout=60.0))
            _assert_landed(dt)
            # clean re-entry across the same clients
            c.multi_set({"fan/a": b"1", "fan/b": b"2"})
            assert c.multi_get(["fan/a", "fan/b"]) == [b"1", b"2"]
        finally:
            c.close()
            group.stop()

    def test_mid_long_poll_lands_and_clone_traffic_unharmed(self, server):
        """A client's ``clone()`` is its own connection: while the first
        client is parked in a long-poll, while the raise lands in it and
        after it has re-entered, a second thread's set/get on the clone go
        on without an error."""
        c = StoreClient("127.0.0.1", server.port, timeout=60.0)
        other = c.clone()
        stop = threading.Event()
        rounds = [0]
        errs = []

        def traffic():
            try:
                while not stop.is_set():
                    v = str(rounds[0]).encode()
                    other.set("clone/k", v)
                    assert other.get("clone/k", timeout=5.0) == v
                    rounds[0] += 1
            except BaseException as exc:  # noqa: BLE001 - surfaced below
                errs.append(exc)

        def more_rounds_than(n):
            deadline = time.monotonic() + 10.0
            while rounds[0] <= n and not errs:
                assert time.monotonic() < deadline, "clone traffic stalled"
                time.sleep(0.01)
            return rounds[0]

        th = threading.Thread(target=traffic, daemon=True)
        th.start()
        try:
            before = more_rounds_than(0)
            dt = _interrupt_parked(lambda: c.get("never/clone", timeout=60.0))
            _assert_landed(dt)
            during = rounds[0]
            assert during > before, "clone made no round while c was parked"
            more_rounds_than(during)
            # the interrupted client re-enters cleanly on a new socket
            assert c._sock is None
            c.set("clone/after", b"ok")
            assert c.get("clone/after", timeout=5.0) == b"ok"
        finally:
            stop.set()
            th.join(timeout=10.0)
            other.close()
            c.close()
        assert not th.is_alive()
        assert not errs, errs


# -- brownout: live listener, wedged event loop -------------------------------


class TestBrownout:
    def test_single_client_escapes_via_first_byte_deadline(self, monkeypatch):
        monkeypatch.setenv("TPURX_STORE_TEST_BROWNOUT", "1")
        srv = StoreServer(host="127.0.0.1", port=0).start_in_thread()
        try:
            c = StoreClient("127.0.0.1", srv.port, timeout=60.0, retries=0)
            t0 = time.monotonic()
            with pytest.raises(StoreBrownout):
                c.set("k", b"v")
            dt = time.monotonic() - t0
            grace = _brownout_grace()
            assert dt < grace + 2.0, (
                f"brownout escape took {dt:.1f}s; first-byte deadline is "
                f"{grace:.1f}s — the op waited out io_timeout instead"
            )
            c.close()
        finally:
            srv.stop()

    def test_failover_client_retries_on_sibling(self, monkeypatch):
        """A browned-out endpoint still ACCEPTS connections, so failover
        cannot rely on connect errors: the brownout hook must rotate to the
        sibling before the retry."""
        monkeypatch.setenv("TPURX_STORE_TEST_BROWNOUT", "1")
        bad = StoreServer(host="127.0.0.1", port=0).start_in_thread()
        monkeypatch.delenv("TPURX_STORE_TEST_BROWNOUT")
        monkeypatch.setattr(
            "tpu_resiliency.store.client._brownout_grace", lambda: 0.5
        )
        good = StoreServer(host="127.0.0.1", port=0).start_in_thread()
        try:
            seed = StoreClient("127.0.0.1", good.port, timeout=10.0)
            seed.set("sib/k", b"v")
            seed.close()
            c = FailoverStoreClient(
                [f"127.0.0.1:{bad.port}", f"127.0.0.1:{good.port}"],
                timeout=60.0, retries=2,
            )
            t0 = time.monotonic()
            assert c.get("sib/k", timeout=30.0) == b"v"
            dt = time.monotonic() - t0
            # one brownout grace on the bad endpoint, then the sibling
            assert dt < _brownout_grace() + 10.0
            c.close()
        finally:
            bad.stop()
            good.stop()

    def test_sharded_brownout_trips_failover_to_promoted_spare(
        self, tmp_path, monkeypatch
    ):
        """The acceptance gate: brown out one shard subprocess, park a
        wait() on it, promote a spare — the parked caller escapes via
        StoreBrownout, rides store_shard_failover, adopts the bumped map
        and completes against the spare.  Nobody hangs."""
        from tpu_resiliency.store import promote_spare
        from tpu_resiliency.store.sharding import RetryPolicy, SHARD_MAP_KEY

        # Production timings (2s park slices, 2s brownout grace, 0.5-5s
        # failover backoff) make each victim touch cost ~4s — correct in the
        # field, needlessly slow here.  Tighten all three: the CONTRACT under
        # test (escape -> failover -> adoption) is timing-shape independent.
        monkeypatch.setattr(
            "tpu_resiliency.store.client._brownout_grace", lambda: 0.5
        )
        monkeypatch.setattr(StoreClient, "BLOCKING_SLICE_S", 0.5)
        fast_failover = RetryPolicy(
            max_attempts=None, base_delay=0.1, max_delay=0.5, deadline=60.0
        )

        ports = [free_port(), free_port()]
        spare_port = free_port()
        spare_ep = f"127.0.0.1:{spare_port}"
        endpoints = [f"127.0.0.1:{p}" for p in ports]
        procs = []
        spare_proc = None
        try:
            procs.append(spawn_shard_subprocess(ports[0]))
            procs.append(
                spawn_shard_subprocess(
                    ports[1], env={"TPURX_STORE_TEST_BROWNOUT": "1"}
                )
            )
            # the browned shard reads but never answers, so the map must be
            # seeded on the healthy one — which is also where recovery
            # discovery (_fetch_map_raw, excluding the victim) will look
            m = ShardMap(endpoints, spares=[spare_ep])
            seed = StoreClient("127.0.0.1", ports[0], timeout=10.0)
            seed.set(SHARD_MAP_KEY, m.to_json())
            c = ShardedStoreClient.from_bootstrap(
                "127.0.0.1", ports[0], timeout=60.0,
                failover_policy=fast_failover,
            )
            victim = 1

            # pick a key that routes to the browned-out shard
            key = next(
                f"bo/key/{i}" for i in range(256)
                if c.map.shard_for(f"bo/key/{i}".encode()) == victim
            )
            waited = {}

            def block():
                try:
                    c.wait([key], timeout=120.0)
                    waited["ok"] = True
                except Exception as exc:  # noqa: BLE001
                    waited["err"] = exc

            t = threading.Thread(target=block, daemon=True)
            t.start()
            time.sleep(0.5)  # parked against the brownout

            # the watchdog's moves: spare up, epoch-bumped map published on
            # the HEALTHY shard
            spare_proc = spawn_shard_subprocess(spare_port)
            mc = StoreClient("127.0.0.1", ports[0], timeout=10.0)
            promoted = promote_spare(mc, victim, spare_ep)
            mc.close()
            assert promoted.epoch == 1

            # release the waiter THROUGH the sharded client: its failover
            # episode must adopt the promoted endpoint first
            deadline = time.monotonic() + 90.0
            while time.monotonic() < deadline:
                try:
                    c.set(key, b"released")
                    break
                except StoreError:
                    time.sleep(0.5)
            t.join(timeout=90.0)
            assert not t.is_alive(), "waiter still parked on browned shard"
            assert waited.get("ok"), waited
            assert c.map.epoch == 1
            assert c.endpoints[victim] == ("127.0.0.1", spare_port)
            c.close()
        finally:
            for p in procs:
                p.kill()
            if spare_proc is not None:
                spare_proc.kill()


# -- non-idempotent resend rules survive the rewrite --------------------------


class TestResendRules:
    def test_non_idempotent_not_resent_after_full_send(self):
        """A connection that dies AFTER the whole ADD frame left must not be
        retried — the server may have applied it.  (The rewrite moved the
        send into sliced _send_all; the `sent` flip must still happen only
        after the last byte.)"""
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        port = lst.getsockname()[1]

        def accept_then_reset():
            conn, _ = lst.accept()
            conn.recv(4096)  # whole (tiny) ADD frame arrives
            conn.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER,
                struct.pack("ii", 1, 0),  # RST on close
            )
            conn.close()

        st = threading.Thread(target=accept_then_reset, daemon=True)
        st.start()
        c = StoreClient("127.0.0.1", port, timeout=10.0, retries=3)
        with pytest.raises(StoreError, match="not retrying non-idempotent"):
            c.add("ctr", 1)
        c.close()
        lst.close()
