"""Blocking KV store client + PrefixStore namespace wrapper.

Primitive surface mirrors what every reference coordination protocol needs
(``inprocess/store.py:50-381`` StoreMixin over TCPStore):
get/set/add/append/compare_set/wait/check/delete, plus list_keys and
multi ops.  Values are ``bytes``; helpers convert ints/strings.

Thread-safety: a client holds one socket guarded by a lock; ``clone()``
returns an independent connection for use from another thread (monitor
threads keep their own clone so a blocked GET can't starve heartbeats).

Interruptible I/O core: no code path in this module sits in a single
C-level socket wait longer than the poll quantum (``TPURX_STORE_POLL_S``,
default 0.5 s).  Every connect/send/recv is a Python-level loop of
quantum-bounded slices, so a pending async raise (in-process restart),
monitor abort, or shutdown lands *between* slices instead of parking
behind an uninterruptible ``recv``.  An async raise that lands mid-frame
drops the socket before propagating — re-entry never sees a half-read
frame.  A server that accepts our bytes but never starts answering (a
"brownout": live TCP listener, wedged serving loop) is detected by
per-op first-byte deadline accounting and surfaces as
:class:`StoreBrownout` — a ``StoreError``, so the sharded client's
``store_shard_failover`` episode trips instead of the caller hanging.
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from typing import List, Optional, Sequence

from ..telemetry import counter, flight, histogram
from ..utils import env
from ..utils.retry import (
    CONNECT_POLICY,
    ROUNDTRIP_POLICY,
    Retrier,
    RetryExhausted,
)
from .protocol import Op, Status, itob

_U32 = struct.Struct("<I")

_DEFAULT_TIMEOUT = 300.0

_OPS_TOTAL = counter(
    "tpurx_store_ops_total", "KV store client round trips", labels=("op",)
)
_OP_LATENCY = histogram(
    "tpurx_store_op_latency_ns",
    "KV store round-trip latency (per sliced request for blocking ops)",
    labels=("op",),
)
# per-op metric children resolved once — the hot path does one dict lookup
_OP_METRICS: dict = {}

# flight-recorder events: every issued op plus the rare recovery paths, so
# a fault-time dump shows what the control plane was doing and whether it
# was limping (retries/failovers) before the trip
EV_OP_ISSUE = flight.declare_event("store.op_issue", "op")
EV_OP_RETRY = flight.declare_event("store.op_retry", "op", "error")
EV_FAILOVER = flight.declare_event("store.failover", "addr")


def _op_metrics(op: Op):
    m = _OP_METRICS.get(op)
    if m is None:
        m = _OP_METRICS[op] = (_OPS_TOTAL.labels(op.name), _OP_LATENCY.labels(op.name))
    return m

# Ops safe to resend after a connection drop: resending cannot change the
# final store state.  ADD/APPEND/COMPARE_SET are NOT here — the server may
# have applied the op before the connection died, and a blind resend would
# double-apply (e.g. a phantom barrier arrival).
_IDEMPOTENT_OPS = frozenset(
    {
        Op.SET,
        Op.GET,
        Op.TRY_GET,
        Op.WAIT,
        Op.CHECK,
        Op.DELETE,
        Op.NUM_KEYS,
        Op.PING,
        Op.LIST_KEYS,
        Op.MULTI_SET,
        Op.MULTI_GET,
        Op.MULTI_TRY_GET,
        # WAIT_GE is a read fence (blocks until a counter reaches a
        # threshold) — resending cannot change store state.  APPEND_CHECK
        # and ADD_SET are NOT idempotent: both mutate on every application.
        Op.WAIT_GE,
    }
)


class StoreError(RuntimeError):
    pass


class StoreTimeout(StoreError, TimeoutError):
    pass


class StoreBrownout(StoreError):
    """The server accepted our connection (and our request bytes) but never
    started answering within the per-op deadline — a live TCP listener in
    front of a wedged serving loop.  Deliberately NOT a :class:`StoreTimeout`:
    the sharded client passes ``StoreTimeout`` through to the caller (a
    legitimately-expired wait budget) but retries ``StoreError`` under its
    ``store_shard_failover`` episode, which is exactly where a browned-out
    shard must land."""


class _IODeadline(Exception):
    """Internal: a sliced socket loop ran out of its deadline.  Never
    escapes ``_roundtrip_inner``; mapped there to StoreTimeout/StoreBrownout
    depending on whether any response bytes had arrived."""


def _poll_quantum() -> float:
    """Upper bound on any single C-level socket wait (seconds)."""
    try:
        q = float(env.STORE_POLL_S.get())
    except (TypeError, ValueError):
        q = 0.5
    return max(0.02, q)


def _interruptible_sleep(seconds: float) -> None:
    """``time.sleep`` chunked at the poll quantum — ``time.sleep(30)`` is
    itself one uninterruptible C-level wait, so retry backoffs must slice
    exactly like socket waits do."""
    deadline = time.monotonic() + seconds
    q = _poll_quantum()
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return
        time.sleep(min(q, remaining))


def _brownout_grace() -> float:
    """How long after the expected server-park time we wait for the FIRST
    response byte before declaring the shard browned out.  Generous relative
    to the quantum so a loaded single-core CI host's scheduling jitter never
    reads as a brownout."""
    return max(20.0 * _poll_quantum(), 2.0)


class StoreFactory:
    """Picklable ``() -> StoreClient`` factory.

    Lambdas work as store factories only under fork; subprocess helpers that
    default to **spawn** (forking a JAX-threaded parent is a deadlock class)
    need the factory to cross a pickle boundary.  Use this instead of a lambda."""

    def __init__(self, host: str, port: int, timeout: float = _DEFAULT_TIMEOUT,
                 **kwargs):
        self.host = host
        self.port = port
        self.timeout = timeout
        self.kwargs = kwargs

    def __call__(self) -> "StoreClient":
        return StoreClient(self.host, self.port, timeout=self.timeout,
                           **self.kwargs)


class StoreClient:
    """Client for :class:`tpu_resiliency.store.server.StoreServer`."""

    def __init__(
        self,
        host: str,
        port: int,
        timeout: float = _DEFAULT_TIMEOUT,
        connect_timeout: float = 60.0,
        retries: int = 3,
    ):
        self.host = host
        self.port = port
        self.timeout = timeout
        self._lock = threading.RLock()
        self._sock: Optional[socket.socket] = None
        self._retries = retries
        self._connect(connect_timeout)

    # -- connection --------------------------------------------------------

    def _connect(self, connect_timeout: float) -> None:
        # Per-attempt connect wait is ONE poll quantum (the retrier supplies
        # the overall budget), and backoff sleeps are quantum-chunked — an
        # async raise lands between attempts even while the endpoint is a
        # SYN black hole.
        r = Retrier("store_connect", CONNECT_POLICY, deadline=connect_timeout,
                    sleep=_interruptible_sleep)
        while True:
            try:
                sock = socket.create_connection(
                    (self.host, self.port), timeout=_poll_quantum()
                )
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                self._sock = sock
                return
            except OSError as exc:
                try:
                    r.backoff(exc)
                except RetryExhausted as give_up:
                    raise StoreError(
                        f"could not connect to store at "
                        f"{self.host}:{self.port}: {give_up.last_exc}"
                    ) from give_up

    def clone(self) -> "StoreClient":
        return StoreClient(self.host, self.port, timeout=self.timeout)

    def close(self) -> None:
        with self._lock:
            if self._sock is not None:
                try:
                    self._sock.close()
                finally:
                    self._sock = None

    # -- request plumbing --------------------------------------------------
    # Every socket wait below is a quantum-bounded slice inside a Python
    # loop (the "interruptible I/O core"); tpurx-lint's unbounded-socket
    # rule sanctions only this module to touch recv/send directly.

    def _read_exact(self, n: int, deadline: float) -> bytes:
        assert self._sock is not None
        buf = b""
        q = _poll_quantum()
        while len(buf) < n:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise _IODeadline(f"no reply within {n - len(buf)}B budget")
            self._sock.settimeout(min(q, remaining))
            try:
                chunk = self._sock.recv(n - len(buf))
            except socket.timeout:
                continue  # slice expired: run bytecode, let raises land
            if not chunk:
                raise ConnectionError("store connection closed")
            buf += chunk
        return buf

    def _send_all(self, data: bytes, deadline: float) -> None:
        assert self._sock is not None
        q = _poll_quantum()
        view = memoryview(data)
        while view:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise _IODeadline("server not draining our request bytes")
            self._sock.settimeout(min(q, remaining))
            try:
                sent = self._sock.send(view)
            except socket.timeout:
                continue
            view = view[sent:]

    def _roundtrip(
        self, op: Op, args: Sequence[bytes], io_timeout: Optional[float],
        park_s: float = 0.0,
    ) -> tuple[Status, List[bytes]]:
        ops_total, op_latency = _op_metrics(op)
        flight.record(EV_OP_ISSUE, op.name)
        t0 = time.monotonic_ns()
        try:
            return self._roundtrip_inner(op, args, io_timeout, park_s)
        finally:
            op_latency.observe(time.monotonic_ns() - t0)
            ops_total.inc()

    def _roundtrip_inner(
        self, op: Op, args: Sequence[bytes], io_timeout: Optional[float],
        park_s: float = 0.0,
    ) -> tuple[Status, List[bytes]]:
        """One request/response exchange.

        ``park_s`` is how long the server may LEGITIMATELY hold the request
        before its first response byte (the wire timeout of a long-poll
        slice; 0 for immediate ops).  The first-byte deadline is
        ``park_s + brownout grace``: a server that hasn't started answering
        by then is browned out — live listener, wedged loop — and the op
        fails over instead of waiting out ``io_timeout``.
        """
        if io_timeout is None:
            io_timeout = self.timeout
        with self._lock:
            if self._sock is None:
                self._connect(10.0)
            payload = [bytes([int(op)]), _U32.pack(len(args))]
            for a in args:
                payload.append(_U32.pack(len(a)))
                payload.append(a)
            wire = b"".join(payload)
            retrier = None  # lazily built: the happy path allocates nothing
            while True:
                sent = False
                brownout = False
                try:
                    now = time.monotonic()
                    attempt_deadline = now + io_timeout
                    first_byte_deadline = min(
                        now + park_s + _brownout_grace(), attempt_deadline
                    )
                    try:
                        # A partial send is never applied (the server needs
                        # the whole frame to parse), so `sent` flips only
                        # after the last byte leaves.
                        self._send_all(wire, first_byte_deadline)
                        sent = True
                        status_b = self._read_exact(1, first_byte_deadline)
                    except _IODeadline as exc:
                        # Zero response bytes by the first-byte deadline:
                        # the shard is browned out.  NOTE the server may
                        # still have APPLIED the op (read but unanswered),
                        # so the non-idempotent resend guard below applies.
                        brownout = True
                        raise StoreBrownout(
                            f"store op {op.name}: no reply from "
                            f"{self.host}:{self.port} within "
                            f"{first_byte_deadline - now:.1f}s "
                            f"(brownout?): {exc}"
                        ) from exc
                    status = Status(status_b[0])
                    (nargs,) = _U32.unpack(
                        self._read_exact(4, attempt_deadline))
                    out = []
                    for _ in range(nargs):
                        (ln,) = _U32.unpack(
                            self._read_exact(4, attempt_deadline))
                        out.append(
                            self._read_exact(ln, attempt_deadline)
                            if ln else b"")
                    return status, out
                except _IODeadline as exc:
                    # Mid-frame stall AFTER the response started arriving:
                    # classic timeout semantics (drop — the stream is
                    # desynced — and let sliced callers re-park).
                    self._drop_socket()
                    raise StoreTimeout(f"store op {op.name} timed out") from exc
                except socket.timeout as exc:
                    # Defensive: slices consume their own timeouts above, so
                    # this should be unreachable — but a half-read frame must
                    # never survive.
                    self._drop_socket()
                    raise StoreTimeout(f"store op {op.name} timed out") from exc
                except (StoreBrownout, ConnectionError, BrokenPipeError,
                        OSError) as exc:
                    self._drop_socket()
                    # A non-idempotent op may already have been applied once
                    # the request bytes left — never resend those.
                    if sent and op not in _IDEMPOTENT_OPS:
                        raise StoreError(
                            f"store op {op.name} connection lost after send; "
                            f"not retrying non-idempotent op: {exc}"
                        ) from exc
                    if retrier is None:
                        # +1: max_attempts counts FAILURES before giving up,
                        # and `retries` means retries-after-first-try
                        retrier = Retrier(
                            "store_roundtrip",
                            ROUNDTRIP_POLICY.with_(
                                max_attempts=self._retries + 1
                            ),
                            sleep=_interruptible_sleep,
                        )
                    try:
                        retrier.backoff(exc)
                    except RetryExhausted as give_up:
                        if brownout:
                            raise StoreBrownout(
                                f"store op {op.name} failed: {exc}"
                            ) from give_up
                        raise StoreError(
                            f"store op {op.name} failed: {exc}"
                        ) from give_up
                    flight.record(
                        EV_OP_RETRY, op.name, type(exc).__name__
                    )
                    if brownout:
                        # A browned-out endpoint still ACCEPTS connections,
                        # so a plain reconnect would re-enter the same black
                        # hole; the failover client advances to a sibling.
                        self._on_brownout()
                    # FailoverStoreClient overrides _connect to walk sibling
                    # endpoints here — a browned-out primary is retried
                    # against the next endpoint, not the same black hole.
                    self._connect(10.0)
                except BaseException:
                    # An async raise (in-process restart, shutdown) landed
                    # between slices mid-frame: the stream position is
                    # unknowable, so drop the socket before propagating —
                    # re-entry reconnects instead of parsing garbage.
                    self._drop_socket()
                    raise

    def _drop_socket(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def _on_brownout(self) -> None:
        """Hook: the endpoint was detected browned out (live listener, no
        replies by the first-byte deadline).  The base single-endpoint
        client has nowhere else to go; :class:`FailoverStoreClient`
        overrides this to advance to a sibling, because reconnecting to a
        brownout would SUCCEED — the listener is up — and the retry would
        wait out the grace against the same wedged server again."""

    @staticmethod
    def _k(key) -> bytes:
        return key.encode() if isinstance(key, str) else bytes(key)

    @staticmethod
    def _v(value) -> bytes:
        if isinstance(value, bytes):
            return value
        if isinstance(value, str):
            return value.encode()
        if isinstance(value, int):
            return itob(value)
        raise TypeError(f"unsupported store value type: {type(value)}")

    # -- public API --------------------------------------------------------

    def ping(self) -> bool:
        status, _ = self._roundtrip(Op.PING, [], io_timeout=5.0)
        return status == Status.OK

    def set(self, key, value) -> None:
        status, _ = self._roundtrip(Op.SET, [self._k(key), self._v(value)], self.timeout)
        if status != Status.OK:
            raise StoreError(f"set({key}) -> {status.name}")

    # Blocking ops are SLICED client-side: a single server-parked request
    # would otherwise occupy the caller for the whole wait with no bytecode
    # running — the progress watchdog's pending-call stamps freeze and the
    # monitor reads a legitimately waiting rank as a hang.  GET/WAIT are
    # idempotent reads, so re-parking every slice is safe; each loop
    # iteration runs bytecode and keeps the liveness stamps flowing.
    # Underneath, the recv for each slice is itself chopped into
    # TPURX_STORE_POLL_S quanta by the interruptible I/O core, so async
    # raises land within one quantum even mid-slice (this used to be the
    # layered-restart flake: a ~30s C-level recv no raise could interrupt).
    BLOCKING_SLICE_S = 2.0

    def get(self, key, timeout: Optional[float] = None) -> bytes:
        """Blocking get: waits for the key up to `timeout` (like TCPStore.get)."""
        t = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + t
        while True:
            remaining = deadline - time.monotonic()
            slice_t = min(max(remaining, 0.05), self.BLOCKING_SLICE_S)
            try:
                status, out = self._roundtrip(
                    Op.GET, [self._k(key), itob(int(slice_t * 1000))],
                    io_timeout=slice_t + 10.0, park_s=slice_t,
                )
            except StoreTimeout:
                # socket-level stall on ONE slice (server event-loop pause,
                # fsync storm): GET is idempotent and the CALLER's budget is
                # what matters — keep slicing until it runs out
                if remaining <= self.BLOCKING_SLICE_S:
                    raise StoreTimeout(f"get({key}) timed out after {t}s")
                continue
            if status == Status.OK:
                return out[0]
            if status == Status.TIMEOUT:
                if remaining <= self.BLOCKING_SLICE_S:
                    raise StoreTimeout(f"get({key}) timed out after {t}s")
                continue
            raise StoreError(f"get({key}) -> {status.name}")

    def try_get(self, key) -> Optional[bytes]:
        status, out = self._roundtrip(Op.TRY_GET, [self._k(key)], self.timeout)
        if status == Status.KEY_MISS:
            return None
        if status != Status.OK:
            raise StoreError(f"try_get({key}) -> {status.name}")
        return out[0]

    def add(self, key, amount: int = 1) -> int:
        status, out = self._roundtrip(Op.ADD, [self._k(key), itob(amount)], self.timeout)
        if status != Status.OK:
            raise StoreError(f"add({key}) -> {status.name}")
        return int(out[0])

    def append(self, key, value) -> int:
        status, out = self._roundtrip(Op.APPEND, [self._k(key), self._v(value)], self.timeout)
        if status != Status.OK:
            raise StoreError(f"append({key}) -> {status.name}")
        return int(out[0])

    def compare_set(self, key, expected, desired) -> bytes:
        """CAS. expected=b'' means set-if-absent. Returns value after the op."""
        return self.compare_set_ex(key, expected, desired)[1]

    def compare_set_ex(self, key, expected, desired) -> tuple[bool, bytes]:
        """CAS exposing whether the swap was APPLIED: ``(True, desired)`` on
        success, ``(False, current)`` on mismatch.  ``compare_set`` loses the
        distinction whenever ``desired`` equals the pre-existing value (e.g.
        idempotent set-if-absent markers), which reentrancy protocols need."""
        status, out = self._roundtrip(
            Op.COMPARE_SET,
            [self._k(key), self._v(expected), self._v(desired)],
            self.timeout,
        )
        if status == Status.OK:
            return True, out[0]
        if status == Status.CAS_FAIL:
            return False, out[0]  # current (b"" if absent and expected != "")
        raise StoreError(f"compare_set({key}) -> {status.name}")

    def wait(self, keys: Sequence, timeout: Optional[float] = None) -> None:
        t = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + t
        wire_keys = [self._k(k) for k in keys]
        while True:
            remaining = deadline - time.monotonic()
            slice_t = min(max(remaining, 0.05), self.BLOCKING_SLICE_S)
            args = [itob(int(slice_t * 1000))] + wire_keys
            try:
                status, _ = self._roundtrip(
                    Op.WAIT, args, io_timeout=slice_t + 10.0, park_s=slice_t
                )
            except StoreTimeout:
                if remaining <= self.BLOCKING_SLICE_S:
                    raise StoreTimeout(
                        f"wait({list(keys)}) timed out after {t}s"
                    )
                continue
            if status == Status.OK:
                return
            if status == Status.TIMEOUT:
                if remaining <= self.BLOCKING_SLICE_S:
                    raise StoreTimeout(
                        f"wait({list(keys)}) timed out after {t}s"
                    )
                continue
            raise StoreError(f"wait -> {status.name}")

    def check(self, keys: Sequence) -> bool:
        status, out = self._roundtrip(Op.CHECK, [self._k(k) for k in keys], self.timeout)
        if status != Status.OK:
            raise StoreError(f"check -> {status.name}")
        return out[0] == b"1"

    def delete(self, key) -> bool:
        status, out = self._roundtrip(Op.DELETE, [self._k(key)], self.timeout)
        if status != Status.OK:
            raise StoreError(f"delete({key}) -> {status.name}")
        return out[0] == b"1"

    def num_keys(self) -> int:
        status, out = self._roundtrip(Op.NUM_KEYS, [], self.timeout)
        if status != Status.OK:
            raise StoreError(f"num_keys -> {status.name}")
        return int(out[0])

    def list_keys(self, prefix="") -> List[bytes]:
        status, out = self._roundtrip(Op.LIST_KEYS, [self._k(prefix)], self.timeout)
        if status != Status.OK:
            raise StoreError(f"list_keys -> {status.name}")
        return out

    def multi_set(self, items: dict) -> None:
        args: List[bytes] = []
        for k, v in items.items():
            args += [self._k(k), self._v(v)]
        status, _ = self._roundtrip(Op.MULTI_SET, args, self.timeout)
        if status != Status.OK:
            raise StoreError(f"multi_set -> {status.name}")

    def multi_get(self, keys: Sequence) -> List[Optional[bytes]]:
        """One round trip for many keys, with **per-key** misses: the result
        holds ``None`` at each absent key's position (the historical
        all-or-nothing ``None`` return hid WHICH key was missing, so callers
        could only report "payload vanished" without a culprit)."""
        status, out = self._roundtrip(
            Op.MULTI_TRY_GET, [self._k(k) for k in keys], self.timeout
        )
        if status != Status.OK:
            raise StoreError(f"multi_get -> {status.name}")
        return [
            out[i + 1] if out[i] == b"1" else None
            for i in range(0, len(out), 2)
        ]

    # -- one-RTT protocol ops ---------------------------------------------
    # Both keys of each op must live on the same server; the sharded client
    # asserts that via affinity groups before delegating here.

    def append_check(
        self, key, value, done_key, done_value,
        required: int = 0, tokens: Sequence = (),
    ) -> tuple[int, bool]:
        """Append ``value`` to ``key`` AND set ``done_key`` server-side when
        the arrival population is complete — one round trip, no crash window
        between a completer's append and its done-set.  With ``tokens`` the
        population is that exact set; otherwise ``required`` distinct
        comma-separated tokens.  Returns ``(new_log_len, done)``."""
        args = [
            self._k(key), self._v(value), self._k(done_key),
            self._v(done_value), itob(required),
        ] + [self._v(t) for t in tokens]
        status, out = self._roundtrip(Op.APPEND_CHECK, args, self.timeout)
        if status != Status.OK:
            raise StoreError(f"append_check({key}) -> {status.name}")
        return int(out[0]), out[1] == b"1"

    def add_set(self, add_key, amount: int, set_key, set_value) -> int:
        """Atomic counter bump + record write in one round trip.  The first
        :data:`~tpu_resiliency.store.protocol.ADD_SLOT` marker in
        ``set_value`` is replaced server-side by the post-add counter (ASCII
        decimal).  Returns the new counter value."""
        status, out = self._roundtrip(
            Op.ADD_SET,
            [self._k(add_key), itob(amount), self._k(set_key),
             self._v(set_value)],
            self.timeout,
        )
        if status != Status.OK:
            raise StoreError(f"add_set({add_key}) -> {status.name}")
        return int(out[0])

    def wait_ge(self, key, threshold: int,
                timeout: Optional[float] = None) -> int:
        """Block until ``key`` holds an integer >= ``threshold`` (missing key
        counts as 0).  Sliced like :meth:`get` so liveness stamps keep
        flowing.  Returns the value observed."""
        t = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + t
        wire = [self._k(key), itob(threshold)]
        while True:
            remaining = deadline - time.monotonic()
            slice_t = min(max(remaining, 0.05), self.BLOCKING_SLICE_S)
            try:
                status, out = self._roundtrip(
                    Op.WAIT_GE, wire + [itob(int(slice_t * 1000))],
                    io_timeout=slice_t + 10.0, park_s=slice_t,
                )
            except StoreTimeout:
                if remaining <= self.BLOCKING_SLICE_S:
                    raise StoreTimeout(
                        f"wait_ge({key}, {threshold}) timed out after {t}s"
                    )
                continue
            if status == Status.OK:
                return int(out[0])
            if status == Status.TIMEOUT:
                if remaining <= self.BLOCKING_SLICE_S:
                    raise StoreTimeout(
                        f"wait_ge({key}, {threshold}) timed out after {t}s"
                    )
                continue
            raise StoreError(f"wait_ge({key}) -> {status.name}")


class PrefixStore:
    """Key-namespace wrapper (equivalent of torch's PrefixStore, used for the
    per-iteration namespaces in ``inprocess/wrap.py:512``)."""

    def __init__(self, prefix: str, store):
        self._prefix = prefix.rstrip("/") + "/"
        self._store = store

    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def base(self):
        return self._store

    def _p(self, key) -> str:
        key = key.decode() if isinstance(key, bytes) else key
        return self._prefix + key

    def clone(self) -> "PrefixStore":
        return PrefixStore(self._prefix, self._store.clone())

    def close(self) -> None:
        self._store.close()

    @property
    def timeout(self) -> float:
        return self._store.timeout

    def ping(self) -> bool:
        return self._store.ping()

    def set(self, key, value) -> None:
        return self._store.set(self._p(key), value)

    def get(self, key, timeout: Optional[float] = None) -> bytes:
        return self._store.get(self._p(key), timeout)

    def try_get(self, key) -> Optional[bytes]:
        return self._store.try_get(self._p(key))

    def add(self, key, amount: int = 1) -> int:
        return self._store.add(self._p(key), amount)

    def append(self, key, value) -> int:
        return self._store.append(self._p(key), value)

    def compare_set(self, key, expected, desired) -> bytes:
        return self._store.compare_set(self._p(key), expected, desired)

    def compare_set_ex(self, key, expected, desired):
        return self._store.compare_set_ex(self._p(key), expected, desired)

    def wait(self, keys: Sequence, timeout: Optional[float] = None) -> None:
        return self._store.wait([self._p(k) for k in keys], timeout)

    def check(self, keys: Sequence) -> bool:
        return self._store.check([self._p(k) for k in keys])

    def delete(self, key) -> bool:
        return self._store.delete(self._p(key))

    def num_keys(self) -> int:
        return self._store.num_keys()

    def list_keys(self, prefix="") -> List[bytes]:
        p = prefix.decode() if isinstance(prefix, bytes) else prefix
        return self._store.list_keys(self._prefix + p)

    def multi_set(self, items: dict) -> None:
        return self._store.multi_set({self._p(k): v for k, v in items.items()})

    def multi_get(self, keys: Sequence):
        return self._store.multi_get([self._p(k) for k in keys])

    def append_check(self, key, value, done_key, done_value,
                     required: int = 0, tokens: Sequence = ()):
        return self._store.append_check(
            self._p(key), value, self._p(done_key), done_value,
            required, tokens,
        )

    def add_set(self, add_key, amount: int, set_key, set_value) -> int:
        return self._store.add_set(
            self._p(add_key), amount, self._p(set_key), set_value
        )

    def wait_ge(self, key, threshold: int,
                timeout: Optional[float] = None) -> int:
        return self._store.wait_ge(self._p(key), threshold, timeout)


class FailoverStoreClient(StoreClient):
    """Client over an ordered list of store endpoints.

    Reference analog: the TCPStore-with-host-failover subclass
    (``inprocess/store.py:358-366``).  When the current endpoint is
    unreachable past the normal retry budget, the client advances to the
    next endpoint (wrapping).  Like the reference, failover is about
    *availability*, not durability: a replacement store starts empty, which
    coordination protocols tolerate (a fresh rendezvous round forms); bulk
    state (checkpoints) never lives in the store.
    """

    def __init__(self, endpoints, timeout: float = _DEFAULT_TIMEOUT, **kwargs):
        self.endpoints = [
            (h, int(p))
            for h, p in (
                e.rsplit(":", 1) if isinstance(e, str) else e for e in endpoints
            )
        ]
        if not self.endpoints:
            raise ValueError("need at least one endpoint")
        self._endpoint_idx = 0
        host, port = self.endpoints[0]
        super().__init__(host, port, timeout=timeout, **kwargs)

    def clone(self) -> "FailoverStoreClient":
        return FailoverStoreClient(
            [f"{h}:{p}" for h, p in self.endpoints], timeout=self.timeout
        )

    def _on_brownout(self) -> None:
        # brownout-specific failover: the wedged listener accepts happily,
        # so endpoint rotation must happen HERE, not in _connect's
        # unreachable-endpoint walk
        flight.record(EV_FAILOVER, f"{self.host}:{self.port} brownout")
        self._endpoint_idx = (self._endpoint_idx + 1) % len(self.endpoints)

    def _connect(self, connect_timeout: float) -> None:
        last_exc: Optional[Exception] = None
        endpoints = getattr(self, "endpoints", None)
        if endpoints is None:  # during base __init__
            return super()._connect(connect_timeout)
        per_endpoint = max(2.0, connect_timeout / len(endpoints))
        for attempt in range(len(endpoints)):
            self.host, self.port = endpoints[self._endpoint_idx]
            if attempt:
                # not the preferred endpoint anymore: an actual failover
                flight.record(EV_FAILOVER, f"{self.host}:{self.port}")
            try:
                super()._connect(per_endpoint)
                return
            except StoreError as exc:
                last_exc = exc
                self._endpoint_idx = (self._endpoint_idx + 1) % len(endpoints)
        raise StoreError(f"no store endpoint reachable: {last_exc}")


def store_from_env(timeout: float = _DEFAULT_TIMEOUT) -> StoreClient:
    """Connect using TPURX_STORE_ADDR / TPURX_STORE_PORT env (set by
    launcher); TPURX_STORE_SHARDS="h1:p1,h2:p2" selects the sharded client
    (consistent-hash routing, per-shard failover);
    TPURX_STORE_ENDPOINTS="h1:p1,h2:p2" enables serial failover."""
    shards = env.STORE_SHARDS.get()
    if shards:
        from .sharding import ShardedStoreClient  # local: avoids a cycle

        return ShardedStoreClient(
            [e.strip() for e in shards.split(",") if e.strip()],
            timeout=timeout,
        )
    endpoints = env.STORE_ENDPOINTS.get()
    if endpoints:
        return FailoverStoreClient(
            [e.strip() for e in endpoints.split(",") if e.strip()], timeout=timeout
        )
    return StoreClient(
        env.STORE_ADDR.get(), env.STORE_PORT.get(), timeout=timeout
    )
