"""Asyncio KV store server.

TPU-native equivalent of hosting a ``TCPStore`` (reference:
``fault_tolerance/c10d_monkey_patch.py:112`` creates it;
``inprocess/store.py:324-366`` hosts it with failover).  Single-threaded
asyncio: every mutation is atomic with respect to other requests, which gives
us the compare_set / add atomicity the rendezvous protocol relies on without
locks.  Blocking ops (GET/WAIT) park an ``asyncio.Event`` per key.

Run standalone:  python -m tpu_resiliency.store.server --port 29500
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal
import struct
import threading
import time
from typing import Dict, List, Optional, Set

from ..utils import env
from ..utils.logging import get_logger
from .protocol import ADD_SLOT, Op, Status, encode_response, itob

log = get_logger("store.server")

_U32 = struct.Struct("<I")


class StoreServer:
    """In-memory KV store with blocking waits, served over TCP.

    With ``journal_path`` every mutation is also appended to an on-disk
    journal (key-state records, crash-tolerant replay, periodic fsync,
    snapshot compaction).  A restarted control plane re-hosting the store
    from the same journal keeps all rendezvous state — cycle numbering,
    round counters, learned timeouts — instead of starting the world from
    zero (reference keeps this state inside the long-lived rendezvous host
    process; our store host is restartable by design, hence the journal).
    """

    def __init__(
        self,
        host: str = "0.0.0.0",
        port: int = 0,
        journal_path: Optional[str] = None,
        journal_max_bytes: int = 64 << 20,
        journal_fsync_interval: float = 1.0,
        journal_strip_prefixes: Optional[List[bytes]] = None,
    ):
        self.host = host
        self.port = port
        self._data: Dict[bytes, bytes] = {}
        self._waiters: Dict[bytes, Set[asyncio.Event]] = {}
        self._server: Optional[asyncio.base_events.Server] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()
        self.journal_path = journal_path
        self.journal_max_bytes = journal_max_bytes
        self.journal_fsync_interval = journal_fsync_interval
        # keys matching these prefixes are dropped during replay, BEFORE the
        # listener opens — terminal state from the previous job (shutdown
        # flag + acks) must never be observable by a new job's agents
        self.journal_strip_prefixes = journal_strip_prefixes or []
        self._journal_file = None
        self._journal_lock_fd: Optional[int] = None
        self._journal_bytes = 0
        self._journal_compact_at = journal_max_bytes
        self._journal_dirty = False
        self._fsync_task: Optional[asyncio.Task] = None
        self._compact_task: Optional[asyncio.Task] = None
        # while a compaction snapshot is being written off-loop, new records
        # land here and are flushed to the fresh journal after the swap
        self._compact_buffer: Optional[List[bytes]] = None
        self.replayed_keys = 0
        # TEST-ONLY brownout mode (TPURX_STORE_TEST_BROWNOUT): accept
        # connections and read requests but never answer — the fault class
        # where a server looks alive at the TCP layer while its serving
        # loop is wedged.  Clients must escape via per-op deadlines.
        self.test_brownout = bool(env.STORE_TEST_BROWNOUT.get())

    # -- journal -----------------------------------------------------------
    # Record formats (final-state records; replay order reconstructs _data):
    #   b"S" u32(klen) key u32(vlen) value     -- key set to value
    #   b"D" u32(klen) key                     -- key deleted

    def _open_journal(self) -> None:
        if not self.journal_path:
            return
        # Exclusive lockfile for the server's lifetime: two instances on one
        # journal would interleave appends and orphan each other's fd at the
        # compaction os.replace — losing exactly the state the journal
        # exists to preserve.  A sidecar lockfile (not the journal fd) stays
        # valid across the inode swap compaction performs.
        import fcntl

        lock_path = self.journal_path + ".lock"
        self._journal_lock_fd = os.open(lock_path, os.O_CREAT | os.O_RDWR, 0o644)
        try:
            fcntl.flock(self._journal_lock_fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            os.close(self._journal_lock_fd)
            self._journal_lock_fd = None
            raise RuntimeError(
                f"journal {self.journal_path} is locked by another store "
                f"instance (stale control plane still running?)"
            )
        good = 0
        try:
            with open(self.journal_path, "rb") as f:
                buf = f.read()
            good = self._replay(buf)
        except OSError:
            buf = b""
        if good < len(buf):
            log.warning(
                "journal %s: truncated/garbled tail at byte %d of %d "
                "(crash mid-write); discarding the tail",
                self.journal_path, good, len(buf),
            )
        self.replayed_keys = len(self._data)
        if self.replayed_keys:
            log.info(
                "journal %s: restored %d key(s)",
                self.journal_path, self.replayed_keys,
            )
        self._journal_file = open(self.journal_path, "ab")
        if good < len(buf):
            self._journal_file.truncate(good)
        self._journal_bytes = good
        self._journal_compact_at = self.journal_max_bytes
        for prefix in self.journal_strip_prefixes:
            for key in [k for k in self._data if k.startswith(prefix)]:
                del self._data[key]
                self._journal_append(key, None)  # D record: stays stripped
                self.replayed_keys -= 1

    def _replay(self, buf: bytes) -> int:
        """Apply journal records to ``_data``; returns the offset of the last
        complete record (a crash mid-append leaves a partial tail)."""
        i, n, good = 0, len(buf), 0
        while i < n:
            tag = buf[i:i + 1]
            if tag == b"S":
                if i + 5 > n:
                    break
                (kl,) = _U32.unpack_from(buf, i + 1)
                if i + 5 + kl + 4 > n:
                    break
                key = buf[i + 5:i + 5 + kl]
                (vl,) = _U32.unpack_from(buf, i + 5 + kl)
                end = i + 9 + kl + vl
                if end > n:
                    break
                self._data[key] = buf[i + 9 + kl:end]
                i = end
            elif tag == b"D":
                if i + 5 > n:
                    break
                (kl,) = _U32.unpack_from(buf, i + 1)
                end = i + 5 + kl
                if end > n:
                    break
                self._data.pop(buf[i + 5:end], None)
                i = end
            else:
                break
            good = i
        return good

    @staticmethod
    def _encode_record(key: bytes, value: Optional[bytes]) -> bytes:
        if value is None:
            return b"D" + _U32.pack(len(key)) + key
        return b"S" + _U32.pack(len(key)) + key + _U32.pack(len(value)) + value

    def _disable_journal(self) -> None:
        """Best-effort close before dropping the reference — otherwise the fd
        leaks for the process lifetime and stop()'s final fsync is skipped."""
        f, self._journal_file = self._journal_file, None
        if f is not None:
            try:
                f.close()
            except (OSError, ValueError):
                pass

    def _journal_append(self, key: bytes, value: Optional[bytes]) -> None:
        if self._journal_file is None:
            return
        rec = self._encode_record(key, value)
        if self._compact_buffer is not None:
            # A compaction snapshot is being written off-loop.  The record
            # buffers in memory (it lands on the fresh journal before the
            # swap) AND is appended to the OLD journal, which stays the
            # authoritative replay source until the os.replace: a SIGKILL
            # mid-snapshot must not lose mutations that were acked while the
            # snapshot was being written.
            self._compact_buffer.append(rec)
            try:
                self._journal_file.write(rec)
                self._journal_file.flush()
            except (OSError, ValueError):
                log.exception("journal write failed; disabling journal")
                self._disable_journal()
            return
        try:
            self._journal_file.write(rec)
            self._journal_file.flush()
        except OSError:
            log.exception("journal write failed; disabling journal")
            self._disable_journal()
            return
        self._journal_bytes += len(rec)
        self._journal_dirty = True
        self._maybe_rearm_compaction()

    def _maybe_rearm_compaction(self) -> None:
        if (
            self._journal_file is not None
            and self._journal_bytes > self._journal_compact_at
            and self._loop is not None
            and self._compact_task is None
        ):
            self._compact_task = self._loop.create_task(self._compact_journal())

    async def _compact_journal(self) -> None:
        """Rewrite the journal as a snapshot of the live data.  The snapshot
        write + fsync (potentially tens of MB) runs in an executor so store
        traffic — rendezvous waits, heartbeat reads — is never stalled behind
        the disk; mutations made meanwhile buffer in memory and are appended
        to the fresh journal after the atomic swap."""
        tmp = self.journal_path + ".tmp"
        snapshot = list(self._data.items())
        self._compact_buffer = []
        # test-only fault hook: die after writing N snapshot records, so the
        # crash-consistency suite can SIGKILL-equivalent the server exactly
        # mid-``write_snapshot`` (the soak harness's fault-injection idiom)
        crash_after = env.STORE_TEST_COMPACT_CRASH.get()

        def write_snapshot() -> int:
            written = 0
            with open(tmp, "wb") as f:
                for key, value in snapshot:
                    f.write(self._encode_record(key, value))
                    written += 1
                    if crash_after is not None and written >= int(crash_after):
                        f.flush()
                        os._exit(137)
                f.flush()
                os.fsync(f.fileno())
                return f.tell()

        try:
            snapshot_bytes = await self._loop.run_in_executor(None, write_snapshot)
            # Complete the NEW journal before it becomes authoritative: the
            # records acked during the snapshot (buffered above, and already
            # crash-safe on the old journal) are appended to the snapshot
            # file BEFORE the swap, so a crash on either side of os.replace
            # leaves one journal holding every acked mutation.  This runs
            # inline on the single-threaded loop — atomic wrt requests.
            buffered = b"".join(self._compact_buffer)
            if buffered:
                with open(tmp, "ab") as f:
                    f.write(buffered)
                    f.flush()
                    os.fsync(f.fileno())
            self._journal_file.close()
            os.replace(tmp, self.journal_path)
            self._journal_file = open(self.journal_path, "ab")
            self._journal_bytes = self._journal_file.tell()
            # when the live snapshot itself exceeds the cap, compacting on
            # every subsequent mutation would rewrite O(total state) per SET;
            # re-arm only at 2x the snapshot size (NOT snapshot + the records
            # buffered during this compaction — those are rewrite-able churn
            # and must not inflate the trigger)
            self._journal_compact_at = max(
                self.journal_max_bytes, 2 * snapshot_bytes
            )
            log.info(
                "journal compacted to %d bytes (%d keys)",
                self._journal_bytes, len(snapshot),
            )
            if self._journal_bytes > self._journal_compact_at:
                # a mutation burst landed while the snapshot was being
                # written; those buffered records bypassed the append-path
                # size trigger, so chain a follow-up compaction now
                self._loop.call_soon(self._maybe_rearm_compaction)
        except asyncio.CancelledError:
            # server stopping mid-snapshot: the buffered records were already
            # appended to the OLD journal (still authoritative) as they
            # arrived — one fsync and the acked mutations survive the restart
            self._compact_buffer = None
            if self._journal_file is not None:
                try:
                    self._journal_file.flush()
                    os.fsync(self._journal_file.fileno())
                except (OSError, ValueError):
                    pass
            raise
        except OSError:
            log.exception("journal compaction failed; disabling journal")
            self._disable_journal()
        finally:
            self._compact_buffer = None
            self._compact_task = None

    async def _fsync_loop(self) -> None:
        import errno

        while True:
            await asyncio.sleep(self.journal_fsync_interval)
            if (
                not self._journal_dirty
                or self._journal_file is None
                or self._compact_task is not None  # compaction fsyncs itself
            ):
                continue
            self._journal_dirty = False
            fd = self._journal_file.fileno()
            try:
                # off-loop: a slow disk (NFS, EIO retry storm) must not stall
                # every GET/WAIT the control plane is serving
                await self._loop.run_in_executor(None, os.fsync, fd)
            except ValueError:
                continue  # file swapped mid-flush by compaction: benign
            except OSError as exc:
                if exc.errno == errno.EBADF:
                    continue  # fd closed under us by compaction: benign
                # after a failed fsync the kernel may have dropped the dirty
                # pages: acking further writes would be silent data loss
                log.exception("journal fsync failed; disabling journal")
                self._disable_journal()
                return

    # -- storage ops (run on the event loop; atomic wrt each other) --------

    def _notify(self, key: bytes) -> None:
        for ev in self._waiters.pop(key, set()):
            ev.set()

    def _set(self, key: bytes, value: bytes) -> None:
        self._data[key] = value
        self._journal_append(key, value)
        self._notify(key)

    async def _wait_for_keys(self, keys: List[bytes], timeout_ms: int) -> Status:
        deadline = time.monotonic() + timeout_ms / 1000.0
        for key in keys:
            while key not in self._data:
                ev = asyncio.Event()
                self._waiters.setdefault(key, set()).add(ev)
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._waiters.get(key, set()).discard(ev)
                    return Status.TIMEOUT
                try:
                    await asyncio.wait_for(ev.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    self._waiters.get(key, set()).discard(ev)
                    return Status.TIMEOUT
                except asyncio.CancelledError:
                    # subscription cancelled (connection closed mid-park):
                    # un-park the event so never-set keys don't accumulate
                    # dead waiters
                    self._waiters.get(key, set()).discard(ev)
                    raise
        return Status.OK

    async def _handle_request(self, op: int, args: List[bytes]) -> bytes:
        data = self._data
        if op == Op.SET:
            self._set(args[0], args[1])
            return encode_response(Status.OK)
        if op == Op.TRY_GET:
            val = data.get(args[0])
            if val is None:
                return encode_response(Status.KEY_MISS)
            return encode_response(Status.OK, val)
        if op == Op.GET:
            key, timeout_ms = args[0], int(args[1])
            status = await self._wait_for_keys([key], timeout_ms)
            if status != Status.OK:
                return encode_response(status)
            return encode_response(Status.OK, data[key])
        if op == Op.ADD:
            key, amount = args[0], int(args[1])
            new = int(data.get(key, b"0")) + amount
            self._set(key, itob(new))
            return encode_response(Status.OK, itob(new))
        if op == Op.APPEND:
            key = args[0]
            new = data.get(key, b"") + args[1]
            self._set(key, new)
            return encode_response(Status.OK, itob(len(new)))
        if op == Op.COMPARE_SET:
            key, expected, desired = args
            current = data.get(key)
            if (current is None and expected == b"") or current == expected:
                self._set(key, desired)
                return encode_response(Status.OK, desired)
            return encode_response(Status.CAS_FAIL, current if current is not None else b"")
        if op == Op.WAIT:
            timeout_ms = int(args[0])
            status = await self._wait_for_keys(list(args[1:]), timeout_ms)
            return encode_response(status)
        if op == Op.CHECK:
            ok = all(k in data for k in args)
            return encode_response(Status.OK, b"1" if ok else b"0")
        if op == Op.DELETE:
            existed = args[0] in data
            if existed:
                data.pop(args[0], None)
                self._journal_append(args[0], None)
            return encode_response(Status.OK, b"1" if existed else b"0")
        if op == Op.NUM_KEYS:
            return encode_response(Status.OK, itob(len(data)))
        if op == Op.PING:
            return encode_response(Status.OK, b"pong")
        if op == Op.LIST_KEYS:
            prefix = args[0]
            keys = [k for k in data if k.startswith(prefix)]
            return encode_response(Status.OK, *keys)
        if op == Op.MULTI_SET:
            for i in range(0, len(args), 2):
                self._set(args[i], args[i + 1])
            return encode_response(Status.OK)
        if op == Op.MULTI_GET:
            vals = []
            for k in args:
                v = data.get(k)
                if v is None:
                    return encode_response(Status.KEY_MISS, k)
                vals.append(v)
            return encode_response(Status.OK, *vals)
        if op == Op.MULTI_TRY_GET:
            pairs: List[bytes] = []
            for k in args:
                v = data.get(k)
                if v is None:
                    pairs += [b"0", b""]
                else:
                    pairs += [b"1", v]
            return encode_response(Status.OK, *pairs)
        if op == Op.APPEND_CHECK:
            # one-RTT barrier arrival: append to the shared log AND set the
            # done key when the participant population is complete — the
            # append and the completion check are one atomic step, so the
            # crash window between a completer's APPEND and its done-SET
            # cannot exist
            key, value, done_key, done_value = args[0], args[1], args[2], args[3]
            required = int(args[4])
            tokens = args[5:]
            new = data.get(key, b"") + value
            self._set(key, new)
            seen = {tok for tok in new.split(b",") if tok}
            if tokens:  # narrowed participant set: exact membership
                done = all(t in seen for t in tokens)
            else:  # full population: distinct-token count (dedup re-entries)
                done = len(seen) >= required
            if done:
                self._set(done_key, done_value)
            return encode_response(
                Status.OK, itob(len(new)), b"1" if done else b"0"
            )
        if op == Op.ADD_SET:
            # one-RTT rendezvous join: counter bump + record write in one
            # trip, splicing the post-add value into the record (the arrival
            # number only the server knows)
            add_key, amount = args[0], int(args[1])
            set_key, set_value = args[2], args[3]
            new_count = int(data.get(add_key, b"0")) + amount
            self._set(add_key, itob(new_count))
            self._set(set_key, set_value.replace(ADD_SLOT, itob(new_count), 1))
            return encode_response(Status.OK, itob(new_count))
        if op == Op.WAIT_GE:
            key, threshold, timeout_ms = args[0], int(args[1]), int(args[2])
            deadline = time.monotonic() + timeout_ms / 1000.0
            while True:
                cur = int(data.get(key) or b"0")
                if cur >= threshold:
                    return encode_response(Status.OK, itob(cur))
                ev = asyncio.Event()
                self._waiters.setdefault(key, set()).add(ev)
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._waiters.get(key, set()).discard(ev)
                    return encode_response(Status.TIMEOUT)
                try:
                    await asyncio.wait_for(ev.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    self._waiters.get(key, set()).discard(ev)
                    return encode_response(Status.TIMEOUT)
                except asyncio.CancelledError:
                    self._waiters.get(key, set()).discard(ev)
                    raise
        return encode_response(Status.ERROR, b"unknown op")

    # -- connection handling ----------------------------------------------

    async def _read_exact(self, reader: asyncio.StreamReader, n: int) -> bytes:
        return await reader.readexactly(n)

    async def _handle_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        try:
            while True:
                header = await reader.read(1)
                if not header:
                    break
                (nargs,) = _U32.unpack(await self._read_exact(reader, 4))
                if nargs > 1 << 20:  # sanity caps match the native server
                    log.warning("dropping connection: absurd nargs %d", nargs)
                    break
                args = []
                for _ in range(nargs):
                    (ln,) = _U32.unpack(await self._read_exact(reader, 4))
                    if ln > 1 << 30:
                        log.warning("dropping connection: absurd arg len %d", ln)
                        nargs = -1
                        break
                    args.append(await self._read_exact(reader, ln) if ln else b"")
                if nargs == -1:
                    break
                try:
                    # a well-framed request with an opcode this server does
                    # not serve (a retired one, a newer client's) falls off
                    # the dispatch's end: ERROR, connection kept.  Garbage
                    # trips the caps above.
                    resp = await self._handle_request(header[0], args)
                except Exception as exc:  # noqa: BLE001 - report to client
                    log.exception("store op %s failed", header[0])
                    resp = encode_response(Status.ERROR, str(exc).encode())
                if self.test_brownout:
                    continue
                writer.write(resp)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError, BrokenPipeError):
            pass
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (OSError, asyncio.CancelledError):
                pass

    # -- lifecycle ---------------------------------------------------------

    async def start_async(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._open_journal()  # replay BEFORE accepting connections
        self._server = await asyncio.start_server(self._handle_conn, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        if self._journal_file is not None:
            # keep a strong reference: the loop's task set is weak, and a
            # GC'd fsync task would silently stop flushing the page cache
            self._fsync_task = self._loop.create_task(self._fsync_loop())
        self._started.set()
        log.info("store server listening on %s:%s", self.host, self.port)

    async def serve_async(self) -> None:
        await self.start_async()
        async with self._server:
            await self._server.serve_forever()

    def start_in_thread(self) -> "StoreServer":
        """Host the store on a daemon thread (used by launchers and tests)."""
        self._start_error: Optional[BaseException] = None

        def _run():
            try:
                asyncio.run(self.serve_async())
            except asyncio.CancelledError:
                pass
            except BaseException as exc:  # noqa: BLE001 - surface to starter
                self._start_error = exc
                self._started.set()  # unblock the waiter with the real error

        self._thread = threading.Thread(target=_run, name="tpurx-store", daemon=True)
        self._thread.start()
        if not self._started.wait(timeout=10):
            raise RuntimeError("store server failed to start")
        if self._start_error is not None:
            raise self._start_error
        return self

    def stop(self) -> None:
        loop, server = self._loop, self._server
        if loop and server:
            def _close():
                server.close()
                for task in asyncio.all_tasks(loop):
                    task.cancel()
            try:
                loop.call_soon_threadsafe(_close)
            except RuntimeError:
                pass
        if self._thread:
            self._thread.join(timeout=5)
        if self._journal_file is not None:
            try:
                os.fsync(self._journal_file.fileno())
                self._journal_file.close()
            except (OSError, ValueError):
                pass
            self._journal_file = None
        if self._journal_lock_fd is not None:
            try:
                os.close(self._journal_lock_fd)  # releases the flock
            except OSError:
                pass
            self._journal_lock_fd = None


def serve_forever(
    host: str,
    port: int,
    journal: Optional[str] = None,
    journal_strip_prefixes: Optional[List[bytes]] = None,
    journal_max_bytes: int = 64 << 20,
) -> None:
    asyncio.run(
        StoreServer(
            host, port, journal_path=journal,
            journal_strip_prefixes=journal_strip_prefixes,
            journal_max_bytes=journal_max_bytes,
        ).serve_async()
    )


def main() -> None:
    parser = argparse.ArgumentParser(description="tpurx KV store server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=29500)
    parser.add_argument(
        "--journal", default=None,
        help="on-disk journal path: state survives a store restart",
    )
    parser.add_argument(
        "--journal-max-bytes", type=int, default=64 << 20,
        help="journal size that triggers snapshot compaction",
    )
    parser.add_argument(
        "--journal-keep-terminal", action="store_true",
        help="replay job-terminal keys (rdzv/shutdown*) too; by default they "
             "are stripped so a restarted store does not instantly terminate "
             "the next job with the previous job's shutdown flag",
    )
    args = parser.parse_args()
    signal.signal(signal.SIGTERM, lambda *_: os._exit(0))
    strip = None if args.journal_keep_terminal else [b"rdzv/shutdown"]
    serve_forever(args.host, args.port, journal=args.journal,
                  journal_strip_prefixes=strip,
                  journal_max_bytes=args.journal_max_bytes)


if __name__ == "__main__":
    main()
