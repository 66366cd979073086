"""Native (C++) store server loader.

Builds ``native/store_server.cpp`` on first use (``utils/native.py``: cached
binary, rebuilt when the source changed) and runs it as a subprocess.  Same
wire protocol, same client — the native server is a drop-in for the asyncio
one where control-plane latency/fan-in matters (rendezvous CAS storms at pod
scale).
"""

from __future__ import annotations

import os
import re
import subprocess
import time
from typing import Optional

from ..utils.logging import get_logger
from ..utils.native import ensure_built
from ..utils.retry import Retrier, RetryExhausted, RetryPolicy

log = get_logger("store.native")

# fixed-cadence startup poll: jitter is pointless against a local child
_STARTUP_POLL = RetryPolicy(max_attempts=None, base_delay=0.05, max_delay=0.05,
                            min_delay_fraction=1.0)


def build_native_server() -> str:
    """Compile the native server unless the binary on disk was built from
    the current source (utils/native.py stamps it); returns its path."""
    return ensure_built("tpurx-store-server")[0]


class NativeStoreServer:
    """Runs the C++ server as a child process (same surface as StoreServer)."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0,
                 journal: Optional[str] = None,
                 journal_strip_prefixes: Optional[list] = None):
        self.host = host
        self.port = port
        self.journal = journal
        self.journal_strip_prefixes = journal_strip_prefixes or []
        self.replayed_keys = 0
        self._proc: Optional[subprocess.Popen] = None

    def start(self, timeout: float = 15.0) -> "NativeStoreServer":
        import select

        binary = build_native_server()
        cmd = [binary, "--host", self.host, "--port", str(self.port)]
        if self.journal:
            cmd += ["--journal", self.journal]
            for prefix in self.journal_strip_prefixes:
                p = prefix.decode() if isinstance(prefix, bytes) else prefix
                cmd += ["--strip-prefix", p]
        self._proc = subprocess.Popen(cmd, stderr=subprocess.PIPE)
        try:
            # the server prints "... listening on <host>:<port>" once bound
            # (journal replay lines may precede it).  Read the RAW fd with a
            # manual line buffer: select() + TextIOWrapper.readline() loses
            # lines that arrived in the same read (buffered in Python, fd
            # empty -> select times out even though the line is waiting).
            deadline_t = time.monotonic() + timeout
            fd = self._proc.stderr.fileno()
            buf = b""
            m = None
            last_line = b""
            while time.monotonic() < deadline_t and m is None:
                ready, _, _ = select.select(
                    [fd], [], [], max(0.0, deadline_t - time.monotonic()),
                )
                if not ready:
                    break
                chunk = os.read(fd, 4096)
                if not chunk:
                    break
                buf += chunk
                while b"\n" in buf and m is None:
                    line, _, buf = buf.partition(b"\n")
                    last_line = line
                    text_line = line.decode(errors="replace")
                    jm = re.search(r"journal restored (\d+) key", text_line)
                    if jm:
                        self.replayed_keys = int(jm.group(1))
                    m = re.search(r"listening on \S+:(\d+)", text_line)
            if not m:
                raise RuntimeError(
                    f"native store server failed to start: {last_line!r}"
                )
            self.port = int(m.group(1))
            from .client import StoreClient, StoreError

            retrier = Retrier("native_store_start", _STARTUP_POLL, deadline=timeout)
            while True:
                if self._proc.poll() is not None:
                    raise RuntimeError("native store server exited at startup")
                try:
                    StoreClient("127.0.0.1", self.port, connect_timeout=1.0).close()
                    self._drain_stderr()
                    return self
                except (StoreError, OSError) as exc:
                    try:
                        retrier.backoff(exc)
                    except RetryExhausted:
                        raise RuntimeError(
                            "native store server did not accept connections"
                        ) from exc
        except BaseException:
            self.stop()  # never leak the child holding the port
            raise

    def _drain_stderr(self) -> None:
        """The journal logs (compaction, disable) after startup; an undrained
        64KB pipe would eventually block the server's event loop.  Raw-fd
        reads, matching start()'s parser (the TextIOWrapper is unused)."""
        import threading

        fd = self._proc.stderr.fileno()

        def drain():
            buf = b""
            try:
                while True:
                    chunk = os.read(fd, 4096)
                    if not chunk:
                        return
                    buf += chunk
                    while b"\n" in buf:
                        line, _, buf = buf.partition(b"\n")
                        log.info("native store: %s",
                                 line.decode(errors="replace"))
            except (OSError, ValueError):
                pass

        threading.Thread(
            target=drain, name="tpurx-native-store-stderr", daemon=True
        ).start()

    # parity with StoreServer
    start_in_thread = start

    def stop(self) -> None:
        if self._proc is not None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self._proc.kill()
            self._proc = None
