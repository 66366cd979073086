"""Monitor process: an external watchdog for one training rank.

Capability parity with ``inprocess/monitor_process.py:55-437``: a detached
process (own session, so it survives the parent's crash and a killpg of the
rank) that watches the training PID and the progress-watchdog timestamp:

- soft timeout (no progress): record a SOFT_TIMEOUT interruption in the store
  so every rank's MonitorThread trips and restarts — the process lives;
- hard timeout (still no progress after the kill budget): SIGTERM then
  SIGKILL the rank (a GIL-holding or device-wedged process cannot restart
  itself) and record HARD_TIMEOUT + terminated;
- process death: record TERMINATED + mark the rank terminated.

Process model: **exec, not fork**.  The training process is JAX-threaded by
the time the wrapper starts (XLA's runtime and compile pools are live), and
forking a threaded parent is a documented deadlock class on TPU hosts; multiprocessing's spawn is no better here because it re-imports
``__main__`` in the child, re-running the training script's module-level
side effects.  Instead the parent execs a dedicated entry
(``inprocess.monitor_main``) and shares the watchdog timestamp / iteration /
enabled flags through a small NAMED shared-memory block
(:class:`MonitorSharedState`) — no pickling, no inherited interpreter state.
The monitor connects to the store with its own client (endpoint from the
store factory when introspectable, else the launcher-provided env).
"""

from __future__ import annotations

import ctypes
import os
import signal
import subprocess
import sys
import time
from typing import Optional, Tuple

from ..utils.env import force_cpu_env
from ..utils.logging import get_logger
from ..utils.shm import attach_shm, create_shm, unlink_shm

log = get_logger("monitor_process")

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")
)

# segments whose mmap stayed pinned at close (see MonitorSharedState.close)
_LEAKED_SHM: list = []


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    # a zombie (dead, unreaped by a slow parent) must count as dead — the
    # interpreter is gone even though the pid still answers signal 0
    try:
        with open(f"/proc/{pid}/stat") as f:
            state = f.read().rsplit(")", 1)[1].split()[0]
        return state != "Z"
    except (OSError, IndexError):
        return False


def _terminate_process(pid: int, grace: float) -> None:
    try:
        os.kill(pid, signal.SIGCONT)
        os.kill(pid, signal.SIGTERM)
    except OSError:
        return
    deadline = time.monotonic() + grace
    while time.monotonic() < deadline:
        if not _pid_alive(pid):
            return
        time.sleep(0.1)
    try:
        os.kill(pid, signal.SIGKILL)
    except OSError:
        pass


class MonitorSharedState:
    """Named-shm state shared between the rank and its monitor process.

    Layout (32 bytes): f64 timestamp | i64 iteration | i64 enabled |
    i64 ready.  Single-writer per field (rank writes the first three, the
    monitor writes ready); plain aligned loads/stores are atomic on the
    targets we run on.  ``timestamp_slot`` exposes a ctypes double with a
    stable address — both the ProgressWatchdog and the native pending-call
    stamper write through it.
    """

    SIZE = 32

    def __init__(self, shm, owner: bool):
        self._shm = shm
        self._owner = owner
        self.name = shm.name
        self.timestamp_slot = ctypes.c_double.from_buffer(shm.buf, 0)
        self._iteration = ctypes.c_int64.from_buffer(shm.buf, 8)
        self._enabled = ctypes.c_int64.from_buffer(shm.buf, 16)
        self._ready = ctypes.c_int64.from_buffer(shm.buf, 24)

    @classmethod
    def create(cls) -> "MonitorSharedState":
        state = cls(create_shm(cls.SIZE), owner=True)
        from ..ops.quorum import wall_time_s

        state.timestamp_slot.value = wall_time_s()
        state._enabled.value = 1
        return state

    @classmethod
    def attach(cls, name: str) -> "MonitorSharedState":
        return cls(attach_shm(name), owner=False)

    @property
    def iteration(self) -> int:
        return int(self._iteration.value)

    @iteration.setter
    def iteration(self, v: int) -> None:
        self._iteration.value = v

    @property
    def enabled(self) -> bool:
        return bool(self._enabled.value)

    @enabled.setter
    def enabled(self, v: bool) -> None:
        self._enabled.value = 1 if v else 0

    @property
    def ready(self) -> bool:
        return bool(self._ready.value)

    def mark_ready(self) -> None:
        self._ready.value = 1

    def close(self) -> None:
        if self._shm is None:
            return  # idempotent: stop() and __exit__ may both close
        # unlink first (owner): even if a pinned ctypes view keeps the
        # mapping alive, the NAME must go so nothing attaches to a dead slot
        if self._owner:
            unlink_shm(self._shm)
        # ctypes views pin the buffer — drop them before closing the mmap
        self.timestamp_slot = None
        self._iteration = None
        self._enabled = None
        self._ready = None
        try:
            self._shm.close()
        except BufferError:
            # a view escaped (the watchdog pins its slot for queued pending
            # calls): keep the object alive forever so its __del__ doesn't
            # retry close() and spray "Exception ignored" at interpreter
            # exit — process teardown unmaps anyway
            _LEAKED_SHM.append(self._shm)
        self._shm = None


def _endpoint_from_factory(store_factory) -> Optional[Tuple[str, int]]:
    """(host, port) resolution so the exec'd monitor reaches the SAME store.

    Attribute introspection first (StoreFactory / bound StoreClient expose
    host/port); opaque callables (lambdas, closures — which the old
    fork-based monitor inherited for free) are CALLED once: any factory
    returning a StoreClient yields a connected client whose host/port we
    read and close.  Only factories returning host/port-less objects fall
    through to the launcher env."""
    host = getattr(store_factory, "host", None)
    port = getattr(store_factory, "port", None)
    if isinstance(host, str) and isinstance(port, int):
        return host, port
    self_obj = getattr(store_factory, "__self__", None)
    if self_obj is not None:
        return _endpoint_from_factory(self_obj)
    try:
        client = store_factory()
    except Exception as exc:  # noqa: BLE001
        log.warning("store factory probe failed (%s); monitor will use "
                    "TPURX_STORE_* env", exc)
        return None
    try:
        host = getattr(client, "host", None)
        port = getattr(client, "port", None)
        if isinstance(host, str) and isinstance(port, int):
            return host, port
    finally:
        try:
            client.close()
        except OSError:
            pass
    return None


class MonitorProcess:
    def __init__(
        self,
        store_factory,                 # () -> StoreClient (fresh connection)
        group: str,
        rank: int,
        timestamp=None,                # unused with shared state (kept for API)
        soft_timeout: float = 60.0,
        hard_timeout: float = 90.0,
        interval: float = 1.0,
        termination_grace: float = 5.0,
        shared_state: Optional[MonitorSharedState] = None,
        fptail_name: Optional[str] = None,
    ):
        self.store_factory = store_factory
        self.group = group
        self.rank = rank
        self.soft_timeout = soft_timeout
        self.hard_timeout = hard_timeout
        self.interval = interval
        self.termination_grace = termination_grace
        self.shared = shared_state or MonitorSharedState.create()
        self._owns_shared = shared_state is None
        # named-shm dispatch tail: lets the monitor fold the rank's last K
        # dispatched programs into SOFT/HARD_TIMEOUT records even when the
        # rank is wedged in a device call (at-abort fingerprint)
        self.fptail_name = fptail_name
        if timestamp is not None:
            # A legacy mp.Value timestamp the caller keeps writing would be
            # INVISIBLE to the exec'd monitor (it reads the shm slot), and
            # the monitor would hard-kill a healthy rank at hard_timeout.
            # Fail construction instead of arming a guaranteed kill.
            raise TypeError(
                "MonitorProcess no longer accepts a 'timestamp' value — "
                "create a MonitorSharedState, pass it as shared_state, and "
                "wire ProgressWatchdog(timestamp_slot=shared.timestamp_slot)"
            )
        self._proc: Optional[subprocess.Popen] = None
        self.parent_pid = os.getpid()

    # -- parent-side control ----------------------------------------------

    def start(self) -> "MonitorProcess":
        endpoint = _endpoint_from_factory(self.store_factory)
        cmd = [
            sys.executable, "-m", "tpu_resiliency.inprocess.monitor_main",
            "--shm", self.shared.name,
            "--group", self.group,
            "--rank", str(self.rank),
            "--parent-pid", str(self.parent_pid),
            "--soft-timeout", str(self.soft_timeout),
            "--hard-timeout", str(self.hard_timeout),
            "--interval", str(self.interval),
            "--termination-grace", str(self.termination_grace),
        ]
        if self.fptail_name:
            cmd += ["--fptail", self.fptail_name]
        if endpoint is not None:
            cmd += ["--store-host", endpoint[0], "--store-port", str(endpoint[1])]
        else:
            log.info(
                "monitor store endpoint not introspectable from the factory; "
                "the monitor will use TPURX_STORE_* env"
            )
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        ).rstrip(os.pathsep)
        # the monitor is deliberately jax-free (stdlib + store client only);
        # should an import ever pull jax in, it must stay off the chip the
        # rank it watches is holding
        force_cpu_env(env)
        self._proc = subprocess.Popen(cmd, env=env)
        # Readiness handshake: the child boots a fresh interpreter (the
        # window stays generous for loaded hosts) and then connects to the
        # store; without this wait
        # the soft/hard clocks would silently include boot time and a hang
        # in the first seconds would be detected late.
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if self.shared.ready:
                return self
            if self._proc.poll() is not None:
                # hang protection was REQUESTED; running without it silently
                # would leave a wedged rank undetected for the whole job
                raise RuntimeError(
                    f"monitor process for rank {self.rank} exited "
                    f"rc={self._proc.returncode} at startup — store "
                    "endpoint unreachable from the monitor? (pass a "
                    "StoreFactory or set TPURX_STORE_*)"
                )
            time.sleep(0.02)
        log.warning(
            "monitor process for rank %s not ready after 60s — hang "
            "protection may lag", self.rank,
        )
        return self

    def set_iteration(self, iteration: int) -> None:
        self.shared.iteration = iteration

    def set_enabled(self, enabled: bool) -> None:
        """Disable hang protection during known-long phases (reference
        ``disable_hang_protection``)."""
        self.shared.enabled = enabled

    def stop(self) -> None:
        if self._proc is not None and self._proc.poll() is None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self._proc.kill()
        self._proc = None
        if self._owns_shared:
            self.shared.close()
