"""Elastic per-host launcher (``torchrun``-replacement analog).

Capability parity with ``fault_tolerance/launcher.py:300-3612``
(``LocalElasticAgent`` + ``launch_agent`` + CLI): one launcher process per TPU
host that

- forks per-rank :class:`RankMonitorServer` watchdog processes *before* any
  threads exist,
- hosts (or connects to) the KV store and performs barrier rendezvous,
- spawns one worker process per local chip/slot with the rank/cycle env,
- monitors workers + peer restarts + workload-control requests in a hot loop,
- on failure: profiling events, progress-tracker gate, restart budget, new
  rendezvous round, worker stop (SIGTERM → grace → SIGKILL), respawn,
- per-cycle log capture via pipes.

TPU-native deltas from the reference: no GPU-memory-reclaim polling (HBM is
freed when the worker process dies — the stop path's waitpid is the
equivalent gate); NUMA binding via numactl when ``numa_binding`` is set.

CLI:  python -m tpu_resiliency.fault_tolerance.launcher \
        --nnodes 1:2 --nproc-per-node 4 --rdzv-endpoint 127.0.0.1:29500 \
        [--host-store] [--ft-cfg path.yaml] [--max-restarts 3] \
        script.py [script args...]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import subprocess
import sys
import threading
import time
import uuid
from typing import Dict, List, Optional

from ..store import StoreClient, StoreError, StoreServer
from ..utils import compile_cache, env
from ..utils.ipc import IpcConnector
from ..utils.logging import get_logger, setup_logger
from ..utils.profiling import ProfilingEvent, get_recorder, record_event
from .config import FaultToleranceConfig
from .data import WorkloadAction
from .per_cycle_logs import CycleLogRouter
from .progress_tracker import TrainingProgressTracker
from .rank_monitor_server import RankMonitorServer
from .rendezvous import (
    K_ACTIVE_ROUND,
    K_SHUTDOWN,
    NodeDesc,
    NodeRole,
    RendezvousClosedError,
    RendezvousHost,
    RendezvousJoiner,
    RendezvousResult,
    UnhealthyNodeError,
    is_next_round_open,
    k_restart_req,
    k_result,
    k_shutdown_ack,
    request_restart,
)

log = get_logger("launcher")


@dataclasses.dataclass
class WorkerSpec:
    cmd: List[str]
    nproc_per_node: int
    monitor_interval: float = 0.1
    extra_env: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Worker:
    local_rank: int
    global_rank: int
    proc: subprocess.Popen


class HostRoundLoop:
    """Store-host side thread: opens/closes rounds for the whole job.

    Loop: close the currently-open round, then wait for either a restart
    request or shutdown; on restart request open the next round."""

    def __init__(self, host: RendezvousHost, round_timeout: float):
        self.host = host
        self.round_timeout = round_timeout
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="tpurx-rdzv-host", daemon=True
        )

    def start(self) -> None:
        self.host.bootstrap()
        self.host.open_round()
        self._thread.start()

    def _run(self) -> None:
        store = self.host.store
        while not self._stop.is_set():
            try:
                n = self.host.close_round_when_ready(timeout=self.round_timeout)
            except Exception as exc:  # noqa: BLE001
                log.error("rendezvous host failed to close round: %s", exc)
                store.set(K_SHUTDOWN, f"rendezvous failed: {exc}")
                return
            # wait for restart request or shutdown
            while not self._stop.is_set():
                if store.try_get(K_SHUTDOWN) is not None:
                    return
                if store.check([k_restart_req(n)]):
                    self.host.open_round()
                    break
                time.sleep(0.1)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


class ElasticAgent:
    def __init__(
        self,
        cfg: FaultToleranceConfig,
        spec: WorkerSpec,
        store_addr: str,
        store_port: int,
        host_store: bool = False,
        node_id: Optional[str] = None,
        max_restarts: Optional[int] = None,
        slice_key: str = "",
    ):
        self.cfg = cfg
        self.spec = spec
        self.store_addr = store_addr
        self.store_port = store_port
        self.host_store = host_store
        self.max_restarts = (
            max_restarts if max_restarts is not None else cfg.max_rank_restarts
        )
        self.node_id = node_id or f"{os.uname().nodename}-{uuid.uuid4().hex[:8]}"
        self.slice_key = slice_key or cfg.node_group_key or ""
        self.remaining_restarts = self.max_restarts
        self._store_server: Optional[StoreServer] = None
        self._host_loop: Optional[HostRoundLoop] = None
        self.store: Optional[StoreClient] = None
        self.workers: List[_Worker] = []
        self.monitors: List = []  # (proc, ctrl_conn, socket_path)
        self.log_router = CycleLogRouter(cfg.per_cycle_log_dir)
        self.progress = TrainingProgressTracker(
            cfg.progress_iteration_file if cfg.enable_progress_tracking else None,
            cfg.max_no_progress_cycles,
        )
        self.cycle_info = None
        self.attr_manager = None  # built in _setup_store (needs the store)
        if host_store and cfg.cycle_info_dir:
            from .cycle_info import CycleInfoReporter

            self.cycle_info = CycleInfoReporter(cfg.cycle_info_dir)
        run_dir = f"/tmp/tpurx-{os.getpid()}"
        os.makedirs(run_dir, exist_ok=True)
        self._run_dir = run_dir
        self.ipc = IpcConnector(os.path.join(run_dir, "launcher.sock"))
        self._pending_exclude = False
        self._pending_shutdown: Optional[str] = None
        self._pending_restart: Optional[str] = None
        # restart interrupted by a store outage after workers were stopped:
        # reason + cached gate verdict (see _complete_restart)
        self._restart_in_flight: Optional[str] = None
        self._restart_in_flight_allowed: Optional[bool] = None
        self._result: Optional[RendezvousResult] = None
        self._last_store_ok = 0.0

    # -- setup -------------------------------------------------------------

    def setup_rank_monitors_early(self) -> None:
        """Fork monitor processes before any threads exist (reference
        constraint, ``launcher.py:703-759``)."""
        if self.cfg.monitor_health_check_interval > 0:
            # fail fast on a bad spec HERE — inside the monitor it could only
            # be logged, and a typo would silently disable the health loop
            from ..health import build_passive_checks

            build_passive_checks(self.cfg.monitor_health_checks)
        for lr in range(self.spec.nproc_per_node):
            sock = os.path.join(self._run_dir, f"monitor_{lr}.sock")
            # the node-scope health loop runs in exactly one monitor per host
            proc, ctrl = RankMonitorServer.run_in_subprocess(
                self.cfg, sock, host_health_loop=(lr == 0)
            )
            self.monitors.append((proc, ctrl, sock))

    def _setup_store(self) -> None:
        if self.host_store:
            if env.NATIVE_STORE.get():
                from ..store.native import NativeStoreServer

                self._store_server = NativeStoreServer(
                    host="0.0.0.0", port=self.store_port
                ).start()
                log.info("hosting native C++ store on port %s", self._store_server.port)
            else:
                self._store_server = StoreServer(
                    host="0.0.0.0", port=self.store_port
                ).start_in_thread()
            self.store_port = self._store_server.port
        self.store = StoreClient(
            self.store_addr, self.store_port, timeout=self.cfg.rdzv_round_timeout
        )
        if self.host_store:
            host = RendezvousHost(
                self.store.clone(),
                min_nodes=self.cfg.min_nodes,
                max_nodes=self.cfg.max_nodes,
                require_equal_slots=self.cfg.require_equal_slots,
            )
            self._host_loop = HostRoundLoop(host, self.cfg.rdzv_round_timeout)
            self._host_loop.start()
        # attribution service lifecycle (reference attribution_manager.py):
        # the store-hosting launcher spawns ONE attrsvc per job and
        # publishes its endpoint; every node resolves it from the store
        from .attribution_manager import AttributionManager

        mode = self.cfg.attribution_service_mode
        if mode == "spawn" and not self.host_store:
            mode = "inline"  # only the host node spawns; others resolve
        self.attr_manager = AttributionManager(
            mode=mode,
            store=self.store,
            url=self.cfg.attribution_service_url,
        )
        self.attr_manager.start()

    def _on_ipc(self, msg: Dict) -> None:
        if msg.get("kind") != "workload_control":
            return
        action = WorkloadAction(msg["action"])
        log.warning("workload control request: %s (%s)", action.value, msg.get("reason"))
        if action == WorkloadAction.ExcludeThisNode:
            self._pending_exclude = True
        elif action == WorkloadAction.ShutdownWorkload:
            self._pending_shutdown = msg.get("reason", "workload requested shutdown")
        elif action == WorkloadAction.RestartWorkload:
            self._pending_restart = msg.get("reason", "workload requested restart")

    # -- worker lifecycle --------------------------------------------------

    def _start_workers(self, result: RendezvousResult) -> None:
        cycle = result.cycle
        if cycle > 0:
            # hard-killed workers may have leaked staged-checkpoint shm;
            # reclaim before the respawn needs the space
            from ..utils.shm_janitor import sweep as shm_sweep

            try:
                shm_sweep(min_age_s=60.0)
            except Exception:  # noqa: BLE001 - never block a restart on cleanup
                log.exception("shm sweep failed")
        self.log_router.start_cycle(cycle)
        for _, ctrl, _ in self.monitors:
            ctrl.send({"cmd": "cycle", "cycle": cycle})
        record_event(ProfilingEvent.WORKER_START_REQUESTED, cycle=cycle)
        self.workers = []
        for lr in range(self.spec.nproc_per_node):
            grank = result.rank_offset + lr
            env = dict(os.environ)
            # every worker of every cycle compiles into, and reads from, the
            # same persistent cache: a respawn must not start cold
            env[compile_cache.ENV_VAR] = compile_cache.cache_dir()
            env.update(self._chip_env(lr))
            env.update(self.spec.extra_env)
            env.update(
                {
                    "TPURX_RANK": str(grank),
                    "TPURX_LOCAL_RANK": str(lr),
                    "TPURX_WORLD_SIZE": str(result.global_world_size),
                    "TPURX_GROUP_RANK": str(result.group_rank),
                    "TPURX_NNODES": str(result.group_world_size),
                    "TPURX_CYCLE": str(cycle),
                    "TPURX_STORE_ADDR": self.store_addr,
                    "TPURX_STORE_PORT": str(self.store_port),
                    "TPURX_RANK_MONITOR_SOCKET": self.monitors[lr][2],
                    "TPURX_LAUNCHER_IPC_SOCKET": self.ipc.socket_path,
                }
            )
            out_fd = self.log_router.make_worker_pipe(grank, "out")
            err_fd = self.log_router.make_worker_pipe(grank, "err")
            proc = subprocess.Popen(
                self._numa_wrap(self.spec.cmd, lr),
                env=env,
                stdout=out_fd,
                stderr=err_fd,
                start_new_session=True,  # own PGID so we can signal the tree
            )
            os.close(out_fd)
            os.close(err_fd)
            self.workers.append(_Worker(lr, grank, proc))
        record_event(ProfilingEvent.WORKER_STARTED, cycle=cycle)
        if self.cycle_info is not None:
            self.cycle_info.start_cycle(
                cycle, result.round_num, result.participants, [],
                result.global_world_size,
            )
        log.info(
            "cycle %s: started %s workers (global ranks %s..%s)",
            cycle, len(self.workers), result.rank_offset,
            result.rank_offset + self.spec.nproc_per_node - 1,
        )

    def _chip_env(self, local_rank: int) -> Dict[str, str]:
        """One process per chip: the env that hands worker ``local_rank``
        its own chip (empty on a CPU host, for a single worker, or when the
        operator pinned the job to the CPU backend).  Raises ``ValueError``
        for a worker count the host's chips cannot carry."""
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            return {}
        from ..health.tpu import visible_tpu_chips
        from ..parallel.distributed import worker_chip_env

        return worker_chip_env(
            self.spec.nproc_per_node, local_rank, visible_tpu_chips()
        )

    def _numa_wrap(self, cmd: List[str], local_rank: int) -> List[str]:
        """NUMA binding (reference ``launcher.py:239-291``): TPU hosts are
        NUMA machines; binding each worker's CPU+memory to the node nearest
        its chips avoids cross-socket HBM staging traffic.  Uses numactl when
        present; silently a no-op otherwise."""
        if not self.cfg.numa_binding:
            return cmd
        import shutil as _shutil

        numactl = _shutil.which("numactl")
        nodes = self._numa_node_count()
        if not numactl or nodes <= 1:
            return cmd
        node = local_rank * nodes // max(1, self.spec.nproc_per_node)
        return [numactl, f"--cpunodebind={node}", f"--membind={node}"] + cmd

    @staticmethod
    def _numa_node_count() -> int:
        try:
            return len([
                d for d in os.listdir("/sys/devices/system/node")
                if d.startswith("node") and d[4:].isdigit()
            ])
        except OSError:
            return 1

    def _stop_workers(self) -> None:
        if not self.workers:
            return
        record_event(ProfilingEvent.WORKER_STOP_REQUESTED)
        stop_sig = getattr(
            signal, self.cfg.worker_stop_signal, signal.SIGTERM
        )
        for w in self.workers:
            if w.proc.poll() is None:
                try:
                    os.killpg(w.proc.pid, stop_sig)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.monotonic() + self.cfg.workers_stop_timeout
        for w in self.workers:
            remaining = max(0.0, deadline - time.monotonic())
            try:
                w.proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                pass
        for w in self.workers:
            # Always sweep the process group: a dead leader can leave live
            # children (data loaders, probes) that would hold devices/ports.
            try:
                os.killpg(w.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            if w.proc.poll() is None:
                w.proc.wait()  # tpurx: disable=TPURX005 -- process group was just SIGKILLed; exit is kernel-guaranteed
        record_event(ProfilingEvent.WORKER_STOPPED)
        self.workers = []

    def _workers_status(self) -> str:
        """'running' | 'succeeded' | 'failed' | 'none'

        'none' = no workers exist (already stopped for an in-flight restart
        or not yet started) — callers must not read an empty list as
        success (``all()`` over ``[]`` is True) or as failure.

        ``restart_policy="min-healthy"`` tolerates worker exits as long as
        at least ``min_healthy_workers`` local workers remain healthy
        (running or exited 0) — for jobs with non-collective sidecar
        workers whose loss should not burn a restart cycle."""
        codes = [w.proc.poll() for w in self.workers]
        if not codes:
            return "none"
        failed = sum(1 for c in codes if c is not None and c != 0)
        if self.cfg.restart_policy == "min-healthy" and self.cfg.min_healthy_workers >= 0:
            healthy = len(codes) - failed
            if healthy < self.cfg.min_healthy_workers:
                return "failed"
            if all(c is not None for c in codes):
                return "succeeded"  # enough zero-exits; losses tolerated
            return "running"
        if failed:
            return "failed"
        if all(c == 0 for c in codes):
            return "succeeded"
        return "running"

    # -- main loop ---------------------------------------------------------

    def run(self) -> int:
        self._setup_store()
        self.ipc.start_receiving(self._on_ipc)
        joiner = RendezvousJoiner(
            self.store.clone(),
            NodeDesc(
                node_id=self.node_id,
                hostname=os.uname().nodename,
                slots=self.spec.nproc_per_node,
                slice_key=self.slice_key,
            ),
            pre_join_health_check=self._pre_join_health_check,
        )
        try:
            return self._run_loop(joiner)
        finally:
            self._stop_workers()
            self._teardown()

    def _pre_join_health_check(self) -> None:
        # Device health gate before joining a round (reference pre_join_hook).
        # Full TPU checks live in tpu_resiliency.health; the launcher-level
        # gate is injectable for tests via env.
        from .health_gate import pre_rendezvous_health_check
        from .rendezvous import K_CYCLE

        cycle = int(self.store.try_get(K_CYCLE) or b"1") - 1
        pre_rendezvous_health_check(self.cfg, self.node_id, current_cycle=cycle)

    def _run_loop(self, joiner: RendezvousJoiner) -> int:
        store_down_since: Optional[float] = None
        while True:
            try:
                result = joiner.join(timeout=self.cfg.rdzv_round_timeout)
                store_down_since = None
            except RendezvousClosedError as exc:
                log.info("rendezvous closed: %s", exc)
                self._ack_shutdown()
                return 0 if "success" in str(exc) else 1
            except UnhealthyNodeError as exc:
                log.error("node unhealthy, leaving the job: %s", exc)
                self._ack_shutdown()
                return 1
            except StoreError as exc:
                # Store host unreachable.  Either the job finished without us
                # (host tore the store down) or the control plane is
                # restarting and --journal will re-host the state.  Keep the
                # fleet: retry joining for a bounded rejoin window before
                # concluding the job is gone.
                now = time.monotonic()
                if store_down_since is None:
                    store_down_since = now
                waited = now - store_down_since
                if waited < self.cfg.store_rejoin_window:
                    log.warning(
                        "store unreachable during rendezvous (%.0fs/%.0fs "
                        "rejoin window): %s",
                        waited, self.cfg.store_rejoin_window, exc,
                    )
                    time.sleep(min(5.0, max(1.0, waited / 4)))
                    continue
                log.warning(
                    "store unreachable past the %.0fs rejoin window, giving "
                    "up: %s", self.cfg.store_rejoin_window, exc,
                )
                return 1
            if result.role != NodeRole.PARTICIPANT:
                continue
            self._result = result
            self._start_workers(result)
            outcome = self._monitor_until_event(result)
            if outcome == "succeeded":
                log.info("workers finished successfully")
                try:
                    self.store.set(K_SHUTDOWN, "success")
                except StoreError:
                    pass  # store host already gone — job is over either way
                self._ack_shutdown()
                return 0
            if outcome == "shutdown":
                self._ack_shutdown()
                return 1
            if outcome == "excluded":
                joiner.desc.excluded = True
                self._stop_workers()
                request_restart(self.store, "node excluded")
                # rejoin so the host can reassign without us; join() raises
                # RendezvousClosedError for excluded nodes
                continue
            # outcome == restart (local failure or peer restart)
            self._stop_workers()
            continue

    def _monitor_until_event(self, result: RendezvousResult) -> str:
        """Hot loop (reference ``launcher.py:629-697``). Returns outcome."""
        store_down_since: Optional[float] = None
        # tpurx: disable=TPURX007 -- outage ride-out, not a retry: the window resets when the store recovers and the verdict depends on live worker status
        while True:
            try:
                outcome = self._monitor_tick(result)
                return outcome
            except StoreError as exc:
                # Store host unreachable mid-training.  If our workers are
                # done, the job most likely succeeded and the host tore down
                # first.  Otherwise ride out a control-plane restart
                # (--journal re-hosts the state): workers keep training on
                # ICI and don't need the store until the next event, so keep
                # them alive for the rejoin window before giving up.
                status = self._workers_status()
                if status == "succeeded":
                    return "succeeded"
                now = time.monotonic()
                if store_down_since is None or self._last_store_ok > store_down_since:
                    store_down_since = now  # fresh outage, fresh window
                waited = now - store_down_since
                if waited < self.cfg.store_rejoin_window:
                    log.warning(
                        "store unreachable in monitor loop (workers: %s; "
                        "%.0fs/%.0fs rejoin window): %s",
                        status, waited, self.cfg.store_rejoin_window, exc,
                    )
                    time.sleep(min(5.0, max(1.0, waited / 4)))
                    continue
                log.warning(
                    "store unreachable past the %.0fs rejoin window "
                    "(workers: %s) — shutting down: %s",
                    self.cfg.store_rejoin_window, status, exc,
                )
                self._stop_workers()
                return "shutdown"

    def _poll_monitor_events(self) -> None:
        """Drain health events the rank-monitor watchdogs push over their
        control pipes.  Polled at the top of every monitor tick so a node
        health failure turns into exclusion BEFORE a possibly-coincident
        worker failure turns into a plain restart (restarting on a sick node
        just fails again)."""
        for _, ctrl, _ in self.monitors:
            try:
                while ctrl.poll(0):
                    evt = ctrl.recv()
                    if not isinstance(evt, dict):
                        continue
                    if evt.get("event") == "health_failure":
                        log.error(
                            "monitor reported node health failure (%s): %s — "
                            "excluding this node",
                            evt.get("check"), evt.get("message"),
                        )
                        record_event(
                            ProfilingEvent.NODE_EXCLUDE_REQUESTED,
                            node=self.node_id,
                            check=evt.get("check"),
                            message=evt.get("message"),
                        )
                        self._pending_exclude = True
            except (EOFError, OSError):
                continue

    def _monitor_tick(self, result: RendezvousResult) -> str:
        while True:
            time.sleep(self.spec.monitor_interval)
            self._poll_monitor_events()
            if self.attr_manager is not None:
                self.attr_manager.tick()  # respawn a dead attrsvc (bounded)
            if self._pending_shutdown:
                log.warning("shutting down workload: %s", self._pending_shutdown)
                self.store.set(K_SHUTDOWN, self._pending_shutdown)
                self._stop_workers()
                return "shutdown"
            if self._pending_exclude:
                self._pending_exclude = False
                return "excluded"
            if self._restart_in_flight is not None:
                # A store outage interrupted a restart AFTER the workers were
                # already stopped and the cycle accounted: resume it instead
                # of letting the dead workers reclassify as a fresh failure
                # (which would charge end_cycle and the restart budget a
                # second time for the same fault).
                return self._complete_restart()
            if self._pending_restart:
                # Quorum tripwire (or other in-workload detector) named a
                # hang: restart the cycle NOW instead of waiting for the
                # rank-heartbeat timeout ring to kill the hung worker.
                reason = self._pending_restart
                self._pending_restart = None
                log.error("in-workload restart request: %s", reason)
                record_event(
                    ProfilingEvent.FAILURE_DETECTED,
                    cycle=result.cycle, reason=reason, source="workload_control",
                )
                if self.cycle_info is not None:
                    self.cycle_info.end_cycle("workload_restart_request", [])
                self._stop_workers()
                if not self.log_router.join_readers(timeout=2.0):
                    log.warning("per-cycle log readers still draining at deadline")
                self._restart_in_flight = reason
                return self._complete_restart()
            shutdown = self.store.try_get(K_SHUTDOWN)
            self._last_store_ok = time.monotonic()
            if shutdown == b"success":
                # Peers finished; let local workers drain instead of killing
                # them mid-final-step, then report success.
                deadline = time.monotonic() + self.cfg.workers_stop_timeout
                for w in self.workers:
                    try:
                        w.proc.wait(timeout=max(0.1, deadline - time.monotonic()))
                    except subprocess.TimeoutExpired:
                        break
                self._stop_workers()
                return "succeeded"
            if shutdown is not None:
                log.info("shutdown flag observed: %s", shutdown.decode())
                self._stop_workers()
                return "shutdown"
            status = self._workers_status()
            if status == "succeeded":
                return "succeeded"
            if status == "failed":
                failed = [
                    (w.global_rank, w.proc.poll())
                    for w in self.workers
                    if w.proc.poll() not in (None, 0)
                ]
                log.error("worker failure detected: ranks %s", failed)
                record_event(
                    ProfilingEvent.FAILURE_DETECTED,
                    cycle=result.cycle,
                    failed=[[r, c] for r, c in failed],
                )
                if self.cycle_info is not None:
                    self.cycle_info.end_cycle(
                        "worker_failure", [r for r, _ in failed]
                    )
                # Stop workers FIRST so the per-cycle pipe readers drain the
                # dying ranks' final output (tracebacks) before the
                # attribution gate reads the cycle log.
                self._stop_workers()
                if not self.log_router.join_readers(timeout=2.0):
                    log.warning("per-cycle log readers still draining at deadline")
                self._restart_in_flight = f"worker failure on {self.node_id}"
                return self._complete_restart()
            if is_next_round_open(self.store, result.round_num):
                log.info("peer-initiated restart: new round open")
                return "restart"

    def _complete_restart(self) -> str:
        """Finish an in-flight restart (workers already stopped, cycle
        already accounted).  Idempotent across StoreError retries: the gate
        verdict is computed once and cached so a store outage between the
        gate and ``request_restart`` can't charge the restart budget twice."""
        if self._restart_in_flight_allowed is None:
            # the cached device verdict predates this node's fault: the gate
            # of the next round must open the chips again
            from ..health import DeviceHealthCheck

            DeviceHealthCheck.clear_cache()
            self._restart_in_flight_allowed = self._restart_allowed()
        if not self._restart_in_flight_allowed:
            self.store.set(K_SHUTDOWN, "restart budget exhausted")
            self._restart_in_flight = None
            self._restart_in_flight_allowed = None
            return "shutdown"
        request_restart(self.store, self._restart_in_flight)
        self._restart_in_flight = None
        self._restart_in_flight_allowed = None
        return "restart"

    def _restart_allowed(self) -> bool:
        self.progress.analyze_previous_cycle()
        if self.progress.should_terminate_early():
            log.error(
                "terminating early: no progress for %s cycles",
                self.progress.no_progress_cycles,
            )
            return False
        if not self._attribution_gate_allows():
            return False
        if self.max_restarts > 0:
            if self.remaining_restarts <= 0:
                log.error("restart budget exhausted (%s)", self.max_restarts)
                return False
            self.remaining_restarts -= 1
        return True

    def _attribution_gate_allows(self) -> bool:
        """Consult the log analyzer before burning a restart on a failure
        that cannot succeed (OOM, NaN, bad data) — reference
        ``attribution_manager.py`` gate."""
        if not self.cfg.enable_attribution_gate or not self.cfg.per_cycle_log_dir:
            return True
        cycle = self._result.cycle if self._result else 0
        path = os.path.join(self.cfg.per_cycle_log_dir, f"cycle_{cycle}.log")
        if not os.path.exists(path):
            return True
        category, should_resume, confidence, summary = None, True, 0.0, ""
        # managed service first (shared cache + coalescing + LLM backend);
        # unhealthy/unreachable falls back to the inline analyzer — the
        # gate must never block recovery on the service
        svc = None
        if self.attr_manager is not None:
            svc = self.attr_manager.analyze_log(path)
        if svc is not None:
            category = svc.get("category")
            should_resume = bool(svc.get("should_resume", True))
            confidence = float(svc.get("confidence", 0.0))
            summary = svc.get("summary", "")
        else:
            try:
                from ..attribution import LogAnalyzer

                verdict = LogAnalyzer().analyze_file(path)
            except Exception:  # noqa: BLE001 - never block recovery
                log.exception("attribution gate failed; allowing restart")
                return True
            category = (
                verdict.category.value
                if hasattr(verdict.category, "value") else verdict.category
            )
            should_resume = verdict.should_resume
            confidence = verdict.confidence
            summary = verdict.summary
        log.info(
            "attribution%s: category=%s resume=%s confidence=%.2f (%s)",
            " (service)" if svc is not None else "", category,
            should_resume, confidence, summary,
        )
        if not should_resume and confidence >= 0.8:
            log.error(
                "attribution gate: %s is not survivable by restart — stopping",
                category,
            )
            return False
        return True

    def _ack_shutdown(self) -> None:
        """Record that this node has observed the shutdown flag (best-effort —
        the store host may already be gone).  Only acks when the flag actually
        exists: an excluded node exiting on a closed rendezvous must not leave
        a premature ack that would later satisfy the host's wait spuriously."""
        try:
            if self.store.try_get(K_SHUTDOWN) is not None:
                self.store.set(k_shutdown_ack(self.node_id), "1")
        except (StoreError, OSError):
            pass

    def _await_shutdown_acks(self, timeout: float = 3.0) -> None:
        """Store-hosting agent: wait until every participant of the latest
        closed round has acked the shutdown flag (or the deadline passes)
        before the store disappears.  Replaces the old fixed grace sleep — a
        loaded host no longer races its peers' final ``try_get(K_SHUTDOWN)``.

        Runs on a dedicated short-timeout connection: this is reachable from
        the SIGTERM handler, where reusing ``self.store`` could re-enter its
        lock mid-frame of an interrupted request and desync the wire protocol.
        """
        try:
            store = StoreClient(
                self.store.host, self.store.port, timeout=2.0, connect_timeout=2.0
            )
        except (StoreError, OSError):
            return
        try:
            if store.try_get(K_SHUTDOWN) is None:
                # tearing down without a published flag (SIGTERM on the host,
                # unhealthy exit): publish one so peers can observe and ack
                # instead of stalling the full deadline for acks that can
                # never arrive
                store.set(K_SHUTDOWN, "host terminated")
            peers = [
                n for n in self._latest_participants(store) if n != self.node_id
            ]
            keys = [k_shutdown_ack(n) for n in peers]
            deadline = time.monotonic() + timeout
            while peers and time.monotonic() < deadline:
                if store.check(keys):
                    break
                time.sleep(0.05)
            else:
                if peers:
                    log.warning(
                        "peers did not all ack shutdown within %.1fs: %s",
                        timeout, peers,
                    )
            # Standby spares and mid-join nodes are not in the ack set; they
            # poll the store on a ~0.25 s cadence.  Hold the store one poll
            # interval past the participant acks so they observe the flag and
            # exit cleanly instead of hitting a dead store.
            time.sleep(0.5)
        except (StoreError, OSError):
            return
        finally:
            store.close()

    def _latest_participants(self, store) -> List[str]:
        """Participants of the latest closed rendezvous round, read from the
        store — ``self._result`` can be stale (e.g. this host was excluded
        after its last participant round while the fleet moved on)."""
        try:
            raw_n = store.try_get(K_ACTIVE_ROUND)
            if raw_n is not None:
                for rnd in (int(raw_n), int(raw_n) - 1):
                    if rnd < 0:
                        continue
                    raw = store.try_get(k_result(rnd))
                    if raw:
                        return list(json.loads(raw)["participants"])
        except (StoreError, OSError, ValueError, KeyError):
            pass
        return list(self._result.participants) if self._result else []

    def _teardown(self) -> None:
        self.ipc.stop_receiving()
        if self.attr_manager is not None:
            self.attr_manager.stop()
        for proc, ctrl, _ in self.monitors:
            try:
                ctrl.send({"cmd": "shutdown"})
            except (BrokenPipeError, OSError):
                pass
        for proc, _, _ in self.monitors:
            proc.join(timeout=3)
            if proc.is_alive():
                proc.terminate()
        if self._host_loop:
            self._host_loop.stop()
        self.log_router.close()
        if self._store_server:
            # peers must observe the shutdown flag before the store disappears
            # (they tolerate store loss after that); wait for their explicit
            # acks rather than sleeping a fixed grace period
            self._await_shutdown_acks(timeout=3.0)
            self._store_server.stop()


# -- CLI ---------------------------------------------------------------------

def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="tpurx-launch", description="TPU-resilient elastic launcher"
    )
    p.add_argument("--nnodes", default="1:1", help="MIN:MAX nodes (or a single N)")
    p.add_argument("--nproc-per-node", type=int, default=1)
    p.add_argument("--rdzv-endpoint", default="127.0.0.1:29400")
    p.add_argument(
        "--host-store",
        action="store_true",
        help="host the KV store + rendezvous rounds in this launcher",
    )
    p.add_argument("--node-id", default=None)
    p.add_argument("--slice-key", default="", help="TPU slice / ICI domain id")
    p.add_argument("--max-restarts", type=int, default=None)
    p.add_argument("--ft-cfg", default=None, help="YAML config path")
    p.add_argument(
        "--ft-param", action="append", default=[], metavar="KEY=VALUE",
        help="FaultToleranceConfig override (repeatable), e.g. "
             "--ft-param rank_heartbeat_timeout=30 --ft-param max_nodes=8",
    )
    p.add_argument("--monitor-interval", type=float, default=0.1)
    p.add_argument("--log-dir", default=None)
    # operator surface (each also reachable via --ft-param; these are the
    # high-traffic knobs the reference exposes as dedicated flags)
    p.add_argument(
        "--worker-stop-signal", default=None, metavar="SIG",
        help="graceful signal before the KILL sweep (default SIGTERM)",
    )
    p.add_argument(
        "--term-signal", default=None, metavar="SIG",
        help="signal the rank monitor uses to kill a hung rank (default SIGKILL)",
    )
    p.add_argument(
        "--workers-stop-timeout", type=float, default=None,
        help="seconds to wait after the stop signal before SIGKILL",
    )
    p.add_argument(
        "--restart-policy", choices=["any-failed", "min-healthy"], default=None,
        help="when a worker exit fails the cycle (default any-failed)",
    )
    p.add_argument(
        "--min-healthy-workers", type=int, default=None,
        help="min-healthy policy: local workers that must stay healthy",
    )
    p.add_argument(
        "--allow-heterogeneous", action="store_true",
        help="accept nodes with differing worker counts (mixed slot fleets)",
    )
    p.add_argument("cmd", nargs=argparse.REMAINDER, help="worker command")
    args = p.parse_args(argv)
    if not args.cmd:
        p.error("worker command required")
    if args.cmd and args.cmd[0] == "--":
        args.cmd = args.cmd[1:]
    return args


def build_agent(args: argparse.Namespace) -> ElasticAgent:
    cfg = (
        FaultToleranceConfig.from_yaml(args.ft_cfg)
        if args.ft_cfg
        else FaultToleranceConfig()
    )
    if args.ft_param:
        from .config import _coerce
        import dataclasses as _dc

        types = {f.name: f.type for f in _dc.fields(FaultToleranceConfig)}
        overrides = {}
        for item in args.ft_param:
            key, sep, value = item.partition("=")
            if not sep or key not in types:
                raise SystemExit(f"bad --ft-param {item!r} (unknown key or missing '=')")
            overrides[key] = _coerce(value, types[key])
        cfg = cfg.merged_with(overrides, allow_none=True)
    cfg = cfg.merged_with_env()
    if ":" in args.nnodes:
        mn, mx = args.nnodes.split(":")
        cfg = cfg.merged_with({"min_nodes": int(mn), "max_nodes": int(mx)})
    else:
        n = int(args.nnodes)
        cfg = cfg.merged_with({"min_nodes": n, "max_nodes": n})
    if args.log_dir:
        cfg = cfg.merged_with({"per_cycle_log_dir": args.log_dir})
    flag_overrides = {}
    if args.worker_stop_signal:
        if not hasattr(signal, args.worker_stop_signal):
            raise SystemExit(f"unknown signal {args.worker_stop_signal!r}")
        flag_overrides["worker_stop_signal"] = args.worker_stop_signal
    if args.term_signal:
        if not hasattr(signal, args.term_signal):
            raise SystemExit(f"unknown signal {args.term_signal!r}")
        flag_overrides["term_signal"] = args.term_signal
    if args.workers_stop_timeout is not None:
        flag_overrides["workers_stop_timeout"] = args.workers_stop_timeout
    if args.restart_policy is not None:
        flag_overrides["restart_policy"] = args.restart_policy
    if args.min_healthy_workers is not None:
        flag_overrides["min_healthy_workers"] = args.min_healthy_workers
    if args.allow_heterogeneous:
        flag_overrides["require_equal_slots"] = False
    if flag_overrides:
        cfg = cfg.merged_with(flag_overrides)
    host, port = args.rdzv_endpoint.rsplit(":", 1)
    cmd = args.cmd
    if cmd[0].endswith(".py"):
        cmd = [sys.executable] + cmd
    spec = WorkerSpec(
        cmd=cmd,
        nproc_per_node=args.nproc_per_node,
        monitor_interval=args.monitor_interval,
    )
    return ElasticAgent(
        cfg,
        spec,
        store_addr=host,
        store_port=int(port),
        host_store=args.host_store,
        node_id=args.node_id,
        max_restarts=args.max_restarts,
        slice_key=args.slice_key,
    )


def main(argv: Optional[List[str]] = None) -> None:
    setup_logger()
    args = parse_args(argv)
    agent = build_agent(args)
    try:
        agent._chip_env(0)
    except ValueError as exc:
        raise SystemExit(f"tpurx-launch: {exc}")
    if agent.cfg.profiling_file:
        get_recorder()._path = agent.cfg.profiling_file
    agent.setup_rank_monitors_early()

    # SIGTERM/SIGINT must sweep the worker process groups before the launcher
    # dies — orphaned workers would keep holding TPU chips and ports
    # (reference stops worker groups on agent shutdown, ``launcher.py:922``).
    def _terminate(signum, frame):
        log.warning("launcher received %s; stopping workers", signal.Signals(signum).name)
        try:
            agent._stop_workers()
            agent._teardown()
        finally:
            os._exit(128 + signum)

    signal.signal(signal.SIGTERM, _terminate)
    signal.signal(signal.SIGINT, _terminate)

    rc = agent.run()
    sys.exit(rc)


if __name__ == "__main__":
    main()
