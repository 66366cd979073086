"""The in-process restart wrapper.

Capability parity with ``inprocess/wrap.py:81-682`` (``Wrapper`` /
``CallWrapper``).  Restart iteration (reference call stack SURVEY.md §3.3):

    rank assignment → monitor thread → initialize → [ACTIVE: run fn |
    INACTIVE: park as reserve] → on fault: record → abort aux engines →
    async-raise RankShouldRestart → finalize → restart health check →
    iteration barrier (survivors) → read terminated → reassign → loop

Faults handled: exceptions in fn (recorded, coalesced), soft/hard hangs (via
MonitorProcess watching the ProgressWatchdog), silent node death (via
SiblingMonitor), peer faults (any rank's record trips every rank's
MonitorThread).
"""

from __future__ import annotations

import contextlib
import gc
import inspect
import json
import threading
import time
from typing import Any, Callable, Optional

from ..store.barrier import BarrierTimeout
from ..store.client import StoreClient, StoreError, StoreTimeout, store_from_env
from ..policy.ledger import ledger
from ..telemetry import counter, flight, gauge, histogram
from ..telemetry import episode as episode_mod
from ..telemetry.clock import mono_ns
from ..utils import env
from ..utils.logging import get_logger
from ..utils.profiling import ProfilingEvent, record_event
from .abort import (
    AbortLadder,
    DegradeToShrink,
    FingerprintStage,
    ShrinkMeshStage,
    as_stage,
    install_degrade_hook,
)
from .attribution import Interruption, InterruptionRecord
from .fingerprint import DispatchTail, install_tail, snapshot_tail
from .exceptions import HealthCheckError, RankShouldRestart, RestartAbort
from .monitor_process import MonitorProcess
from .monitor_thread import MonitorThread
from .progress_watchdog import ProgressWatchdog
from .rank_assignment import RankAssignmentCtx, RankDiscontinued, ShiftRanks
from .sibling_monitor import SiblingMonitor
from .state import Mode, State
from .store_ops import InprocStore

log = get_logger("inproc.wrap")


class _JobCompleted:
    """Singleton return value for a rank whose JOB finished elsewhere: a
    peer completed fn in the same iteration this rank was restarting (or
    parked as a reserve), so there is no per-rank result to return.  It is
    falsy, like the historical ``None`` return — but distinguishable from
    a wrapped fn that legitimately returned ``None``, which made the
    ``ret=None`` worker output ambiguous between "completed via the
    any_completed gate" and "restart machinery lost the result" (the
    layered-restart flake's signature).  ``repr`` is what workers print."""

    __slots__ = ()

    def __repr__(self) -> str:
        return "job-completed"

    def __bool__(self) -> bool:
        return False


JOB_COMPLETED = _JobCompleted()

_RESTARTS = counter(
    "tpurx_inprocess_restarts_total", "In-process restart cycles entered"
)
_INTERRUPTIONS = counter(
    "tpurx_inprocess_interruptions_total",
    "Faults observed by the wrapper",
    labels=("kind",),
)
_PHASE_NS = histogram(
    "tpurx_restart_phase_latency_ns",
    "Duration of each restart-pipeline phase",
    labels=("phase",),
)
_RESTART_NS = histogram(
    "tpurx_restart_total_latency_ns",
    "Fault observed to wrapped fn re-entered, end to end",
)
_COLLECTED = counter(
    "tpurx_restart_gc_collected_total",
    "Unreachable objects the restart path's gc.collect() found",
)
_FROZEN = gauge(
    "tpurx_restart_gc_frozen_objects",
    "Survivors the last restart's gc.freeze() moved to the permanent generation",
)


# the restart path on the main thread, from the fault caught to the wrapped fn
# about to be called again; ident = the faulted iteration (kept after
# state.advance()).  Its eight children follow one another without a gap:
# _RestartClock ends one and begins the next from one stamp
IV_RESTART = flight.declare_interval(
    "inproc.restart_begin", "inproc.restart_end"
)
_IV_PHASE = {
    "abort_wait": flight.declare_interval(
        "inproc.restart.abort_wait_begin", "inproc.restart.abort_wait_end"
    ),
    "finalize": flight.declare_interval(
        "inproc.restart.finalize_begin", "inproc.restart.finalize_end"
    ),
    "health_check": flight.declare_interval(
        "inproc.restart.health_check_begin", "inproc.restart.health_check_end"
    ),
    "iteration_barrier": flight.declare_interval(
        "inproc.restart.iteration_barrier_begin",
        "inproc.restart.iteration_barrier_end",
    ),
    "reassign": flight.declare_interval(
        "inproc.restart.reassign_begin", "inproc.restart.reassign_end"
    ),
    # the end alone carries the two fields: what gc.collect() returned and
    # how many survivors gc.freeze() then moved to the permanent generation
    "collect": flight.declare_interval(
        "inproc.restart.collect_begin", "inproc.restart.collect_end",
        "collected", "frozen",
    ),
    "rearm": flight.declare_interval(
        "inproc.restart.rearm_begin", "inproc.restart.rearm_end"
    ),
    "initialize": flight.declare_interval(
        "inproc.restart.initialize_begin", "inproc.restart.initialize_end"
    ),
}


class _RestartClock:
    """The restart path's one set of stamps.  Each boundary reads the
    recorder's clock once: that stamp closes the phase in
    ``tpurx_restart_phase_latency_ns``, ends its ``inproc.restart.<phase>``
    interval and begins the next one's, so histogram and ring cannot disagree;
    the last one also ends ``inproc.restart`` and feeds
    ``tpurx_restart_total_latency_ns``.  Main thread only.  A restart that
    leaves the path early (job completed, ``RestartAbort``) leaves its begins
    open in the ring: where it stopped."""

    def __init__(self) -> None:
        self.ident: Optional[int] = None
        self.started_ns = 0
        self._phase: Optional[str] = None
        self._phase_ns = 0
        self._annotations: list = []  # IV_RESTART's, then the open phase's

    @property
    def running(self) -> bool:
        return self._phase is not None

    def _open(self, iv, parent, now: int) -> None:
        flight.begin(iv, self.ident, parent, at_ns=now)
        self._annotations.append(flight.annotation(iv))

    def _close(self, iv, parent, now: int, *extra: Any) -> None:
        entered = self._annotations.pop()
        if entered is not None:
            entered.__exit__(None, None, None)
        flight.end(iv, self.ident, parent, *extra, at_ns=now)

    def start(self, ident: int) -> int:
        """Open ``inproc.restart`` and its first phase; returns the stamp."""
        self.abandon()
        now = self.started_ns = self._phase_ns = mono_ns()
        self.ident, self._phase = ident, "abort_wait"
        self._open(IV_RESTART, None, now)
        self._open(_IV_PHASE[self._phase], IV_RESTART, now)
        return now

    def next(self, phase: Optional[str], *extra: Any) -> None:
        """End the open phase (``extra``: its end event's fields) and begin
        ``phase``; with None, end ``inproc.restart`` too: the wrapped fn is
        about to be called."""
        now = mono_ns()
        _PHASE_NS.labels(self._phase).observe(now - self._phase_ns)
        self._close(_IV_PHASE[self._phase], IV_RESTART, now, *extra)
        self._phase, self._phase_ns = phase, now
        if phase is not None:
            self._open(_IV_PHASE[phase], IV_RESTART, now)
        else:
            self._close(IV_RESTART, None, now)
            _RESTART_NS.observe(now - self.started_ns)

    def abandon(self) -> None:
        """Leave whatever is open as it is in the ring; exit its annotations."""
        while self._annotations:
            entered = self._annotations.pop()
            if entered is not None:
                entered.__exit__(None, None, None)
        self._phase = None


class Wrapper:
    """Decorator adding in-process restart to a training function.

    The wrapped function may declare a ``call_wrapper`` keyword parameter to
    receive the :class:`CallWrapper` (``ping()``, ``atomic()``, ``state``).
    """

    def __init__(
        self,
        store_factory: Optional[Callable[[], StoreClient]] = None,
        group: str = "default",
        initialize: Optional[Callable] = None,
        abort: Optional[Callable] = None,
        finalize: Optional[Callable] = None,
        health_check: Optional[Callable] = None,
        rank_assignment: Optional[Callable] = None,
        completion: Optional[Callable] = None,
        terminate: Optional[Callable] = None,
        max_iterations: Optional[int] = None,
        soft_timeout: float = 60.0,
        hard_timeout: float = 90.0,
        monitor_process_interval: float = 1.0,
        monitor_thread_interval: float = 0.25,
        last_call_wait: float = 0.2,
        heartbeat_interval: float = 1.0,
        sibling_timeout: float = 10.0,
        barrier_timeout: float = 120.0,
        enable_monitor_process: bool = True,
        enable_sibling_monitor: bool = True,
        quorum_mesh=None,
        quorum_budget_ms: float = 50.0,
        quorum_interval: float = 0.01,
        quorum_auto_beat_interval: Optional[float] = 0.002,
        quorum_calibrate: bool = True,
        # operator floor only — calibration (safety*p99 + margin, sampled on
        # this host) finds the real budget; 2ms keeps a guardrail while
        # letting low-jitter hosts detect in ~3ms instead of flooring at 5
        quorum_min_budget_ms: float = 2.0,
        quorum_native_beat: bool = False,
        # event/futex-wait local tripwire on the beat stream (sub-ms local
        # staleness at wake latency; the collective stays the pod-wide path)
        quorum_futex_tripwire: bool = False,
        # at-abort fingerprint gather budget before the restart proceeds
        # (0 disables the verdict log; publication still happens)
        fingerprint_wait: float = 1.0,
    ):
        self.store_factory = store_factory or store_from_env
        self.group = group
        self.initialize = initialize
        self.abort = abort
        self.finalize = finalize
        self.health_check = health_check
        self.completion = completion
        self.terminate = terminate
        self.rank_assignment = rank_assignment or ShiftRanks()
        self.max_iterations = max_iterations
        self.soft_timeout = soft_timeout
        self.hard_timeout = hard_timeout
        self.monitor_process_interval = monitor_process_interval
        self.monitor_thread_interval = monitor_thread_interval
        self.last_call_wait = last_call_wait
        self.heartbeat_interval = heartbeat_interval
        self.sibling_timeout = sibling_timeout
        self.barrier_timeout = barrier_timeout
        self.enable_monitor_process = enable_monitor_process
        self.enable_sibling_monitor = enable_sibling_monitor
        # on-device ICI quorum tripwire (ms-scale hang detection feeding the
        # SAME interruption log the monitor thread watches); pass the
        # training mesh to enable
        self.quorum_mesh = quorum_mesh
        self.quorum_budget_ms = quorum_budget_ms
        self.quorum_min_budget_ms = quorum_min_budget_ms
        self.quorum_interval = quorum_interval
        self.quorum_auto_beat_interval = quorum_auto_beat_interval
        self.quorum_native_beat = quorum_native_beat
        self.quorum_futex_tripwire = quorum_futex_tripwire
        self.quorum_calibrate = quorum_calibrate
        self.fingerprint_wait = fingerprint_wait

    def __call__(self, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            with CallWrapper(self, fn) as cw:
                try:
                    return cw.run(*args, **kwargs)
                except RestartAbort:
                    flight.dump("restart_abort")
                    if self.terminate:
                        # Terminate plugin (reference `terminate.py` ABC):
                        # last hook before this rank leaves the loop for good
                        try:
                            self.terminate(cw.state.freeze())
                        except Exception:  # noqa: BLE001
                            log.exception("terminate plugin failed")
                    raise
                except Exception:
                    # black box for the failure the wrapper could NOT absorb
                    flight.dump("wrapper_exception")
                    raise

        wrapped.__name__ = getattr(fn, "__name__", "wrapped")
        return wrapped


class CallWrapper:
    def __init__(self, wrapper: Wrapper, fn: Callable):
        self.w = wrapper
        self.fn = fn
        self.state = State.from_env()
        self.atomic_lock = threading.Lock()
        self._store: Optional[StoreClient] = None
        self.ops: Optional[InprocStore] = None
        self.watchdog: Optional[ProgressWatchdog] = None
        self.monitor_process: Optional[MonitorProcess] = None
        self.quorum = None  # QuorumTripwire when wrapper.quorum_mesh is set
        self.ladder: Optional[AbortLadder] = None
        self._tail: Optional[DispatchTail] = None
        self._prev_tail: Optional[DispatchTail] = None
        self._accepts_cw = "call_wrapper" in inspect.signature(fn).parameters
        # stamp of the last fault, cleared when the restarted fn re-enters
        self._restart_started_ns: Optional[int] = None
        self._restart_clock = _RestartClock()
        # (fault_class, rung, episode_id) of the restart episode in flight;
        # closed into the policy rung ledger when the restarted fn re-enters
        self._episode: Optional[tuple] = None
        self._clock_ref = None  # telemetry.clock.ClockReference on rank 0

    # -- public API for the wrapped fn ------------------------------------

    def ping(self) -> None:
        if self.watchdog:
            self.watchdog.ping()
        if self.quorum:
            self.quorum.beat()

    @contextlib.contextmanager
    def atomic(self):
        """Critical section: restart raises are deferred until exit."""
        with self.atomic_lock:
            yield

    @contextlib.contextmanager
    def disable_hang_protection(self):
        """For known-long phases (huge compiles, first checkpoint load).

        The raised quorum budget is LOCAL: the quorum collective is pod-wide,
        so peers' monitors still apply their own budgets to this rank's
        stamps.  With an auto-beater the beater keeps the stamps fresh
        throughout, so peers see a live rank; in manual-beat configs
        (``quorum_auto_beat_interval=None``) a long protected phase freezes
        this rank's stamp and PEERS will trip — every rank entering a known
        long phase must wrap it in its own ``disable_hang_protection()``
        (which keeps protection pod-consistent), or the config should keep
        the auto-beater on.
        """
        if self.monitor_process:
            self.monitor_process.set_enabled(False)
        saved_budget = None
        if self.quorum:
            saved_budget = self.quorum.monitor.budget_ms
            self.quorum.monitor.budget_ms = float("inf")
        try:
            yield
        finally:
            if self.monitor_process:
                self.monitor_process.set_enabled(True)
            if self.quorum and saved_budget is not None:
                # resume_auto_beat = beat + FENCE + re-arm beater: an
                # in-flight pipelined collective dispatched before this beat
                # still carries the stale stamp and must not fire once the
                # budget is restored — the fence drops it.
                self.quorum.monitor.resume_auto_beat()
                self.quorum.monitor.budget_ms = saved_budget

    def calibrate_quorum(self, load_fn: Callable[[], Any],
                         n_ticks: int = 20) -> Optional[float]:
        """Derive the quorum budget from healthy tick ages and beat periods
        sampled while ``load_fn`` runs — one real training step with its
        :meth:`ping`, so they embed the host contention and the step time of
        THIS model on THIS device (the tick after a step reads dispatch time;
        the time from one ping to the next is the step, and the budget is at
        least two of them).  The constructor budget is tuned for nothing:
        with manual beats it must exceed the step time, which only the
        workload knows.  Call once the step is compiled and warm.  Returns
        the new budget (ms), or None without a quorum tripwire."""
        if not self.quorum:
            return None
        return self.quorum.monitor.calibrate(
            n_ticks=n_ticks, min_budget_ms=self.w.quorum_min_budget_ms,
            load_fn=load_fn,
        )

    @property
    def iteration(self) -> int:
        return self.state.iteration

    # -- lifecycle ---------------------------------------------------------

    def __enter__(self) -> "CallWrapper":
        from ..telemetry.exporter import serve_from_env_once

        serve_from_env_once()  # per-rank scrape endpoint, when env asks
        self._store = self.w.store_factory()
        self.ops = InprocStore(self._store, self.w.group)
        # shm-backed dispatch tail: the monitor process reads it post-mortem
        # when this rank wedges in a device call (at-abort fingerprint)
        self._tail = DispatchTail.create()
        self._prev_tail = install_tail(self._tail)
        self.ladder = self._build_ladder()
        # the monitor process is exec'd (never forked — the parent is
        # JAX-threaded) and reads the watchdog stamps through a named-shm
        # slot the watchdog writes into
        shared = None
        if self.w.enable_monitor_process:
            from .monitor_process import MonitorSharedState

            shared = MonitorSharedState.create()
        self.watchdog = ProgressWatchdog(
            interval=self.w.monitor_process_interval,
            timestamp_slot=shared.timestamp_slot if shared else None,
        )
        # the watchdog must run BEFORE hang protection arms: the initial
        # barrier blocks for peers, and its store-wait loop only keeps the
        # liveness timestamp fresh via the watchdog's pending calls
        self.watchdog.start()
        if self.w.enable_monitor_process:
            self.monitor_process = MonitorProcess(
                store_factory=self.w.store_factory,
                group=self.w.group,
                rank=self.state.initial_rank,
                soft_timeout=self.w.soft_timeout,
                hard_timeout=self.w.hard_timeout,
                interval=self.w.monitor_process_interval,
                shared_state=shared,
                fptail_name=self._tail.name if self._tail else None,
            ).start()
        # flight-recorder plumbing: SIGUSR2 dump trigger, and every dump is
        # fed to the attribution engine's trace analyzer
        flight.install_signal_handler()
        flight.add_dump_hook(self._analyze_dump_hook)
        # rank 0 serves the job's reference clock; it must be answering
        # before peers leave the barrier and calibrate against it
        clock_cal = False
        try:
            clock_cal = bool(env.CLOCK_CAL.get())
        except ValueError:
            pass
        if clock_cal and self.state.initial_rank == 0:
            from ..telemetry import clock

            try:
                self._clock_ref = clock.serve_reference(self._store)
            except (OSError, StoreError):
                log.debug("clock reference unavailable", exc_info=True)
        self.ops.initial_barrier(
            self.state.initial_rank, self.state.initial_world_size,
            timeout=self.w.barrier_timeout,
        )
        if clock_cal and self.state.initial_rank != 0:
            from ..telemetry import clock
            from ..utils.profiling import get_recorder

            try:
                clock.calibrate(self._store)
                # re-emit the profiling meta header so the file carries the
                # freshly estimated offset for the trace merger
                get_recorder().write_meta()
            except (OSError, StoreError, StoreTimeout):
                log.debug("clock calibration failed", exc_info=True)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._restart_clock.abandon()
        flight.remove_dump_hook(self._analyze_dump_hook)
        if self._clock_ref is not None:
            self._clock_ref.stop()
            self._clock_ref = None
        if self.quorum:
            self.quorum.stop()
        if self.watchdog:
            self.watchdog.stop()
        if self.monitor_process:
            self.monitor_process.stop()
            # the shm slot is pinned by the watchdog's (possibly queued)
            # pending-call refs; close() tolerates that — janitor reaps
            self.monitor_process.shared.close()
        if self._store:
            self._store.close()
        if self._tail is not None:
            if self._prev_tail is not None:
                install_tail(self._prev_tail)
            self._tail.close()
            self._tail = None

    # -- restart loop ------------------------------------------------------

    def run(self, *args, **kwargs) -> Any:
        w = self.w
        state = self.state
        main_tid = threading.get_ident()
        restart_clock = self._restart_clock
        # initial assignment
        self._assign()
        if w.quorum_mesh is not None and self.quorum is None:
            from .quorum_tripwire import QuorumTripwire

            self.quorum = QuorumTripwire(
                w.quorum_mesh,
                self.ops,
                rank=state.initial_rank,
                budget_ms=w.quorum_budget_ms,
                interval=w.quorum_interval,
                auto_beat_interval=w.quorum_auto_beat_interval,
                native_beat=w.quorum_native_beat,
                futex_tripwire=w.quorum_futex_tripwire,
                calibrate=w.quorum_calibrate,
                min_budget_ms=w.quorum_min_budget_ms,
            ).start(state.iteration)

        while True:
            iteration = state.iteration
            if self.quorum:
                self.quorum.set_iteration(iteration)
                self.quorum.beat()
            if w.max_iterations is not None and iteration >= w.max_iterations:
                raise RestartAbort(f"max_iterations {w.max_iterations} reached")
            if self.monitor_process:
                self.monitor_process.set_iteration(iteration)
            terminated_now = set(self.ops.terminated_ranks())
            survivors = [
                r for r in range(state.initial_world_size) if r not in terminated_now
            ]
            monitor = MonitorThread(
                self.ops,
                iteration,
                main_tid,
                survivors,
                abort_fn=self._abort_fn,
                last_call_wait=w.last_call_wait,
                poll_interval=w.monitor_thread_interval,
                on_trip=self._on_trip,
            )
            sibling = None
            if w.enable_sibling_monitor and len(survivors) > 1:
                sibling = SiblingMonitor(
                    self.ops,
                    state.initial_rank,
                    survivors,
                    iteration,
                    heartbeat_interval=w.heartbeat_interval,
                    timeout=w.sibling_timeout,
                )
            restart = False
            ret = None
            fault_exc = None
            completed = False
            # Async-raise discipline (VERDICT r4 weak #4): handler bodies
            # are MINIMAL flag assignments (no I/O, no GIL-releasing calls),
            # the outer except absorbs a stray delivered inside a handler
            # body's few bytecodes, and the finally's inline absorbing loop
            # quiesces the monitor on EVERY exit — completion and abort
            # included.  The residual escape window is the ~2 bytecodes
            # between finally entry and the loop's try (no calls, no GIL
            # release) against the monitor's 0.5 s re-raise cadence — the
            # irreducible minimum for async exceptions in pure Python.  All
            # fault bookkeeping (logging, interruption records) runs after
            # the finally, when the async-exc slot is provably empty.
            try:
                try:
                    monitor.start()
                    if sibling:
                        sibling.start()
                    if restart_clock.running:
                        restart_clock.next("initialize")
                    if w.initialize:
                        w.initialize(state.freeze())
                    state.set_distributed_vars()
                    self.watchdog.ping()
                    if self._restart_started_ns is not None:
                        recovery_ns = mono_ns() - self._restart_started_ns
                        self._restart_started_ns = None
                        if self._episode is not None:
                            # re-entering fn closes the episode: the rung
                            # that ran recovered this fault class, at this
                            # measured cost — the policy ledger's input
                            cls, rung, eid = self._episode
                            self._episode = None
                            ledger().record(
                                cls, rung, True, recovery_ns / 1e9,
                                episode_id=eid,
                            )
                        ep = episode_mod.current()
                        if ep is not None:
                            # fn re-entered: MTTR decomposition complete
                            ep.close()
                    record_event(
                        ProfilingEvent.INPROCESS_RESTART_COMPLETED
                        if iteration
                        else ProfilingEvent.WORKER_STARTED,
                        iteration=iteration, rank=state.initial_rank,
                    )
                    if restart_clock.running:
                        restart_clock.next(None)
                        # the restart is over: the trip's and the ladder's
                        # black boxes, captured on the monitor thread, may
                        # be encoded and written now.  Last before fn, so the
                        # writer's first slice cannot begin ahead of it
                        flight.release_deferred()
                    if state.mode == Mode.ACTIVE:
                        if self._accepts_cw:
                            kwargs = {**kwargs, "call_wrapper": self}
                        ret = self.fn(*args, **kwargs)
                        if self.quorum:
                            # fn returned: its pings are over, and so is
                            # what the tripwire was watching
                            self.quorum.suspend()
                        if w.completion:
                            # Completion plugin (reference `completion.py`
                            # ABC): may transform/validate the return value
                            # before the group is released
                            ret = w.completion(state.freeze(), ret)
                        self.ops.mark_completed(iteration)
                        completed = True
                    else:
                        ret = self._reserve_wait(iteration)
                        if ret == "completed":
                            ret = JOB_COMPLETED
                            completed = True
                        # else: unreachable — _reserve_wait only exits via
                        # RankShouldRestart or completion
                except RankShouldRestart:
                    restart = True
                except RestartAbort:
                    raise
                except Exception as exc:  # noqa: BLE001 - fn fault
                    fault_exc = exc
                    restart = True
            except RankShouldRestart:
                # stray async raise delivered inside a handler body — same
                # outcome; a fault_exc assigned before the stray is kept
                restart = True
            finally:
                # inline (not quiesce_with_retry): a helper CALL's own
                # bytecodes would re-open the delivery window the loop exists
                # to close
                while True:
                    try:
                        monitor.quiesce_raises()
                        break
                    except RankShouldRestart:
                        continue
                if not restart:
                    monitor.stop()
                    if sibling:
                        sibling.stop()
            if completed:
                # covers the completed-but-peer-raised race (restart flag
                # set after completion): stop() is idempotent and the
                # completion already won
                monitor.stop()
                if sibling:
                    sibling.stop()
                return ret

            # ---- restart path ---- (async-exc slot empty from here on)
            self._restart_started_ns = restart_clock.start(iteration)
            if self.quorum:
                self.quorum.suspend()  # re-armed by set_iteration at loop top
            _RESTARTS.inc()
            _INTERRUPTIONS.labels(
                "exception" if fault_exc is not None else "peer_signal"
            ).inc()
            if fault_exc is not None:
                state.fn_exception = fault_exc
                log.warning(
                    "rank %s: exception in wrapped fn at iteration %s: %r",
                    state.initial_rank, iteration, fault_exc,
                )
                record_event(
                    ProfilingEvent.INPROCESS_INTERRUPTED,
                    iteration=iteration, rank=state.initial_rank,
                    error=repr(fault_exc),
                )
                self.ops.record_interruption(
                    iteration,
                    InterruptionRecord(
                        rank=state.initial_rank,
                        interruption=Interruption.EXCEPTION,
                        message=repr(fault_exc),
                        fingerprint=snapshot_tail(),
                    ),
                )
            else:
                log.warning(
                    "rank %s: restart signal at iteration %s",
                    state.initial_rank, iteration,
                )
            record_event(
                ProfilingEvent.INPROCESS_RESTART_STARTED,
                iteration=iteration, rank=state.initial_rank,
            )
            # the episode usually already exists (minted in _on_trip at the
            # detection instant); a locally-raised fault reaching here first
            # mints it now — begin() is idempotent on a live episode
            ep = episode_mod.begin(
                store=self._store,
                claim=lambda eid: self.ops.claim_episode(iteration, eid),
                fault_class=(
                    "exception" if fault_exc is not None else "peer_signal"
                ),
                rank=state.initial_rank,
            )
            self.watchdog.ping()
            # let the monitor thread finish abort duties (the trip flow runs
            # independently of the raise loop the finally already silenced);
            # with the staged ladder those duties take real time, so wait on
            # the explicit completion handshake, not just the trip marker —
            # stopping the monitor mid-ladder would abandon rungs
            if monitor.tripped.wait(timeout=w.last_call_wait + 5.0):
                monitor.abort_done.wait(
                    timeout=sum(s.timeout for s in self.ladder.stages) + 5.0
                )
            # abort duties done: the episode moves to its decision phase
            # (fault classification, rung choice, attribution verdict)
            ep.phase("decide")
            # the ladder already counted stage outcomes in telemetry; emit
            # them into the profiling stream too so cross-process gates
            # (chaos soak) can assert rung behavior from the JSONL
            ladder_results = self.ladder.take_results()
            for res in ladder_results:
                record_event(
                    ProfilingEvent.ABORT_STAGE,
                    iteration=iteration, rank=state.initial_rank,
                    stage=res.stage, outcome=res.outcome,
                    duration_ms=round(res.duration_ms, 3),
                )
            fault_class = (
                "exception" if fault_exc is not None else "peer_signal"
            )
            # which restart rung this episode is riding: in_process unless
            # the ladder's shrink rung actually ran
            rung = (
                "mesh_shrink"
                if any(
                    r.stage == "shrink_mesh" and r.outcome == "released"
                    for r in ladder_results
                )
                else "in_process"
            )
            ep.set_fault_class(fault_class)
            self._episode = (fault_class, rung, ep.id)
            self._fingerprint_verdict(iteration, survivors)
            if (
                env.POLICY.get()
                and ledger().start_rung(fault_class) == "in_job"
            ):
                # the ledger says this fault class historically escalates
                # anyway: skip the in-process rungs and hand the episode to
                # the launcher ring (in-job restart) immediately
                ledger().record(
                    fault_class, "in_process", False,
                    (mono_ns() - self._restart_started_ns) / 1e9,
                    episode_id=ep.id,
                )
                self._episode = None
                ep.close()
                raise RestartAbort(
                    f"policy: start rung for {fault_class} is in_job"
                )
            monitor.stop()
            if sibling:
                sibling.stop()
            restart_clock.next("finalize")
            if self.ops.any_completed(iteration):
                # a peer finished fn in the same iteration our restart
                # signal fired: the job is DONE — restarting (or joining the
                # iteration barrier the completed peer will never attend)
                # would wedge the survivors until barrier_timeout
                log.info(
                    "rank %s: job completed during restart of iteration %s;"
                    " exiting", state.initial_rank, iteration,
                )
                ep.close()
                return JOB_COMPLETED
            # finalize + health check + survivor barrier = regrouping the
            # job around the fault: the episode's rendezvous phase
            ep.phase("rendezvous")
            if w.finalize:
                w.finalize(state.freeze())
            restart_clock.next("health_check")
            try:
                if w.health_check:
                    w.health_check(state.freeze())
                restart_clock.next("iteration_barrier")
            except HealthCheckError as exc:
                if self._episode is not None:
                    # episode escalates out of the process: the in-process
                    # rung failed for this fault class
                    cls, rung, eid = self._episode
                    self._episode = None
                    ledger().record(
                        cls, rung, False,
                        (mono_ns() - self._restart_started_ns) / 1e9,
                        episode_id=eid,
                    )
                ep.close()
                log.error("rank %s failed restart health check: %s", state.initial_rank, exc)
                self.ops.mark_terminated(state.initial_rank)
                self.ops.record_interruption(
                    iteration,
                    InterruptionRecord(
                        rank=state.initial_rank,
                        interruption=Interruption.TERMINATED,
                        message=f"health check: {exc}",
                    ),
                )
                raise RestartAbort(str(exc)) from exc
            if self.quorum:
                self.quorum.beat()  # restart path is progress, not a hang
            if self._iteration_barrier(iteration) == "completed":
                log.info(
                    "rank %s: job completed while waiting at the iteration"
                    " %s barrier; exiting", state.initial_rank, iteration,
                )
                ep.close()
                return JOB_COMPLETED
            restart_clock.next("reassign")
            # survivors regrouped: restoring this rank's place in the job
            ep.phase("restore")
            # the iteration-i barrier closing means every survivor advanced
            # past i-2: its interruption/fingerprint/barrier keys are settled
            # and can be GC'd (idempotent; any rank may do it)
            if state.initial_rank == 0:
                try:
                    self.ops.gc_iteration(iteration - 2)
                except (OSError, StoreError) as exc:
                    # GC is best-effort: a store hiccup here must never turn
                    # a successful recovery round into a failure
                    log.debug("iteration key GC skipped: %r", exc)
            state.rank = state.initial_rank
            state.world_size = state.initial_world_size
            self._assign()
            restart_clock.next("collect")
            # last leg: initialize + loop re-entry, closed when fn restarts
            ep.phase("resume")
            state.advance()
            self.watchdog.ping()
            # what this frame still holds of the dead iteration goes before
            # the collection (on the exception path fault_exc's traceback
            # holds fn's frames and their locals; the loop assigns each of
            # these anew before it reads it): whatever survives the
            # collection is frozen, and a frozen cycle is never examined again
            fault_exc = ret = ep = ladder_results = monitor = sibling = None
            collected = gc.collect()
            # the survivors are reachable from outside fn and outlive every
            # restart (modules, jax's traced and lowered programs): in the
            # permanent generation the next restart's collection, still a
            # full one, walks what was allocated since this one and no more.
            # They are counted as they go (generations 0-2 are listed, the
            # permanent one is not): gc.get_freeze_count() would walk the
            # permanent generation itself, every restart, for their sum
            frozen = len(gc.get_objects())
            gc.freeze()
            _COLLECTED.inc(collected)
            _FROZEN.set(frozen)
            restart_clock.next("rearm", collected, frozen)

    # -- helpers -----------------------------------------------------------

    def _build_ladder(self) -> AbortLadder:
        """Normalize the ``abort=`` plugin into the staged ladder.

        A user-provided :class:`AbortLadder` is used as-is (its unbound
        :class:`FingerprintStage`, if any, is bound to this wrapper's store
        ops); a plain callable becomes one rung between the fingerprint
        dump and the opt-in mesh-shrink; ``None`` still gets the
        fingerprint + shrink rungs — publication must not depend on the
        user remembering to configure it.
        """
        fp = FingerprintStage(
            self.ops, self.state.initial_rank, lambda: self.state.iteration
        )
        # targeted-shrink entry for the collective degrade ladder: a wrapped
        # collective that exhausted retry+relayout trips ONLY the shrink
        # rung (per-stage deadline and outcome accounting intact), not the
        # full restart ladder — parallel/degrade.py fetches this hook
        install_degrade_hook(
            DegradeToShrink(AbortLadder(ShrinkMeshStage(), name="degrade"))
        )
        user = self.w.abort
        if isinstance(user, AbortLadder):
            bound = False
            for stage in user.stages:
                if isinstance(stage, FingerprintStage):
                    # (re)bind to THIS wrapper: user ladders hold unbound
                    # stages, and a Wrapper reused across CallWrappers must
                    # not publish through a closed store client
                    stage.ops = self.ops
                    stage.rank = self.state.initial_rank
                    stage.iteration_fn = lambda: self.state.iteration
                    bound = True
            if not bound:
                user.stages.insert(0, fp)
            return user
        stages = [fp]
        if user is not None:
            # generous rung deadline for unknown user plugins: the old
            # Compose path had none at all
            stages.append(as_stage(user, timeout=30.0))
        stages.append(ShrinkMeshStage())
        return AbortLadder(*stages)

    def _on_trip(self) -> None:
        """Runs on the monitor thread at the detection instant: mint the
        fault episode (first detector job-wide wins the id) and capture the
        black box while the ring still holds the pre-fault picture.  The
        capture is the ring's snapshot, taken here; its file is written
        behind the restart (``flight.dump_deferred``), once ``run`` has
        re-entered fn."""
        iteration = self.state.iteration
        try:
            episode_mod.begin(
                store=self._store,
                claim=lambda eid: self.ops.claim_episode(iteration, eid),
                fault_class="peer_signal",
                rank=self.state.initial_rank,
            )
        except (OSError, StoreError):
            log.debug("episode mint at trip failed", exc_info=True)
        flight.dump_deferred("monitor_trip")

    def _analyze_dump_hook(self, records) -> None:
        try:
            from ..attribution.trace_analyzer import analyze_flight_dump

            summary = analyze_flight_dump(records)
            if summary:
                log.warning("flight dump analysis: %s", summary)
        except Exception:  # noqa: BLE001 - analysis never worsens a fault
            log.debug("flight dump analysis failed", exc_info=True)

    def _abort_fn(self) -> None:
        with self.atomic_lock:  # never abort inside a user atomic section
            self.ladder(self.state.freeze())

    def _fingerprint_verdict(self, iteration: int, survivors) -> None:
        """Best-effort at-abort attribution: gather the ranks' fingerprints
        and log which collective was in flight and who lagged.  Bounded by
        ``fingerprint_wait``; never blocks or fails the restart."""
        if self.w.fingerprint_wait <= 0:
            return
        try:
            tails = self.ops.wait_fingerprints(
                iteration, n=len(survivors), timeout=self.w.fingerprint_wait
            )
            for r in survivors:
                tails.setdefault(r, [])
            if not any(tails.values()):
                return
            from ..attribution.trace_analyzer import (
                analyze_fingerprints,
                degrade_verdict,
            )

            verdict = analyze_fingerprints(tails)
            log.warning(
                "abort fingerprint verdict: category=%s culprits=%s — %s",
                verdict.category, verdict.culprit_ranks, verdict.summary,
            )
            ep = episode_mod.current()
            if ep is not None and self._store is not None:
                # attach the attribution verdict to the episode record so
                # smonsvc's GET /episodes can name the implicated ranks
                # tpurx: disable=TPURX013 -- GC'd by telemetry.episode._gc: rank 0 prefix-sweeps episode/ep{n-EPISODE_KEEP}/ at every close
                self._store.set(
                    f"episode/{ep.id}/verdict",
                    json.dumps({
                        "category": verdict.category,
                        "culprit_ranks": list(verdict.culprit_ranks),
                        "summary": verdict.summary,
                    }),
                )
            # machine-readable half: pre-arm the implicated collective's
            # route so the first post-restart call starts at the verdict's
            # degrade rung instead of re-burning its deadline
            dv = degrade_verdict(verdict)
            if dv.action != "none":
                log.warning(
                    "abort degrade verdict: action=%s op=%s axis=%s — %s",
                    dv.action, dv.op, dv.axis or "-", dv.reason,
                )
                from ..parallel.health import health

                health().apply_verdict(dv)
        except Exception:  # noqa: BLE001 - attribution never blocks recovery
            log.exception("fingerprint verdict failed")

    def _reserve_wait(self, iteration: int) -> str:
        """INACTIVE spare: park until the job completes or a fault restarts
        us (via RankShouldRestart from the monitor thread)."""
        log.info(
            "rank %s inactive at iteration %s; waiting in reserve",
            self.state.initial_rank, iteration,
        )
        while True:
            if self.ops.any_completed(iteration):
                return "completed"
            self.watchdog.ping()
            if self.quorum:
                # a parked spare isn't training; its quiet stamps must not
                # read as a pod hang
                self.quorum.beat()
            time.sleep(0.2)

    def _assign(self) -> None:
        """Run the rank-assignment policy against the store's terminated set.

        A policy may discontinue a *healthy* rank (e.g. :class:`Tree`
        ``min_ranks`` propagation terminates a whole host when one chip
        dies).  That rank must record itself terminated before leaving, or
        peers' survivor sets — and therefore iteration barriers — would keep
        waiting for it.
        """
        # keep the store's global termination ORDER: stateful policies (Tree)
        # replay it event-by-event, so every rank must see the same sequence
        terminated = self.ops.terminated_ranks()
        try:
            self.w.rank_assignment(RankAssignmentCtx(self.state, terminated))
        except RankDiscontinued:
            if self.state.initial_rank not in terminated:
                self.ops.mark_terminated(self.state.initial_rank)
            raise

    def _iteration_barrier(self, iteration: int) -> str:
        """Barrier among survivors; re-computes the survivor set when peers
        die mid-barrier (their monitor marks them terminated).  Returns
        ``"ok"``, or ``"completed"`` when a peer finished the job during the
        wait — a completed peer exits without attending, so waiting for it
        would always end in BarrierTimeout."""
        deadline = time.monotonic() + self.w.barrier_timeout
        while True:
            if self.quorum:
                self.quorum.beat()  # waiting at the barrier is not a hang
            terminated_now = set(self.ops.terminated_ranks())
            survivors = [
                r
                for r in range(self.state.initial_world_size)
                if r not in terminated_now
            ]
            try:
                self.ops.iteration_barrier(
                    iteration,
                    self.state.initial_rank,
                    survivors,
                    timeout=min(10.0, max(1.0, deadline - time.monotonic())),
                )
                return "ok"
            except BarrierTimeout:
                if self.ops.any_completed(iteration):
                    return "completed"
                if time.monotonic() >= deadline:
                    raise
                log.warning(
                    "iteration %s barrier retry (survivors may have changed)",
                    iteration,
                )
