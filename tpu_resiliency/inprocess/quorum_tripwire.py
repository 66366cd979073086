"""Quorum tripwire: wire the on-device ICI hang detector into the restart rings.

The :class:`~tpu_resiliency.ops.quorum.QuorumMonitor` detects a pod-wide
stale heartbeat in milliseconds (one int32 all-reduce over ICI), but detection
that triggers nothing shortens no recovery.  This module converts a quorum
trip into the SAME signals the host-side detectors produce, so the existing
restart machinery runs — just sooner:

- **In-process ring** (:class:`QuorumTripwire`): a trip writes an
  :class:`~tpu_resiliency.inprocess.attribution.InterruptionRecord` of kind
  ``QUORUM_STALE`` into the iteration's interruption log — exactly what the
  reference's monitor thread watches (``inprocess/monitor_thread.py:157-186``).
  Every rank's :class:`MonitorThread` sees the record, runs Abort, and
  async-raises ``RankShouldRestart``; the restart loop proceeds without ever
  waiting for the soft/hard host timeouts.
- **In-job ring** (:func:`quorum_restart_requester`): a trip sends a
  ``WorkloadControlRequest(RestartWorkload)`` through the rank-monitor IPC to
  the launcher (reference ``data.py:272`` semantics), which stops the cycle's
  workers and opens a new rendezvous round immediately instead of waiting for
  the rank-heartbeat timeout.

The stale *rank* is identified in the same single collective via
age-device packing (``ops/quorum.py::pack_age_device``): the trip names the
culprit chip, mapped to the process that owns it.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from ..ops.quorum import QuorumMonitor
from ..utils.logging import get_logger
from ..utils.profiling import ProfilingEvent, record_event
from .attribution import Interruption, InterruptionRecord
from .store_ops import InprocStore

log = get_logger("quorum_tripwire")


def device_owner_rank(mesh, device_idx: Optional[int]) -> int:
    """Map a global mesh-flat device index to the rank (process index) that
    owns it.  Single-process meshes own every device — the culprit is rank 0
    by definition of "process rank", but the device index itself still names
    the chip."""
    if device_idx is None:
        return -1
    flat = list(mesh.devices.flatten())
    if not 0 <= device_idx < len(flat):
        return -1
    return int(getattr(flat[device_idx], "process_index", 0))


class QuorumTripwire:
    """In-process-ring glue: quorum trip -> interruption record -> restart.

    One tripwire per :class:`CallWrapper` iteration.  ``beat()`` is the
    workload's progress signal (call it every step); an optional auto-beater
    covers liveness between steps.  On a trip the stale rank's interruption
    record is written at most once per iteration, by every observer (the
    store's interruption log is append-only and the monitor thread coalesces
    duplicates, reference ``wrap.py:162`` last-call-wait semantics).
    """

    def __init__(
        self,
        mesh,
        ops: InprocStore,
        rank: int,
        budget_ms: float = 50.0,
        interval: float = 0.01,
        auto_beat_interval: Optional[float] = 0.002,
        calibrate: bool = True,
        min_budget_ms: float = 2.0,
        use_pallas: Optional[bool] = None,
        fetch_workers: int = 0,
        native_beat: bool = False,
        futex_tripwire: bool = False,
        on_trip: Optional[Callable[[int, int], None]] = None,
    ):
        self.mesh = mesh
        self.ops = ops
        self.rank = rank
        self.calibrate = calibrate
        self.min_budget_ms = min_budget_ms
        self.on_trip = on_trip
        self._iteration = 0
        self._fired_iteration: Optional[int] = None
        self._lock = threading.Lock()
        self._suspended_budget: Optional[float] = None
        self.trip_time: Optional[float] = None
        self.monitor = QuorumMonitor(
            mesh,
            budget_ms=budget_ms,
            interval=interval,
            auto_beat_interval=auto_beat_interval,
            on_stale=self._on_stale,
            use_pallas=use_pallas,
            fetch_workers=fetch_workers,
            native_beat=native_beat,
            # event/futex wait on the local beat stream: a local stamp
            # freeze is observed at wake latency and recorded through the
            # same interruption path, without waiting for a collective round
            futex_tripwire=futex_tripwire,
            identify=True,
            # pre-start calibration can only sample an idle interpreter;
            # after 256 in-vivo healthy ticks under the real workload the
            # budget is recomputed from those samples (see QuorumMonitor)
            online_recalibrate_after=256,
            online_min_budget_ms=min_budget_ms,
        )

    # -- workload API ------------------------------------------------------

    def beat(self) -> None:
        self.monitor.beat()

    def start(self, iteration: int = 0) -> "QuorumTripwire":
        self._iteration = iteration
        self._fired_iteration = None
        if self.calibrate:
            # the idle-calibrated budget is PROVISIONAL: doubled until the
            # online recalibration has seen real-workload ages, because an
            # idle sample undershoots busy-interpreter stamp lateness and
            # a too-tight early budget would fire a spurious restart
            self.monitor.calibrate(min_budget_ms=self.min_budget_ms)
            self.monitor.budget_ms *= 2.0
        self.monitor.start()
        return self

    def suspend(self) -> None:
        """No trips until the next :meth:`set_iteration`.  The restart path
        stops pinging by design — last-call wait, abort ladder, health
        check, barrier — and with manual beats it outlasts any step-sized
        budget; a wedged restart is the monitor process's to catch."""
        with self._lock:
            if self._suspended_budget is None:
                self._suspended_budget = self.monitor.budget_ms
                self.monitor.budget_ms = float("inf")

    def set_iteration(self, iteration: int) -> None:
        with self._lock:
            self._iteration = iteration
            self._fired_iteration = None
        # a restarted rank is alive by construction: refresh the stamp and
        # re-arm the liveness beater so the OLD hang's silence doesn't trip
        # the NEW iteration
        self.monitor.resume_auto_beat()
        with self._lock:
            if self._suspended_budget is not None:
                # after the fence above: ticks dispatched while suspended
                # carry restart-path ages and must not fire on the way out
                self.monitor.budget_ms = self._suspended_budget
                self._suspended_budget = None

    def stop(self) -> None:
        self.monitor.stop()

    # -- trip path ---------------------------------------------------------

    def _on_stale(self, age_ms: int, device_idx: Optional[int]) -> None:
        with self._lock:
            it = self._iteration
            if self._fired_iteration == it:
                return  # at most one record per iteration from this observer
            self._fired_iteration = it
        stale_rank = device_owner_rank(self.mesh, device_idx)
        self.trip_time = time.monotonic()
        log.error(
            "quorum tripwire: heartbeat stale by %.3fms (device %s, rank %s) "
            "at iteration %s — recording interruption",
            age_ms, device_idx, stale_rank, it,
        )
        record_event(
            ProfilingEvent.HANG_DETECTED,
            source="quorum_tripwire", age_ms=age_ms,
            device=device_idx if device_idx is not None else -1,
            rank=stale_rank, iteration=it,
        )
        try:
            self.ops.record_interruption(
                it,
                InterruptionRecord(
                    rank=stale_rank,
                    interruption=Interruption.QUORUM_STALE,
                    message=f"ICI quorum: heartbeat stale {age_ms:.3f}ms "
                            f"(device {device_idx})",
                    origin_rank=self.rank,
                ),
            )
        except Exception:  # noqa: BLE001 - the tick thread must survive
            log.exception("failed recording quorum interruption")
        if self.on_trip is not None:
            try:
                self.on_trip(age_ms, stale_rank)
            except Exception:  # noqa: BLE001
                log.exception("on_trip callback failed")


def quorum_restart_requester(client, min_interval_s: float = 5.0) -> Callable:
    """In-job-ring glue: returns an ``on_stale``/``on_trip`` callback that
    asks the launcher to restart the cycle via the rank monitor IPC
    (``WorkloadControlRequest(RestartWorkload)``).

    ``client`` is a connected
    :class:`~tpu_resiliency.fault_tolerance.rank_monitor_client.RankMonitorClient`.
    Requests are rate-limited: the launcher needs one signal, not one per
    tick while the stop is in flight.
    """
    from ..fault_tolerance.data import WorkloadAction

    state = {"last": 0.0}
    lock = threading.Lock()

    def on_stale(age_ms, stale=None):
        now = time.monotonic()
        with lock:
            if now - state["last"] < min_interval_s:
                return
            state["last"] = now
        log.error(
            "quorum tripwire: requesting in-job restart (stale %sms, rank %s)",
            age_ms, stale,
        )
        try:
            client.send_workload_control_request(
                WorkloadAction.RestartWorkload,
                reason=f"ICI quorum: heartbeat stale {age_ms:.3f}ms (rank {stale})",
            )
        except Exception:  # noqa: BLE001 - detection must not kill the detector
            log.exception("failed sending quorum restart request")

    return on_stale
