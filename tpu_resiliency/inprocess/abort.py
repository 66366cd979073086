"""The staged abort ladder: ordered, measured teardown before a restart.

Reference analog: ``inprocess/abort.py`` — ``AbortTorchDistributed`` aborts
every NCCL backend in parallel threads.  JAX exposes no collective-abort
API (SURVEY.md §7 hard part (a)) and in-flight XLA programs cannot be
cancelled from Python, so recovery here is a *degradation ladder* selected
at fault time from the cheapest viable tier (the Chameleon argument,
PAPERS.md): each rung is an :class:`AbortStage` with its own deadline and a
recorded outcome, and the monitor process's hard-timeout kill remains the
backstop below the bottom rung.

Stage outcomes (telemetry ``tpurx_abort_stage_outcomes_total{stage,outcome}``):

- ``released``  — the stage freed its resources within its deadline;
- ``timed_out`` — the stage was still blocked at its deadline (its worker
  thread is abandoned; the monitor-kill backstop covers whatever it held);
- ``failed``    — the stage raised (logged, ladder continues);
- ``escalate``  — the stage determined in-process recovery cannot proceed
  (``EscalateAbort``); remaining rungs are skipped and the fault falls
  through to the monitor-kill → launcher ring;
- ``skipped``   — gated off (``applicable()`` false, or after an escalate).

Built-in rungs:

- :class:`FingerprintStage` — publish this rank's dispatch-tail fingerprint
  (last K dispatched device programs + ages) to the store for attribution —
  the at-abort analog of the reference's Flight-Recorder dump
  (``abort.py:127-160``).  Always first: later rungs may block.
- :class:`AbortCheckpointWorkers` — kill persistent async-ckpt writers
  (reference ``AbortPersistentCheckpointProcesses`` ``:194``).
- :class:`AbortPeerExchange` — close local-ckpt replication sockets.
- :class:`AbortQuorumMonitor` — stop the device-quorum tick thread (it would
  otherwise keep dispatching collectives into a broken mesh).
- :class:`ShrinkMeshStage` — **opt-in, not measured on the chip**: tear
  down the ``jax.distributed`` client, caches and backends in-process so
  the next iteration can re-init over the surviving hosts
  (``docs/inprocess.md``, "Mesh shrink").  A wedged runtime can block the
  shutdown past any Python control — hence the hard per-stage deadline
  with automatic fallback to the backstop.
- :class:`ClearJaxCaches` — drop compiled-executable caches so the next
  iteration re-traces against the new topology when world size changed.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional

from ..telemetry import counter, flight, histogram
from ..utils import env
from ..utils.logging import get_logger

log = get_logger("inproc.abort")

EV_LADDER = flight.declare_event("abort.ladder", "name")
EV_STAGE = flight.declare_event("abort.stage", "stage", "outcome", "dur_ms")
# the ladder on the clock of every other interval.  ladder: around the
# monitor thread's abort_fn (the wait for the wrapper's atomic lock included),
# recorded in monitor_thread.py; stage: a rung's worker thread started ->
# joined or left at its deadline, one per rung that ran.  ident = the
# wrapper's (faulted) iteration, or the ladder's name and run number where it
# runs for no wrapper (the degrade ladder)
IV_LADDER = flight.declare_interval(
    "inproc.abort.ladder_begin", "inproc.abort.ladder_end"
)
IV_STAGE = flight.declare_interval(
    "inproc.abort.stage_begin", "inproc.abort.stage_end", "stage"
)

_STAGE_OUTCOMES = counter(
    "tpurx_abort_stage_outcomes_total",
    "Abort-ladder stage outcomes per restart",
    labels=("stage", "outcome"),
)
_STAGE_NS = histogram(
    "tpurx_abort_stage_latency_ns",
    "Abort-ladder per-stage wall time",
    labels=("stage",),
)
_LADDER_RUNS = counter(
    "tpurx_abort_ladder_runs_total", "Abort-ladder executions"
)


class EscalateAbort(Exception):
    """Raised by a stage to declare in-process recovery non-viable; the
    ladder stops and the fault falls through to the monitor-kill backstop."""


RELEASED = "released"
TIMED_OUT = "timed_out"
FAILED = "failed"
ESCALATE = "escalate"
SKIPPED = "skipped"


@dataclasses.dataclass
class StageResult:
    stage: str
    outcome: str
    duration_ms: float
    detail: str = ""

    def brief(self) -> str:
        return f"{self.stage}={self.outcome}({self.duration_ms:.1f}ms)"


class AbortStage:
    """One rung of the ladder.  Subclasses override :meth:`release` (and
    optionally :meth:`applicable`).  Stages stay plain callables too, so a
    bare stage still composes with ``Compose`` and the ``abort=`` plugin
    slot exactly like the pre-ladder classes did."""

    name = "stage"
    timeout: float = 5.0

    def __init__(self, timeout: Optional[float] = None):
        if timeout is not None:
            self.timeout = timeout

    def applicable(self, state=None) -> bool:
        return True

    def release(self, state=None) -> Optional[str]:
        """Free resources; return an optional human detail string."""
        raise NotImplementedError

    def __call__(self, state=None):
        self.release(state)
        return state

    def __repr__(self) -> str:
        return f"{type(self).__name__}(name={self.name!r}, timeout={self.timeout})"


class FnStage(AbortStage):
    """Adapter wrapping a plain ``fn(state)`` plugin as a ladder rung."""

    def __init__(self, fn: Callable, name: Optional[str] = None,
                 timeout: Optional[float] = None):
        super().__init__(timeout)
        self.fn = fn
        self.name = name or getattr(fn, "__name__", None) or type(fn).__name__

    def release(self, state=None) -> Optional[str]:
        self.fn(state)
        return None


def as_stage(obj, timeout: Optional[float] = None) -> AbortStage:
    if isinstance(obj, AbortStage):
        return obj
    return FnStage(obj, timeout=timeout)


class AbortLadder:
    """Ordered, per-stage-deadlined abort pipeline with recorded outcomes.

    Plugin-compatible: pass an instance as ``Wrapper(abort=...)``.  Each
    stage runs in a worker thread joined at its deadline — Python cannot
    cancel the thread, so a timed-out stage is *abandoned* (outcome
    recorded; the monitor-kill backstop owns whatever it was holding) and
    the ladder proceeds to the next rung.  ``last_results`` keeps the most
    recent run for the restart loop's telemetry/logging.
    """

    def __init__(self, *stages, name: str = "abort"):
        flat: List[AbortStage] = []
        for s in stages:
            # a Compose chain contributed as one argument flattens into rungs
            inner = getattr(s, "fns", None)
            if inner is not None and not isinstance(s, AbortStage):
                flat.extend(as_stage(f) for f in inner)
            else:
                flat.append(as_stage(s))
        self.stages = flat
        self.name = name
        self.last_results: List[StageResult] = []
        self._lock = threading.Lock()
        self._runs = 0

    def _ident(self, state):
        iteration = getattr(state, "iteration", None)
        return f"{self.name}.{self._runs}" if iteration is None else iteration

    def _run_stage(self, stage: AbortStage, state) -> StageResult:
        box = {}

        def body():
            try:
                box["detail"] = stage.release(state) or ""
            except EscalateAbort as exc:
                box["escalate"] = str(exc)
            except BaseException as exc:  # noqa: BLE001 - recorded, not fatal
                box["error"] = exc

        t0 = time.monotonic_ns()
        worker = threading.Thread(
            target=body, name=f"tpurx-abort-{stage.name}", daemon=True
        )
        with flight.span(IV_STAGE, self._ident(state), IV_LADDER, stage.name):
            worker.start()
            worker.join(timeout=stage.timeout)
        dur_ms = (time.monotonic_ns() - t0) / 1e6
        if worker.is_alive():
            return StageResult(stage.name, TIMED_OUT, dur_ms,
                               f"still blocked at {stage.timeout}s deadline")
        if "escalate" in box:
            return StageResult(stage.name, ESCALATE, dur_ms, box["escalate"])
        if "error" in box:
            log.error("abort stage %s failed: %r", stage.name, box["error"])
            return StageResult(stage.name, FAILED, dur_ms, repr(box["error"]))
        return StageResult(stage.name, RELEASED, dur_ms, box.get("detail", ""))

    def __call__(self, state=None):
        with self._lock:  # one abort episode at a time per wrapper
            _LADDER_RUNS.inc()
            self._runs += 1
            flight.record(EV_LADDER, self.name)
            # entering the ladder: mark the live episode's abort phase (the
            # degrade ladder runs outside any episode — phase() is a no-op
            # guarded by the episode's own lifecycle) and capture a black
            # box before teardown overwrites the pre-fault ring tail: the
            # snapshot is taken here, the file written once the wrapper has
            # re-entered fn or, outside any restart, after flight's bound
            from ..telemetry import episode as episode_mod

            ep = episode_mod.current()
            if ep is not None:
                ep.phase("abort")
            flight.dump_deferred("abort_ladder")
            results: List[StageResult] = []
            escalated = False
            for stage in self.stages:
                t0 = time.monotonic_ns()
                if escalated or not self._applicable(stage, state):
                    res = StageResult(stage.name, SKIPPED, 0.0,
                                      "after escalate" if escalated else "gated off")
                else:
                    res = self._run_stage(stage, state)
                    _STAGE_NS.labels(stage.name).observe(
                        time.monotonic_ns() - t0
                    )
                    if res.outcome == ESCALATE:
                        escalated = True
                _STAGE_OUTCOMES.labels(stage.name, res.outcome).inc()
                flight.record(
                    EV_STAGE, stage.name, res.outcome,
                    round(res.duration_ms, 3),
                )
                results.append(res)
            self.last_results = results
            log.warning("abort ladder: %s", self.summary(results))
            return state

    @staticmethod
    def _applicable(stage: AbortStage, state) -> bool:
        try:
            return bool(stage.applicable(state))
        except Exception:  # noqa: BLE001 - a broken gate must not stall the ladder
            log.exception("abort stage %s applicable() failed; running it",
                          stage.name)
            return True

    def take_results(self) -> List[StageResult]:
        """Drain the latest run's results exactly once (blocks until an
        in-flight run finishes — bounded by the stages' own deadlines)."""
        with self._lock:
            out, self.last_results = self.last_results, []
            return out

    def summary(self, results: Optional[List[StageResult]] = None) -> str:
        results = self.last_results if results is None else results
        return " ".join(r.brief() for r in results) or "(empty)"

    def __repr__(self) -> str:
        return f"AbortLadder({', '.join(s.name for s in self.stages)})"


# -- built-in rungs ---------------------------------------------------------


class FingerprintStage(AbortStage):
    """Publish this rank's dispatch-tail fingerprint to the store so the
    trace analyzer can name the in-flight collective and the lagging rank
    (reference: FR dump at abort, ``abort.py:127-160``)."""

    name = "fingerprint"
    timeout = 2.0

    def __init__(self, ops=None, rank: Optional[int] = None,
                 iteration_fn: Optional[Callable[[], int]] = None,
                 timeout: Optional[float] = None):
        super().__init__(timeout)
        self.ops = ops
        self.rank = rank
        self.iteration_fn = iteration_fn

    def applicable(self, state=None) -> bool:
        return self.ops is not None and self.rank is not None

    def release(self, state=None) -> Optional[str]:
        from .fingerprint import snapshot_tail

        tail = snapshot_tail()
        iteration = (
            self.iteration_fn() if self.iteration_fn is not None
            else getattr(state, "iteration", 0) or 0
        )
        self.ops.record_fingerprint(iteration, self.rank, tail)
        return f"{len(tail)} entries"


class AbortCheckpointWorkers(AbortStage):
    name = "ckpt_workers"
    timeout = 10.0

    def __init__(self, *queues, timeout: Optional[float] = None):
        super().__init__(timeout)
        self.queues = queues

    def release(self, state=None) -> Optional[str]:
        n = 0
        for q in self.queues:
            try:
                q.abort()
                n += 1
            except Exception:  # noqa: BLE001
                log.exception("failed aborting checkpoint queue")
        return f"{n}/{len(self.queues)} queues"


class AbortPeerExchange(AbortStage):
    name = "peer_exchange"
    timeout = 5.0

    def __init__(self, *exchanges, timeout: Optional[float] = None):
        super().__init__(timeout)
        self.exchanges = exchanges

    def release(self, state=None) -> Optional[str]:
        n = 0
        for ex in self.exchanges:
            try:
                ex.close()
                n += 1
            except Exception:  # noqa: BLE001
                log.exception("failed closing peer exchange")
        return f"{n}/{len(self.exchanges)} exchanges"


class AbortQuorumMonitor(AbortStage):
    name = "quorum_monitor"
    timeout = 8.0

    def __init__(self, *monitors, timeout: Optional[float] = None):
        super().__init__(timeout)
        self.monitors = monitors

    def release(self, state=None) -> Optional[str]:
        n = 0
        for m in self.monitors:
            try:
                m.stop()
                n += 1
            except Exception:  # noqa: BLE001
                log.exception("failed stopping quorum monitor")
        return f"{n}/{len(self.monitors)} monitors"


class ShrinkMeshStage(AbortStage):
    """Opt-in in-process mesh-shrink (SURVEY §7(a)).

    Tears down the ``jax.distributed`` client, the compiled caches and the
    backends *inside the process* so the next restart iteration can re-init
    at the surviving world size without a respawn.  Whether the re-init
    half works is a property of the JAX version and of how the peer left
    (``docs/inprocess.md``, "Mesh shrink"), and the chip has not measured
    it (no cell sets ``TPURX_SHRINK_MESH``) — so this rung is gated:

    - opt-in via constructor or ``TPURX_SHRINK_MESH=1``;
    - a hard ``timeout`` (a wedged runtime can block ``shutdown()`` in C++
      past any Python control) after which the outcome records
      ``timed_out`` and the fault falls through to the monitor-kill
      backstop — the ladder's automatic fallback, exercised by
      ``tests/test_layered_restart.py``.
    """

    name = "shrink_mesh"
    timeout = 20.0

    def __init__(self, enabled: Optional[bool] = None,
                 timeout: Optional[float] = None):
        super().__init__(timeout)
        if enabled is None:
            enabled = env.SHRINK_MESH.get()
        self.enabled = enabled

    def applicable(self, state=None) -> bool:
        return self.enabled

    def release(self, state=None) -> Optional[str]:
        import jax
        import jax.extend.backend as jeb  # lazy submodule

        from ..checkpointing.async_ckpt import resident as resident_mod
        from ..parallel import distributed as dist_mod

        detail = []
        if jax.distributed.is_initialized():
            jax.distributed.shutdown()
            detail.append("distributed client shut down")
        else:
            detail.append("no distributed client")
        jax.clear_caches()
        # the full reset: clearing compiled caches is NOT enough —
        # jax.distributed refuses re-init while backends are live, so the
        # backends must go too.  Every device array of the process goes with
        # them: a committed checkpoint generation must stop serving restores
        # from its snapshot slot first (its shm part stays)
        resident_mod.unpublish_device()
        jeb.clear_backends()
        detail.append("caches+backends cleared")
        # reset the bootstrap helper so the next iteration's initialize
        # plugin may re-init at the surviving world size
        dist_mod._initialized = False
        return "; ".join(detail)


class ClearJaxCaches(AbortStage):
    name = "jax_caches"
    timeout = 5.0

    def release(self, state=None) -> Optional[str]:
        import jax

        jax.clear_caches()
        return None


class DegradeToShrink:
    """Targeted mesh-shrink entry point for the collective degrade ladder.

    The self-healing collective layer (``parallel/degrade.py``) reaches its
    bottom rung when retry and re-layout both failed: the implicated link
    needs the real teardown — distributed client + backends — that
    :class:`ShrinkMeshStage` owns.  This hook runs *only* the shrink rung
    (plus any stages the caller composed into ``ladder``), through the
    ladder machinery so the stage deadline / abandoned-worker / outcome
    accounting applies — a single collective's route is rebuilt without
    tripping the full restart ladder or the pod.

    The in-process :class:`~tpu_resiliency.inprocess.wrap.Wrapper` installs
    one bound to a dedicated shrink-only ladder at build time
    (:func:`install_degrade_hook`); standalone processes get a bare
    fallback from ``parallel/degrade.py``.
    """

    def __init__(self, ladder: AbortLadder):
        self.ladder = ladder
        self.trips = 0

    def __call__(self, op: str = "", axis: str = "",
                 culprits: tuple = ()) -> str:
        self.trips += 1
        log.warning(
            "degrade-to-shrink: op=%s axis=%s culprits=%s — running "
            "targeted shrink rung", op or "?", axis or "?", list(culprits),
        )
        self.ladder(None)
        return self.ladder.summary()


_degrade_hook: Optional[DegradeToShrink] = None
_degrade_hook_lock = threading.Lock()


def install_degrade_hook(hook: Optional[DegradeToShrink]) -> None:
    """Publish the process's targeted-shrink hook (``None`` uninstalls).
    Latest install wins: the hook belongs to the live wrapper."""
    global _degrade_hook
    with _degrade_hook_lock:
        _degrade_hook = hook


def get_degrade_hook() -> Optional[DegradeToShrink]:
    with _degrade_hook_lock:
        return _degrade_hook


def default_ladder(ops=None, rank: Optional[int] = None,
                   iteration_fn: Optional[Callable[[], int]] = None,
                   *extra_stages) -> AbortLadder:
    """The standard rung order: fingerprint first (later rungs may block),
    engine teardown, opt-in mesh-shrink, cache clear."""
    return AbortLadder(
        FingerprintStage(ops, rank, iteration_fn),
        *extra_stages,
        ShrinkMeshStage(),
        ClearJaxCaches(),
    )


def evacuation_ladder(victim_rank: int, rank: Optional[int] = None,
                      *extra_stages) -> Optional[AbortLadder]:
    """Victim-scoped teardown for a policy-driven evacuation.

    Unlike the reactive ``default_ladder`` (which every rank walks after a
    fault fired), an evacuation tears down ONE predicted-to-fail rank
    while the survivors keep training: only the victim gets a ladder —
    mesh-shrink force-enabled (evacuation IS a planned shrink; the opt-in
    gate guards the measured-risk reactive path, not a deliberate
    decision) plus whatever engine-teardown stages the caller composes in.
    Every other rank gets ``None`` and must not run anything.
    """
    if rank is None:
        rank = env.RANK.get()
    if rank != victim_rank:
        return None
    return AbortLadder(
        *extra_stages,
        ShrinkMeshStage(enabled=True),
        ClearJaxCaches(),
        name="evacuate",
    )
