"""On-device ICI quorum heartbeat — the sub-millisecond hang-detection path.

North-star design (BASELINE.json): the reference's hang detection is a
host-side socket loop with seconds-scale latency (heartbeat timeout check
interval 5s — ``fault_tolerance/config.py:115-121``).  On TPU the pod's ICI
fabric itself can carry the liveness signal: every chip contributes a
heartbeat *age* (now - last_beat, wrap-safe on a shared wall-clock epoch),
one all-reduce-max over the mesh returns the staleness of the oldest
heartbeat anywhere in the pod, and any chip observing ``max_age > budget``
knows some rank stalled — one collective (~µs over ICI at pod scale), no
host round-trips on the hot path.

Stamp contract (v3 — the ns-scale rebuild; see ``docs/detection.md``):

- **Host domain**: stamps are ``CLOCK_REALTIME`` nanoseconds folded into
  ``[0, 2^63)`` (:func:`now_stamp_ns`) — wall clock so every process and the
  native C beater share the epoch (pod hosts are NTP-synced to ~ms, far
  inside any budget).  Age math is wrap-safe mod 2^63
  (:func:`stamp_age_ns`), and any age past the half-wrap horizon can only
  be a FUTURE stamp (NTP skew, a concurrently-stamping C thread), so it
  clamps to 0: future == fresh.
- **Device domain**: TPUs lack native int64 and f32 lacks ns precision at
  epoch magnitude, so the collective reduces int32 *ages* quantized to the
  device quantum ``2^15 ns = 32.768 µs`` (:data:`DEV_QUANTUM_NS`).  Ages
  saturate rather than wrap on device: the host computes the wrap-safe ns
  age, shifts, and clips — the device only ever compares saturating
  non-negative int32 units.
- **Intervals and jitter** are measured on ``CLOCK_MONOTONIC`` (native
  side) — an NTP step must never appear as beat jitter or a negative age.

Layers:

- :func:`make_quorum_fn` — the jitted collective: per-device ages →
  pod-wide max age.  The local reduce body is a Pallas kernel on TPU feeding
  a ``lax.pmax`` over the mesh axis; a pure-jnp fallback covers CPU test
  meshes.  Identifying WHICH rank is stale rides the same single int32
  all-reduce via age/device packing (:func:`pack_age_device`).
- :class:`FusedStepQuorum` — the ICI lane: the same packed reduce fused
  into the *training step's* dispatch, so pod-wide oldest-stamp detection
  is one allreduce riding the interconnect at step cadence — detection cost
  independent of rank count, host tripwire as backstop.
- :class:`NativeBeater` — pinned C pthread (ABI v3) stamping the slot at
  machine cadence with a generation word futex-woken on every beat.
- :class:`StampTripwire` — event-driven staleness watcher:
  ``futex(FUTEX_WAIT)`` on the beat generation word (``threading.Event``
  fallback), so staleness is observed at wake latency, not poll-interval
  granularity.  The wait loop contains no polling sleep.
- :class:`QuorumMonitor` — host-side driver: publishes this process's
  stamp, runs the collective on a cadence, reports stale devices.  The host
  monitor path (RankMonitorServer) remains the source of truth: the kernel
  can only run while the program can still run collectives, so a wedged
  chip is detected by the *other* chips observing its stale stamp — and a
  wedged fabric falls through to the host path.
"""

from __future__ import annotations

import math
import os
import threading
import time
from typing import Callable, Optional

import numpy as np

from ..telemetry import counter, gauge, histogram
from ..utils import env
from ..utils.logging import get_logger

log = get_logger("quorum")


def _on_tpu() -> bool:
    """Platform of the default backend.  A backend that fails to initialise
    raises here: answering False would silently build the ``jnp.max`` lane
    on a host whose TPU did not come up."""
    import jax

    return jax.devices()[0].platform == "tpu"


# -- stamp contract (host ns domain / device quantum) -----------------------

_WRAP_BITS = 63
_WRAP_NS = 1 << _WRAP_BITS          # host epoch fold (int64-safe)
_HALF_NS = 1 << (_WRAP_BITS - 1)    # future==fresh horizon
_MASK_NS = np.uint64(_WRAP_NS - 1)

DEV_SHIFT = 15                      # device quantum: 2^15 ns = 32.768 µs
DEV_QUANTUM_NS = 1 << DEV_SHIFT
_I32_MAX = 2 ** 31 - 1

# identify-mode packing: i32 = clamp(age_units, 0, 2^15-1) << 16 | dev_idx.
# A pmax over packed values sorts lexicographically by (age, device), so ONE
# collective — the same single int32 all-reduce as the age-only hot path —
# yields both the pod-wide max age AND which device holds it.  16 bits of
# device index covers 65k chips; 15 bits of age in device-quantum units
# saturates at (2^15-1) * 2^15 ns ≈ 1.073 s — identify-mode budgets must sit
# below AGE_CAP_MS (any sane detection budget does; saturated ages still
# compare correctly, they lose magnitude, not ordering).
_AGE_CAP = (1 << 15) - 1            # identify-mode age cap, in quantum units
_AGE_CAP_NS = _AGE_CAP << DEV_SHIFT
AGE_CAP_MS = _AGE_CAP_NS / 1e6      # ≈ 1073.7 ms


def now_stamp_ns() -> int:
    """Wall-clock ns folded into ``[0, 2^63)`` — wall clock so every process
    (and the native beater, ABI v3 parity) shares the epoch.  The fold is an
    identity until year ~2262; the age math stays wrap-safe regardless."""
    return time.time_ns() % _WRAP_NS


def stamp_age_ns(now: int, then: int) -> int:
    """Wrap-safe ns age of ``then`` as seen at ``now`` (both folded)."""
    return (now - then) % _WRAP_NS


def clamp_future_ns(age_ns: int) -> int:
    """future == fresh: an age past the half-wrap horizon can only be a
    stamp from the future (NTP skew across processes, a concurrently
    stamping native thread) — a genuinely stale stamp would have tripped
    eons earlier.  Without this clamp one such tick reads as an eras-stale
    heartbeat and trips a spurious pod-wide restart."""
    return 0 if age_ns > _HALF_NS else age_ns


def wall_time_s() -> float:
    """Sanctioned wall-clock seconds for double-slot stamp contracts (the
    progress-watchdog shm slot, monitor shared state).  Every liveness stamp
    in the repo derives from this module's clock helpers — the hygiene suite
    bans raw ``time.time()``-derived stamps elsewhere so the epoch/clock
    contract has exactly one home."""
    return time.time_ns() / 1e9


def ages_ns_from_stamps(now_ns: int, stamps_ns: "np.ndarray") -> "np.ndarray":
    """Vector wrap-safe ages (uint64 ns) with the future==fresh clamp.

    The mod-2^63 subtraction runs in uint64 with a mask — numpy int64 can
    hold neither the 2^63 modulus nor the intermediate difference."""
    local = np.asarray(stamps_ns).astype(np.uint64)
    age = (np.uint64(now_ns) - local) & _MASK_NS
    return np.where(age > np.uint64(_HALF_NS), np.uint64(0), age)


def age_units(age_ns) -> "np.ndarray":
    """ns age → saturating int32 device units (quantum ``2^15 ns``)."""
    units = np.asarray(age_ns).astype(np.uint64) >> np.uint64(DEV_SHIFT)
    return np.minimum(units, np.uint64(_I32_MAX)).astype(np.int32)


def units_to_ns(units: int) -> int:
    return int(units) << DEV_SHIFT


# -- telemetry (single declaration site for the detection plane) ------------

_DETECT_NS = histogram(
    "tpurx_quorum_detect_ns",
    "Staleness age observed at trip time (ns), per detection lane "
    "(collective / futex / fused)",
    labels=("lane",),
)
_BEAT_JITTER_P99_US = gauge(
    "tpurx_beat_jitter_p99_us",
    "Native beater stamp-interval lateness p99 (µs) — CLOCK_MONOTONIC-"
    "sourced, so an NTP step can never appear as beat jitter",
)
_BEAT_SCHED = gauge(
    "tpurx_beat_sched_flags",
    "Native beater scheduling state: bit0 = affinity-pinned, "
    "bit1 = SCHED_FIFO granted",
)
_TRIPWIRE_WAITS = counter(
    "tpurx_quorum_futex_waits_total",
    "Stamp-tripwire wait outcomes (fresh = woken by a beat, stale = "
    "budget elapsed with no beat, error = futex unavailable)",
    labels=("outcome",),
)


def make_local_max(use_pallas: bool) -> Callable:
    import jax
    import jax.numpy as jnp

    if not use_pallas:
        return jnp.max

    from jax.experimental import pallas as pl

    def kernel(ages_ref, out_ref):
        # scalar stores to VMEM are rejected; write the (1,1) tile
        out_ref[:] = jnp.max(ages_ref[:]).reshape(1, 1)

    def local_max(x):
        # pad to the int32 tile (8, 128)
        n = x.shape[0]
        pad = (-n) % (8 * 128)
        x2 = jnp.pad(x, (0, pad), constant_values=0).reshape(-1, 128)
        rows = x2.shape[0]
        row_pad = (-rows) % 8
        x2 = jnp.pad(x2, ((0, row_pad), (0, 0)), constant_values=0)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1, 1), x.dtype),
        )(x2)
        return out[0, 0]

    return local_max


def pack_age_device(age_units_arr: "np.ndarray", device_idx: "np.ndarray") -> "np.ndarray":
    """Pack (age in device-quantum units, device index) into one int32 whose
    pmax sorts lexicographically by (age, device)."""
    return (
        (np.minimum(np.asarray(age_units_arr, dtype=np.int64), _AGE_CAP)
         .astype(np.int32) << 16)
        | np.asarray(device_idx).astype(np.int32)
    )


def unpack_age_device(packed: int) -> tuple:
    """packed int32 → (age in quantum units, device index)."""
    return packed >> 16, packed & 0xFFFF


def make_quorum_fn(
    mesh,
    axis_name: Optional[str] = None,
    use_pallas: Optional[bool] = None,
    blocking: bool = True,
    identify: bool = False,
) -> Callable:
    """Build the jitted quorum collective over ``mesh``.

    Returns fn(stamps_ns: i64[n_local_devices]) -> max_age_ns (int): the
    staleness of the OLDEST heartbeat anywhere on the mesh, quantized to the
    device quantum (``2^15 ns``).  The reduction runs over wrap-safe *ages*
    (now - stamp, mod 2^63, future==fresh clamped, then quantized to
    saturating int32 units), not raw stamps — a pmin over raw wrapped
    stamps would let a fresh post-wrap stamp mask a pre-wrap hung rank.

    With ``identify=True`` the ages are packed with each device's global
    index before the reduce (see :func:`pack_age_device` — the device path
    is the identical single int32 pmax) and the fn returns
    ``(max_age_ns, stale_device_idx)``: which chip's heartbeat is oldest,
    for free, so a trip can name the culprit without a second collective.
    Identify-mode ages saturate at :data:`AGE_CAP_MS` (~1.07 s) — budgets
    must sit below it (they do: sub-ms is the point of this lane).

    Each process passes stamps for its OWN devices; the input global array is
    assembled with ``make_array_from_process_local_data`` so the call works on
    multi-host meshes.  All processes must call it together (collective)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = axis_name or mesh.axis_names[0]
    if use_pallas is None:
        use_pallas = _on_tpu()
    local_max = make_local_max(use_pallas)

    def _body(ages):
        return jax.lax.pmax(local_max(ages), axis)

    smapped = jax.shard_map(
        _body,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(),
        check_vma=False,  # the pallas local-reduce's out vma is opaque to the checker
    )
    sharding = NamedSharding(mesh, P(axis))
    jitted = jax.jit(smapped)
    n_total = int(np.prod(mesh.devices.shape))
    n_local = len(mesh.local_devices) if hasattr(mesh, "local_devices") else n_total
    single_process = n_local == n_total
    if identify:
        # global flat position of each local device in mesh order
        flat = list(mesh.devices.flatten())
        local_devs = mesh.local_devices if hasattr(mesh, "local_devices") else flat
        local_idx = np.asarray([flat.index(d) for d in local_devs], dtype=np.int32)

    def _finish(packed: int):
        if not identify:
            return units_to_ns(packed)
        units, dev = unpack_age_device(packed)
        return units_to_ns(units), dev

    def run(local_stamps_ns):
        now = now_stamp_ns()
        ages = age_units(ages_ns_from_stamps(now, local_stamps_ns).reshape(n_local))
        if identify:
            ages = pack_age_device(ages, local_idx)
        if single_process:
            # jit owns the tiny host->device transfer (one dispatch)
            global_ages = ages
        else:
            global_ages = jax.make_array_from_process_local_data(
                sharding, ages, (n_total,)
            )
        out = jitted(global_ages)
        # blocking: materialize now; non-blocking: hand back the device value
        # (int() on it later completes the dispatch) for pipelined ticks
        if blocking:
            return _finish(int(out))
        return out

    run.finish = _finish  # for pipelined callers materializing later
    return run


# -- native beater (ABI v3): pinned C pthread + futex-woken generation ------

ENV_PIN_CPU = env.BEAT_PIN_CPU.name
ENV_RT_PRIO = env.BEAT_RT_PRIO.name

# ctypes slots/generation words written by live native beater threads (and
# touchable by queued futex waiters): pinned until the matching
# tpurx_beat_stop returns — a beater dropped without stop() must never let
# the C thread write freed memory (__del__ is only best-effort)
_NATIVE_SLOT_KEEPALIVE: dict = {}


def _default_pin_cpu() -> int:
    """Default pin target: the highest-numbered CPU in our affinity mask
    (conventionally the least-contended by rank-pinned workloads); -1
    disables pinning (single-CPU hosts: pinning to the only core is a
    no-op that still costs an RT-throttle risk, skip it)."""
    try:
        cpus = sorted(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return -1
    if len(cpus) <= 1:
        return -1
    return cpus[-1]


def load_beat_lib():
    """Load (building if needed) the ABI-v3 beat helper; None without a
    toolchain.  The loader's source stamp forces a rebuild over any stale
    v2 ``.so`` — v2 stamped int32 milliseconds and lacks the generation
    word, so mixing it with ns-domain readers would silently break age
    math."""
    import ctypes

    from ..utils.native import load_native

    lib = load_native("libtpurx-beat.so")
    if lib is not None:
        lib.tpurx_beat_start.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ]
        lib.tpurx_beat_start.restype = ctypes.c_void_p
        lib.tpurx_beat_stop.argtypes = [ctypes.c_void_p]
        lib.tpurx_beat_freeze.argtypes = [ctypes.c_void_p]
        lib.tpurx_beat_flags.argtypes = [ctypes.c_void_p]
        lib.tpurx_beat_flags.restype = ctypes.c_int
        lib.tpurx_beat_jitter.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ]
        lib.tpurx_beat_jitter.restype = ctypes.c_int
        lib.tpurx_beat_wait_stale.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_uint32, ctypes.c_int64,
        ]
        lib.tpurx_beat_wait_stale.restype = ctypes.c_int
        lib.tpurx_beat_kick.argtypes = [ctypes.POINTER(ctypes.c_uint32)]
        lib.tpurx_beat_now_ns.restype = ctypes.c_int64
        lib.tpurx_beat_wrap_bits.restype = ctypes.c_int
    return lib


class NativeBeater:
    """Pinned native liveness beater: a C pthread stamping ns wall-clock
    into ``slot`` at a fixed CLOCK_MONOTONIC cadence, bumping ``gen`` and
    futex-waking waiters on every beat.

    Why native: the Python auto-beat thread's stamp jitter is GIL-scheduling
    noise (p99 ~1 ms contended) and calibrated budgets must sit above
    safety*p99 — a hard multi-ms floor.  The C thread never touches the GIL
    and is pinned (sched affinity + best-effort SCHED_FIFO, graceful
    fallback), so its p99 is tens of µs, unlocking sub-ms budgets for the
    PROCESS/DEVICE-liveness hang class.  It deliberately does NOT prove
    interpreter schedulability: a GIL-wedged interpreter keeps a C thread
    stamping — the Python beater and pending-call watchdog own that class.

    ``slot``/``gen`` are allocated once per instance and survive
    start/stop cycles, so :class:`StampTripwire` references stay valid
    across a freeze (stop) / resume — stop() freezes the stamp at its last
    value, mirroring a wedged process."""

    JITTER_RING = 256

    def __init__(self, interval_s: float = 0.001,
                 pin_cpu: Optional[int] = None,
                 rt_prio: Optional[int] = None):
        import ctypes

        self.interval_s = max(0.00005, interval_s)
        if pin_cpu is None:
            pin_cpu = env.BEAT_PIN_CPU.get(default=_default_pin_cpu())
        if rt_prio is None:
            rt_prio = env.BEAT_RT_PRIO.get()
        self.pin_cpu = pin_cpu
        self.rt_prio = rt_prio
        self.slot = ctypes.c_int64(now_stamp_ns())
        self.gen = ctypes.c_uint32(0)
        self.flags = 0
        self._lib = None
        self._handle = None
        self._final_jitter: Optional[np.ndarray] = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> bool:
        import ctypes

        if self._handle is not None:
            return True
        if self._lib is None:
            self._lib = load_beat_lib()
        if self._lib is None:
            return False
        self.slot.value = now_stamp_ns()
        self._handle = self._lib.tpurx_beat_start(
            ctypes.byref(self.slot), ctypes.byref(self.gen),
            int(self.interval_s * 1e6), self.pin_cpu, self.rt_prio,
        )
        if self._handle is None:
            return False
        _NATIVE_SLOT_KEEPALIVE[id(self)] = (self.slot, self.gen)
        self.flags = int(self._lib.tpurx_beat_flags(self._handle))
        _BEAT_SCHED.set(self.flags)
        self._final_jitter = None
        return True

    def freeze(self) -> None:
        """Stop stamping WITHOUT joining the C thread: the stamp freezes
        within one beat interval, exactly as on a real wedge — tests and
        ``chip_smoke.py`` use this so freeze->detect excludes a join.
        :meth:`stop` must still follow to join and free."""
        if self._handle is not None:
            self._lib.tpurx_beat_freeze(self._handle)

    def stop(self) -> None:
        """Stop stamping (joins the C thread).  The slot keeps its last
        stamp and the gen word freezes — ages grow from the freeze instant,
        and futex waiters time out exactly as they would on a wedge."""
        if self._handle is None:
            return
        self._final_jitter = self.jitter_ns()
        self._lib.tpurx_beat_stop(self._handle)
        self._handle = None
        _NATIVE_SLOT_KEEPALIVE.pop(id(self), None)

    def __del__(self):  # best-effort: keepalive registry prevents UAF
        try:
            self.stop()
        # tpurx: disable=TPURX009 -- __del__ at interpreter teardown: any raise prints unraisable-noise to stderr
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass

    @property
    def alive(self) -> bool:
        return self._handle is not None

    # -- stamp / generation ------------------------------------------------

    @property
    def stamp_ns(self) -> int:
        return self.slot.value % _WRAP_NS

    @property
    def generation(self) -> int:
        return self.gen.value

    def age_ns(self) -> int:
        return clamp_future_ns(stamp_age_ns(now_stamp_ns(), self.stamp_ns))

    def wait_stale(self, expected_gen: int, timeout_ns: int) -> int:
        """futex(FUTEX_WAIT) on the generation word: 0 = a beat arrived
        (or the word already moved), 1 = ``timeout_ns`` elapsed with no
        beat, <0 = -errno (no futex on this platform).  Releases the GIL
        for the wait (ctypes foreign call)."""
        import ctypes

        if self._lib is None:
            self._lib = load_beat_lib()
        if self._lib is None:
            return -95  # EOPNOTSUPP: caller falls back to Event mode
        return int(self._lib.tpurx_beat_wait_stale(
            ctypes.byref(self.gen), ctypes.c_uint32(expected_gen),
            ctypes.c_int64(timeout_ns),
        ))

    def kick(self) -> None:
        """Bump gen + wake futex waiters without a stamp (tripwire stop)."""
        import ctypes

        if self._lib is not None:
            self._lib.tpurx_beat_kick(ctypes.byref(self.gen))

    # -- jitter accounting (CLOCK_MONOTONIC, native-measured) --------------

    def jitter_ns(self) -> np.ndarray:
        """Most recent per-beat wake lateness samples (ns, monotonic clock;
        ≤ :data:`JITTER_RING`).  After stop(), the last live snapshot."""
        import ctypes

        if self._handle is None:
            if self._final_jitter is not None:
                return self._final_jitter
            return np.empty(0, dtype=np.int64)
        buf = (ctypes.c_int64 * self.JITTER_RING)()
        n = int(self._lib.tpurx_beat_jitter(self._handle, buf, self.JITTER_RING))
        return np.asarray(buf[: max(0, n)], dtype=np.int64)

    def jitter_p99_us(self) -> Optional[float]:
        samples = self.jitter_ns()
        if samples.size == 0:
            return None
        p99 = float(np.percentile(samples, 99)) / 1e3
        _BEAT_JITTER_P99_US.set(p99)
        return p99


class StampTripwire:
    """Event-driven staleness watcher on the liveness beat.

    Replaces the polling read of the stamp slot: the watcher thread parks in
    ``futex(FUTEX_WAIT)`` on the beater's generation word (native mode) or
    in ``threading.Event.wait`` (fallback), with the detection budget as the
    wait timeout.  A beat wakes it (re-arm); a timeout IS the detection —
    staleness is observed at wake latency, not poll-interval granularity.
    The wait loop contains **no polling sleep** (asserted by test).

    What it proves depends on the beat source: wired to a
    :class:`NativeBeater` it detects process/device-liveness loss; wired to
    the Python beater's event (or :class:`ProgressWatchdog` pings) it
    detects GIL-liveness loss.  Either way the budget is read through
    ``budget_ms_fn`` every wait, so calibration updates and
    protected-section suspensions (budget=inf) apply to the *next* wait
    without restarting the thread.
    """

    REARM_MS = 200.0  # chunked re-arm wait while suppressed or post-trip

    def __init__(
        self,
        on_stale: Callable[[float], None],
        budget_ms: float = 50.0,
        budget_ms_fn: Optional[Callable[[], float]] = None,
        beater: Optional[NativeBeater] = None,
        event: Optional[threading.Event] = None,
        age_ns_fn: Optional[Callable[[], int]] = None,
        name: str = "tpurx-stamp-tripwire",
    ):
        if (beater is None) == (event is None):
            raise ValueError("exactly one of beater= / event= is required")
        self.on_stale = on_stale
        self._budget_fn = budget_ms_fn or (lambda: budget_ms)
        self.beater = beater
        self.event = event
        if age_ns_fn is None:
            if beater is None:
                raise ValueError("event mode requires age_ns_fn")
            age_ns_fn = beater.age_ns
        self._age_ns_fn = age_ns_fn
        self._stop = False
        self.trip_count = 0
        self.last_trip_age_ms: Optional[float] = None
        self._thread = threading.Thread(target=self._loop, name=name, daemon=True)

    def start(self) -> "StampTripwire":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop = True
        # wake the parked waiter so stop() returns at wake latency too
        if self.beater is not None:
            self.beater.kick()
        else:
            self.event.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2)

    def _fire(self, age_ns: int) -> None:
        age_ms = age_ns / 1e6
        self.trip_count += 1
        self.last_trip_age_ms = age_ms
        _TRIPWIRE_WAITS.labels("stale").inc()
        _DETECT_NS.labels("futex").observe(age_ns)
        try:
            self.on_stale(age_ms)
        except Exception:  # noqa: BLE001 - the watcher must survive
            log.exception("stamp tripwire on_stale failed")

    def _loop(self) -> None:
        if self.beater is not None:
            self._loop_futex()
        else:
            self._loop_event()

    def _loop_futex(self) -> None:
        rearm_ns = int(self.REARM_MS * 1e6)
        while not self._stop:
            budget_ms = self._budget_fn()
            finite = math.isfinite(budget_ms)
            g = self.beater.generation
            rc = self.beater.wait_stale(
                g, int(budget_ms * 1e6) if finite else rearm_ns
            )
            if self._stop:
                return
            if rc == 0:
                _TRIPWIRE_WAITS.labels("fresh").inc()
                continue
            if rc < 0:
                # no futex on this platform: nothing to park on — bail out
                # (callers pair with the Event-mode fallback)
                _TRIPWIRE_WAITS.labels("error").inc()
                log.warning("futex wait unavailable (errno %d); tripwire exiting", -rc)
                return
            if not finite:
                continue  # suppressed (protected section): re-check budget
            age_ns = self._age_ns_fn()
            if age_ns / 1e6 <= budget_ms:
                # a manual beat() refreshed the stamp without bumping gen
                _TRIPWIRE_WAITS.labels("fresh").inc()
                continue
            self._fire(age_ns)
            # re-arm: park until the beat stream resumes (still event-driven)
            while not self._stop and self.beater.generation == g:
                self.beater.wait_stale(g, rearm_ns)

    def _loop_event(self) -> None:
        rearm_s = self.REARM_MS / 1e3
        while not self._stop:
            budget_ms = self._budget_fn()
            finite = math.isfinite(budget_ms)
            beat = self.event.wait(budget_ms / 1e3 if finite else rearm_s)
            if self._stop:
                return
            if beat:
                self.event.clear()
                _TRIPWIRE_WAITS.labels("fresh").inc()
                continue
            if not finite:
                continue
            age_ns = self._age_ns_fn()
            if age_ns / 1e6 <= budget_ms:
                _TRIPWIRE_WAITS.labels("fresh").inc()
                continue
            self._fire(age_ns)
            # re-arm: park until the beat stream resumes
            while not self._stop and not self.event.wait(rearm_s):
                pass
            self.event.clear()


class FusedStepQuorum:
    """The ICI lane: pod-wide oldest-stamp detection fused into the training
    step — one allreduce riding the step's own dispatch, so detection cost
    is a single collective independent of rank count and needs no separate
    tick thread.  The host tripwire (:class:`QuorumMonitor` /
    :class:`StampTripwire`) remains the backstop for a wedged fabric.

    ``fuse(step_fn)`` returns a jitted step that additionally reduces the
    packed per-device ages (the identical int32 pmax packing as
    :func:`make_quorum_fn` identify mode, expressed as a ``jnp.max`` over a
    mesh-sharded array so GSPMD inserts the all-reduce) and returns the
    packed pod max alongside the step outputs.  The wrapper materializes
    the PREVIOUS step's packed result each call (one-step result lag,
    bounded by step time — the collective itself ran with the step), so the
    hot path never blocks on a readback.

    Budgets must sit below :data:`AGE_CAP_MS` (~1.07 s): the packed age
    saturates there (it loses magnitude, not ordering)."""

    def __init__(
        self,
        mesh,
        axis_name: Optional[str] = None,
        budget_ms: float = 1000.0,
        on_stale: Optional[Callable[[float, int], None]] = None,
        identify: bool = True,
    ):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.axis = axis_name or mesh.axis_names[0]
        self.budget_ms = budget_ms
        if identify and math.isfinite(budget_ms) and budget_ms > AGE_CAP_MS:
            # packed ages saturate at the cap: a finite budget above it
            # could never trip — clamp so "stale beyond representable"
            # still fires (inf stays inf: lane-disabled sentinel)
            log.warning(
                "fused-quorum budget %.0fms exceeds the packed age cap; "
                "clamped to %.0fms", budget_ms, AGE_CAP_MS,
            )
            self.budget_ms = AGE_CAP_MS
        self.on_stale = on_stale
        self.identify = identify
        self.n_total = int(np.prod(mesh.devices.shape))
        self.n_local = (
            len(mesh.local_devices) if hasattr(mesh, "local_devices")
            else self.n_total
        )
        self._single_process = self.n_local == self.n_total
        self._sharding = NamedSharding(mesh, P(self.axis))
        flat = list(mesh.devices.flatten())
        local_devs = mesh.local_devices if hasattr(mesh, "local_devices") else flat
        self._local_idx = np.asarray(
            [flat.index(d) for d in local_devs], dtype=np.int32
        )
        self._jax = jax
        self._last_beat_ns = now_stamp_ns()
        self._pending = None
        self._readback = None  # lazy ResilientCollective (parallel layer)
        self.last_max_age_ms: Optional[float] = None
        self.last_stale_device: Optional[int] = None
        self.trip_count = 0

    def beat(self) -> None:
        self._last_beat_ns = now_stamp_ns()

    # -- host side ---------------------------------------------------------

    def local_ages(self) -> np.ndarray:
        ages_ns = ages_ns_from_stamps(
            now_stamp_ns(),
            np.full(self.n_local, self._last_beat_ns, dtype=np.int64),
        )
        units = age_units(ages_ns)
        if self.identify:
            return pack_age_device(units, self._local_idx)
        return units

    def device_ages(self):
        ages = self.local_ages()
        if self._single_process:
            return self._jax.device_put(ages, self._sharding)
        return self._jax.make_array_from_process_local_data(
            self._sharding, ages, (self.n_total,)
        )

    # -- fused step --------------------------------------------------------

    def fuse(self, step_fn: Callable, donate_argnums: tuple = ()) -> Callable:
        """Wrap ``step_fn`` with the fused quorum reduce.  The returned
        callable has ``step_fn``'s signature; quorum age injection, the
        one-step-lagged check, and trip firing are internal.
        ``donate_argnums`` refer to ``step_fn``'s own positions."""
        import jax.numpy as jnp

        def fused(quorum_ages, *args, **kwargs):
            out = step_fn(*args, **kwargs)
            # jnp.max over the axis-sharded ages with a replicated output:
            # GSPMD inserts the single all-reduce-max (the packed values
            # sort lexicographically by (age, device) — identify for free)
            return out, jnp.max(quorum_ages)

        jfused = self._jax.jit(
            fused, donate_argnums=tuple(i + 1 for i in donate_argnums)
        )

        def run(*args, **kwargs):
            out, packed = jfused(self.device_ages(), *args, **kwargs)
            previous, self._pending = self._pending, packed
            if previous is not None:
                # materialize LAST step's already-dispatched reduce (async
                # dispatch means this is usually a completed value) — the
                # host readback is THE blockable point of the fused lane,
                # so it rides the resilient-collective deadline lane: a
                # wedged fabric trips CollectiveTimeout (folded into the
                # staleness path below) instead of wedging the step thread
                self._materialize_check(previous)
            return out

        run.check_now = self.check_now
        run.quorum = self
        return run

    def _materialize_check(self, packed_arr) -> float:
        rc = self._readback
        if rc is None:
            # lazy: parallel.collectives imports this module (stamp/tripwire
            # machinery), so the wrapper must be built at call time
            from ..parallel.collectives import ResilientCollective
            from ..parallel.degrade import DegradePolicy

            budget = (
                max(self.budget_ms * 4.0, 50.0)
                if math.isfinite(self.budget_ms) else 0.0
            )
            rc = self._readback = ResilientCollective(
                "fused_quorum_readback",
                lambda p: int(p),
                axis=self.axis,
                deadline_ms=budget,  # 0 (budget inf) = inline fast path
                # retry/relayout cannot help a readback: the value either
                # materializes or the fabric is wedged — fail fast into the
                # staleness trip below
                policy=DegradePolicy(rungs=(), retries=0),
            )
        from ..parallel.deadline import CollectiveTimeout

        try:
            value = rc(packed_arr)
        except CollectiveTimeout:
            # the readback itself wedged: that IS the staleness signal —
            # report the saturated age (magnitude lost, ordering correct)
            self.trip_count += 1
            self.last_max_age_ms = AGE_CAP_MS
            self.last_stale_device = None
            _DETECT_NS.labels("fused").observe(int(_AGE_CAP_NS))
            if self.on_stale is not None:
                try:
                    self.on_stale(AGE_CAP_MS, None)
                except Exception:  # noqa: BLE001
                    log.exception("fused-quorum on_stale failed")
            else:
                log.error(
                    "fused quorum: readback wedged past %.0fms deadline "
                    "(axis %s)", rc.budget_ms(), self.axis,
                )
            return AGE_CAP_MS
        return self._check(value)

    def check_now(self) -> Optional[float]:
        """Materialize and check the in-flight packed result (end-of-loop
        drain; also lets tests assert synchronously).  Returns age_ms."""
        if self._pending is None:
            return None
        pending, self._pending = self._pending, None
        return self._materialize_check(pending)

    def _check(self, packed: int) -> float:
        if self.identify:
            units, dev = unpack_age_device(packed)
        else:
            units, dev = packed, None
        age_ns = units_to_ns(units)
        age_ms = age_ns / 1e6
        self.last_max_age_ms = age_ms
        self.last_stale_device = dev
        if age_ms > self.budget_ms:
            self.trip_count += 1
            _DETECT_NS.labels("fused").observe(age_ns)
            if self.on_stale is not None:
                try:
                    self.on_stale(age_ms, dev)
                except Exception:  # noqa: BLE001
                    log.exception("fused-quorum on_stale failed")
            else:
                log.error(
                    "fused quorum: pod heartbeat stale by %.3fms (device %s)",
                    age_ms, dev,
                )
        return age_ms


# calibrated budgets are at least this many median beat periods: the two a
# loop one step ahead goes without a beat when it drains the device, and a
# quarter more for steps that grow after calibration
PERIOD_SAFETY = 2.5


class QuorumMonitor:
    """Host driver for the on-device quorum tripwire.

    The workload calls :meth:`beat` every step (a host int write).  A daemon
    thread ticks the collective every ``interval`` seconds and calls
    ``on_stale(age_ms)`` when the pod-wide oldest stamp exceeds
    ``budget_ms``.  Ticks interleave with training steps on the device
    stream, so keep ``interval`` ≳ a step time.  With
    ``futex_tripwire=True`` a :class:`StampTripwire` additionally watches
    the LOCAL beat stream event-driven (futex on the native beater's gen
    word; Event fallback on the Python beater), so a local stamp freeze is
    observed at wake latency without waiting for a collective round.
    """

    def __init__(
        self,
        mesh,
        budget_ms: float = 1000.0,
        interval: float = 0.1,
        on_stale: Optional[Callable] = None,
        use_pallas: Optional[bool] = None,
        auto_beat_interval: Optional[float] = None,
        fetch_workers: int = 0,
        identify: bool = False,
        online_recalibrate_after: Optional[int] = None,
        online_min_budget_ms: float = 2.0,
        native_beat: bool = False,
        futex_tripwire: bool = False,
    ):
        self.mesh = mesh
        self.budget_ms = budget_ms
        self.interval = interval
        self.auto_beat_interval = auto_beat_interval
        # >0 enables the overlapped loop: collectives dispatch every
        # ``interval`` and results are evaluated by a fetch thread pool, so
        # detection latency is budget + interval/2 + ONE readback even when
        # the result readback takes longer than the interval (readbacks
        # multiplex across threads)
        self.fetch_workers = fetch_workers
        self.identify = identify
        self._last_seq = 0
        def _default_on_stale(age):
            from ..utils.profiling import ProfilingEvent, record_event

            log.error("pod heartbeat stale by %.1fms", age)
            record_event(ProfilingEvent.HANG_DETECTED, source="quorum", age_ms=age)

        self.on_stale = on_stale or _default_on_stale
        # tripwire callbacks may accept (age_ms, stale_device_idx); plain
        # age-only callbacks keep working
        try:
            import inspect

            n_params = len([
                p for p in inspect.signature(self.on_stale).parameters.values()
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                or p.kind == p.VAR_POSITIONAL
            ])
        except (TypeError, ValueError):
            n_params = 1
        self._on_stale_wants_device = identify and n_params >= 2
        # resolved here, once, so the lane in use can be read back
        self.use_pallas = _on_tpu() if use_pallas is None else use_pallas
        self._fn = make_quorum_fn(
            mesh, use_pallas=self.use_pallas, identify=identify
        )
        self._fn_async = None
        self._pending = None  # (dispatch_t, device_value) in-flight slot
        # results DISPATCHED at or before this fence never fire on_stale —
        # they observed a hang era that a restart has since resolved
        self._fence_t = float("-inf")
        self._last_beat_ns = now_stamp_ns()
        self.beat_event = threading.Event()  # event-mode tripwire feed
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="tpurx-quorum", daemon=True
        )
        self._beater_stop = threading.Event()
        self._beater: Optional[threading.Thread] = None
        self.last_max_age: Optional[float] = None       # ms
        self.last_max_age_ns: Optional[int] = None
        self.last_stale_device: Optional[int] = None
        self.last_calibration_p99_ms: Optional[float] = None
        self.last_calibration_period_ms: Optional[float] = None
        # Online recalibration: a pre-start calibrate() can only sample an
        # IDLE interpreter, and an idle-calibrated budget undershoots the
        # stamp lateness real training produces (false trips) — so after
        # ``online_recalibrate_after`` healthy ages observed by the RUNNING
        # loop (i.e. under the actual workload), the budget is recomputed
        # once from those in-vivo samples: safety*p99 + margin, floored at
        # ``online_min_budget_ms``.  Tripping ages are excluded (a real
        # hang must not inflate its own detection budget).
        self._recal_after = online_recalibrate_after
        self._recal_min_budget = online_min_budget_ms
        self._recal_ages: list = []
        self._recal_done = False
        # Native liveness beater (north-star lane): a pinned C pthread
        # stamping the slot at machine cadence — its p99 jitter is tens of
        # µs (scheduler noise, CLOCK_MONOTONIC-measured), not GIL
        # scheduling (~1 ms), so calibrated budgets can go sub-ms.  It
        # proves PROCESS/DEVICE liveness only: a GIL-wedged interpreter
        # keeps a C thread stamping, so the Python beater (GIL jitter is
        # its feature) and the pending-call watchdog ring retain GIL-wedge
        # detection.  Falls back to the Python beater when the toolchain
        # can't build the helper.
        self._native_beat = native_beat
        self._native_beater: Optional[NativeBeater] = None
        self._native_slot = None  # the beater's ctypes slot (tests poke it)
        self._futex_tripwire = futex_tripwire
        self._tripwire: Optional[StampTripwire] = None

    def beat(self) -> None:
        self._last_beat_ns = now_stamp_ns()
        self.beat_event.set()

    # -- liveness auto-beat (reference ProgressWatchdog auto-timestamps,
    # ``progress_watchdog.py:50-61``): a daemon thread stamping at
    # ``auto_beat_interval`` proves the interpreter schedules threads —
    # detects process death / GIL-holding wedges with a ms-scale budget,
    # independent of step cadence.  Manual ``beat()`` remains the
    # progress signal (budget tied to step time).
    def _beater_loop(self) -> None:
        while not self._beater_stop.is_set():
            self.beat()
            self._beater_stop.wait(self.auto_beat_interval)

    def _current_stamp(self) -> int:
        """Freshest liveness stamp (ns): manual beat() or the native slot.

        Freshness compares wrap-safe AGES, not raw stamps — both sources
        fold into the 2^63 ns epoch (the ABI-v3 C side mirrors
        ``now_stamp_ns``), and a raw max() would both break at the wrap and
        let a stale native stamp shadow a fresh manual ``beat()``.

        A source can legitimately stamp NEWER than our pre-read ``now``
        (the C thread runs concurrently; NTP skew across processes): its
        age then folds to ~2^63 and a naive compare would discard the
        freshest stamp for a stale one — on a monitor whose manual beat()
        is seconds old, that single race tick trips a spurious restart.
        Any age past the half-wrap horizon can only be a future stamp (a
        genuinely stale one would have tripped eons earlier), so clamp it
        to 0: future == fresh."""
        if self._native_slot is None:
            return self._last_beat_ns
        now = now_stamp_ns()
        a = self._last_beat_ns
        b = self._native_slot.value % _WRAP_NS
        age_a = clamp_future_ns((now - a) % _WRAP_NS)
        age_b = clamp_future_ns((now - b) % _WRAP_NS)
        return a if age_a <= age_b else b

    def _start_native_beater(self) -> bool:
        if self._native_beater is not None and self._native_beater.alive:
            return True
        if self._native_beater is None:
            self._native_beater = NativeBeater(
                interval_s=self.auto_beat_interval or 0.001
            )
        ok = self._native_beater.start()
        if ok:
            self._native_slot = self._native_beater.slot
        return ok

    def _stop_native_beater(self) -> None:
        if self._native_beater is not None:
            # freeze semantics: the slot keeps its last stamp so ages grow
            # from the freeze instant, mirroring a wedged process; the
            # jitter snapshot lands in the gauge before the thread joins
            self._native_beater.jitter_p99_us()
            self._native_beater.stop()

    def __del__(self):  # best-effort: registry already prevents UAF
        try:
            self._stop_native_beater()
        # tpurx: disable=TPURX009 -- __del__ at interpreter teardown: any raise prints unraisable-noise to stderr
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass

    def _start_beater(self) -> None:
        if self.auto_beat_interval is None:
            return
        if self._native_beat and self._start_native_beater():
            self._start_tripwire()
            return
        if self._beater is None or not self._beater.is_alive():
            self._beater_stop.clear()  # un-latch a previous stop_auto_beat
            self._beater = threading.Thread(
                target=self._beater_loop, name="tpurx-quorum-beat", daemon=True
            )
            self._beater.start()
        self._start_tripwire()

    def _start_tripwire(self) -> None:
        if not self._futex_tripwire or self._tripwire is not None:
            return
        local_dev = None
        if self.identify:
            # local staleness: name our own first device as the culprit
            flat = list(self.mesh.devices.flatten())
            local = (
                self.mesh.local_devices if hasattr(self.mesh, "local_devices")
                else flat
            )
            local_dev = flat.index(local[0]) if local else None

        def on_local_stale(age_ms):
            self._fire(age_ms, local_dev, lane=None)  # lane recorded by tripwire

        kwargs = dict(
            on_stale=on_local_stale,
            budget_ms_fn=lambda: self.budget_ms,
            # age from the freshest of manual beat() and the native slot —
            # a manual beat between gen wakes must suppress a false trip
            age_ns_fn=lambda: clamp_future_ns(
                stamp_age_ns(now_stamp_ns(), self._current_stamp())
            ),
        )
        if self._native_beater is not None and self._native_beater.alive:
            self._tripwire = StampTripwire(beater=self._native_beater, **kwargs)
        else:
            self._tripwire = StampTripwire(event=self.beat_event, **kwargs)
        self._tripwire.start()

    def stop_auto_beat(self) -> None:
        """Stop the liveness beater (tests simulate a wedged
        process this way — stamps freeze while the tick loop, playing the
        healthy peers' role, keeps reducing)."""
        self._beater_stop.set()
        if self._beater is not None:
            self._beater.join(timeout=2)
        # freeze semantics: the slot keeps its last stamp so ages grow from
        # the freeze instant, mirroring a wedged process
        self._stop_native_beater()

    def resume_auto_beat(self) -> None:
        """Re-arm the liveness beater (a rank recovered by the restart ring
        is alive again; its silence must stop reading as a pod hang).
        In-flight collectives dispatched during the hang era are fenced:
        their (stale-by-construction) results must not re-trip the ring."""
        self.beat()
        self._fence_t = time.monotonic()
        self._start_beater()

    def calibrate(self, n_ticks: int = 20, safety: float = 3.0,
                  margin_ms: float = 2.0, min_budget_ms: float = 5.0,
                  load_fn: Optional[Callable] = None) -> float:
        """Derive the detection budget from OBSERVED healthy tick ages
        (beat jitter + scheduling noise) instead of a safety factor over the
        beat period alone — ages already embed every real-world delay, so the
        budget is as tight as the platform allows without false positives.
        Runs ``n_ticks`` blocking ticks, sets and returns ``budget_ms``.

        ``load_fn`` (e.g. one training-step dispatch) runs before each
        calibration tick so the sampled ages embed the GIL/scheduler
        contention of REAL training — required before trusting a tight
        ``margin_ms``: a budget calibrated on an idle interpreter undershoots
        the stamp lateness a busy one produces and then false-trips.

        A calibration tick blocks, so it drains the device and the age it
        reads after ``load_fn`` is dispatch time whatever the step lasts.
        The running loop's ticks are not so lucky: with manual beats they
        read anything up to the time from one beat to the next, and up to
        two such periods when a loop that runs one step ahead drains the
        device after its last beat (a ``block_until_ready`` before a save).
        So the freshest stamp's age is also sampled right BEFORE each
        ``load_fn`` but the first — the beat period under load where
        ``load_fn`` beats, beater jitter where an auto-beater does — and the
        budget is at least ``PERIOD_SAFETY`` times their median (kept in
        ``last_calibration_period_ms``): those two periods and a quarter
        more for steps that grow after calibration.  On the chip a 0.21 s
        step beside a 250 ms floor restarted a healthy job, and a budget of
        two periods left a loop's drain 20-40 ms of room.  The
        median and not the p99: one late beat in a dozen is no period.

        The floor physics (BASELINE north-star accounting): in XLA's
        execution model a collective observes stamps only at dispatch, so
        end-to-end detection = budget + dispatch cadence + one readback.
        The budget itself cannot go below the observed p99 healthy age
        times ``safety`` without false positives — and that p99 is the beat
        interval plus the beater's stamp jitter: GIL-scheduling noise for
        the Python beater (~1 ms contended, its GIL-liveness feature), tens
        of µs for the pinned native beater.  ``min_budget_ms`` is an
        operator floor, not a physical one; set it to ~0.1 to let the
        calibration find the platform's true floor (the measured p99 is
        kept in ``last_calibration_p99_ms``)."""
        self._start_beater()
        ages, periods = [], []
        for i in range(max(3, n_ticks)):
            if load_fn is not None:
                if i:
                    periods.append(clamp_future_ns(stamp_age_ns(
                        now_stamp_ns(), self._current_stamp())) / 1e6)
                load_fn()
            saved = self.budget_ms
            self.budget_ms = float("inf")  # no trips during calibration
            try:
                ages.append(self.tick())
            finally:
                self.budget_ms = saved
        ages_arr = np.asarray(sorted(ages), dtype=np.float64)
        p99 = float(ages_arr[min(len(ages_arr) - 1, int(0.99 * len(ages_arr)))])
        self.last_calibration_p99_ms = p99
        budget = max(min_budget_ms, safety * p99 + margin_ms)
        period = float(np.median(periods)) if periods else None
        if period is not None:
            budget = max(budget, PERIOD_SAFETY * period + margin_ms)
        self.last_calibration_period_ms = period
        if self.identify and budget >= AGE_CAP_MS:
            # packed ages saturate at the cap: a budget there never trips
            log.warning(
                "calibrated quorum budget %.0fms is past the %.0fms "
                "identify-mode age cap; clamped to %.0fms",
                budget, AGE_CAP_MS, 0.9 * AGE_CAP_MS,
            )
            budget = 0.9 * AGE_CAP_MS
        log.info(
            "quorum calibration: budget %.1fms (p99 tick age %.2fms, median beat "
            "period %s over %d ticks)", budget, p99,
            "not sampled" if period is None else "%.2fms" % period, len(ages),
        )
        self.budget_ms = budget
        return self.budget_ms

    def _observe_healthy_age(self, age: float) -> None:
        """Feed the online recalibration with an under-load healthy age."""
        if self._recal_after is None or self._recal_done or age > self.budget_ms:
            return
        self._recal_ages.append(float(age))
        if len(self._recal_ages) < self._recal_after:
            return
        ages = sorted(self._recal_ages)
        p99 = ages[min(len(ages) - 1, int(0.99 * len(ages)))]
        new_budget = max(self._recal_min_budget, 3.0 * p99 + 2.0)
        log.info(
            "quorum online recalibration: budget %.1fms -> %.1fms "
            "(p99 under load %.2fms over %d ticks)",
            self.budget_ms, new_budget, p99, len(ages),
        )
        self.last_calibration_p99_ms = p99
        self.budget_ms = new_budget
        self._recal_done = True
        self._recal_ages = []

    def _split(self, result):
        if self.identify:
            return result
        return result, None

    def _fire(self, age_ms: float, dev: Optional[int], lane: str = "collective") -> None:
        if lane is not None:
            _DETECT_NS.labels(lane).observe(int(age_ms * 1e6))
        if self._on_stale_wants_device:
            self.on_stale(age_ms, dev)
        else:
            self.on_stale(age_ms)

    def _record(self, age_ns: int, dev: Optional[int]) -> float:
        age_ms = age_ns / 1e6
        self.last_max_age = age_ms
        self.last_max_age_ns = age_ns
        self.last_stale_device = dev
        return age_ms

    def tick(self) -> float:
        """One collective; returns the pod-wide max heartbeat age (ms,
        quantized to the device quantum)."""
        n_local = (
            len(self.mesh.local_devices)
            if hasattr(self.mesh, "local_devices")
            else int(np.prod(self.mesh.devices.shape))
        )
        stamps = np.full(n_local, self._current_stamp(), dtype=np.int64)
        age_ns, dev = self._split(self._fn(stamps))
        age = self._record(age_ns, dev)
        self._observe_healthy_age(age)
        if age > self.budget_ms:
            self._fire(age, dev)
        return age

    def tick_pipelined(self) -> Optional[float]:
        """Pipelined variant: dispatch this tick's collective without blocking
        and evaluate the PREVIOUS tick's result.  Hides the device round-trip
        behind the tick interval — on a dispatch-latency-bound link the
        effective cadence doubles, at the cost of results lagging one tick
        (bounded, and far under any budget).  Returns the previous age (ms),
        or None on the first call."""
        if self._fn_async is None:
            self._fn_async = make_quorum_fn(
                self.mesh, use_pallas=self.use_pallas, blocking=False,
                identify=self.identify,
            )
        n_local = (
            len(self.mesh.local_devices)
            if hasattr(self.mesh, "local_devices")
            else int(np.prod(self.mesh.devices.shape))
        )
        # the fence compares against the instant BEFORE the stamp is read: a
        # dispatch can take long enough (host-to-device copy, a busy
        # stream, the GIL) for a beat + fence to land in between, and a
        # time taken after it would let the pre-fence age through
        t_disp = time.monotonic()
        stamps = np.full(n_local, self._current_stamp(), dtype=np.int64)
        pending = self._fn_async(stamps)
        previous, self._pending = self._pending, (t_disp, pending)
        if previous is None:
            return None
        t_disp, value = previous
        # int() materializes the already-dispatched result
        age_ns, dev = self._split(self._fn_async.finish(int(value)))
        age = self._record(age_ns, dev)
        self._observe_healthy_age(age)
        if age > self.budget_ms and t_disp > self._fence_t:
            self._fire(age, dev)
        return age

    def warmup(self) -> None:
        """Compile + run both collective variants so the monitor loop's
        first iteration doesn't spend ~0.5s tracing while hangs go
        unobserved."""
        saved = self.budget_ms
        self.budget_ms = float("inf")
        try:
            self.tick()
            self.tick_pipelined()
            self.tick_pipelined()
            # drain the in-flight dispatch: its host-side age includes the
            # compile time above and would trip a spurious on_stale as the
            # loop's first evaluated result
            if self._pending is not None:
                int(self._pending[1])
                self._pending = None
        finally:
            self.budget_ms = saved

    def start(self) -> "QuorumMonitor":
        self.beat()
        self._start_beater()
        if self._fn_async is None:
            self.warmup()
        self.beat()
        self._thread.start()
        return self

    def _loop(self) -> None:
        if self.fetch_workers > 0:
            self._loop_overlapped()
            return
        # pipelined ticks: the device round-trip hides behind the interval,
        # so the effective detection cadence is ~interval instead of
        # interval + round-trip (documented one-tick result lag)
        while not self._stop.is_set():
            try:
                self.tick_pipelined()
            except Exception as exc:  # noqa: BLE001
                log.warning("quorum tick failed: %s", exc)
                return
            self._stop.wait(self.interval)

    def _loop_overlapped(self) -> None:
        from concurrent.futures import ThreadPoolExecutor

        if self._fn_async is None:
            self._fn_async = make_quorum_fn(
                self.mesh, use_pallas=self.use_pallas, blocking=False,
                identify=self.identify,
            )
        n_local = (
            len(self.mesh.local_devices)
            if hasattr(self.mesh, "local_devices")
            else int(np.prod(self.mesh.devices.shape))
        )
        lock = threading.Lock()
        inflight = [0]

        def evaluate(seq, t_disp, pending):
            try:
                age_ns, dev = self._split(self._fn_async.finish(int(pending)))
            except Exception as exc:  # noqa: BLE001
                log.warning("quorum fetch failed: %s", exc)
                return
            finally:
                with lock:
                    inflight[0] -= 1
            # on_stale stays serialized and at-most-once per dispatch seq
            # (monotonic), matching the single-threaded tick loop's contract
            # — restart machinery wired to it need not be re-entrant
            fire = False
            age = age_ns / 1e6
            with lock:
                if seq > self._last_seq:
                    self._last_seq = seq
                    self._record(age_ns, dev)
                    self._observe_healthy_age(age)
                    fire = age > self.budget_ms and t_disp > self._fence_t
                if fire:
                    self._fire(age, dev)

        # interval == 0 is the DENSE RE-DISPATCHED CHAIN: the next collective
        # dispatches the moment a slot frees, so the cadence term of the
        # detection floor (budget + cadence + readback) collapses from a
        # polling interval to the dispatch cost itself (~0.1-0.5 ms).  The
        # in-flight cap keeps the chain bounded; evaluation stays on the
        # fetch pool.
        seq = 0
        with ThreadPoolExecutor(
            max_workers=self.fetch_workers, thread_name_prefix="tpurx-quorum-fetch"
        ) as pool:
            while not self._stop.is_set():
                with lock:
                    free = inflight[0] < self.fetch_workers
                if free:
                    t_disp = time.monotonic()  # before the stamp is read
                    try:
                        stamps = np.full(n_local, self._current_stamp(), dtype=np.int64)
                        pending = self._fn_async(stamps)
                    except Exception as exc:  # noqa: BLE001
                        log.warning("quorum dispatch failed: %s", exc)
                        return
                    seq += 1
                    with lock:
                        inflight[0] += 1
                    pool.submit(evaluate, seq, t_disp, pending)
                    if self.interval > 0:
                        self._stop.wait(self.interval)
                else:
                    # all slots busy: yield briefly instead of spinning
                    self._stop.wait(self.interval or 0.0002)

    def stop(self) -> None:
        self._stop.set()
        if self._tripwire is not None:
            self._tripwire.stop()
            self._tripwire = None
        self.stop_auto_beat()
        if self._thread.is_alive():
            self._thread.join(timeout=5)


def quorum_reduce(mesh, stamps_ns) -> int:
    """One-shot quorum collective: max heartbeat age (ns) across the mesh
    (builds + caches the fn per mesh)."""
    key = id(mesh)
    fn = _FN_CACHE.get(key)
    if fn is None:
        fn = make_quorum_fn(mesh)
        _FN_CACHE[key] = fn
    return fn(stamps_ns)


_FN_CACHE: dict = {}
