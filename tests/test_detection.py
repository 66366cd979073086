"""Sub-millisecond detection path: native beater (ABI v3), futex/event
tripwire, fused ICI step quorum, and the ABI-staleness forcing contract.

The acceptance property asserted here (ISSUE 7): the tripwire's wake path
is EVENT-DRIVEN — the wait loop parks in ``futex(FUTEX_WAIT)`` (or
``threading.Event.wait``) and contains no polling sleep, so staleness is
observed at wake latency instead of poll-interval granularity.
"""

import ctypes
import os
import shutil
import subprocess
import threading
import time

import numpy as np
import pytest

from tpu_resiliency.ops import quorum as q
from tpu_resiliency.ops.quorum import (
    FusedStepQuorum,
    NativeBeater,
    QuorumMonitor,
    StampTripwire,
    load_beat_lib,
    now_stamp_ns,
)


def _require_native():
    if load_beat_lib() is None:
        pytest.skip("native beat helper unavailable (no toolchain)")


@pytest.fixture
def beater():
    _require_native()
    b = NativeBeater(interval_s=0.0005)
    assert b.start()
    yield b
    b.stop()


# -- NativeBeater ------------------------------------------------------------

def test_native_beater_stamps_gen_and_jitter(beater):
    time.sleep(0.1)
    g0 = beater.generation
    assert g0 > 50  # ~200 beats in 100ms at 500µs
    first = beater.stamp_ns
    assert beater.age_ns() < 500_000_000
    time.sleep(0.02)
    assert beater.generation > g0
    assert beater.stamp_ns >= first or beater.stamp_ns < first  # folded ints
    jitter = beater.jitter_ns()
    assert jitter.size > 10
    # CLOCK_MONOTONIC wake lateness: non-negative, and sane on any host
    assert (jitter >= 0).all()
    assert np.median(jitter) < 100_000_000
    p99 = beater.jitter_p99_us()
    assert p99 is not None and p99 >= 0


def test_native_beater_freeze_then_stop(beater):
    time.sleep(0.02)
    beater.freeze()
    time.sleep(0.01)
    frozen_stamp = beater.stamp_ns
    frozen_gen = beater.generation
    time.sleep(0.05)
    assert beater.stamp_ns == frozen_stamp  # stamping stopped without join
    assert beater.generation == frozen_gen
    assert beater.age_ns() >= 40_000_000
    beater.stop()  # join + free after freeze must be clean
    assert not beater.alive
    # jitter snapshot survives stop for post-mortem reporting
    assert beater.jitter_ns().size > 0


def test_native_beater_restart_reuses_slot_and_gen(beater):
    """slot/gen are allocated once per instance: tripwire references stay
    valid across a freeze/stop -> resume cycle."""
    slot_id = id(beater.slot)
    gen_id = id(beater.gen)
    beater.stop()
    assert beater.start()
    assert id(beater.slot) == slot_id and id(beater.gen) == gen_id
    time.sleep(0.01)
    assert beater.age_ns() < 500_000_000


# -- StampTripwire: event-driven staleness ----------------------------------

def _watch_sleeps(monkeypatch):
    """Record every time.sleep() call made from a tripwire thread — the
    wait loop must never poll."""
    calls = []
    real_sleep = time.sleep

    def spy(seconds):
        if threading.current_thread().name.startswith("tpurx-stamp-tripwire"):
            calls.append(seconds)
        real_sleep(seconds)

    monkeypatch.setattr(time, "sleep", spy)
    return calls


def test_futex_tripwire_detects_freeze_event_driven(monkeypatch, beater):
    sleeps = _watch_sleeps(monkeypatch)
    hits = []
    trip = StampTripwire(
        on_stale=lambda age_ms: hits.append((age_ms, time.monotonic())),
        budget_ms=2.0, beater=beater,
    ).start()
    time.sleep(0.1)
    assert not hits, f"false trip on healthy beater: {hits}"
    t_hang = time.monotonic()
    beater.freeze()
    deadline = time.monotonic() + 3.0
    while not hits and time.monotonic() < deadline:
        time.sleep(0.0002)
    trip.stop()
    assert hits, "futex tripwire never fired"
    age_ms, t_detect = hits[0]
    latency_ms = (t_detect - t_hang) * 1e3
    # budget 2ms + one beat interval + wake latency; generous CI slack
    assert latency_ms < 500, latency_ms
    assert age_ms > 2.0
    # the acceptance assert: no polling sleep anywhere in the wait loop
    assert not sleeps, f"tripwire wait loop slept: {sleeps}"


def test_event_tripwire_detects_freeze_event_driven(monkeypatch):
    """threading.Event fallback: same contract without the native shim."""
    sleeps = _watch_sleeps(monkeypatch)
    ev = threading.Event()
    last = [now_stamp_ns()]
    hits = []
    trip = StampTripwire(
        on_stale=lambda age_ms: hits.append(time.monotonic()),
        budget_ms=20.0, event=ev,
        age_ns_fn=lambda: q.clamp_future_ns(
            q.stamp_age_ns(now_stamp_ns(), last[0])
        ),
    ).start()
    for _ in range(10):
        last[0] = now_stamp_ns()
        ev.set()
        time.sleep(0.005)
    assert not hits, "false trip while beating"
    t_hang = time.monotonic()
    deadline = time.monotonic() + 3.0
    while not hits and time.monotonic() < deadline:
        time.sleep(0.001)
    trip.stop()
    assert hits, "event tripwire never fired"
    # detection lands within ~2x budget (a beat can race the freeze by
    # almost a full budget) — far from any poll-interval quantization
    assert (hits[0] - t_hang) * 1e3 < 200
    assert not sleeps, f"tripwire wait loop slept: {sleeps}"


def test_tripwire_budget_inf_suppresses_then_rearms(beater):
    """budget=inf (protected sections) suppresses trips without stopping
    the thread; restoring a finite budget re-enables detection."""
    budget = [float("inf")]
    hits = []
    trip = StampTripwire(
        on_stale=lambda age_ms: hits.append(age_ms),
        budget_ms_fn=lambda: budget[0], beater=beater,
    ).start()
    beater.freeze()
    time.sleep(0.5)  # > REARM_MS: several suppressed timeout rounds
    assert not hits, "tripwire fired during suppression"
    budget[0] = 2.0
    deadline = time.monotonic() + 3.0
    while not hits and time.monotonic() < deadline:
        time.sleep(0.001)
    trip.stop()
    assert hits, "tripwire never fired after unsuppression"


def test_tripwire_stop_wakes_parked_waiter_fast(beater):
    trip = StampTripwire(
        on_stale=lambda age_ms: None, budget_ms=5000.0, beater=beater,
    ).start()
    time.sleep(0.02)
    t0 = time.monotonic()
    trip.stop()  # kick() must release the 5s futex wait at wake latency
    assert (time.monotonic() - t0) < 1.0


def test_quorum_monitor_futex_lane_end_to_end():
    """QuorumMonitor(native_beat, futex_tripwire): a stamp freeze fires
    on_stale through the local tripwire lane without waiting for a
    collective round.

    Held as an ordering, not against the host's clock: the collective lane
    dispatches once at start and then not again for ``interval`` = 60 s, so
    a trip that lands while the monitor's count of evaluated rounds still
    reads what it read at the freeze cannot have come from a round that saw
    the frozen stamp; and the tripwire counts its own trip before it calls
    on_stale, so the first hit says which lane raised it."""
    _require_native()
    import jax
    from tpu_resiliency.parallel.mesh import make_mesh
    from tpu_resiliency.telemetry import get_registry

    def stale_waits():
        return get_registry().value_of(
            "tpurx_quorum_futex_waits_total", {"outcome": "stale"})

    mesh = make_mesh(("all",), (len(jax.devices()),))
    hits = []
    mon = QuorumMonitor(
        mesh, budget_ms=1e9, interval=60.0,
        on_stale=lambda age: hits.append(
            (age, mon._tripwire.trip_count, mon._last_seq)),
        use_pallas=False, auto_beat_interval=0.0005, fetch_workers=2,
        native_beat=True, futex_tripwire=True,
    )
    try:
        mon.calibrate(n_ticks=5, min_budget_ms=0.5, margin_ms=0.3)
        # the lane's wiring is what is held here, not how tight a budget an
        # idle interpreter calibrates: a few ms false-trips on a host whose
        # cores six test workers share
        mon.budget_ms = max(mon.budget_ms, 50.0)
        mon.start()
        if mon._native_beater is None or not mon._native_beater.alive:
            pytest.skip("native beater unavailable")
        assert mon._tripwire.beater is mon._native_beater  # futex mode
        deadline = time.monotonic() + 30.0
        while mon._last_seq < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        rounds_at_freeze = mon._last_seq
        assert rounds_at_freeze >= 1, "the first collective round never ended"
        time.sleep(0.15)
        assert not hits, f"false trip: {hits}"
        stale_before = stale_waits()
        mon.stop_auto_beat()
        deadline = time.monotonic() + 30.0
        while not hits and time.monotonic() < deadline:
            time.sleep(0.0005)
        assert hits, "futex lane never fired"
        age_ms, trips, rounds = hits[0]
        assert trips == 1, f"the first trip came from another lane: {hits}"
        assert rounds == rounds_at_freeze, (
            f"a collective round ran between the freeze and the trip: {hits}")
        assert age_ms > mon.budget_ms
        assert stale_waits() - stale_before >= 1
    finally:
        mon.stop()


def test_progress_watchdog_watch_stale():
    """The watchdog's event-driven GIL-liveness tripwire: pings feed the
    beat event; a paused watchdog (frozen stamps) trips at wake latency."""
    from tpu_resiliency.inprocess.progress_watchdog import ProgressWatchdog

    w = ProgressWatchdog(interval=0.02).start()
    hits = []
    trip = w.watch_stale(0.15, lambda age_ms: hits.append(age_ms))
    try:
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.3:
            w.ping()
            time.sleep(0.02)
        assert not hits, f"false trip while pinging: {hits}"
        w.pause()
        deadline = time.monotonic() + 3.0
        while not hits and time.monotonic() < deadline:
            time.sleep(0.005)
        assert hits, "watchdog tripwire never fired"
        assert hits[0] >= 150.0  # age_ms at trip >= budget
    finally:
        trip.stop()
        w.stop()


# -- FusedStepQuorum: the ICI lane ------------------------------------------

@pytest.fixture(scope="module")
def mesh8():
    import jax
    from tpu_resiliency.parallel.mesh import make_mesh

    return make_mesh(("all",), (len(jax.devices()),))


def test_fused_step_quorum_healthy_and_stale(mesh8):
    import jax
    import jax.numpy as jnp

    trips = []
    fq = FusedStepQuorum(
        mesh8, budget_ms=100.0, on_stale=lambda a, d: trips.append((a, d)),
    )
    step = jax.jit(lambda x: x * 2 + 1)
    run = fq.fuse(step)
    x = jnp.ones(8)
    for _ in range(4):
        fq.beat()
        x = run(x)
    assert fq.check_now() is not None
    assert not trips, trips
    assert fq.last_max_age_ms < 100.0
    # freeze the stamp: the NEXT fused step's reduce carries the stale age
    fq._last_beat_ns = (now_stamp_ns() - 500_000_000) % q._WRAP_NS
    x = run(x)
    fq.check_now()
    assert trips and trips[0][0] > 100.0
    assert trips[0][1] is not None and 0 <= trips[0][1] < 8
    assert float(x[0]) > 0  # step outputs still flow


def test_fused_step_quorum_one_step_lag(mesh8):
    """The wrapper materializes the PREVIOUS step's packed result: the
    first call never blocks on its own reduce (check_now drains it)."""
    import jax
    import jax.numpy as jnp

    fq = FusedStepQuorum(mesh8, budget_ms=float("inf"))
    run = fq.fuse(jax.jit(lambda x: x + 1))
    fq.beat()
    run(jnp.zeros(4))
    assert fq.last_max_age_ms is None      # nothing evaluated yet
    run(jnp.zeros(4))
    assert fq.last_max_age_ms is not None  # step 2 evaluated step 1's reduce
    assert fq.check_now() is not None      # drain the in-flight one


def test_fused_step_quorum_budget_clamped_to_cap(mesh8):
    """A finite identify-mode budget above the packed age cap could never
    trip (ages saturate below it) — the constructor clamps it."""
    fq = FusedStepQuorum(mesh8, budget_ms=5000.0)
    assert fq.budget_ms == pytest.approx(q.AGE_CAP_MS)
    fq_inf = FusedStepQuorum(mesh8, budget_ms=float("inf"))
    assert fq_inf.budget_ms == float("inf")  # disabled-lane sentinel kept


def test_fused_matches_collective_fn(mesh8):
    """The fused reduce and make_quorum_fn(identify=True) agree on the
    same frozen stamp (same packing, same single-pmax semantics)."""
    from tpu_resiliency.ops.quorum import make_quorum_fn

    stale_ns = 300_000_000
    fq = FusedStepQuorum(mesh8, budget_ms=float("inf"))
    fq._last_beat_ns = (now_stamp_ns() - stale_ns) % q._WRAP_NS
    import jax

    run = fq.fuse(jax.jit(lambda x: x))
    import jax.numpy as jnp

    run(jnp.zeros(2))
    age_fused = fq.check_now()
    fn = make_quorum_fn(mesh8, use_pallas=False, identify=True)
    n = len(mesh8.devices.flatten())
    age_ns, _dev = fn(np.full(
        n, (now_stamp_ns() - stale_ns) % q._WRAP_NS, dtype=np.int64,
    ))
    assert abs(age_fused - age_ns / 1e6) < 250.0  # same stamp, ~same age


# -- ABI v3 staleness forcing ------------------------------------------------

_V2_STUB = r"""
#include <stdint.h>
void *tpurx_beat_start(int64_t *slot, int64_t interval_us) {
    (void)slot; (void)interval_us; return 0;
}
void tpurx_beat_stop(void *handle) { (void)handle; }
int tpurx_beat_abi_v2(void) { return 2; }
"""


def test_stale_v2_so_forces_rebuild(tmp_path, monkeypatch):
    """A prebuilt v2 ``.so`` (int32-ms stamps, no gen word) loads fine and
    exports start/stop — only the loader's source stamp can reject it.
    load_beat_lib must rebuild from source and come back ABI v3."""
    from tpu_resiliency.utils import native as native_mod

    cc = shutil.which(os.environ.get("CC", "cc"))
    if cc is None:
        pytest.skip("no C toolchain")
    # stage: stale v2 .so + the REAL v3 source in a scratch native dir
    src_v2 = tmp_path / "beat_v2.c"
    src_v2.write_text(_V2_STUB)
    stale_so = tmp_path / "libtpurx-beat.so"
    subprocess.run(
        [cc, "-shared", "-fPIC", "-o", str(stale_so), str(src_v2)],
        check=True, timeout=60,
    )
    shutil.copy(
        os.path.join(native_mod.NATIVE_DIR, "beat_thread.c"),
        tmp_path / "beat_thread.c",
    )
    lib_stale = ctypes.CDLL(str(stale_so))
    assert hasattr(lib_stale, "tpurx_beat_abi_v2")
    assert not hasattr(lib_stale, "tpurx_beat_abi_v3")

    monkeypatch.setattr(native_mod, "NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native_mod, "_cache", {})
    lib = load_beat_lib()
    assert lib is not None, "rebuild from source failed"
    assert int(lib.tpurx_beat_abi_v3()) == 3
    assert hasattr(lib, "tpurx_beat_wait_stale")
    # the on-disk .so was actually replaced by the rebuild (symbol names
    # live in .dynstr as plain bytes; a re-dlopen of the same path would
    # dedupe to the stale mapping, which is exactly why the loader opens a
    # fresh build under a private name — see utils/native.load_native)
    disk = stale_so.read_bytes()
    assert b"tpurx_beat_abi_v3" in disk
    assert b"tpurx_beat_abi_v2" not in disk

    # same symbols, older source: only the stamp can tell.  An edit to the
    # source must rebuild; an untouched source must not.
    assert native_mod.ensure_built("libtpurx-beat.so")[1] is False
    with open(tmp_path / "beat_thread.c", "a") as f:
        f.write("\n/* edited */\n")
    assert native_mod.ensure_built("libtpurx-beat.so")[1] is True
    assert native_mod.ensure_built("libtpurx-beat.so")[1] is False


# -- telemetry ---------------------------------------------------------------

def test_detection_telemetry_series_emit(beater):
    from tpu_resiliency.telemetry import get_registry

    reg = get_registry()
    hits = []
    trip = StampTripwire(
        on_stale=lambda age_ms: hits.append(age_ms), budget_ms=2.0,
        beater=beater,
    ).start()
    time.sleep(0.05)
    beater.jitter_p99_us()
    beater.freeze()
    deadline = time.monotonic() + 3.0
    while not hits and time.monotonic() < deadline:
        time.sleep(0.001)
    trip.stop()
    assert hits
    assert reg.value_of(
        "tpurx_quorum_futex_waits_total", {"outcome": "stale"}
    ) >= 1
    assert reg.value_of(
        "tpurx_quorum_futex_waits_total", {"outcome": "fresh"}
    ) >= 1
    names = {fam["name"] for fam in reg.collect()}
    assert "tpurx_quorum_detect_ns" in names
    assert "tpurx_beat_jitter_p99_us" in names
    assert "tpurx_beat_sched_flags" in names
