"""Model / mesh / quorum tests on the 8-device CPU mesh."""

import time

import jax
import numpy as np
import pytest

from tpu_resiliency.models.transformer import (
    TransformerConfig,
    init_opt_state,
    init_params,
    loss_fn,
    make_batch,
    make_train_step,
)
from tpu_resiliency.ops.quorum import QuorumMonitor, make_quorum_fn, now_stamp_ns
from tpu_resiliency.parallel.collectives import device_max_reduce, make_timeouts_reduce_fn
from tpu_resiliency.parallel.mesh import make_mesh

CFG = TransformerConfig(vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=128, max_seq=32)


def test_make_mesh_shapes():
    mesh = make_mesh(("data", "model"), (4, 2))
    assert mesh.shape == {"data": 4, "model": 2}
    mesh2 = make_mesh(("data", "model"), (-1, 2))
    assert mesh2.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(("a",), (3,))


def test_forward_loss_finite():
    params = init_params(CFG)
    batch = make_batch(CFG, 2, 32)
    loss = loss_fn(params, batch, CFG)
    assert np.isfinite(float(loss))
    assert abs(float(loss) - np.log(CFG.vocab)) < 1.0  # random init ≈ uniform


def test_train_step_learns_sharded():
    mesh = make_mesh(("data", "model"), (4, 2))
    params = init_params(CFG, mesh=mesh)
    opt = init_opt_state(params)
    batch = make_batch(CFG, 8, 32, mesh=mesh)
    step = make_train_step(CFG, mesh=mesh, lr=1e-2)
    losses = []
    for _ in range(5):
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # memorizing a fixed batch
    # params kept their sharding through the step
    wq = params["layers"][0]["wq"]
    assert len(wq.sharding.device_set) == 8


def test_device_max_reduce_single_process():
    out = device_max_reduce([1.0, 5.0, -2.0])
    assert out == [1.0, 5.0, -2.0]
    fn = make_timeouts_reduce_fn()
    assert fn({"a": 3.0, "b": 7.0}) == {"a": 3.0, "b": 7.0}


def test_quorum_reduce_max_age():
    mesh = make_mesh(("all",), (8,))
    fn = make_quorum_fn(mesh, use_pallas=False)
    now = now_stamp_ns()
    stamps = np.full(8, now, dtype=np.int64)
    stamps[3] = now - 500_000_000  # one device 500ms stale
    age_ns = fn(stamps)
    assert 500_000_000 <= age_ns < 2_000_000_000, age_ns


def test_quorum_age_wrap_safe():
    """A hung rank's pre-wrap stamp must dominate fresh post-wrap stamps."""
    mesh = make_mesh(("all",), (8,))
    fn = make_quorum_fn(mesh, use_pallas=False)
    import tpu_resiliency.ops.quorum as q
    now = 100_000_000  # 100ms after the 2^63 wrap
    hung = q._WRAP_NS - 400_000_000  # beat 500ms ago, before the wrap
    orig = q.now_stamp_ns
    q.now_stamp_ns = lambda: now
    try:
        fn2 = make_quorum_fn(mesh, use_pallas=False)
        stamps = np.full(8, now - 1_000_000, dtype=np.int64)
        stamps[5] = hung
        age_ns = fn2(stamps)
        assert 400_000_000 <= age_ns < 800_000_000, age_ns
    finally:
        q.now_stamp_ns = orig


def test_quorum_identify_names_stale_device():
    """identify=True returns (age_ns, device_idx) from the SAME single int32
    pmax (host-side packing, ops/quorum.py::pack_age_device)."""
    mesh = make_mesh(("all",), (8,))
    fn = make_quorum_fn(mesh, use_pallas=False, identify=True)
    now = now_stamp_ns()
    stamps = np.full(8, now, dtype=np.int64)
    stamps[5] = now - 500_000_000  # 500ms: below the packed cap
    age_ns, dev = fn(stamps)
    assert 500_000_000 <= age_ns < 2_000_000_000, age_ns
    assert dev == 5
    # saturation: ages past the 15-bit cap still compare and identify
    stamps[2] = now - 10_000_000_000  # 10s >> ~1.07s cap
    age2, dev2 = fn(stamps)
    assert dev2 == 2
    from tpu_resiliency.ops.quorum import _AGE_CAP, units_to_ns
    assert age2 == units_to_ns(_AGE_CAP)


def test_quorum_monitor_identify_passes_device_to_on_stale():
    mesh = make_mesh(("all",), (8,))
    hits = []
    mon = QuorumMonitor(
        mesh, budget_ms=100.0, interval=0.01,
        on_stale=lambda age, dev: hits.append((age, dev)),
        use_pallas=False, identify=True,
    )
    mon.start()
    deadline = time.monotonic() + 5.0
    while not hits and time.monotonic() < deadline:
        time.sleep(0.01)
    mon.stop()
    assert hits
    age, dev = hits[0]
    assert age > 100
    assert 0 <= dev < 8


def test_quorum_monitor_detects_stale():
    mesh = make_mesh(("all",), (8,))
    hits = []
    mon = QuorumMonitor(
        mesh, budget_ms=100.0, interval=0.01,
        on_stale=lambda age: hits.append(age), use_pallas=False,
    )
    mon.start()
    # healthy while beating
    for _ in range(10):
        mon.beat()
        time.sleep(0.02)
    assert not hits
    # stop beating -> stale trip within budget + a few ticks
    t0 = time.monotonic()
    deadline = t0 + 5.0
    while not hits and time.monotonic() < deadline:
        time.sleep(0.01)
    mon.stop()
    assert hits
    latency_ms = (time.monotonic() - t0) * 1000
    assert latency_ms < 2000


def test_quorum_tick_pipelined():
    mesh = make_mesh(("all",), (8,))
    hits = []
    mon = QuorumMonitor(
        mesh, budget_ms=100.0, interval=0.01,
        on_stale=lambda age: hits.append(age), use_pallas=False,
    )
    mon.beat()
    assert mon.tick_pipelined() is None      # first call primes the pipe
    age1 = mon.tick_pipelined()
    assert age1 is not None and age1 < 100
    # stop beating; ages grow; stale fires once past budget (1-tick lag)
    time.sleep(0.15)
    mon.tick_pipelined()
    age = mon.tick_pipelined()
    assert age is not None and age >= 100
    assert hits


@pytest.mark.parametrize("identify", [False, True])
def test_quorum_calibration_follows_the_beat_period(identify):
    """Manual beats: a calibration tick blocks, so the age it reads after the
    load is dispatch time, while the running loop's ticks read up to a whole
    beat period, and two when a loop one step ahead drains the device.  The
    period is sampled before each load and the budget is two and a half of
    their median where that passes the operator's floor (a 0.21 s step
    beside a 250 ms floor false-tripped a healthy job on the chip); a floor
    above that still binds, one late beat does not move it, and identify
    mode keeps the budget under the packed-age cap, past which it could
    never trip."""
    from tpu_resiliency.ops.quorum import AGE_CAP_MS

    mesh = make_mesh(("all",), (8,))
    mon = QuorumMonitor(mesh, budget_ms=1e9, interval=0.01, use_pallas=False,
                        identify=identify)
    busy_s = [0.04]
    reduce = mon._fn

    def blocking_reduce(stamps):  # the device is busy with the step: the
        out = reduce(stamps)      # age is taken at dispatch, the result
        time.sleep(busy_s[0])     # comes when the step has drained
        return out

    mon._fn = blocking_reduce
    try:
        budget = mon.calibrate(n_ticks=5, min_budget_ms=20.0, load_fn=mon.beat)
        period = mon.last_calibration_period_ms
        assert period >= 40.0
        assert mon.last_calibration_p99_ms < period  # dispatch time, not a step
        assert budget >= 2.5 * period
        # one late beat in five is no period: the median holds
        late = iter([0.0, 0.3, 0.0, 0.0, 0.0])
        mon.calibrate(n_ticks=5, min_budget_ms=20.0,
                      load_fn=lambda: (time.sleep(next(late)), mon.beat()))
        assert mon.last_calibration_period_ms < 150.0
        # the floor binds when the periods' share stays under it
        assert mon.calibrate(n_ticks=5, min_budget_ms=900.0,
                             load_fn=mon.beat) == 900.0
        # no load, no period: the old formula alone
        mon.calibrate(n_ticks=3, min_budget_ms=1.0)
        assert mon.last_calibration_period_ms is None
        if identify:
            busy_s[0] = 0.6
            assert mon.calibrate(n_ticks=3, load_fn=mon.beat) < AGE_CAP_MS
    finally:
        mon.stop()


def test_quorum_overlapped_loop_and_calibrate():
    """fetch_workers>0: dispatches overlap result readbacks; calibrated
    budget derives from observed healthy ages; auto-beat keeps the pod
    healthy until stopped, then the stale trip fires."""
    mesh = make_mesh(("all",), (8,))
    hits = []
    mon = QuorumMonitor(
        mesh, budget_ms=1e9, interval=0.005,
        on_stale=lambda age: hits.append(age), use_pallas=False,
        auto_beat_interval=0.002, fetch_workers=4,
    )
    budget = mon.calibrate(n_ticks=8)
    assert budget >= 5.0
    mon.start()
    time.sleep(0.3)
    assert not hits, f"false trip on healthy pod: {hits}"
    assert mon.last_max_age is not None  # overlapped loop is evaluating
    mon.stop_auto_beat()
    t0 = time.monotonic()
    while not hits and time.monotonic() - t0 < 5.0:
        time.sleep(0.005)
    mon.stop()
    assert hits
    assert (time.monotonic() - t0) * 1000 < 2000


def test_quorum_dense_chain_and_load_calibration():
    """interval=0 (dense re-dispatched chain): the next collective
    dispatches as soon as a slot frees, so the cadence term of the
    detection floor collapses to the dispatch cost; calibrate(load_fn=...)
    samples healthy ages UNDER LOAD so a tight margin stays honest."""
    import jax

    from tpu_resiliency.parallel.mesh import make_mesh

    mesh = make_mesh(("all",), (len(jax.devices()),))
    hits = []
    loads = []
    mon = QuorumMonitor(
        mesh, budget_ms=1e9, interval=0.0,
        on_stale=lambda age: hits.append(age), use_pallas=False,
        auto_beat_interval=0.001, fetch_workers=4,
    )
    try:
        # default margin/floor: the test's subject is the dense loop and the
        # load_fn plumbing, not budget tightness — a deliberately tight
        # budget here would flake on loaded CI hosts
        budget = mon.calibrate(n_ticks=8, load_fn=lambda: loads.append(1))
        assert len(loads) == 8          # load ran before every sample
        assert budget >= 5.0
        # the healthy window below must not trip on a host whose cores the
        # other test workers share (a 6 ms budget did, under -n 6)
        mon.budget_ms = max(budget, 50.0)
        mon.start()
        time.sleep(0.25)
        assert not hits, f"false trip on healthy pod: {hits}"
        assert mon.last_max_age is not None
        mon.stop_auto_beat()
        t0 = time.monotonic()
        while not hits and time.monotonic() - t0 < 5.0:
            time.sleep(0.002)
        assert hits
        # dense chain on a loaded host: generous bound, but far under the
        # pipelined loop's interval-dominated latency
        assert (time.monotonic() - t0) * 1000 < 2000
    finally:
        mon.stop()


def test_current_stamp_future_native_stamp_is_fresh():
    """ADVICE r5 regression: the native C thread can stamp NEWER than
    ``_current_stamp``'s ``now`` read between it and the slot read.  The
    folded age then lands near the half-wrap horizon and a naive
    wrap-compare would select a seconds-stale manual beat instead — a
    spurious trip.  Future stamps must be treated as fresh (age 0)."""
    import ctypes

    from tpu_resiliency.ops.quorum import _WRAP_NS

    # __new__: _current_stamp needs only the two stamp fields, and the full
    # constructor builds device collectives this logic test doesn't touch
    mon = QuorumMonitor.__new__(QuorumMonitor)
    now = now_stamp_ns()
    mon._last_beat_ns = (now - 10_000_000_000) % _WRAP_NS  # beat: 10s stale
    fut = (now + 50_000_000) % _WRAP_NS          # native slot: "the future"
    mon._native_slot = ctypes.c_int64(fut)
    assert mon._current_stamp() == fut           # pre-fix: stale manual beat
    # stale native + fresh manual: manual must still win
    mon._native_slot = ctypes.c_int64((now - 60_000_000_000) % _WRAP_NS)
    mon._last_beat_ns = now
    assert mon._current_stamp() == now
    # no native slot: manual beat passes through
    mon._native_slot = None
    assert mon._current_stamp() == now


def test_quorum_native_beater_stamps_and_freezes():
    """native_beat=True: a C pthread stamps the liveness slot (no GIL);
    stop_auto_beat freezes the slot so ages grow — the wedged-process
    simulation contract the tests rely on.  Skips cleanly when
    the toolchain can't build the helper (python-beater fallback)."""
    import jax

    from tpu_resiliency.parallel.mesh import make_mesh

    mesh = make_mesh(("all",), (len(jax.devices()),))
    mon = QuorumMonitor(
        mesh, budget_ms=1e9, interval=0.01, use_pallas=False,
        auto_beat_interval=0.0005, native_beat=True,
    )
    try:
        mon._start_beater()
        if mon._native_beater is None or not mon._native_beater.alive:
            pytest.skip("native beat helper unavailable (no toolchain)")
        time.sleep(0.1)
        first = mon._native_slot.value
        assert first > 0
        time.sleep(0.05)
        assert mon._current_stamp() >= first
        age_live = mon.tick()
        assert age_live < 1000  # stamping keeps the pod fresh
        mon.stop_auto_beat()
        frozen = mon._native_slot.value
        time.sleep(0.25)
        assert mon._native_slot.value == frozen  # frozen: thread stopped
        age_stale = mon.tick()
        assert age_stale >= 200  # ages grow from the freeze instant
    finally:
        mon.stop()


def test_quorum_online_recalibration_under_load():
    """After N in-vivo healthy ticks, the budget is recomputed from ages
    observed UNDER the real workload (idle pre-start calibration undershoots
    busy-interpreter stamp lateness); tripping ages are excluded so a real
    hang cannot inflate its own detection budget."""
    import jax

    from tpu_resiliency.parallel.mesh import make_mesh

    mesh = make_mesh(("all",), (len(jax.devices()),))
    mon = QuorumMonitor(
        mesh, budget_ms=1000.0, interval=0.005, use_pallas=False,
        auto_beat_interval=0.001, online_recalibrate_after=10,
        online_min_budget_ms=2.0,
    )
    try:
        mon.beat()
        # feed synthetic healthy ages through the observation hook
        for age in [1.0, 1.2, 0.8, 1.1, 2.0, 1.4, 0.9, 1.3, 1.1]:
            mon._observe_healthy_age(age)
        assert not mon._recal_done
        mon._observe_healthy_age(1.6)   # 10th sample completes the window
        assert mon._recal_done
        # budget = max(floor, 3*p99 + 2) with p99 = 2.0 -> 8.0
        assert abs(mon.budget_ms - 8.0) < 1e-6
        # further observations are no-ops
        mon._observe_healthy_age(500.0)
        assert abs(mon.budget_ms - 8.0) < 1e-6
    finally:
        mon.stop()


def test_quorum_online_recalibration_excludes_tripping_ages():
    import jax

    from tpu_resiliency.parallel.mesh import make_mesh

    mesh = make_mesh(("all",), (len(jax.devices()),))
    mon = QuorumMonitor(
        mesh, budget_ms=10.0, interval=0.005, use_pallas=False,
        online_recalibrate_after=3,
    )
    try:
        mon._observe_healthy_age(5000.0)   # tripping age: excluded
        assert not mon._recal_ages
        for age in [1.0, 1.0, 1.0]:
            mon._observe_healthy_age(age)
        assert mon._recal_done
        assert mon.budget_ms == max(2.0, 3.0 * 1.0 + 2.0)
    finally:
        mon.stop()


def test_calibrate_floor_release_and_p99_export():
    """min_budget_ms releases the operator floor; the measured healthy p99
    is kept for floor accounting (``last_calibration_p99_ms``)."""
    import jax

    from tpu_resiliency.ops.quorum import QuorumMonitor
    from tpu_resiliency.parallel.mesh import make_mesh

    mesh = make_mesh(("all",), (len(jax.devices()),))
    mon = QuorumMonitor(mesh, budget_ms=1e9, interval=0.01,
                        auto_beat_interval=0.001)
    try:
        budget = mon.calibrate(n_ticks=8, min_budget_ms=1.0)
        assert budget >= 1.0
        assert mon.last_calibration_p99_ms is not None
        assert mon.last_calibration_p99_ms >= 0.0
        # the formula: budget = max(floor, safety*p99 + margin)
        assert budget >= 3.0 * mon.last_calibration_p99_ms
        # a high operator floor binds
        assert mon.calibrate(n_ticks=8, min_budget_ms=500.0) >= 500.0
    finally:
        mon.stop()
