"""The repo's pre-benchmark harness: detection, checkpoint and control-plane
lanes in one process, one JSON line.

One process measures on whatever backend JAX gives it and says which
(``platform``, ``device_kind``, ``device_count`` in the line).  There is no
fallback: where JAX finds no accelerator the line says ``"platform": "cpu"``
and nothing in it is a device number (see the ``on-chip-measurement``
guide).  A lane that raises is reported under ``lane_errors`` and the exit
code is 1; a lane left out because the deadline (``TPURX_BENCH_DEADLINE_S``)
was near is listed under ``lanes_skipped_for_time``.  The lanes themselves
predate any run on a chip and are the benchmark PR's to replace (ROADMAP
Queue 1 item 1): they time one toy model, and on CPU they anchor every
step on a fetch because the slow backend's dispatch queue otherwise grows
without bound.

Headline key: hung-rank detection latency (ms), end to end — from the
instant a rank's heartbeats freeze to the instant the quorum monitor trips,
against the reference's 61 s (``soft_timeout + monitor_process_interval``,
``docs/source/inprocess/usage_guide.rst:659-660``, BASELINE.md).

Method notes:
- The detection path: a liveness auto-beat thread stamps every 1ms
  (reference ProgressWatchdog auto-timestamps analog); the budget is
  CALIBRATED from observed healthy tick ages (jitter-aware), not a 5x
  safety factor over step time; a hang is injected by freezing the stamps.
  Detection latency = budget + tick cycle + one readback.
- ``collective_extra_ms`` isolates the quorum collective's own cost: median
  fetch time of the quorum reduction minus median fetch time of a trivial
  one-op computation over the same transport.
- The ckpt arm sizes its save cadence to the MEASURED D2H bandwidth
  (reported as ``d2h_mbps``) so the background drain fits the save
  interval, exactly how production picks checkpoint cadence.
"""

import json
import os
import signal
import sys
import tempfile
import time
import traceback

_BENCH_DEADLINE_S = int(os.environ.get("TPURX_BENCH_DEADLINE_S", "480"))
_BASELINE_MS = 61000.0  # reference GIL-released hang detection (BASELINE.md)


class _Deadline(Exception):
    pass


def _alarm(signum, frame):
    raise _Deadline()


def _median(xs):
    import numpy as np

    return float(np.median(np.asarray(xs, dtype=np.float64)))


def bench_detection(mesh, step_dispatch, repeats: int, native_beat=False):
    """End-to-end hung-rank detection latency with a calibrated budget.

    Healthy phase: auto-beat at 1ms + training dispatches in flight.
    Hang: stamps freeze (stop_auto_beat).  The DENSE re-dispatched chain
    (interval=0: the next collective dispatches the moment a slot frees)
    plays the healthy peers' role; latency = freeze -> stale trip.

    Floor accounting (measured, r5): e2e = budget + dispatch cadence + one
    readback.  The dense chain collapses the cadence term from a polling
    interval to the dispatch cost itself; the budget is calibrated UNDER
    TRAINING LOAD (load_fn=step_dispatch) so safety*p99 + 0.5ms margin is
    tight without false trips — idle-calibrated budgets undershoot the
    stamp lateness a busy interpreter produces.  Finer beats than 1ms
    RAISE p99 on a contended host (GIL thrash), so 1ms stays the beat."""
    from tpu_resiliency.ops.quorum import QuorumMonitor

    latencies, budgets, p99s = [], [], []
    for _ in range(repeats):
        holder = {}

        def on_stale(age_ms, _h=holder):
            if "t_hang" in _h and "t_detect" not in _h:
                _h["t_detect"] = time.monotonic()

        mon = QuorumMonitor(
            mesh, budget_ms=1e9, interval=0.0, on_stale=on_stale,
            auto_beat_interval=0.0005 if native_beat else 0.001,
            fetch_workers=8, native_beat=native_beat,
        )
        # min_budget_ms=1: let calibration find the PLATFORM floor (beat
        # jitter p99 x safety), not an operator default
        budgets.append(mon.calibrate(
            n_ticks=15, min_budget_ms=1.0, margin_ms=0.5,
            load_fn=step_dispatch,
        ))
        p99s.append(mon.last_calibration_p99_ms)
        mon.start()
        t_end = time.monotonic() + 0.25
        while time.monotonic() < t_end:  # healthy, training in flight
            step_dispatch()
            time.sleep(0.005)
        holder["t_hang"] = time.monotonic()
        mon.stop_auto_beat()
        deadline = time.monotonic() + 15.0
        while "t_detect" not in holder and time.monotonic() < deadline:
            time.sleep(0.0005)
        mon.stop()
        if "t_detect" in holder:
            latencies.append((holder["t_detect"] - holder["t_hang"]) * 1e3)
    assert latencies, "hang was never detected"
    return _median(latencies), _median(budgets), _median(p99s)


# r5 detection medians (BENCH_r05.json): the regression reference for the
# µs-scale lanes — the futex lane must beat the native-collective number
# by >= 4x (or go sub-ms outright) for the gate to pass un-waived.
_R5_DETECT_NATIVE_US = 4485.0
_R5_DETECT_PY_US = 7184.0
_R5_RING_RECOVER_MS = 85.459  # BENCH_r05 in-process restart-ring median


def bench_detection_futex(repeats: int):
    """Event-driven native lane: pinned C beater + futex tripwire.

    The beater stamps every 200µs; the tripwire parks in
    ``futex(FUTEX_WAIT)`` on the generation word with a budget calibrated
    from the beater's MEASURED wake-lateness p99 (CLOCK_MONOTONIC, native
    ring) — same calibration law as the collective lane, at µs scale.
    Hang: ``freeze()`` stops stamping without a join, so the measured
    freeze->callback latency is interval-remainder + budget + futex wake,
    with no simulation artifacts.  Returns medians
    ``(detect_us, budget_us, jitter_p99_us)``."""
    from tpu_resiliency.ops.quorum import NativeBeater, StampTripwire

    detects, budgets, p99s = [], [], []
    for _ in range(repeats):
        beater = NativeBeater(interval_s=0.0002)
        if not beater.start():
            raise RuntimeError("native beat helper unavailable (no toolchain)")
        try:
            time.sleep(0.15)  # fill the jitter ring under steady state
            p99_us = beater.jitter_p99_us() or 1000.0
            budget_us = max(150.0, 3.0 * p99_us + 100.0)
            holder = {}

            def on_stale(age_ms, _h=holder):
                _h.setdefault("t_detect", time.monotonic())

            trip = StampTripwire(
                on_stale=on_stale, budget_ms=budget_us / 1e3, beater=beater,
            ).start()
            time.sleep(0.1)
            assert "t_detect" not in holder, "false trip on healthy beater"
            t_hang = time.monotonic()
            beater.freeze()
            deadline = time.monotonic() + 5.0
            while "t_detect" not in holder and time.monotonic() < deadline:
                time.sleep(0.0001)
            trip.stop()
            if "t_detect" in holder:
                detects.append((holder["t_detect"] - t_hang) * 1e6)
                budgets.append(budget_us)
                p99s.append(p99_us)
        finally:
            beater.stop()
    assert detects, "futex tripwire never fired"
    return _median(detects), _median(budgets), _median(p99s)


def bench_ici_step_quorum(mesh, step, params, opt, batch, reps: int):
    """Per-step cost of the fused ICI quorum lane (µs): median fused-step
    wall minus median plain-step wall, both fetch-anchored.  The fused step
    carries the packed age all-reduce inside the step's own dispatch (one
    collective, no tick thread).  Returns
    ``(extra_us, fused_step_us, params, opt)`` — state is handed back
    because the donated buffers are consumed."""
    from tpu_resiliency.ops.quorum import FusedStepQuorum

    for _ in range(3):
        params, opt, loss = step(params, opt, batch)
    float(loss)
    t_plain = []
    for _ in range(reps):
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, batch)
        float(loss)
        t_plain.append(time.perf_counter() - t0)
    fq = FusedStepQuorum(mesh, budget_ms=float("inf"))
    fused = fq.fuse(step, donate_argnums=(0, 1))
    for _ in range(3):
        fq.beat()
        params, opt, loss = fused(params, opt, batch)
    float(loss)
    fq.check_now()
    t_fused = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fq.beat()
        params, opt, loss = fused(params, opt, batch)
        float(loss)
        t_fused.append(time.perf_counter() - t0)
    fq.check_now()
    extra_us = max(0.0, (_median(t_fused) - _median(t_plain)) * 1e6)
    return extra_us, _median(t_fused) * 1e6, params, opt


def bench_detect_to_restart(mesh, repeats: int):
    """Detect -> RECOVERED latency through the full in-process restart ring.

    A Wrapper-wrapped workload (real store, real monitor thread) beats the
    quorum tripwire, then stalls: stamps freeze, the on-device collective
    trips, a QUORUM_STALE interruption record lands, the monitor thread
    async-raises, and the SAME process restarts the function.  Reported:
    freeze -> trip (detect) and freeze -> restarted-fn-entry (recover).
    Host-side rings are configured orders of magnitude too slow to
    contribute (soft 3600s; monitor process off — its fork is unsafe under
    a threaded JAX runtime, VERDICT r2 weak #5)."""
    from tpu_resiliency.inprocess import Wrapper
    from tpu_resiliency.store import StoreServer
    from tpu_resiliency.store.client import StoreClient

    srv = StoreServer(host="127.0.0.1", port=0).start_in_thread()
    detect, recover = [], []
    try:
        for rep in range(repeats):
            times = {}

            def train(call_wrapper=None, _t=times):
                it = call_wrapper.iteration
                if it == 0:
                    t_end = time.monotonic() + 0.25
                    while time.monotonic() < t_end:
                        call_wrapper.ping()
                        time.sleep(0.002)
                    _t["t_hang"] = time.monotonic()
                    call_wrapper.quorum.monitor.stop_auto_beat()
                    while True:  # stalled; the restart raise lands here
                        time.sleep(0.005)
                _t["t_restart"] = time.monotonic()
                _t["t_detect"] = call_wrapper.quorum.trip_time
                return "recovered"

            wrapper = Wrapper(
                store_factory=lambda: StoreClient("127.0.0.1", srv.port),
                group=f"bench-dtr-{rep}",
                quorum_mesh=mesh,
                quorum_budget_ms=1e9,  # calibrate() tightens it
                quorum_interval=0.005,
                quorum_auto_beat_interval=0.001,
                quorum_calibrate=True,
                soft_timeout=3600.0,
                hard_timeout=7200.0,
                enable_monitor_process=False,
                enable_sibling_monitor=False,
                last_call_wait=0.0,
            )
            assert wrapper(train)() == "recovered"
            if "t_detect" in times and times["t_detect"]:
                detect.append((times["t_detect"] - times["t_hang"]) * 1e3)
                recover.append((times["t_restart"] - times["t_hang"]) * 1e3)
    finally:
        srv.stop()
    assert recover, "ring never recovered"
    return _median(detect), _median(recover)


def bench_transport_and_collective(mesh):
    """Median fetch RTT of a trivial computation vs the quorum reduction."""
    import numpy as np
    import jax

    from tpu_resiliency.ops.quorum import make_quorum_fn, now_stamp_ns

    x = jax.device_put(np.ones(1, np.int32))
    triv = jax.jit(lambda v: v + 1)
    int(triv(x)[0])
    t_triv = []
    for _ in range(20):
        t0 = time.perf_counter()
        int(triv(x)[0])
        t_triv.append((time.perf_counter() - t0) * 1e3)
    n_local = (
        len(mesh.local_devices) if hasattr(mesh, "local_devices")
        else int(np.prod(mesh.devices.shape))
    )
    qfn = make_quorum_fn(mesh)
    stamps = np.full(n_local, now_stamp_ns(), dtype=np.int64)
    qfn(stamps)
    t_q = []
    for _ in range(20):
        t0 = time.perf_counter()
        qfn(stamps)
        t_q.append((time.perf_counter() - t0) * 1e3)
    readback = _median(t_triv)
    collective_only = _median(t_q)  # full dispatch->evaluated quorum latency
    return readback, max(0.0, collective_only - readback), collective_only


def bench_async_ckpt(reps: int, group_steps: int, sync_each_step: bool = False):
    """Fetch-anchored step-time overhead of async checkpointing."""
    import shutil

    import numpy as np
    import jax

    from tpu_resiliency.checkpointing import AsyncCheckpointer
    from tpu_resiliency.models.transformer import (
        TransformerConfig, init_opt_state, init_params, make_batch,
        make_train_step,
    )

    cfg = TransformerConfig(
        vocab=4096, d_model=128, n_heads=4, n_layers=2, d_ff=512, max_seq=128,
    )
    params = init_params(cfg)
    opt = init_opt_state(params)
    batch = make_batch(cfg, 8, cfg.max_seq)
    step = make_train_step(cfg)
    params, opt, loss = step(params, opt, batch)
    float(loss)  # fetch-anchored warmup

    state_bytes = sum(
        l.nbytes for l in jax.tree_util.tree_leaves({"params": params, "opt": opt})
        if hasattr(l, "nbytes")
    )
    # measured D2H bandwidth (the drain's budget) — a FRESH device array per
    # sample (jax caches the host copy after the first np.asarray)
    bump = jax.jit(lambda v: v + 1)
    big = jax.device_put(np.ones((2 * 1024 * 1024,), np.float32))
    samples = []
    for _ in range(3):
        big = bump(big)
        t0 = time.perf_counter()
        np.asarray(big)
        samples.append(big.nbytes / 1e6 / max(1e-9, time.perf_counter() - t0))
    d2h_mbps = _median(samples)

    def timed_steps(n, ckpt=None, ckpt_dir=None, save_every=0):
        nonlocal params, opt
        t0 = time.perf_counter()
        for i in range(n):
            params, opt, loss = step(params, opt, batch)
            if sync_each_step:
                float(loss)  # slow-backend mode: keep the queue shallow
            if ckpt is not None:
                if save_every and i % save_every == 0:
                    ckpt.async_save(
                        {"params": params, "opt": opt},
                        os.path.join(ckpt_dir, f"step_{i}"),
                        extra_metadata={"iteration": i},
                    )
                ckpt.maybe_finalize()
        float(loss)  # one fetch: waits for the whole queued chain
        return (time.perf_counter() - t0) / n

    tmp = tempfile.mkdtemp(prefix="tpurx-bench-")
    ckpt = AsyncCheckpointer()
    try:
        # warm save: compiles the snapshot jit, spawns stager + worker —
        # one-time costs that must not pollute the steady-state measurement
        ckpt.async_save(
            {"params": params, "opt": opt}, os.path.join(tmp, "warm"),
            extra_metadata={"iteration": -1},
        )
        ckpt.finalize_all()
        # Throughput drifts over a run, so long separated base/ckpt arms
        # measure drift, not overhead.  Instead measure the
        # two per-save costs against ADJACENT baseline groups and amortize
        # over the production cadence:
        #   overhead = (save_call + post_save_stall) / save_interval
        g = group_steps
        stalls_s, calls_s, bases_s = [], [], []
        for rep in range(reps):
            t_a = timed_steps(g) * g
            t0 = time.perf_counter()
            ckpt.async_save(
                {"params": params, "opt": opt},
                os.path.join(tmp, f"s{rep}"),
                extra_metadata={"iteration": rep},
            )
            calls_s.append(time.perf_counter() - t0)
            t_b = timed_steps(g, ckpt=ckpt, ckpt_dir=tmp) * g  # absorbs drain
            ckpt.finalize_all()
            t_c = timed_steps(g) * g
            base = (t_a + t_c) / 2
            bases_s.append(base / g)
            stalls_s.append(max(0.0, t_b - base))
        stall_s, call_s = _median(stalls_s), _median(calls_s)
        base_step_s = _median(bases_s)
        # FIXED reference cadence (60s — an aggressive production save
        # interval) so the metric tracks framework regressions linearly
        # instead of being normalized away by a drain-sized cadence
        interval_s = 60.0
        save_every = max(1, int(interval_s / base_step_s))
        overhead_pct = 100.0 * (call_s + stall_s) / interval_s
    finally:
        ckpt.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return overhead_pct, d2h_mbps, state_bytes, save_every, stall_s, call_s


def _bench_peer_restore(peer_mb: int) -> dict:
    """Peer-memory MTTR lane: a 2-rank clique on loopback.  Rank 1 loses its
    disk AND its own resident copy after the save, so its restore streams
    chunk-granular requests from rank 0's memory-resident replica over the
    ``PeerExchange`` fabric (crc verified per tile, footer verified whole).
    The measured window is rank 1's ``load`` call — the peer rung plus the
    collective exchange round — reported as MB/s over the blob size.  Kept
    deliberately smaller than the 1 GiB arm: the lane measures the fabric +
    verify pipeline, and loopback bandwidth is size-invariant past ~100 MB."""
    import shutil
    import threading

    import numpy as np

    from tpu_resiliency.checkpointing.local.manager import LocalCheckpointManager
    from tpu_resiliency.checkpointing.local.replication import (
        CliqueReplication,
        PeerExchange,
    )
    from tpu_resiliency.store import StoreClient, StoreServer

    srv = StoreServer(host="127.0.0.1", port=0).start_in_thread()
    tmp = tempfile.mkdtemp(prefix="tpurx-bench-peer-")
    n_leaves = max(1, peer_mb // 16)
    leaf_elems = 16 * 1024 * 1024 // 4

    def mk_tree(rank):
        return {
            f"w{i}": np.full((leaf_elems,), float(rank * 1000 + i), np.float32)
            for i in range(n_leaves)
        }

    out, errors = {}, []
    barrier = threading.Barrier(2)

    def member(rank):
        store = StoreClient("127.0.0.1", srv.port, timeout=60.0)
        ex = PeerExchange(store, rank, namespace="pxbench")
        repl = CliqueReplication(ex, 2, replication_factor=2)
        mgr = LocalCheckpointManager(
            os.path.join(tmp, f"node{rank}"), rank, 2,
            store=store, replication=repl,
        )
        try:
            tree = mk_tree(rank)
            mgr.save(tree, iteration=1, is_async=False)
            if rank == 1:
                mgr.drop_resident()
                shutil.rmtree(mgr.root)
            barrier.wait(timeout=60)
            t0 = time.perf_counter()
            mgr.load(tree, iteration=1)
            dt = time.perf_counter() - t0
            if rank == 1:
                nbytes = sum(a.nbytes for a in tree.values())
                out.update({
                    "ckpt1g_restore_peer_s": round(dt, 3),
                    "ckpt1g_restore_peer_mbps": round(
                        nbytes / 1e6 / max(1e-9, dt), 1
                    ),
                    "ckpt1g_restore_peer_state_mb": round(nbytes / 1e6, 1),
                })
        except Exception as exc:  # noqa: BLE001
            errors.append((rank, exc))
        finally:
            mgr.close()
            ex.close()
            store.close()

    threads = [threading.Thread(target=member, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    srv.stop()
    shutil.rmtree(tmp, ignore_errors=True)
    if errors:
        return {"ckpt1g_restore_peer_error": repr(errors[0][1])}
    return out


def bench_ckpt_large(target_mb: int, time_left_fn, light: bool):
    """Async-ckpt overhead at REALISTIC state size (>=1 GB when budget
    allows) — the reference async writer's reason for existing is multi-GB
    states (``checkpointing/async_ckpt/filesystem_async.py``), and round 4
    only ever measured an 11 MB toy (VERDICT r4 'do this' #2).

    Method: one warm save (pool/plan reuse — production steady state), then
    one measured save.  ``call_ms`` is the trainer-blocking part of
    ``async_save`` (snapshot dispatch); the drain runs in the background
    while a fetch-anchored foreground work quantum repeats, and ``stall_ms``
    is the summed foreground excess over its no-drain baseline across the
    whole drain — i.e. the TOTAL foreground time one save steals.  Overhead
    is amortized over a fixed 60 s production cadence.  D2H bandwidth is
    measured on a fresh 64 MB leaf (the staging path's unit of transfer).

    If the time budget cannot fit 1 GB (e.g. a slow D2H lane), the
    state is scaled down to what fits and reported as such — the overhead
    model is linear in state size through ``call``+``stall``, so the
    extrapolation to 1 GB is ``scale * measured`` and is emitted too.
    """
    import shutil

    import numpy as np
    import jax

    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint

    leaf_mb = 64
    leaf_elems = leaf_mb * 1024 * 1024 // 4
    bump = jax.jit(lambda v: v + 1)

    # D2H at scale first — it both is a reported metric and sizes the arm.
    probe = jax.device_put(np.ones((leaf_elems,), np.float32))
    probe.block_until_ready()
    samples = []
    for _ in range(3):
        probe = bump(probe)
        probe.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(probe)
        samples.append(probe.nbytes / 1e6 / max(1e-9, time.perf_counter() - t0))
    d2h_mbps = _median(samples)
    del probe

    # Fit the state to the budget: 3 saves (warm + digest-off reference +
    # measured), each staging state_mb at ~d2h and writing it to disk; leave
    # half the remaining budget for everything else.
    budget_s = max(10.0, time_left_fn() * 0.5)
    est_per_mb = 4 * (1.0 / max(1.0, d2h_mbps))  # stage ~ d2h; write ~ d2h-ish
    fit_mb = int(budget_s / max(1e-6, est_per_mb))
    state_mb = max(leaf_mb, min(target_mb, (fit_mb // leaf_mb) * leaf_mb))
    n_leaves = state_mb // leaf_mb
    state = {
        f"w{i}": jax.device_put(np.full((leaf_elems,), float(i), np.float32))
        for i in range(n_leaves)
    }
    jax.block_until_ready(state)
    state_bytes = sum(l.nbytes for l in state.values())

    mm = jax.jit(lambda a: a @ a)
    a0 = jax.device_put(np.ones((256, 256), np.float32))
    np.asarray(mm(a0))[0, 0]

    def work_quantum(n=10):
        t0 = time.perf_counter()
        x = None
        for _ in range(n):
            x = mm(a0)
        np.asarray(x[0, :1])  # fetch anchor: dispatch returns before the device is done
        return time.perf_counter() - t0

    work_quantum()

    tmp = tempfile.mkdtemp(prefix="tpurx-bench-1g-")
    # write_threads=None: pool sized from the host (writer.resolve_write_threads)
    ckpt = AsyncCheckpointer(write_threads=None)
    out = {}
    try:
        ckpt.async_save(state, os.path.join(tmp, "warm"),
                        extra_metadata={"iteration": -1})
        ckpt.finalize_all()
        shutil.rmtree(os.path.join(tmp, "warm"), ignore_errors=True)
        # Verify-overhead A/B (steady state: pool + plan reused; both drains
        # run UNLOADED so the delta isolates the digest, not foreground
        # contention): digest-off reference, then digest-on.  The summed crc
        # CPU (crc_ns) hides behind the pool's GIL-released I/O waits on any
        # host with a spare core, so the wall delta — not crc_ns — is the
        # honest verify cost.
        ckpt.async_save(state, os.path.join(tmp, "nodigest"),
                        extra_metadata={"iteration": -2}, digest=False)
        ckpt.finalize_all()
        drain_off_ns = ckpt.last_drain_stats.get("drain_ns", 0)
        shutil.rmtree(os.path.join(tmp, "nodigest"), ignore_errors=True)
        ckpt.async_save(state, os.path.join(tmp, "withdigest"),
                        extra_metadata={"iteration": -3}, digest=True)
        ckpt.finalize_all()
        drain_ab_on_ns = ckpt.last_drain_stats.get("drain_ns", 0)
        ab_crc_ns = ckpt.last_drain_stats.get("crc_ns", 0)
        shutil.rmtree(os.path.join(tmp, "withdigest"), ignore_errors=True)
        # no-drain baseline AFTER the warm save: the stall sum compares ~1000
        # drain-window quanta against this, so it must see the same heap/shm/
        # page-cache state the drain window will — measured before warm-up it
        # drifts by O(100µs)/quantum, which fabricates O(100ms) of stall
        base_s = _median([work_quantum() for _ in range(9)])

        t0 = time.perf_counter()
        ckpt.async_save(state, os.path.join(tmp, "big"),
                        extra_metadata={"iteration": 0})
        call_s = time.perf_counter() - t0
        quanta, truncated = [], False
        t_drain0 = time.perf_counter()
        cap = time_left_fn() - 10.0
        while True:
            if time.perf_counter() - t_drain0 >= cap:
                truncated = True  # drain outlived the budget: stall under-
                break             # counted — flagged, never silently valid
            quanta.append(work_quantum())
            ckpt.maybe_finalize()
            if ckpt.num_pending_saves == 0:
                break
        if truncated:
            # the worker streams bytes-written/total up the pipe: a killed
            # run still reports HOW FAR the drain got
            written, total = ckpt.drain_progress()
            if total > 0:
                out["ckpt1g_drain_progress_pct"] = round(100.0 * written / total, 1)
        ckpt.finalize_all()
        drain_s = time.perf_counter() - t_drain0
        stall_s = sum(max(0.0, q - base_s) for q in quanta)
        interval_s = 60.0
        overhead_pct = 100.0 * (call_s + stall_s) / interval_s
        # production sizes the cadence so the drain FITS the interval (the
        # small arm's save_every does exactly that); report overhead at that
        # fitted cadence too so a host whose drain outgrows 60s (e.g. this
        # 1-core sandbox, where the niced I/O path starves behind the
        # foreground) is distinguishable from a framework regression
        fit_interval_s = max(interval_s, 1.2 * drain_s)
        overhead_fit_pct = 100.0 * (call_s + stall_s) / fit_interval_s
        scale = (target_mb * 1024 * 1024) / state_bytes  # MiB, like the leaves
        out.update({
            "ckpt1g_state_mb": round(state_bytes / 1e6, 1),
            "ckpt1g_d2h_mbps": round(d2h_mbps, 1),
            "ckpt1g_call_ms": round(call_s * 1e3, 1),
            "ckpt1g_stall_ms": round(stall_s * 1e3, 1),
            "ckpt1g_drain_s": round(drain_s, 2),
            "ckpt1g_write_mbps": round(state_bytes / 1e6 / max(1e-9, drain_s), 1),
            "ckpt1g_overhead_pct": round(overhead_pct, 3),
            "ckpt1g_fit_interval_s": round(fit_interval_s, 1),
            "ckpt1g_overhead_fit_pct": round(overhead_fit_pct, 3),
            # regression tripwires for the pipelined drain: how much staging
            # memcpy hid behind in-flight D2H, and the writer pool size used
            "ckpt1g_stage_overlap_pct": round(
                ckpt.last_stage_stats.get("stage_overlap_pct", 0.0), 1
            ),
            "ckpt1g_write_threads": ckpt.write_threads,
            "host_cpus": os.cpu_count(),
        })
        # Verify-overhead gate: chunk digests must cost <5% of the drain,
        # measured as the WALL delta between the unloaded digest-on and
        # digest-off A/B drains (worker-reported engine lifetimes).
        # ckpt1g_crc_ns is the summed digest CPU across pool threads — the
        # accounting cross-check; it overlaps I/O waits, so on any host with
        # a spare core it legitimately exceeds the wall delta.  A 1-core
        # host physically cannot overlap digest CPU with anything, so there
        # the gate is reported but WAIVED (same convention as
        # ckpt1g_scaled_down / drain_truncated: flagged, never silently ok).
        if drain_off_ns and drain_ab_on_ns:
            verify_ns = max(0, drain_ab_on_ns - drain_off_ns)
            overhead = 100.0 * verify_ns / drain_off_ns
            waived = (os.cpu_count() or 1) < 2 and overhead > 5.0
            out.update({
                "ckpt1g_verify_ns": verify_ns,
                "ckpt1g_crc_ns": ab_crc_ns,
                "ckpt1g_verify_overhead_pct": round(overhead, 2),
                "ckpt1g_verify_ok": bool(overhead <= 5.0 or waived),
            })
            if waived:
                out["ckpt1g_verify_gate_waived"] = "1-core host"
        # Restore A/B on the committed "big" checkpoint, verification ON in
        # both arms: the serial reference path (one leaf at a time,
        # whole-buffer reads, inline crc, blocking per-leaf device_put)
        # against the parallel verified pipeline (threaded chunked reads,
        # in-flight crc, overlapped H2D).  Both arms read the page-cache
        # state the drain just left.  Gate: the pipeline must clear 2x the
        # serial read bandwidth; a 1-core host cannot overlap preads with
        # crc or H2D, so there the gate is reported but WAIVED (the same
        # convention as the digest gate above).
        if time_left_fn() > 15.0:
            big_dir = os.path.join(tmp, "big")
            t0 = time.perf_counter()
            jax.block_until_ready(load_checkpoint(big_dir, state, serial=True))
            serial_s = time.perf_counter() - t0
            rstats = {}
            t0 = time.perf_counter()
            # resident=False: this arm measures the DISK lane — the shm-
            # resident generation from the save above would otherwise serve
            # the whole restore without touching a file
            jax.block_until_ready(
                load_checkpoint(big_dir, state, stats=rstats, resident=False)
            )
            restore_s = time.perf_counter() - t0
            read_mbps = state_bytes / 1e6 / max(1e-9, restore_s)
            serial_mbps = state_bytes / 1e6 / max(1e-9, serial_s)
            speedup = read_mbps / max(1e-9, serial_mbps)
            r_waived = (os.cpu_count() or 1) < 2 and speedup < 2.0
            out.update({
                "ckpt1g_restore_s": round(restore_s, 3),
                "ckpt1g_restore_serial_s": round(serial_s, 3),
                "ckpt1g_read_mbps": round(read_mbps, 1),
                "ckpt1g_read_mbps_serial": round(serial_mbps, 1),
                "ckpt1g_restore_speedup": round(speedup, 2),
                "ckpt1g_restore_verify_ns": int(rstats.get("verify_ns", 0)),
                "ckpt1g_restore_threads": int(rstats.get("threads", 0)),
                "ckpt1g_restore_ok": bool(speedup >= 2.0 or r_waived),
            })
            if r_waived:
                out["ckpt1g_restore_gate_waived"] = "1-core host"
            # Warm (shm-resident) MTTR lane: the committed generation is
            # still memory-resident from the save above, so this restore
            # sources every chunk from shm with crc verification against the
            # committed index — no checkpoint file is opened.  Gate: >=5x
            # the disk lane's verified read bandwidth; a 1-core host cannot
            # overlap the verify crc with the copy-out, so there the gate is
            # reported but WAIVED (same convention as the gates above).
            wstats = {}
            t0 = time.perf_counter()
            jax.block_until_ready(load_checkpoint(big_dir, state, stats=wstats))
            warm_s = time.perf_counter() - t0
            warm_mbps = state_bytes / 1e6 / max(1e-9, warm_s)
            warm_speedup = warm_mbps / max(1e-9, read_mbps)
            bytes_shm = int(wstats.get("bytes_shm", 0))
            fully_warm = bytes_shm > 0 and bytes_shm == int(
                wstats.get("bytes_read", 0)
            )
            w_waived = (os.cpu_count() or 1) < 2 and warm_speedup < 5.0
            out.update({
                "ckpt1g_restore_warm_s": round(warm_s, 3),
                "ckpt1g_restore_warm_mbps": round(warm_mbps, 1),
                "ckpt1g_restore_warm_speedup": round(warm_speedup, 2),
                "ckpt1g_restore_warm_shm_pct": round(
                    100.0 * bytes_shm / max(1, int(wstats.get("bytes_read", 0))),
                    1,
                ),
                "ckpt1g_restore_warm_ok": bool(
                    (fully_warm and warm_speedup >= 5.0) or w_waived
                ),
            })
            if w_waived:
                out["ckpt1g_restore_warm_gate_waived"] = "1-core host"
        # Delta MTTR lane: a 90%-frozen tree (bump 1 leaf in 10) saved with
        # delta on must drain <=25% of the full-save bytes — the crc-matched
        # chunks ride the previous committed generation via provenance rows.
        # A state too small for 10 leaves cannot BE 90% frozen at chunk
        # granularity, so the gate is waived (scaled-down convention).
        if time_left_fn() > 15.0 and n_leaves >= 2:
            # device digest rides this lane (A/B vs the crc path above):
            # the baseline save records on-device fingerprints, the delta
            # save then skips the D2H itself for every frozen shard
            os.environ["TPURX_CKPT_DEVICE_DIGEST"] = "1"
            try:
                ckpt.async_save(state, os.path.join(tmp, "delta_base"),
                                extra_metadata={"iteration": 1}, delta=False)
                ckpt.finalize_all()
                full_bytes = int(ckpt.last_drain_stats.get("bytes_written", 0))
                for i in range(max(1, n_leaves // 10)):
                    state[f"w{i}"] = bump(state[f"w{i}"])
                jax.block_until_ready(state)
                # step-overhead probe: same call+stall accounting as the big
                # save, but with delta + device digest on — the zero-stall
                # path's trainer-visible cost at the fitted cadence
                t0 = time.perf_counter()
                ckpt.async_save(state, os.path.join(tmp, "delta_inc"),
                                extra_metadata={"iteration": 2}, delta=True)
                dd_call_s = time.perf_counter() - t0
                dd_quanta = []
                t_dd0 = time.perf_counter()
                dd_cap = time_left_fn() - 8.0
                while True:
                    if time.perf_counter() - t_dd0 >= dd_cap:
                        break
                    dd_quanta.append(work_quantum())
                    ckpt.maybe_finalize()
                    if ckpt.num_pending_saves == 0:
                        break
                ckpt.finalize_all()
            finally:
                os.environ.pop("TPURX_CKPT_DEVICE_DIGEST", None)
            dstats = ckpt.last_drain_stats
            sstats = ckpt.last_stage_stats
            delta_pct = 100.0 * int(dstats.get("bytes_written", 0)) / max(
                1, full_bytes
            )
            dd_stall_s = sum(max(0.0, q - base_s) for q in dd_quanta)
            step_pct = 100.0 * (dd_call_s + dd_stall_s) / fit_interval_s
            d2h_skip_pct = 100.0 * int(
                sstats.get("d2h_skipped_bytes", 0)
            ) / max(1, state_bytes)
            out.update({
                "ckpt1g_delta_bytes_pct": round(delta_pct, 1),
                "ckpt1g_delta_skipped_mb": round(
                    int(dstats.get("bytes_skipped", 0)) / 1e6, 1
                ),
                "ckpt1g_delta_d2h_skipped_pct": round(d2h_skip_pct, 1),
                "ckpt1g_device_digest_ns": int(
                    float(sstats.get("device_digest_s", 0.0)) * 1e9
                ),
                "ckpt1g_step_overhead_pct": round(step_pct, 3),
            })
            one_core = (os.cpu_count() or 1) < 2
            out["ckpt1g_step_overhead_ok"] = bool(step_pct < 0.5 or one_core)
            if one_core and step_pct >= 0.5:
                out["ckpt1g_step_overhead_gate_waived"] = "1-core host"
            if n_leaves >= 10:
                out["ckpt1g_delta_ok"] = bool(delta_pct <= 25.0)
                out["ckpt1g_delta_d2h_ok"] = bool(d2h_skip_pct >= 80.0)
            else:
                out["ckpt1g_delta_gate_waived"] = (
                    f"scaled-down state ({n_leaves} leaves < 10)"
                )
                out["ckpt1g_delta_d2h_gate_waived"] = (
                    f"scaled-down state ({n_leaves} leaves < 10)"
                )
        if time_left_fn() > 30.0:
            out.update(_bench_peer_restore(min(128, state_mb)))
        if truncated or not quanta:
            out["ckpt1g_drain_truncated"] = True
        if scale > 1.01:  # could not fit the full target: extrapolate
            out["ckpt1g_scaled_down"] = True
            out["ckpt1g_extrapolated_overhead_pct"] = round(
                overhead_pct * scale, 3
            )
    finally:
        ckpt.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_store_fanin(time_left_fn) -> dict:
    """Sharded control-plane A/B at simulated 1k-client fan-in.

    K=4 shard servers run as SUBPROCESSES (in-thread asyncio shards would
    share this interpreter's GIL and measure nothing); the same op stream —
    each simulated client SETs then TRY_GETs its own key — is driven by a
    thread pool against (a) one shard and (b) all four via the
    consistent-hash client.  Reported: client-observed op p50/p99 per arm,
    the p99 speedup (gate: >=2x with K=4, waived on a 1-core host like the
    ckpt lanes — one core cannot run four shard event loops in parallel),
    and the rendezvous round-close latency over each arm (the protocol this
    control plane exists to serve)."""
    import threading

    from tpu_resiliency.fault_tolerance.rendezvous import (
        NodeDesc, RendezvousHost, RendezvousJoiner,
    )
    from tpu_resiliency.store.sharding import (
        ShardedStoreClient, free_port, spawn_shard_subprocess,
    )
    from tpu_resiliency.utils.env import force_cpu_env

    n_shards = 4
    sim_clients = 1024
    ops_per_client = 4
    n_threads = 32
    shard_env = {"JAX_PLATFORMS": "cpu"}
    force_cpu_env(shard_env)  # shard procs must not touch TPU

    procs, endpoints = [], []
    try:
        for _ in range(n_shards):
            port = free_port()
            procs.append(spawn_shard_subprocess(port, env=shard_env))
            endpoints.append(f"127.0.0.1:{port}")

        def fanin_arm(arm_endpoints, tag) -> list:
            latencies: list = []
            lock = threading.Lock()
            per_thread = sim_clients // n_threads

            def worker(tid):
                c = ShardedStoreClient(arm_endpoints, timeout=60.0)
                local = []
                try:
                    for cid in range(per_thread):
                        key = f"fanin/{tag}/{tid}/{cid}"
                        for op in range(ops_per_client):
                            t0 = time.perf_counter_ns()
                            if op % 2 == 0:
                                c.set(key, b"x" * 64)
                            else:
                                c.try_get(key)
                            local.append(time.perf_counter_ns() - t0)
                finally:
                    c.close()
                with lock:
                    latencies.extend(local)

            threads = [
                threading.Thread(target=worker, args=(t,))
                for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return sorted(latencies)

        def quantile(sorted_ns, q):
            return sorted_ns[min(len(sorted_ns) - 1, int(q * len(sorted_ns)))]

        def rdzv_close_ms(arm_endpoints, n_nodes=32) -> float:
            # both arms share the live shard fleet: clear the previous
            # arm's round state so each measures a fresh round 0
            sweeper = ShardedStoreClient(endpoints, timeout=30.0)
            for k in sweeper.list_keys("rdzv/"):
                sweeper.delete(k)
            sweeper.close()
            host_client = ShardedStoreClient(arm_endpoints, timeout=120.0)
            host = RendezvousHost(
                host_client, min_nodes=n_nodes, max_nodes=n_nodes,
                settle_time=0.3,
            )
            host.bootstrap()
            host.open_round()
            clients = [
                ShardedStoreClient(arm_endpoints, timeout=120.0)
                for _ in range(n_nodes)
            ]

            def agent(i):
                joiner = RendezvousJoiner(
                    clients[i],
                    NodeDesc.create(node_id=f"fanin-node-{i}", slots=1),
                    open_poll_interval=0.02,
                )
                try:
                    joiner.join(timeout=20.0)
                except Exception:  # noqa: BLE001 - a joiner losing the
                    pass  # close race only affects itself, not the metric

            threads = [
                threading.Thread(target=agent, args=(i,), daemon=True)
                for i in range(n_nodes)
            ]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            host.close_round_when_ready(timeout=90.0)
            close_ms = (time.monotonic() - t0) * 1e3
            for t in threads:
                t.join(timeout=30)
            for c in clients:
                c.close()
            host_client.close()
            return close_ms

        single = fanin_arm(endpoints[:1], "single")
        sharded = fanin_arm(endpoints, "sharded")
        p99_single = quantile(single, 0.99) / 1e3
        p99_sharded = quantile(sharded, 0.99) / 1e3
        speedup = p99_single / max(1e-9, p99_sharded)
        waived = (os.cpu_count() or 1) < 2 and speedup < 2.0
        out = {
            "store_fanin_clients": sim_clients,
            "store_fanin_shards": n_shards,
            "store_fanin_p50_us": round(quantile(single, 0.5) / 1e3, 1),
            "store_fanin_p50_sharded_us": round(quantile(sharded, 0.5) / 1e3, 1),
            "store_fanin_p99_us": round(p99_single, 1),
            "store_fanin_p99_sharded_us": round(p99_sharded, 1),
            "store_shard_speedup": round(speedup, 2),
            "store_fanin_ok": bool(speedup >= 2.0 or waived),
        }
        if waived:
            out["store_fanin_gate_waived"] = "1-core host"
        if time_left_fn() > 30:
            out["store_rdzv_close_ms"] = round(rdzv_close_ms(endpoints[:1]), 1)
        if time_left_fn() > 30:
            out["store_rdzv_close_sharded_ms"] = round(
                rdzv_close_ms(endpoints), 1
            )
        return out
    finally:
        for p in procs:
            p.kill()


def bench_store_mux(time_left_fn) -> dict:
    """Multiplexed-client A/B plus the interrupt-latency contract number.

    Both arms drive one shard SUBPROCESS (real parallelism against this
    driver) from 32 threads in a closed loop, every thread SETting and
    TRY_GETting its own key through ONE shared client object — the
    process model the mux exists for (monitor threads, checkpoint drains
    and the main loop sharing a per-shard connection):

    (a) classic ``StoreClient``: the client lock holds each FULL
        request/response RTT, so concurrent callers queue head-of-line;
    (b) ``MuxStoreClient``: whole frames leave under a short send lock
        with correlation ids and replies route out of order, so the RTTs
        of concurrent callers overlap on the single socket.

    Gate: ``store_mux_speedup`` (p99 ratio) >= 2x, waived on a 1-core
    host where client, server and receiver thread share one core.

    ``store_interrupt_latency_ms``: a thread parked in a server-held
    ``wait()`` receives ``PyThreadState_SetAsyncExc``; the poll-quantum
    I/O core must land the raise between slices.  Reported: the worst
    landing latency over the trials (contract: ~2x TPURX_STORE_POLL_S)."""
    import ctypes
    import threading

    from tpu_resiliency.store.client import StoreClient
    from tpu_resiliency.store.mux import MuxStoreClient
    from tpu_resiliency.store.sharding import free_port, spawn_shard_subprocess
    from tpu_resiliency.utils.env import force_cpu_env

    shard_env = {"JAX_PLATFORMS": "cpu"}
    force_cpu_env(shard_env)
    port = free_port()
    proc = spawn_shard_subprocess(port, env=shard_env)
    n_threads = 32
    ops_per_thread = 64
    try:
        def shared_client_arm(client) -> list:
            latencies: list = []
            lock = threading.Lock()

            def worker(tid):
                local = []
                for i in range(ops_per_thread):
                    key = f"mux/{tid}/{i}"
                    t0 = time.perf_counter_ns()
                    if i % 2 == 0:
                        client.set(key, b"x" * 64)
                    else:
                        client.try_get(key)
                    local.append(time.perf_counter_ns() - t0)
                with lock:
                    latencies.extend(local)

            threads = [
                threading.Thread(target=worker, args=(t,))
                for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return sorted(latencies)

        def quantile(sorted_ns, q):
            return sorted_ns[min(len(sorted_ns) - 1, int(q * len(sorted_ns)))]

        classic = StoreClient("127.0.0.1", port, timeout=60.0)
        shared = shared_client_arm(classic)
        classic.close()
        mux_client = MuxStoreClient("127.0.0.1", port, timeout=60.0)
        muxed = shared_client_arm(mux_client)

        p99_shared = quantile(shared, 0.99) / 1e3
        p99_mux = quantile(muxed, 0.99) / 1e3
        speedup = p99_shared / max(1e-9, p99_mux)
        waived = (os.cpu_count() or 1) < 2 and speedup < 2.0
        out = {
            "store_fanin_p99_shared_us": round(p99_shared, 1),
            "store_fanin_p99_mux_us": round(p99_mux, 1),
            "store_mux_speedup": round(speedup, 2),
            "store_mux_ok": bool(speedup >= 2.0 or waived),
        }
        if waived:
            out["store_mux_gate_waived"] = "1-core host"

        # the interrupt-latency contract: worst observed landing over trials
        landings = []
        for trial in range(5):
            if time_left_fn() < 10:
                break
            box = {}

            def parked():
                try:
                    mux_client.wait([f"mux/never/{trial}"], timeout=30.0)
                except BaseException:  # noqa: BLE001 - the injected raise
                    box["landed"] = time.perf_counter_ns()

            th = threading.Thread(target=parked, daemon=True)
            th.start()
            time.sleep(0.4)  # deep inside the server-held wait
            t0 = time.perf_counter_ns()
            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_ulong(th.ident), ctypes.py_object(KeyboardInterrupt)
            )
            th.join(timeout=15.0)
            if "landed" in box:
                landings.append((box["landed"] - t0) / 1e6)
        mux_client.close()
        if landings:
            out["store_interrupt_latency_ms"] = round(max(landings), 1)
        return out
    finally:
        proc.kill()


def bench_rendezvous_10k(time_left_fn) -> dict:
    """10k-rank rendezvous close A/B: affinity-routed one-RTT rounds vs
    the prior protocol (3-RTT joins, per-key host reads, count-marker
    waits) over an EQUAL shard fleet, plus the measured mutation-RTT
    counts and the spare-promotion latency.  Single-source: the sweep
    lives in benchmarks/bench_control_plane.py (standalone:
    ``python benchmarks/bench_control_plane.py --native --shards 4``).
    Gate: >=2x close speedup, waived on a 1-core host like the other
    subprocess lanes."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.bench_control_plane import rendezvous_10k_sweep

    ranks = 10000 if time_left_fn() > 120 else 2000
    try:
        return rendezvous_10k_sweep(shards=4, ranks=ranks, native=True)
    except Exception as exc:  # no C++ toolchain: measure the python servers
        print(f"bench: rdzv10k native shards unavailable ({exc!r}); "
              f"python shards", file=sys.stderr, flush=True)
        return rendezvous_10k_sweep(shards=4, ranks=ranks, native=False)


def bench_policy_goodput() -> dict:
    """Adaptive-vs-best-fixed goodput gate: a deterministic seeded fault
    schedule with a regime step drives the REAL policy components (the
    GoodputEstimator's windowed MTBF, the Actuator's clamp + hysteresis +
    knob override, the RungLedger's start-rung pick) against a swept grid
    of fixed cadences.  Single-source: the sim lives in
    benchmarks/bench_policy.py (standalone: ``python
    benchmarks/bench_policy.py --seed N``).  Gate: mean gain >= 1.1x over
    the best fixed knob; fully deterministic, so no 1-core waiver needed."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.bench_policy import run as policy_run

    report = policy_run(seed=0xA11CE, trials=3)
    return {
        "policy_goodput_gain": report["policy_goodput_gain"],
        "policy_adaptive_goodput": report["policy_adaptive_goodput"],
        "policy_best_fixed_goodput": report["policy_best_fixed_goodput"],
        "policy_trial_gains": report["policy_trial_gains"],
        "policy_retunes": report["policy_retunes"],
        "policy_hang_start_rung": report["policy_hang_start_rung"],
        "policy_ok": report["policy_ok"],
    }


def bench_evac_goodput() -> dict:
    """Predict-and-evacuate vs react-after-failure gate: a seeded ramping-
    degradation schedule drives the REAL PolicyController end to end (the
    RankRiskModel's noisy-OR fusion, the streak guard, the hysteresis
    latch, the one-shot Actuator evacuate) with noisy healthy ranks as
    false-positive bait; the evacuate arm pays the planned handoff, the
    react arm the full reactive episode.  Single-source: the sim lives in
    benchmarks/bench_evac.py (standalone: ``python
    benchmarks/bench_evac.py --seed N``).  Gates: mean gain >= 1.1x
    (1-core waiver, like the soak lanes), zero healthy-rank evacuations,
    zero missed ramps."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.bench_evac import run as evac_run

    report = evac_run(seed=0xE7AC, trials=3)
    return {
        "evac_goodput_gain": report["evac_goodput_gain"],
        "evac_goodput": report["evac_goodput"],
        "react_goodput": report["react_goodput"],
        "evac_trial_gains": report["evac_trial_gains"],
        "evac_join_mttr_ms": report["evac_join_mttr_ms"],
        "evac_false_positives": report["evac_false_positives"],
        "evac_missed": report["evac_missed"],
        "evac_ok": report["evac_ok"],
    }


def bench_flight() -> dict:
    """tm_flight lane: the flight recorder's hot-append cost (enabled and
    ``TPURX_FLIGHT=0`` no-op), black-box dump latency at a full ring, and
    the MTTR phase-coverage gate over the fault episodes the
    detect->restart lane actually ran.

    Gates: enabled append p50 < 1 µs and disabled (no-op) call p50 <
    0.1 µs — both waived on a 1-core host, where the GIL shares the only
    core with every monitor thread; phase coverage >= 95% (no waiver:
    coverage is arithmetic over monotonic marks, not a scheduling race).
    """
    from tpu_resiliency.telemetry import episode as episode_mod
    from tpu_resiliency.telemetry import flight

    try:
        ev = flight.declare_event("bench.append_probe", "i")
    except ValueError:  # already declared (lane re-entry)
        ev = "bench.append_probe"

    n = 20_000

    def append_p50_ns(record):
        samples = []
        for _ in range(7):
            t0 = time.perf_counter_ns()
            for i in range(n):
                record(ev, i)
            samples.append((time.perf_counter_ns() - t0) / n)
        return _median(samples)

    out = {}
    try:
        flight.configure(enabled=True, capacity=4096)
        enabled_ns = append_p50_ns(flight.record)
        # dump latency with every slot occupied (the fault-time cost: the
        # ring is always full by the time anything trips)
        fd, path = tempfile.mkstemp(suffix=".jsonl")
        os.close(fd)
        try:
            t0 = time.perf_counter_ns()
            flight.dump("bench", path=path, min_interval_s=0.0)
            dump_ms = (time.perf_counter_ns() - t0) / 1e6
        finally:
            os.unlink(path)
        flight.configure(enabled=False)
        disabled_ns = append_p50_ns(flight.record)
    finally:
        flight.configure()  # back to the env-configured recorder

    out["tm_flight_append_ns"] = round(enabled_ns, 1)
    out["tm_flight_append_disabled_ns"] = round(disabled_ns, 1)
    out["tm_flight_dump_ms"] = round(dump_ms, 3)

    # phase coverage over the episodes this process really closed (the
    # detect->restart lane's injected faults); a synthetic episode walks
    # all six phases when that lane didn't run
    episodes = [ep for ep in episode_mod.recent() if ep.closed_ns]
    if not episodes:
        ep = episode_mod.begin(fault_class="bench_synthetic")
        for phase in episode_mod.PHASES[1:]:
            time.sleep(0.001)
            ep.phase(phase)
        ep.close()
        episodes = [ep]
    coverage = min(ep.coverage_pct() for ep in episodes)
    out["episode_phase_coverage_pct"] = round(coverage, 2)
    out["flight_episodes"] = len(episodes)

    one_core = (os.cpu_count() or 1) < 2
    en_ok = enabled_ns < 1000.0
    dis_ok = disabled_ns < 100.0
    out["flight_ok"] = bool(
        (en_ok or one_core) and (dis_ok or one_core) and coverage >= 95.0
    )
    if one_core and not (en_ok and dis_ok):
        out["flight_gate_waived"] = "1-core host"
    return out


def _telemetry_keys() -> dict:
    """Derive bench keys from the in-process telemetry registry — the same
    series production scrapes from the per-rank exporter, so bench numbers
    and dashboards can be cross-checked against each other."""
    from tpu_resiliency.telemetry import get_registry

    reg = get_registry()
    out = {}

    def fam_sum(name):
        m = reg.get(name)
        if m is None:
            return None
        return sum(v.get("value", 0.0) for _l, v in m._sample_rows())

    def hist_quantile(name, q):
        m = reg.get(name)
        if m is None:
            return None
        rows = m._sample_rows()
        if not rows:
            return None
        bounds = rows[0][1]["bounds"]
        counts = [0] * (len(bounds) + 1)
        for _l, v in rows:
            counts = [a + b for a, b in zip(counts, v["counts"])]
        total = sum(counts)
        if not total:
            return None
        target = max(1, int(q * total + 0.5))
        seen = 0
        for i, c in enumerate(counts):
            seen += c
            if seen >= target:
                return bounds[min(i, len(bounds) - 1)]
        return bounds[-1]

    ops = fam_sum("tpurx_store_ops_total")
    if ops:
        out["tm_store_ops"] = int(ops)
        p50 = hist_quantile("tpurx_store_op_latency_ns", 0.5)
        p99 = hist_quantile("tpurx_store_op_latency_ns", 0.99)
        if p50 is not None:
            out["tm_store_op_p50_us"] = round(p50 / 1e3, 1)
        if p99 is not None:
            out["tm_store_op_p99_us"] = round(p99 / 1e3, 1)
    shard_ops = fam_sum("tpurx_store_shard_ops_total")
    if shard_ops:
        out["tm_store_shard_ops"] = int(shard_ops)
        out["tm_store_shard_failovers"] = int(
            fam_sum("tpurx_store_shard_failovers_total") or 0
        )
    tree_rounds = fam_sum("tpurx_tree_rounds_total")
    if tree_rounds:
        out["tm_tree_rounds"] = int(tree_rounds)
    saves = fam_sum("tpurx_ckpt_saves_total")
    if saves:
        out["tm_ckpt_saves"] = int(saves)
        stage_b = fam_sum("tpurx_ckpt_stage_bytes_total") or 0
        out["tm_ckpt_stage_mb"] = round(stage_b / 1e6, 1)
    restarts = fam_sum("tpurx_inprocess_restarts_total")
    if restarts:
        out["tm_restarts"] = int(restarts)
        p50 = hist_quantile("tpurx_restart_total_latency_ns", 0.5)
        if p50 is not None:
            out["tm_restart_p50_ms"] = round(p50 / 1e6, 1)
    trips = fam_sum("tpurx_monitor_trips_total")
    if trips:
        out["tm_monitor_trips"] = int(trips)
    # hot-path cost of one enabled counter increment (the instrumented
    # paths above pay this per event)
    probe = reg.counter("tpurx_bench_probe_total", "bench: inc cost probe")
    n = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        probe.inc()
    out["tm_metric_inc_ns"] = round((time.perf_counter_ns() - t0) / n, 1)
    return out


def main() -> None:
    from tpu_resiliency.utils import compile_cache

    compile_cache.enable()  # before the first jit
    signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(max(20, _BENCH_DEADLINE_S - 8))
    t_start = time.monotonic()

    def time_left() -> float:
        return _BENCH_DEADLINE_S - 8 - (time.monotonic() - t_start)

    import jax

    from tpu_resiliency.models.transformer import (
        TransformerConfig, init_opt_state, init_params, make_batch,
        make_train_step,
    )
    from tpu_resiliency.parallel.mesh import make_mesh

    devices = jax.devices()
    # the CPU backend cannot keep up with an unsynchronised dispatch loop
    # and gets shorter arms; chosen from the platform, not by an option
    light = devices[0].platform == "cpu"
    results = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    lane_errors, skipped = {}, []

    def lane(name, needs_s, fn):
        """Run one lane: its keys land in ``results``; a raise lands in
        ``lane_errors`` (and in the exit code), never in silence."""
        if time_left() <= needs_s:
            skipped.append(name)
            return
        try:
            results.update(fn())
        except _Deadline:
            raise
        except Exception as exc:  # noqa: BLE001 - the other lanes still run
            traceback.print_exc()
            lane_errors[name] = repr(exc)

    try:
        mesh = make_mesh(("all",), (len(devices),))
        cfg = TransformerConfig(
            vocab=4096, d_model=128, n_heads=4, n_layers=2, d_ff=512,
            max_seq=128,
        )
        params = init_params(cfg)
        opt = init_opt_state(params)
        batch = make_batch(cfg, 8, cfg.max_seq)
        step = make_train_step(cfg)
        params, opt, loss = step(params, opt, batch)
        float(loss)

        def step_dispatch():
            nonlocal params, opt
            params, opt, loss = step(params, opt, batch)
            if light:
                float(loss)

        def transport():
            readback_ms, extra_ms, only_ms = bench_transport_and_collective(mesh)
            return {"transport_readback_ms": round(readback_ms, 3),
                    "collective_extra_ms": round(extra_ms, 3),
                    "collective_only_ms": round(only_ms, 3)}

        def detection():
            detect_ms, budget_ms, beat_p99_ms = bench_detection(
                mesh, step_dispatch, repeats=3 if light else 5)
            return {"detect_ms": detect_ms,
                    "detection_budget_ms": round(budget_ms, 3),
                    "beat_jitter_p99_ms": round(beat_p99_ms, 3),
                    "detect_python_us": round(detect_ms * 1e3, 1)}

        def detection_native():
            # native C beater lane: GIL-free liveness stamps
            nat_ms, nat_budget, nat_p99 = bench_detection(
                mesh, step_dispatch, repeats=2 if light else 3,
                native_beat=True)
            return {"detect_native_ms": round(nat_ms, 3),
                    "detect_native_budget_ms": round(nat_budget, 3),
                    "native_beat_p99_ms": round(nat_p99, 3),
                    "detect_native_us": round(nat_ms * 1e3, 1)}

        def detection_futex():
            # futex lane: pinned C beater + event-driven tripwire (no
            # collective, no polling read)
            fx_us, fx_budget_us, fx_p99_us = bench_detection_futex(
                repeats=3 if light else 5)
            out = {"detect_futex_us": round(fx_us, 1),
                   "detect_futex_budget_us": round(fx_budget_us, 1),
                   "beat_jitter_p99_us": round(fx_p99_us, 1)}
            # regression gate vs the r5 ms-scale numbers: sub-ms outright,
            # or >= 4x over the r5 native-collective median; waived on a
            # 1-core host (GIL handoff to the callback thread shares the
            # only core with the harness loop)
            waived = (os.cpu_count() or 1) <= 1
            ok = fx_us < 1000.0 or fx_us <= _R5_DETECT_NATIVE_US / 4.0
            out["detect_ok"] = bool(ok or waived)
            if waived and not ok:
                out["detect_gate_waived"] = "1-core host"
            return out

        def ici_step_quorum():
            # fused ICI lane: the packed-age all-reduce riding the training
            # step's own dispatch
            nonlocal params, opt
            ici_us, fused_us, params, opt = bench_ici_step_quorum(
                mesh, step, params, opt, batch, reps=15 if light else 40)
            return {"ici_quorum_step_us": round(ici_us, 1),
                    "ici_quorum_fused_step_us": round(fused_us, 1)}

        def detect_to_restart():
            ring_detect_ms, ring_recover_ms = bench_detect_to_restart(
                mesh, repeats=2 if light else 3)
            return {"ring_detect_ms": round(ring_detect_ms, 3),
                    "ring_recover_ms": round(ring_recover_ms, 3)}

        def async_ckpt():
            # size the arm to the measured step time so it FITS the budget:
            # each rep runs 3 groups of g steps (+ warm save ~2 groups)
            nonlocal params, opt
            t0 = time.perf_counter()
            for _ in range(10):
                step_dispatch()
            # rebind: the step donates its inputs — dropping the outputs
            # here would leave params/opt as dead buffers for later arms
            params, opt, loss = step(params, opt, batch)
            float(loss)
            step_s = max(1e-4, (time.perf_counter() - t0) / 11)
            reps = 2 if light else 4
            budget_steps = (time_left() * 0.6) / step_s
            g = int(budget_steps / (reps * 3 + 2))
            g = max(30, min(g, 120 if light else 300))
            (ckpt_pct, d2h_mbps, state_bytes, save_every, ckpt_stall_s,
             ckpt_call_s) = bench_async_ckpt(
                reps=reps, group_steps=g, sync_each_step=light)
            return {"async_ckpt_overhead_pct": round(ckpt_pct, 3),
                    "async_ckpt_vs_target": round(ckpt_pct / 5.0, 3),
                    "d2h_mbps": round(d2h_mbps, 1),
                    "ckpt_state_mb": round(state_bytes / 1e6, 1),
                    "ckpt_save_every": save_every,
                    "ckpt_stall_ms": round(ckpt_stall_s * 1e3, 1),
                    "ckpt_call_ms": round(ckpt_call_s * 1e3, 1)}

        lane("transport", 0, transport)
        lane("detection", 0, detection)
        lane("detection_native", 30, detection_native)
        lane("detection_futex", 15, detection_futex)
        lane("ici_step_quorum", 15, ici_step_quorum)
        lane("detect_to_restart", 25, detect_to_restart)
        lane("async_ckpt", 40, async_ckpt)
        lane("ckpt_large", 60,
             lambda: bench_ckpt_large(1024, time_left, light))
        lane("straggler_collector", 20, lambda: {
            "straggler_collector_overhead_pct": round(
                _bench_straggler_collector(step, params, opt, batch), 3)})
        lane("collectives", 15,
             lambda: bench_collectives(results.get("ring_recover_ms")))
        lane("store_fanin", 45, lambda: bench_store_fanin(time_left))
        lane("store_mux", 25, lambda: bench_store_mux(time_left))
        lane("rendezvous_10k", 60, lambda: bench_rendezvous_10k(time_left))
        lane("policy_goodput", 10, bench_policy_goodput)
        lane("evac_goodput", 5, bench_evac_goodput)
        # AFTER detect->restart so the coverage gate sees the episodes those
        # injected faults minted and closed
        lane("flight", 5, bench_flight)
    except _Deadline:
        print("bench: hit its deadline — reporting what was measured",
              file=sys.stderr, flush=True)
        results["partial"] = True
    signal.alarm(0)
    lane("telemetry_keys", float("-inf"), _telemetry_keys)
    detect_ms = results.get("detect_ms")
    line = {
        "metric": "hung_rank_detection_latency_ms",
        "value": round(detect_ms, 3) if detect_ms is not None else None,
        "unit": "ms",
        "vs_baseline": (
            round(detect_ms / _BASELINE_MS, 6) if detect_ms is not None
            else None
        ),
        **results,
    }
    if skipped:
        line["lanes_skipped_for_time"] = skipped
    if lane_errors:
        line["lane_errors"] = lane_errors
    print(json.dumps(line), flush=True)
    if lane_errors or detect_ms is None:
        sys.exit(1)


def _bench_straggler_collector(step, params, opt, batch) -> float:
    """Always-on collector overhead as percent of a real step.

    Differential A/B timing cannot resolve <1% against multi-hundred-ms
    steps (run-to-run variance swamps it — measured ±5% on this host), so
    measure the two costs separately and deterministically:
    - step time: fetch-anchored, median of real steps;
    - instrument cost: the EXACT code the wrap adds to the training thread
      (perf_counter + first-leaf lookup + watcher enqueue), timed over many
      iterations on a live collector.  The completion fetch runs off-thread
      by design and never bills the step path.
    Reference claim being matched: CUPTI profiling overhead 'generally
    expected to be <1%' (straggler usage_guide.rst:169)."""
    from tpu_resiliency.straggler.collector import (
        OpCollector, _first_array_leaf,
    )

    # the step donates its inputs: thread state through every call
    state = {"p": params, "o": opt}

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            state["p"], state["o"], loss = step(state["p"], state["o"], batch)
            float(loss)
        return time.perf_counter() - t0

    run(2)  # warm
    step_s = _median([run(5) / 5 for _ in range(3)])

    coll = OpCollector()
    try:
        out = (state["p"], state["o"])
        op_idx = coll.arena.intern("bench_step")
        # batches of 50 with an UNTIMED drain between them: production
        # enqueues one sample per multi-hundred-ms step into a never-full
        # queue, so the timed path must be the success path, not the
        # queue-full drop path a saturating micro-loop would hit
        total_s, iters = 0.0, 0
        for _ in range(40):
            t0 = time.perf_counter()
            for _ in range(50):
                t_call = time.perf_counter()
                leaf = _first_array_leaf(out)
                if leaf is not None:
                    coll.watcher.submit(op_idx, t_call, leaf)
            total_s += time.perf_counter() - t0
            iters += 50
            coll.flush(timeout=2.0)
        instr_s = total_s / iters
        assert sum(coll.drops().values()) == 0, "queue filled: timing drops"
    finally:
        coll.close()
    return 100.0 * instr_s / max(1e-9, step_s)


def bench_collectives(ring_recover_ms=None) -> dict:
    """coll_* lane: the self-healing collective wrapper's two costs.

    ``coll_wrap_overhead_pct`` — healthy-path tax: median wall of a
    representative wrapped collective vs the raw op.  The wrapper's whole
    steady-state cost is the deadline-lane thread handoff + telemetry +
    health bookkeeping, so this is the number the <5% gate holds (waived
    on a 1-core host, where the lane worker shares the only core with the
    caller).

    ``coll_degrade_ms`` — MTTR of a deadline-tripped collective through
    the degrade ladder (deadline trip -> retry exhausted -> re-layout onto
    the fallback lane), vs ``coll_restart_baseline_ms``: what the SAME
    fault costs on the restart path (the deadline to notice + the measured
    in-process ring recover latency; r5 median when this run didn't
    measure one).  The ladder turns a restart-scale event into a
    deadline-scale one.
    """
    import numpy as np
    import jax

    from tpu_resiliency.parallel.collectives import ResilientCollective
    from tpu_resiliency.parallel.degrade import DegradePolicy
    from tpu_resiliency.parallel.health import health

    out: dict = {}
    # representative payload: big enough that the op cost dominates noise
    x = np.ones((2048, 2048), np.float32)
    jfn = jax.jit(lambda v: (v * 2.0).sum())

    def raw_op():
        return float(jfn(x))

    raw_op()  # warm / compile
    t_raw = []
    for _ in range(30):
        t0 = time.perf_counter()
        raw_op()
        t_raw.append(time.perf_counter() - t0)
    wrapped = ResilientCollective(
        "bench_coll", raw_op, axis="bench", deadline_ms=30000.0,
    )
    wrapped()
    t_wrap = []
    for _ in range(30):
        t0 = time.perf_counter()
        wrapped()
        t_wrap.append(time.perf_counter() - t0)
    raw_ms = _median(t_raw) * 1e3
    wrap_ms = _median(t_wrap) * 1e3
    overhead = 100.0 * max(0.0, wrap_ms - raw_ms) / max(1e-9, raw_ms)
    out["coll_raw_ms"] = round(raw_ms, 3)
    out["coll_wrap_ms"] = round(wrap_ms, 3)
    out["coll_wrap_overhead_pct"] = round(overhead, 2)
    waived = (os.cpu_count() or 1) < 2 and overhead >= 5.0
    out["coll_ok"] = bool(overhead < 5.0 or waived)
    if waived:
        out["coll_wrap_gate_waived"] = "1-core host"

    # degrade MTTR: primary lane stalls past a 100ms deadline; the ladder
    # (retry exhausted immediately, re-layout onto the healthy fallback)
    # must land the result
    deadline_ms = 100.0

    def stalled_primary():
        time.sleep(deadline_ms * 3 / 1e3)
        return raw_op()

    degr = ResilientCollective(
        "bench_coll_degrade", stalled_primary, axis="bench",
        fallback=raw_op, deadline_ms=deadline_ms,
        policy=DegradePolicy(rungs=("retry", "relayout"), retries=0),
        relayout=lambda: "noop",
    )
    t_degr = []
    for _ in range(3):
        # clear the route bias so every rep pays the FULL ladder (trip ->
        # retry-exhausted -> re-layout), not the biased warm path
        health().clear_route("bench_coll_degrade", "bench")
        t0 = time.perf_counter()
        degr()
        t_degr.append(time.perf_counter() - t0)
    degrade_ms = _median(t_degr) * 1e3
    # the restart-path cost of the same fault: notice at the same deadline,
    # then ride the in-process restart ring (measured this run when
    # available; r5 medians otherwise)
    recover_ms = (
        float(ring_recover_ms) if ring_recover_ms else _R5_RING_RECOVER_MS
    )
    baseline_ms = deadline_ms + recover_ms
    out["coll_degrade_ms"] = round(degrade_ms, 1)
    out["coll_restart_baseline_ms"] = round(baseline_ms, 1)
    out["coll_degrade_speedup"] = round(
        baseline_ms / max(1e-9, degrade_ms), 2
    )
    return out


if __name__ == "__main__":
    main()
