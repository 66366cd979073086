"""Layered-restart workload: in-process Wrapper UNDER the elastic launcher.

The key composition (SURVEY.md §1): the wrapper recovers faults in-process
while the launcher's rank monitor knows (via the nested-restarter section)
that recovery is in progress; only faults the wrapper cannot survive fall
through to the launcher ring.

Scenario (env LAYERED_SCENARIO):
  inner  — rank 1 raises at wrapper-iteration 0; the in-process ring recovers
           it; the LAUNCHER must see zero worker failures (cycle stays 0).
           With TPURX_SHRINK_MESH=1 the abort ladder's ShrinkMeshStage runs
           on the recovery path (no distributed client here, so it releases
           by clearing caches+backends) — the opt-in rung end to end.
  outer  — rank 1 hard-exits; the in-process ring cannot save a dead process;
           its launcher respawns it and the wrapper group re-forms.
  stall  — the wedged-COLLECTIVE case the abort ladder absorbs in-process:
           both ranks record a dispatch of ``unified_allreduce`` every step
           (the at-abort fingerprint feed); rank 1 stops beating mid-run (a
           ping-less wait, how a rank parked on a missing participant
           presents when the interpreter still runs).  The armed quorum
           tripwire records QUORUM_STALE, every rank's ladder publishes its
           dispatch tail, the trace-analyzer verdict names the in-flight op
           and the lagging rank, and the ring restarts in-process — the
           launcher never sees a failure.
  degrade — the link fault the SELF-HEALING COLLECTIVE layer absorbs below
           both restart rings (docs/collectives.md): every step runs a
           wrapped collective (``device_max_reduce``); the armed rank
           (``TPURX_FAULT=coll_stall``) has its primary lane stall past the
           deadline every call, so the wrapper walks retry → re-layout in
           process and the route-health bias keeps later calls off the dead
           primary.  Mid-run the armed rank also trips a shrink-only probe
           through the Wrapper-installed DegradeToShrink hook, running the
           real (opt-in) ShrinkMeshStage as a TARGETED rung.  Neither the
           in-process ring nor the launcher ever sees a fault: zero wrapper
           restarts, zero launcher cycles.
  wedged — rank 1 blocks forever inside a DEVICE program (a jit'd infinite
           while_loop: stuck in PJRT C++ with the GIL released — how a
           collective with a missing participant presents to Python).  The
           async raise cannot land, pings and the watchdog's pending-call
           auto-stamps freeze, so the exec'd monitor process records
           SOFT_TIMEOUT (folding in the rank's dispatch tail read from shm
           post-mortem) and then hard-kills at the hard timeout; the
           launcher ring re-rendezvouses.  Reference layered contract:
           ``inprocess/monitor_process.py:269-288`` (GIL-released hang ->
           kill) + ``inprocess/nested_restarter.py:36-107``.
"""

import itertools
import os
import sys
import time

sys.path.insert(0, os.environ.get("TPURX_REPO", "/root/repo"))

from tpu_resiliency.fault_tolerance import FaultToleranceConfig, RankMonitorClient
from tpu_resiliency.fault_tolerance.progress_tracker import write_progress_iteration
from tpu_resiliency.inprocess import ShiftRanks, Wrapper, record_dispatch
from tpu_resiliency.inprocess.nested_restarter import NestedRestarterCallback

RANK = int(os.environ["TPURX_RANK"])
CYCLE = int(os.environ["TPURX_CYCLE"])
SCENARIO = os.environ.get("LAYERED_SCENARIO", "inner")
# wedged/outer DEPEND on the short run: rank 0 finishing cycle 0 quickly is
# part of those scenarios' choreography.
STEPS = int(os.environ.get("LAYERED_STEPS") or 40)
# inner/stall recover IN-PROCESS: the healthy rank must not complete the
# whole fn before the trip -> abort ladder -> restart raise lands
# (completion would legitimately end the job at iteration 0, both ranks
# short of "done@1").  No count of steps holds that order on a loaded host
# — with TPURX_SHRINK_MESH=1 the ladder's first `import jax` and
# `clear_backends` alone take 1.3-1.5 s on an idle one — so in the faulted
# iteration the healthy rank steps until the raise lands; the launcher
# call's timeout is the bound.
UNTIL_RESTARTED = SCENARIO in ("inner", "stall") and CYCLE == 0 and RANK != 1

quorum_kw = {}
if SCENARIO == "stall":
    # the stall is detected by the on-device quorum tripwire (manual beats:
    # ping() IS the progress signal, so a ping-less rank reads as stale)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    quorum_kw = dict(
        quorum_mesh=Mesh(np.array(jax.devices()), ("d",)),
        quorum_budget_ms=500.0,
        quorum_interval=0.05,
        quorum_auto_beat_interval=None,
        quorum_calibrate=False,
    )

client = RankMonitorClient(
    FaultToleranceConfig(
        rank_section_timeouts={"inprocess_restart": 30.0},
        skip_section_response=False,
    )
)
client.init_workload_monitoring()
bridge = NestedRestarterCallback(client)


@Wrapper(
    group=f"layered-c{CYCLE}",
    rank_assignment=ShiftRanks(),
    initialize=bridge.on_initialize,
    abort=bridge.on_abort,
    finalize=bridge.on_finalize,
    soft_timeout=float(os.environ.get("WRAP_SOFT_TIMEOUT", "15.0")),
    hard_timeout=float(os.environ.get("WRAP_HARD_TIMEOUT", "30.0")),
    monitor_process_interval=0.2,
    monitor_thread_interval=0.1,
    heartbeat_interval=0.2,
    sibling_timeout=3.0,
    **quorum_kw,
)
def train(call_wrapper=None):
    it = call_wrapper.iteration
    state = call_wrapper.state
    print(f"train rank={state.active_rank} world={state.active_world_size} "
          f"iter={it} cycle={CYCLE}", flush=True)
    for step in (
        itertools.count() if UNTIL_RESTARTED and it == 0 else range(STEPS)
    ):
        call_wrapper.ping()
        client.send_heartbeat()
        # at-abort fingerprint feed: the step's collective, at dispatch
        record_dispatch("unified_allreduce")
        time.sleep(0.05)
        if SCENARIO == "degrade":
            from tpu_resiliency.parallel import device_max_reduce

            # the step collective, wrapped: the armed rank's primary lane
            # stalls past deadline and the ladder absorbs it IN PROCESS
            got = device_max_reduce([float(step)])
            assert got and got[0] >= float(step), got
            if RANK == 1 and step == 3:
                # targeted-shrink probe: a shrink-only ladder walks the
                # Wrapper-installed DegradeToShrink hook — the real
                # ShrinkMeshStage (TPURX_SHRINK_MESH=1) as ONE rung, not a
                # restart; the healthy fallback lane completes the op
                from tpu_resiliency.parallel import ResilientCollective
                from tpu_resiliency.parallel.degrade import DegradePolicy

                probe = ResilientCollective(
                    "shrink_probe", lambda: "primary", axis="ici",
                    fallback=lambda: "shrunk", deadline_ms=250.0,
                    policy=DegradePolicy(rungs=("shrink",), retries=0),
                )
                print(f"shrink probe -> {probe()}", flush=True)
        if CYCLE == 0 and it == 0 and RANK == 1 and step == 5:
            if SCENARIO == "inner":
                raise RuntimeError("inner fault: recover in-process")
            if SCENARIO == "outer":
                print("outer fault: dying for real", flush=True)
                os._exit(29)
            if SCENARIO == "stall":
                print("stalling: parked on a collective, no beats", flush=True)
                # a ping-less wait: the interpreter still runs (the restart
                # raise can land) but progress beats stop — the quorum
                # tripwire must name this rank from the pod-wide age reduce
                while True:
                    time.sleep(0.02)
            if SCENARIO == "wedged":
                print("wedging in a device program", flush=True)
                import jax
                import jax.numpy as jnp

                spin = jax.jit(
                    lambda x: jax.lax.while_loop(
                        lambda c: jnp.bool_(True), lambda c: c + 1, x
                    )
                )
                # the dispatch lands in the shm tail BEFORE the block: the
                # monitor process reads it post-mortem for the fingerprint
                record_dispatch("spin_forever")
                # never returns: the main thread is blocked inside the PJRT
                # runtime with the GIL released — pings and pending-call
                # stamps freeze, async raises cannot land
                spin(jnp.int32(0)).block_until_ready()
        if state.active_rank == 0:
            write_progress_iteration(os.environ["TOY_CKPT"], step)
    if SCENARIO == "degrade":
        from tpu_resiliency.telemetry import get_registry

        def metric_sum(name):
            m = get_registry().get(name)
            if m is None:
                return 0.0
            return sum(v.get("value", 0.0) for _l, v in m._sample_rows())

        print(
            f"colldeg[{RANK}] "
            f"degrades={int(metric_sum('tpurx_collective_degrades_total'))} "
            f"timeouts={int(metric_sum('tpurx_collective_timeouts_total'))}",
            flush=True,
        )
    return f"done@{it}"


if __name__ == "__main__":
    ret = train()
    print(f"RESULT rank={RANK} cycle={CYCLE} ret={ret}", flush=True)
