"""Chaos soak: the full resiliency stack under randomized fault injection.

One command, repeatable, bounded; the round's regression gate (VERDICT r4
'do this' #9).  Stack under test: elastic launcher + rank monitors +
rendezvous + KV store (in-launcher or external control plane, optionally
the native C++ server) + in-process Wrapper ring + on-device quorum
tripwire, with four randomized fault classes injected per worker step:

- ``exception`` — absorbed by the in-process ring (no respawn),
- ``quorum_stall`` — ping-less stall; the on-device quorum collective
  trips and the in-process ring restarts the iteration,
- ``collective_wedge`` — the wedged-collective injection: the rank
  dispatches a named collective every step (feeding the at-abort
  fingerprint tail) and then parks ping-less "inside" it; the quorum
  tripwire trips, every rank's abort LADDER runs, and the report asserts
  the ladder's recorded stage outcomes (fingerprint rung released) from
  the profiling stream,
- ``hang`` — GIL-released C sleep; the rank monitor's heartbeat timeout
  kills the worker (outer ring respawn),
- ``crash`` — hard exit (outer ring respawn).

With ``--chaos-store`` the KV store runs as an EXTERNAL control plane
with a journal, and a chaos thread SIGKILLs and restarts it at random
intervals mid-run — launchers and monitors must ride the outage out.
With ``--store-kill-mid-save`` the kills are TARGETED instead: rank 0
runs a periodic store-backed "save" (chunked marker writes through the
unified retry policy) and the chaos thread kills the store inside the
save window — the gate asserts every started save still completed.

With ``--corrupt-blob {bitflip,truncate}`` the soak switches to the
checkpoint-integrity campaign: every rank runs a real
``LocalCheckpointManager`` (sealed blobs, clique replication over TCP),
saves every few steps, and in cycle 0 rank 0 corrupts EVERY copy of the
newest committed iteration (``utils.inject_fault.corrupt_checkpoint``)
then hard-exits.  The restarted gang must ``load(fallback=True)`` its way
down the ladder: the gate asserts the corrupt blobs were detected AND
quarantined (``*.corrupt`` debris on disk,
``tpurx_ckpt_corrupt_detected_total`` > 0 in-process), the restored
iteration is strictly OLDER than the corrupted one on every rank, and the
fallback-depth gauge is nonzero.

With ``--peer-mem-kill`` the soak runs the warm-restore campaign instead:
the same ``LocalCheckpointManager`` gang saves every few steps, then at a
drill step every non-serving rank drops its shm-resident copy and reloads
the newest iteration while the serving rank — fault-armed via
``TPURX_FAULT=peer_mem_stall`` — silently drops the peer-memory chunk
requests it receives.  The gate asserts the stalled rung timed out and
fell through to each rank's OWN DISK blob (``tpurx_ckpt_restore_source``
disk bytes > 0, peer bytes == 0) with ``tpurx_ckpt_fallback_depth`` 0:
a stalled peer degrades the restore to a colder source, never to an
older iteration.

With ``--link-degrade`` the soak runs the self-healing-collectives
campaign: every rank loops a wrapped collective
(``parallel.collectives.device_max_reduce``) while rank 0's PRIMARY lane
is fault-armed to stall past its deadline (``TPURX_FAULT=coll_stall``).
The gate asserts the wrapper handled the bad link entirely in process —
deadline trip (``tpurx_collective_timeouts_total`` > 0), degrade ladder
walked (``tpurx_collective_degrades_total`` > 0 on the armed rank only),
every rank FINISHED, and the launcher ring recorded ZERO restart cycles.

With ``--ramp-degrade`` the soak runs the predict-and-evacuate campaign:
one rank's health and straggler scores ramp worse round by round while
rank 0 hosts a ``PolicyController`` over the tree-gathered snapshot feed
with ``TPURX_EVAC=1``.  The gate asserts the fused rank risk evacuated
the ramping victim (checkpoint-ahead + published ``evac/`` record)
BEFORE its hard-fault deadline, that no healthy rank was ever evacuated,
and that the evacuated slot warm-joined chunk-granular from peer
holders' resident copies — peer-memory bytes > 0, disk bytes == 0, no
global restore round.

With ``--store-longpoll-abort`` the soak runs the interruptible-long-poll
campaign: each restart episode parks one rank deep in a server-held store
``wait()`` and a sibling injects a fault while it is parked.  The gate
asserts every injected abort LANDED on the parked rank (the async raise
arrives between poll-quantum I/O slices — the historical flake was a
~30s uninterruptible C-level recv swallowing it) within the
abort-propagation budget plus 2x ``TPURX_STORE_POLL_S``, and that no rank
ever exits ``ret=None``.

Every process appends profiling events to one JSONL
(``TPURX_PROFILING_FILE``); the report derives detect->recover latencies
for both rings from those events and ASSERTS bounds, so a regression in
any layer fails the gate rather than hiding in an average.

Gate (documented in README):    python tests/harness/soak_launcher.py --gate
Quick smoke (CI):               python tests/harness/soak_launcher.py --seconds 45
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from tpu_resiliency.utils.env import force_cpu_env  # noqa: E402

WORKLOAD = r"""
import os, random, sys, time
sys.path.insert(0, os.environ["TPURX_REPO"])
from tpu_resiliency.fault_tolerance import RankMonitorClient
from tpu_resiliency.fault_tolerance.progress_tracker import write_progress_iteration
from tpu_resiliency.inprocess import ShiftRanks, Wrapper, record_dispatch

rank = int(os.environ["TPURX_RANK"])
cycle = int(os.environ["TPURX_CYCLE"])
p_exc = float(os.environ.get("SOAK_EXC_P", "0.01"))
p_crash = float(os.environ.get("SOAK_CRASH_P", "0.008"))
p_hang = float(os.environ.get("SOAK_HANG_P", "0.004"))
p_qstall = float(os.environ.get("SOAK_QSTALL_P", "0.0"))
p_cwedge = float(os.environ.get("SOAK_CWEDGE_P", "0.0"))
save_every = int(os.environ.get("SOAK_SAVE_EVERY", "0"))
total = int(os.environ.get("SOAK_STEPS", "100000"))
ckpt = os.environ["SOAK_CKPT"]
rng = random.Random(f"{cycle}:{rank}:{os.getpid()}")

# seeded replay: a pre-drawn per-(rank, step) schedule replaces the RNG
# draws so two runs (e.g. the adaptive-vs-fixed A/B arms) see the EXACT
# same injection timeline
sched_path = os.environ.get("SOAK_FAULT_SCHEDULE", "")
fired_dir = os.environ.get("SOAK_FAULT_FIRED_DIR", "")
fault_sched = {}
if sched_path:
    import json as json_mod
    with open(sched_path) as f:
        fault_sched = json_mod.load(f)["faults"].get(str(rank), {})


def claim_fault(step):
    '''One-shot gate: restarts rewind the loop over already-run steps, so
    each scheduled injection fires exactly once via an O_EXCL marker.'''
    try:
        fd = os.open(os.path.join(fired_dir, f"r{rank}_s{step}"),
                     os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        os.close(fd)
        return True
    except OSError:
        return False

save_store = None
if save_every and rank == 0:
    from tpu_resiliency.store.client import store_from_env
    save_store = store_from_env(timeout=10.0)


def store_save(step):
    '''A store-backed "save": chunked marker writes, each riding the
    unified retry policy in the store client; the whole commit retried
    under the same policy — mid-save store kills must not lose a save.'''
    from tpu_resiliency.utils.retry import Retrier, RetryPolicy
    print(f"soak[{rank}] save start at step {step}", flush=True)
    r = Retrier("soak_save", RetryPolicy(max_attempts=None, base_delay=0.5,
                                         max_delay=3.0, deadline=60.0))
    while True:
        try:
            for i in range(8):
                save_store.set(f"soakckpt/{step}/{i}", str(step))
                time.sleep(0.08)
            save_store.set(f"soakckpt/{step}/commit", "1")
            break
        except Exception as exc:
            r.backoff(exc)
    print(f"soak[{rank}] save done at step {step}", flush=True)

quorum_kw = {}
if os.environ.get("SOAK_QUORUM") == "1":
    import jax
    import numpy as np
    from jax.sharding import Mesh
    quorum_kw = dict(
        quorum_mesh=Mesh(np.array(jax.devices()), ("d",)),
        quorum_budget_ms=float(os.environ.get("SOAK_QUORUM_BUDGET_MS", "500")),
        quorum_interval=0.05,
        quorum_auto_beat_interval=None,   # manual ping only: progress semantics
        quorum_calibrate=False,
    )

client = RankMonitorClient(); client.init_workload_monitoring()


@Wrapper(
    group=f"soak-c{cycle}",
    rank_assignment=ShiftRanks(),
    soft_timeout=3600.0, hard_timeout=7200.0,   # host ring owns hang kills
    monitor_thread_interval=0.1,
    heartbeat_interval=0.2, sibling_timeout=8.0,
    last_call_wait=0.1,
    enable_monitor_process=False,  # rank monitor (launcher ring) is the backstop here
    **quorum_kw,
)
def run(call_wrapper=None):
    start = 0
    if os.path.exists(ckpt):
        try:
            start = int(open(ckpt).read().strip() or 0)
        except ValueError:
            start = 0
    for step in range(start, total):
        call_wrapper.ping()
        client.send_heartbeat()
        record_dispatch("soak_allreduce")   # at-abort fingerprint feed
        time.sleep(0.03)
        if save_every and save_store is not None and step and step % save_every == 0:
            store_save(step)
        if sched_path:
            kind = fault_sched.get(str(step))
            if kind and claim_fault(step):
                print(f"soak[{rank}] {kind} at step {step}", flush=True)
                if kind == "crash":
                    os._exit(41)
                if kind == "hang":
                    time.sleep(3600)
                if kind in ("quorum stall", "collective wedge"):
                    while True:
                        time.sleep(0.02)
                raise RuntimeError(f"scheduled exception step {step}")
            if call_wrapper.state.active_rank == 0:
                write_progress_iteration(ckpt, step + 1)
            continue
        r = rng.random()
        if r < p_crash:
            print(f"soak[{rank}] crash at step {step}", flush=True); os._exit(41)
        r -= p_crash
        if r < p_hang:
            print(f"soak[{rank}] hang at step {step}", flush=True)
            time.sleep(3600)   # GIL released; heartbeat timeout must kill us
        r -= p_hang
        if r < p_exc:
            print(f"soak[{rank}] exception at step {step}", flush=True)
            raise RuntimeError(f"injected exception step {step}")
        r -= p_exc
        if r < p_qstall and quorum_kw:
            print(f"soak[{rank}] quorum stall at step {step}", flush=True)
            while True:     # ping-less python loop: quorum trips, raise lands
                time.sleep(0.02)
        r -= p_qstall
        if r < p_cwedge and quorum_kw:
            # wedged-collective injection: the collective was DISPATCHED
            # (it's in the tail) and this rank now parks "inside" it —
            # the ladder's fingerprint rung must name soak_allreduce
            print(f"soak[{rank}] collective wedge at step {step}", flush=True)
            while True:
                time.sleep(0.02)
        if call_wrapper.state.active_rank == 0:
            write_progress_iteration(ckpt, step + 1)
    return "done"

print(f"soak[{rank}] result={run()}", flush=True)
"""


WORKLOAD_LCKPT = r"""
import os, sys, time
sys.path.insert(0, os.environ["TPURX_REPO"])
import numpy as np
from tpu_resiliency.fault_tolerance import RankMonitorClient
from tpu_resiliency.store.client import store_from_env
from tpu_resiliency.checkpointing.local.manager import LocalCheckpointManager
from tpu_resiliency.checkpointing.local.replication import (
    CliqueReplication, PeerExchange)
from tpu_resiliency.telemetry import get_registry
from tpu_resiliency.utils.inject_fault import Fault, corrupt_checkpoint

rank = int(os.environ["TPURX_RANK"])
world = int(os.environ["TPURX_WORLD_SIZE"])
cycle = int(os.environ["TPURX_CYCLE"])
root = os.environ["SOAK_CKPT_ROOT"]
save_every = int(os.environ.get("SOAK_LCKPT_EVERY", "10"))
corrupt_step = int(os.environ.get("SOAK_CORRUPT_STEP", "35"))
drill_step = int(os.environ.get("SOAK_PEER_DRILL_STEP", "0"))
mode = os.environ.get("SOAK_CORRUPT_MODE", "bitflip")
total = int(os.environ.get("SOAK_STEPS", "100000"))


def metric_sum(name):
    m = get_registry().get(name)
    if m is None:
        return 0.0
    return sum(v.get("value", 0.0) for _l, v in m._sample_rows())


def source_bytes(src):
    return get_registry().value_of(
        "tpurx_ckpt_restore_source_total", {"source": src})


client = RankMonitorClient(); client.init_workload_monitoring()
store = store_from_env(timeout=15.0)
ex = PeerExchange(store, rank, namespace=f"soaklc-c{cycle}")
repl = CliqueReplication(ex, world, replication_factor=min(2, world))
mgr = LocalCheckpointManager(
    os.path.join(root, f"n{rank}"), rank, world, store=store,
    replication=repl, keep_last=8, peer_timeout=30.0,
    store_namespace=f"localckpt/c{cycle}",
)


def make_tree(step):
    return {"w": np.full((4096,), float(step), dtype=np.float32),
            "step": np.int64(step),
            "rank_marker": np.array([rank], dtype=np.int32)}


start = 0
if mgr.find_latest() is not None:
    tree, it = mgr.load(make_tree(0), fallback=True)
    depth = int(get_registry().get("tpurx_ckpt_fallback_depth").value)
    assert int(tree["step"]) == it, (int(tree["step"]), it)
    assert int(tree["rank_marker"][0]) == rank, "restored ANOTHER rank's data"
    import glob as glob_mod
    debris = len(glob_mod.glob(
        os.path.join(root, f"n{rank}", "**", "*.corrupt"), recursive=True))
    print(f"soaklc[{rank}] restored iter={it} depth={depth} "
          f"corrupt={int(metric_sum('tpurx_ckpt_corrupt_detected_total'))} "
          f"quarantined={int(metric_sum('tpurx_ckpt_quarantined_total'))} "
          f"debris={debris}",
          flush=True)
    start = it + 1
else:
    print(f"soaklc[{rank}] fresh start (no checkpoint)", flush=True)

for step in range(start, total):
    client.send_heartbeat()
    time.sleep(0.05)
    if step and step % save_every == 0:
        mgr.save(make_tree(step), iteration=step, is_async=False)
        print(f"soaklc[{rank}] saved iter={step}", flush=True)
    if drill_step and step == drill_step and mgr.find_latest() is not None:
        # peer-memory stall drill: the serving peer (TPURX_FAULT_RANKS)
        # silently drops chunk requests, so every other rank — having shed
        # its own resident copy — must try the peer-memory rung, time out,
        # and fall through to its own disk blob WITHOUT burning a fallback
        # rung (depth stays 0: same iteration, colder source)
        it = mgr.find_latest()
        peer0, disk0 = source_bytes("peer_memory"), source_bytes("local_disk")
        if rank != 0:
            mgr.drop_resident()
        t0 = time.time()
        tree2, it2 = mgr.load(make_tree(0), iteration=it)
        depth = int(get_registry().get("tpurx_ckpt_fallback_depth").value)
        assert int(tree2["rank_marker"][0]) == rank, "restored ANOTHER rank's data"
        print(f"soaklc[{rank}] peer-drill it={it2} "
              f"disk_b={int(source_bytes('local_disk') - disk0)} "
              f"peer_b={int(source_bytes('peer_memory') - peer0)} "
              f"depth={depth} s={time.time() - t0:.2f}", flush=True)
    if cycle == 0 and rank == 0 and step == corrupt_step:
        mutated = corrupt_checkpoint(root, Fault(mode))
        its = sorted({os.path.basename(os.path.dirname(p)) for p in mutated})
        print(f"soaklc[{rank}] corrupted newest mode={mode} "
              f"files={len(mutated)} iters={','.join(its)}", flush=True)
        time.sleep(0.3)
        os._exit(41)
print(f"soaklc[{rank}] result=done", flush=True)
"""


WORKLOAD_COLL = r"""
import os, sys, time
sys.path.insert(0, os.environ["TPURX_REPO"])
from tpu_resiliency.fault_tolerance import RankMonitorClient
from tpu_resiliency.fault_tolerance.progress_tracker import write_progress_iteration
from tpu_resiliency.parallel import device_max_reduce
from tpu_resiliency.telemetry import get_registry

rank = int(os.environ["TPURX_RANK"])
world = int(os.environ["TPURX_WORLD_SIZE"])
total = int(os.environ.get("SOAK_COLL_STEPS", "25"))
ckpt = os.environ["SOAK_CKPT"]


def metric_sum(name):
    m = get_registry().get(name)
    if m is None:
        return 0.0
    return sum(v.get("value", 0.0) for _l, v in m._sample_rows())


client = RankMonitorClient(); client.init_workload_monitoring()
from tpu_resiliency.store.client import store_from_env
store = store_from_env(timeout=10.0)
for step in range(total):
    client.send_heartbeat()
    # every step runs one wrapped collective; on the fault-armed rank
    # (TPURX_FAULT=coll_stall) the primary lane stalls past its deadline
    # and the wrapper must degrade (retry -> re-layout) IN PROCESS — the
    # launcher ring must never see a restart
    got = device_max_reduce([float(step)])
    assert got and got[0] >= float(step), (got, step)
    time.sleep(0.02)
    if rank == 0:
        write_progress_iteration(ckpt, step + 1)
# gang-synchronized exit: a rank exiting while the degraded rank is still
# grinding reads as a failure to the launcher ring, which would restart
# the gang and mask the zero-restart assertion
store.set(f"soakcoll/done/r{rank}", "1")
t_barrier = time.monotonic()
while time.monotonic() - t_barrier < 120.0:
    client.send_heartbeat()
    if all(store.try_get(f"soakcoll/done/r{r}") is not None
           for r in range(world)):
        break
    time.sleep(0.2)
print(f"soakcoll[{rank}] result=done "
      f"degrades={int(metric_sum('tpurx_collective_degrades_total'))} "
      f"timeouts={int(metric_sum('tpurx_collective_timeouts_total'))}",
      flush=True)
"""


WORKLOAD_EVAC = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["TPURX_REPO"])
import numpy as np
from tpu_resiliency.fault_tolerance import RankMonitorClient
from tpu_resiliency.store.client import store_from_env
from tpu_resiliency.checkpointing.local.manager import LocalCheckpointManager
from tpu_resiliency.checkpointing.local.replication import (
    CliqueReplication, PeerExchange)
from tpu_resiliency.policy import (
    EvacuationPipeline, PolicyController, SnapshotFeed,
    set_evacuation_handler)
from tpu_resiliency.telemetry import get_registry
from tpu_resiliency.telemetry.aggregate import (
    CrossRankAggregator, read_latest_snapshots)

rank = int(os.environ["TPURX_RANK"])
world = int(os.environ["TPURX_WORLD_SIZE"])
cycle = int(os.environ["TPURX_CYCLE"])
victim = int(os.environ.get("SOAK_EVAC_VICTIM", "1"))
ramp_rounds = int(os.environ.get("SOAK_EVAC_RAMP_ROUNDS", "12"))
deadline_step = int(os.environ.get("SOAK_EVAC_DEADLINE", "45"))
root = os.environ["SOAK_CKPT_ROOT"]
save_every = int(os.environ.get("SOAK_LCKPT_EVERY", "5"))
total = int(os.environ.get("SOAK_STEPS", "200"))

client = RankMonitorClient(); client.init_workload_monitoring()
store = store_from_env(timeout=15.0)
ex = PeerExchange(store, rank, namespace=f"soakev-c{cycle}")
repl = CliqueReplication(ex, world, replication_factor=min(2, world))
mgr = LocalCheckpointManager(
    os.path.join(root, f"n{rank}"), rank, world, store=store,
    replication=repl, keep_last=8, peer_timeout=30.0,
    store_namespace=f"localckpt/c{cycle}",
)
agg = CrossRankAggregator(store, rank, world)
reg = get_registry()
health = reg.gauge("tpurx_health_score", labels=("check",))
strag = reg.gauge("tpurx_straggler_score", labels=("rank",))


def source_bytes(src):
    return get_registry().value_of(
        "tpurx_ckpt_restore_source_total", {"source": src})


def make_tree(step):
    return {"w": np.full((4096,), float(step), dtype=np.float32),
            "step": np.int64(step),
            "rank_marker": np.array([rank], dtype=np.int32)}


pipe = EvacuationPipeline(store=store, rank=rank,
                          shrink_fn=lambda victim_rank: None)
ctl = None
if rank == 0:
    # job-level controller over the tree-gathered snapshot feed; the
    # handler runs the real pipeline (checkpoint-ahead + record publish)
    # and announces a future JOIN step every rank will reach in lockstep
    step_box = {"step": 0}

    def on_evacuate(victim_rank, reason):
        join_step = step_box["step"] + 10
        pipe.evacuate(victim_rank, reason=reason)
        store.set(f"soakev/c{cycle}/evacuate", json.dumps(
            {"victim": victim_rank, "join_step": join_step}))
        print(f"soakev[0] EVACUATE rank={victim_rank} "
              f"at step={step_box['step']} join_step={join_step}",
              flush=True)

    set_evacuation_handler(on_evacuate)
    ctl = PolicyController(
        feed=SnapshotFeed(lambda: read_latest_snapshots(store)),
        store=store)

joined = False
for step in range(total):
    client.send_heartbeat()
    time.sleep(0.05)
    if step and step % save_every == 0 and not joined:
        mgr.save(make_tree(step), iteration=step, is_async=False)
    # the ramping degradation: the victim's node health worsens round by
    # round, and the straggler report scores it slower and slower —
    # nothing hard-faults until the deadline below
    if rank == victim:
        health.labels("soak_ramp").set(min(1.0, step / ramp_rounds))
    if rank == 0:
        for r in range(world):
            score = (max(0.2, 1.0 - step / ramp_rounds)
                     if r == victim else 1.0)
            strag.labels(str(r)).set(score)
    agg.round(reg, timeout=60.0)
    if ctl is not None:
        step_box["step"] = step
        ctl.tick()
    plan_raw = store.try_get(f"soakev/c{cycle}/evacuate")
    if plan_raw is not None and not joined:
        plan = json.loads(plan_raw.decode()
                          if isinstance(plan_raw, bytes) else plan_raw)
        if step >= int(plan["join_step"]):
            # the handoff: every rank joins the collective restore round;
            # the evacuated slot drops its resident copy first, so its
            # bytes must come CHUNK-GRANULAR off peer holders' memory —
            # never a disk rung, never a global restore
            it = mgr.find_latest()
            peer0 = source_bytes("peer_memory")
            disk0 = (source_bytes("local_disk")
                     + source_bytes("peer_disk"))
            if rank == int(plan["victim"]):
                mgr.drop_resident()
                out = pipe.warm_join(mgr, make_tree(0), iteration=it)
                peer_b = int(source_bytes("peer_memory") - peer0)
                disk_b = int(source_bytes("local_disk")
                             + source_bytes("peer_disk") - disk0)
                assert int(out["tree"]["rank_marker"][0]) == rank
                print(f"soakev[{rank}] JOIN warm={out['warm']} "
                      f"iter={out['iteration']} peer_b={peer_b} "
                      f"disk_b={disk_b} "
                      f"dur_ms={out['dur_ms']:.1f}", flush=True)
            else:
                mgr.load(make_tree(0), iteration=it)
            joined = True
            break  # every rank leaves at the SAME plan step
    if rank == victim and step >= deadline_step and not joined:
        print(f"soakev[{rank}] HARD FAULT at step {step}", flush=True)
        os._exit(41)
# gang-synchronized exit (a lone early exit reads as a failure to the
# launcher ring and would restart the gang)
store.set(f"soakev/c{cycle}/done/r{rank}", "1")
t_barrier = time.monotonic()
while time.monotonic() - t_barrier < 120.0:
    client.send_heartbeat()
    if all(store.try_get(f"soakev/c{cycle}/done/r{r}") is not None
           for r in range(world)):
        break
    time.sleep(0.2)
print(f"soakev[{rank}] result=done joined={joined}", flush=True)
"""


WORKLOAD_LONGPOLL = r"""
import os, sys, time
sys.path.insert(0, os.environ["TPURX_REPO"])
from tpu_resiliency.fault_tolerance import RankMonitorClient
from tpu_resiliency.inprocess import ShiftRanks, Wrapper
from tpu_resiliency.store.client import StoreTimeout, store_from_env

rank = int(os.environ["TPURX_RANK"])
cycle = int(os.environ["TPURX_CYCLE"])
inject_delay = float(os.environ.get("SOAK_LP_INJECT_DELAY", "2.0"))

client = RankMonitorClient(); client.init_workload_monitoring()
store = store_from_env(timeout=30.0)


@Wrapper(
    group=f"soaklp-c{cycle}",
    rank_assignment=ShiftRanks(),
    soft_timeout=3600.0, hard_timeout=7200.0,
    monitor_thread_interval=0.05,
    heartbeat_interval=0.1, sibling_timeout=5.0,
    last_call_wait=0.1,
    enable_monitor_process=False,
)
def run(call_wrapper=None):
    # One fault EPISODE per restart iteration: active rank 0 parks deep in a
    # server-held store long poll, active rank 1 raises after inject_delay.
    # The in-process ring's async abort must LAND on the parked rank between
    # poll-quantum slices (the historical flake: one ~30s C-level recv
    # swallowed the raise and the rank exited ret=None).  Both sides print
    # CLOCK_MONOTONIC stamps (system-wide on Linux) so the report can
    # compute injection->landing latency across processes.
    while True:
        call_wrapper.ping()
        client.send_heartbeat()
        ep = call_wrapper.state.iteration
        me = call_wrapper.state.active_rank
        if me == 0:
            print(f"soaklp[{rank}] park ep={ep} t={time.monotonic():.6f}",
                  flush=True)
            try:
                store.wait([f"soaklp/never/c{cycle}/ep{ep}"], timeout=120.0)
            except StoreTimeout:
                pass  # episode fizzled (injector restarted first); re-park
            except BaseException:
                print(f"soaklp[{rank}] landed ep={ep} "
                      f"t={time.monotonic():.6f}", flush=True)
                raise
        elif me == 1:
            # stay live for the heartbeat ring while the victim parks
            t0 = time.monotonic()
            while time.monotonic() - t0 < inject_delay:
                call_wrapper.ping()
                client.send_heartbeat()
                time.sleep(0.05)
            print(f"soaklp[{rank}] inject ep={ep} t={time.monotonic():.6f}",
                  flush=True)
            raise RuntimeError(f"soaklp scheduled abort ep={ep}")
        else:
            # spectator ranks idle-heartbeat until the episode's abort lands
            while True:
                call_wrapper.ping()
                client.send_heartbeat()
                time.sleep(0.05)

print(f"soaklp[{rank}] result={run()}", flush=True)
"""


WORKLOAD_GOODPUT = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["TPURX_REPO"])
from tpu_resiliency.fault_tolerance import RankMonitorClient
from tpu_resiliency.fault_tolerance.progress_tracker import write_progress_iteration
from tpu_resiliency.inprocess import ShiftRanks, Wrapper, record_dispatch
from tpu_resiliency.checkpointing.async_ckpt.checkpointer import SaveScheduler
from tpu_resiliency.telemetry import get_registry

rank = int(os.environ["TPURX_RANK"])
cycle = int(os.environ["TPURX_CYCLE"])
ckpt = os.environ["SOAK_CKPT"]
step_s = float(os.environ.get("SOAK_STEP_S", "0.02"))
save_cost_s = float(os.environ.get("SOAK_SAVE_COST_S", "0.4"))
fixed_interval_s = float(os.environ.get("SOAK_SAVE_INTERVAL_S", "4.0"))
total = int(os.environ.get("SOAK_STEPS", "100000"))
with open(os.environ["SOAK_FAULT_SCHEDULE"]) as f:
    faults = json.load(f)["faults"].get(str(rank), {})
fired_dir = os.environ["SOAK_FAULT_FIRED_DIR"]


def claim_fault(step):
    try:
        fd = os.open(os.path.join(fired_dir, f"r{rank}_s{step}"),
                     os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        os.close(fd)
        return True
    except OSError:
        return False


client = RankMonitorClient(); client.init_workload_monitoring()

# the adaptive arm: a per-rank closed loop over this rank's own telemetry
# — the estimator measures MTBF / C / R from the SAME counters the real
# stack records (interruptions, save-call latency, restart latency) and
# retunes TPURX_CKPT_INTERVAL_S through the actuator; the fixed arm runs
# the identical workload with the policy off
policy_ctl = None
if os.environ.get("TPURX_POLICY", "0") == "1":
    from tpu_resiliency.policy import PolicyController
    policy_ctl = PolicyController()
    policy_ctl.start(
        interval_s=float(os.environ.get("TPURX_POLICY_INTERVAL_S", "2.0")))

scheduler = SaveScheduler(default_interval_s=fixed_interval_s)
SAVE_NS = get_registry().get("tpurx_ckpt_save_call_ns")


@Wrapper(
    group=f"goodput-c{cycle}",
    rank_assignment=ShiftRanks(),
    soft_timeout=3600.0, hard_timeout=7200.0,
    monitor_thread_interval=0.1,
    heartbeat_interval=0.2, sibling_timeout=8.0,
    last_call_wait=0.1,
    enable_monitor_process=False,
)
def run(call_wrapper=None):
    start = 0
    if os.path.exists(ckpt):
        try:
            start = int(open(ckpt).read().strip() or 0)
        except ValueError:
            start = 0
    for step in range(start, total):
        call_wrapper.ping()
        client.send_heartbeat()
        record_dispatch("goodput_allreduce")
        time.sleep(step_s)           # the useful work
        if scheduler.due():          # re-reads TPURX_CKPT_INTERVAL_S
            t0 = time.monotonic_ns()
            time.sleep(save_cost_s)  # the checkpoint cost C
            scheduler.note_saved()
            if SAVE_NS is not None:
                SAVE_NS.observe(time.monotonic_ns() - t0)
            if call_wrapper.state.active_rank == 0:
                # durable progress == last save: a fault rewinds to here
                write_progress_iteration(ckpt, step + 1)
        kind = faults.get(str(step))
        if kind and claim_fault(step):
            print(f"soak[{rank}] {kind} at step {step}", flush=True)
            if kind == "crash":
                os._exit(41)
            if kind == "hang":
                time.sleep(3600)
            raise RuntimeError(f"scheduled exception step {step}")
    return "done"

print(f"soak[{rank}] result={run()}", flush=True)
"""


def _gen_fault_schedule(seed, nproc, horizon, probs, shift_at=None,
                        shift_mult=1.0):
    """Pre-draw the whole injection timeline: ``probs`` maps fault kind ->
    per-step probability; from ``shift_at`` on, every probability is
    multiplied by ``shift_mult`` (the fault-regime step the adaptive
    policy must chase).  Same seed -> byte-identical schedule."""
    rng = random.Random(seed)
    faults: dict = {str(r): {} for r in range(nproc)}
    for r in range(nproc):
        for step in range(1, horizon):
            mult = (
                shift_mult
                if shift_at is not None and step >= shift_at
                else 1.0
            )
            draw = rng.random()
            for kind, p_kind in probs.items():
                if draw < p_kind * mult:
                    faults[str(r)][str(step)] = kind
                    break
                draw -= p_kind * mult
    return {
        "seed": seed,
        "nproc": nproc,
        "horizon": horizon,
        "shift_at": shift_at,
        "shift_mult": shift_mult,
        "faults": faults,
    }


def _run_fault_shift_ab(args) -> None:
    """Adaptive-vs-fixed goodput A/B: both arms replay ONE seeded fault
    schedule (same injection timeline) for the same wall time; goodput is
    durably-saved progress.  ``ok`` says that both arms ran to their end
    ``ok``; ``policy_goodput_gain`` = adaptive / fixed is reported as a
    reading of a CPU host's clock, not gated on: which arm wins is held by
    the simulated clock of ``sim_policy.py``."""
    workdir = tempfile.mkdtemp(prefix="tpurx-soak-ab-")
    sched_path = args.fault_schedule
    if sched_path is None:
        seed = args.fault_seed if args.fault_seed is not None else 0x600D
        step_s = 0.02
        horizon = max(400, int(args.seconds / step_s) * 2)
        sched = _gen_fault_schedule(
            seed, args.nproc, horizon, {"exception": 0.004},
            shift_at=horizon // 4, shift_mult=6.0,
        )
        sched_path = os.path.join(workdir, "fault_schedule.json")
        with open(sched_path, "w") as f:
            json.dump(sched, f)
    arms: dict = {}
    for arm in ("fixed", "adaptive"):
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--seconds", str(args.seconds),
            "--nproc", str(args.nproc),
            "--fault-schedule", os.path.abspath(sched_path),
            "--goodput-arm", arm,
        ]
        env = dict(os.environ)
        env.update({
            "TPURX_POLICY": "1" if arm == "adaptive" else "0",
            "TPURX_POLICY_INTERVAL_S": "2.0",
            # the Young/Daly optimum here lives in single-digit seconds;
            # production clamp floors would pin the controller
            "TPURX_POLICY_CADENCE_MIN_S": "0.5",
            "TPURX_POLICY_CADENCE_MAX_S": "60.0",
        })
        proc = subprocess.run(cmd, cwd=REPO, env=env,
                              capture_output=True, text=True)
        last = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        arms[arm] = (
            json.loads(last[-1]) if last
            else {"ok": False, "final_progress": 0}
        )
        print(f"soak-ab[{arm}]: final={arms[arm].get('final_progress')} "
              f"ok={arms[arm].get('ok')}", flush=True)
    fixed_g = max(1, int(arms["fixed"].get("final_progress") or 0))
    adaptive_g = int(arms["adaptive"].get("final_progress") or 0)
    gain = adaptive_g / fixed_g
    arms_ok = bool(arms["fixed"].get("ok") and arms["adaptive"].get("ok"))
    print(json.dumps({
        "metric": "soak_fault_shift",
        "seconds_per_arm": args.seconds,
        "fault_schedule": os.path.abspath(sched_path),
        "adaptive_progress": adaptive_g,
        "fixed_progress": fixed_g,
        "policy_goodput_gain": round(gain, 3),
        "arms_ok": arms_ok,
        "ok": arms_ok,
    }))
    sys.exit(0 if arms_ok else 1)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class StoreChaos(threading.Thread):
    """Kill and restart the external control plane — at random intervals,
    or (``trigger`` given) the moment the trigger fires, so kills can be
    TARGETED inside a save window (store-outage-mid-save)."""

    def __init__(self, spawn_fn, min_s: float, max_s: float, down_s: float,
                 trigger=None):
        super().__init__(daemon=True)
        self.spawn_fn = spawn_fn
        self.min_s, self.max_s, self.down_s = min_s, max_s, down_s
        self.trigger = trigger
        self.proc = spawn_fn()
        self.kills = 0
        self._halt = threading.Event()
        self.rng = random.Random(0xC4A05)

    def _wait_for_next_kill(self) -> bool:
        """True when a kill is due; False when halting."""
        if self.trigger is None:
            return not self._halt.wait(self.rng.uniform(self.min_s, self.max_s))
        while not self._halt.is_set():
            if self.trigger():
                return True
            if self._halt.wait(0.2):
                break
        return False

    def run(self):
        while not self._halt.is_set():
            if not self._wait_for_next_kill():
                break
            try:
                os.kill(self.proc.pid, signal.SIGKILL)
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                pass
            self.kills += 1
            print(f"soak: store host KILLED (#{self.kills})", flush=True)
            if self._halt.wait(self.down_s):
                break
            self.proc = self.spawn_fn()
            print("soak: store host restarted", flush=True)

    def stop(self):
        # join BEFORE terminating: run() may be mid-respawn, and killing the
        # old proc while it assigns a fresh one would leak an orphan store
        self._halt.set()
        self.join(timeout=15)
        try:
            self.proc.terminate()
            self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            try:
                self.proc.kill()
            except OSError:
                pass


def _ring_latencies(events):
    """Derive detect->recover latencies (ms) for both rings from the JSONL.

    Outer: FAILURE_DETECTED -> next WORKER_STARTED recorded by the SAME pid
    (the launcher records both; the wrapper's worker_started is a worker
    pid and never pairs).
    Inner: earliest DETECTION event in a worker pid (HANG_DETECTED from the
    quorum tripwire, INPROCESS_INTERRUPTED for exceptions;
    INPROCESS_RESTART_STARTED as the fallback anchor) ->
    INPROCESS_RESTART_COMPLETED in the same pid, so a detection-latency
    regression moves the measured number, not just teardown+re-entry.
    """
    outer, inner = [], []
    pending_outer = None
    pending_inner = {}
    for ev in events:
        name = ev.get("event")
        if name == "failure_detected" and pending_outer is None:
            pending_outer = ev["mono_ns"], ev["pid"]
        elif name == "worker_started" and pending_outer is not None:
            t0, pid = pending_outer
            if ev["pid"] == pid and ev["mono_ns"] > t0:
                outer.append((ev["mono_ns"] - t0) / 1e6)
                pending_outer = None
        elif name in ("hang_detected", "inprocess_interrupted",
                      "inprocess_restart_started"):
            # setdefault keeps the EARLIEST anchor: real detection when
            # recorded, restart entry otherwise
            pending_inner.setdefault(ev["pid"], ev["mono_ns"])
        elif name == "inprocess_restart_completed":
            t0 = pending_inner.pop(ev["pid"], None)
            if t0 is not None and ev["mono_ns"] > t0:
                inner.append((ev["mono_ns"] - t0) / 1e6)
    return outer, inner


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seconds", type=float, default=120.0)
    p.add_argument("--gate", action="store_true",
                   help="the regression gate: 900s, chaos-store, quorum")
    p.add_argument("--exc-p", type=float, default=0.01)
    p.add_argument("--crash-p", type=float, default=0.008)
    p.add_argument("--hang-p", type=float, default=0.004)
    p.add_argument("--qstall-p", type=float, default=0.006)
    p.add_argument("--cwedge-p", type=float, default=0.004,
                   help="wedged-collective injection probability "
                        "(quorum-armed runs only)")
    p.add_argument("--save-every", type=int, default=0,
                   help="steps between rank-0 store-backed saves (0=off)")
    p.add_argument("--store-kill-mid-save", action="store_true",
                   help="target store kills INSIDE save windows; asserts "
                        "every started save still completes")
    p.add_argument("--corrupt-blob", choices=("bitflip", "truncate"),
                   help="checkpoint-integrity campaign: corrupt every copy "
                        "of the newest local-checkpoint iteration mid-run; "
                        "the restarted gang must fallback-restore the "
                        "next-oldest valid iteration")
    p.add_argument("--peer-mem-kill", action="store_true",
                   help="warm-restore campaign: stall the peer-memory "
                        "serving rank mid-restore drill; the other ranks' "
                        "ladders must fall through to their own disk with "
                        "fallback depth 0")
    p.add_argument("--ramp-degrade", action="store_true",
                   help="predict-and-evacuate campaign: one rank's health "
                        "and straggler scores ramp worse round by round; "
                        "the policy's fused rank risk must EVACUATE it "
                        "(checkpoint-ahead, published record, peer "
                        "warm-join with zero disk bytes) before its "
                        "hard-fault deadline, and never evacuate a "
                        "healthy rank")
    p.add_argument("--link-degrade", action="store_true",
                   help="self-healing-collectives campaign: one rank's "
                        "primary collective lane is fault-armed to stall "
                        "past its deadline (TPURX_FAULT=coll_stall); the "
                        "wrapper must degrade (retry -> re-layout) and the "
                        "job must finish with ZERO launcher-ring restarts")
    p.add_argument("--store-longpoll-abort", action="store_true",
                   help="interruptible-long-poll campaign: each restart "
                        "episode parks one rank in a server-held store "
                        "wait() and injects a sibling fault; the gate "
                        "asserts the async abort LANDS on the parked rank "
                        "within the poll-quantum contract and that no rank "
                        "ever exits ret=None")
    p.add_argument("--longpoll-bound-s", type=float, default=None,
                   help="bound on injection->landing latency per episode "
                        "(default: abort-propagation budget + 2x "
                        "TPURX_STORE_POLL_S)")
    p.add_argument("--fault-seed", type=int, default=None,
                   help="derive a deterministic per-(rank,step) fault "
                        "schedule from this seed and replay it (each "
                        "scheduled injection fires exactly once) instead "
                        "of per-step RNG draws")
    p.add_argument("--fault-schedule", default=None,
                   help="replay an exact recorded schedule file "
                        "(overrides --fault-seed generation)")
    p.add_argument("--fault-shift", action="store_true",
                   help="adaptive-vs-fixed goodput A/B under ONE seeded "
                        "fault schedule whose fault rate steps up "
                        "mid-run; reports policy_goodput_gain")
    p.add_argument("--goodput-arm", choices=("adaptive", "fixed"),
                   default=None, help=argparse.SUPPRESS)  # one A/B arm
    p.add_argument("--nproc", type=int, default=2)
    p.add_argument("--native-store", action="store_true")
    p.add_argument("--chaos-store", action="store_true",
                   help="external journaled control plane, randomly killed")
    p.add_argument("--quorum", action="store_true",
                   help="arm the on-device quorum tripwire in the workload")
    p.add_argument("--store-kill-every", type=float, nargs=2,
                   default=(35.0, 70.0), metavar=("MIN", "MAX"))
    p.add_argument("--store-down", type=float, default=3.0)
    p.add_argument("--inner-bound-ms", type=float, default=8000.0,
                   help="bound on median inner-ring detect->recover")
    p.add_argument("--outer-bound-ms", type=float, default=30000.0,
                   help="bound on median outer-ring detect->recover")
    args = p.parse_args()
    if args.gate:
        args.seconds = max(args.seconds, 900.0)
        args.chaos_store = True
        args.quorum = True
        args.store_kill_mid_save = True
        if not args.save_every:
            args.save_every = 60
    if args.store_kill_mid_save:
        args.chaos_store = True
        if not args.save_every:
            args.save_every = 40
    if args.fault_shift:
        _run_fault_shift_ab(args)
        return

    workdir = tempfile.mkdtemp(prefix="tpurx-soak-")
    wl_path = os.path.join(workdir, "workload.py")
    with open(wl_path, "w") as f:
        if args.goodput_arm:
            f.write(WORKLOAD_GOODPUT)
        elif args.store_longpoll_abort:
            f.write(WORKLOAD_LONGPOLL)
        elif args.ramp_degrade:
            f.write(WORKLOAD_EVAC)
        elif args.link_degrade:
            f.write(WORKLOAD_COLL)
        elif args.corrupt_blob or args.peer_mem_kill:
            f.write(WORKLOAD_LCKPT)
        else:
            f.write(WORKLOAD)
    ckpt = os.path.join(workdir, "progress.txt")
    profile = os.path.join(workdir, "profile.jsonl")
    journal = os.path.join(workdir, "store.journal")
    port = _free_port()

    env = dict(os.environ)
    force_cpu_env(env)
    env.update(
        {
            "TPURX_REPO": REPO,
            "SOAK_CKPT": ckpt,
            "SOAK_EXC_P": str(args.exc_p),
            "SOAK_CRASH_P": str(args.crash_p),
            "SOAK_HANG_P": str(args.hang_p),
            "SOAK_QSTALL_P": str(args.qstall_p if args.quorum else 0.0),
            "SOAK_CWEDGE_P": str(args.cwedge_p if args.quorum else 0.0),
            "SOAK_SAVE_EVERY": str(args.save_every),
            "SOAK_QUORUM": "1" if args.quorum else "0",
            "TPURX_PROFILING_FILE": profile,
            "TPURX_FT_ENABLE_DEVICE_HEALTH_CHECK": "0",
            "TPURX_FT_RANK_HEARTBEAT_TIMEOUT": "3.0",
            "TPURX_FT_INITIAL_RANK_HEARTBEAT_TIMEOUT": "60.0",
            "TPURX_FT_WORKLOAD_CHECK_INTERVAL": "0.2",
            "TPURX_FT_WORKERS_STOP_TIMEOUT": "3.0",
            "TPURX_FT_MAX_NO_PROGRESS_CYCLES": "0",  # chaos: no early stop
            "TPURX_FT_STORE_REJOIN_WINDOW": "120.0",
            "JAX_PLATFORMS": "cpu",
        }
    )
    sched_path = args.fault_schedule
    if sched_path is None and (args.fault_seed is not None or args.goodput_arm):
        sched = _gen_fault_schedule(
            args.fault_seed if args.fault_seed is not None else 0x600D,
            args.nproc, 20000,
            {"exception": args.exc_p, "crash": args.crash_p,
             "hang": args.hang_p},
        )
        sched_path = os.path.join(workdir, "fault_schedule.json")
        with open(sched_path, "w") as f:
            json.dump(sched, f)
    if sched_path is not None:
        fired = os.path.join(workdir, "fault_fired")
        os.makedirs(fired, exist_ok=True)
        env["SOAK_FAULT_SCHEDULE"] = os.path.abspath(sched_path)
        env["SOAK_FAULT_FIRED_DIR"] = fired
        with open(sched_path) as f:
            n_sched = sum(len(v) for v in json.load(f)["faults"].values())
        print(f"soak: replaying fault schedule {sched_path} "
              f"({n_sched} scheduled injections)", flush=True)
    if args.corrupt_blob or args.peer_mem_kill:
        env.update({
            "SOAK_CKPT_ROOT": os.path.join(workdir, "lckpt"),
            "SOAK_LCKPT_EVERY": "10",
            # barriers/replication pause heartbeats briefly; keep the kill
            # threshold clear of normal collective latency
            "TPURX_FT_RANK_HEARTBEAT_TIMEOUT": "10.0",
        })
    if args.corrupt_blob:
        env.update({
            "SOAK_CORRUPT_MODE": args.corrupt_blob,
            "SOAK_CORRUPT_STEP": "35",
        })
    if args.peer_mem_kill:
        env.update({
            "SOAK_PEER_DRILL_STEP": "25",
            # arm the stall fault on the SERVING rank only: rank 0 keeps
            # its resident copy (so its advert attracts probes) but drops
            # every peer-memory request it receives
            "TPURX_FAULT": "peer_mem_stall",
            "TPURX_FAULT_RANKS": "0",
            "TPURX_CKPT_PEER_MEM_TIMEOUT": "2.0",
        })
        if not args.corrupt_blob:
            env["SOAK_CORRUPT_STEP"] = "-1"  # drill only, no corruption leg
    if args.ramp_degrade:
        env.update({
            "SOAK_CKPT_ROOT": os.path.join(workdir, "lckpt"),
            "SOAK_LCKPT_EVERY": "5",
            "SOAK_EVAC_VICTIM": "1",
            "SOAK_EVAC_RAMP_ROUNDS": "12",
            "SOAK_EVAC_DEADLINE": "45",
            "TPURX_EVAC": "1",
            # saves/tree rounds/joins pause heartbeats briefly
            "TPURX_FT_RANK_HEARTBEAT_TIMEOUT": "15.0",
        })
    lp_poll_s = 0.25
    if args.store_longpoll_abort:
        env.update({
            # a visible (but short) quantum so the landing-latency numbers
            # actually exercise the slicing, not sub-millisecond noise
            "TPURX_STORE_POLL_S": str(lp_poll_s),
            "SOAK_LP_INJECT_DELAY": "2.0",
            # the parked rank legitimately skips rank-monitor heartbeats
            # while inside wait(); keep the outer ring's kill threshold
            # clear of a whole episode
            "TPURX_FT_RANK_HEARTBEAT_TIMEOUT": "30.0",
        })
    if args.link_degrade:
        env.update({
            # stall rank 0's PRIMARY collective lane past its deadline;
            # fallback lanes stay healthy so the degrade ladder can land
            "TPURX_FAULT": "coll_stall",
            "TPURX_FAULT_RANKS": "0",
            "TPURX_COLL_DEADLINE_MS": "300",
            "TPURX_COLL_RETRIES": "1",
            "SOAK_COLL_STEPS": "25",
            # the first degraded call eats ~2 deadlines + a re-layout;
            # keep the heartbeat kill threshold well clear of that
            "TPURX_FT_RANK_HEARTBEAT_TIMEOUT": "10.0",
        })
    if args.quorum:
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    if args.native_store:
        env["TPURX_NATIVE_STORE"] = "1"

    chaos = None
    chunks: list = []   # launcher stdout, drained continuously (shared with
    # the mid-save trigger, which scans it for save-start markers)
    launch_cmd = [
        sys.executable, "-m", "tpu_resiliency.fault_tolerance.launcher",
        "--nnodes", "1", "--nproc-per-node", str(args.nproc),
        "--rdzv-endpoint", f"127.0.0.1:{port}",
        "--max-restarts", "0",   # unlimited
        "--monitor-interval", "0.05",
    ]
    if args.chaos_store:
        def spawn_store():
            cmd = [
                sys.executable, "-m",
                "tpu_resiliency.fault_tolerance.control_plane",
                "--host", "127.0.0.1", "--port", str(port),
                "--journal", journal,
            ]
            if args.native_store:
                cmd.append("--native-store")
            return subprocess.Popen(cmd, env=env, cwd=REPO,
                                    stdout=subprocess.DEVNULL,
                                    stderr=subprocess.STDOUT)

        trigger = None
        if args.store_kill_mid_save:
            state = {"last": 0, "next_kill_t": 0.0}

            def trigger():
                # fire INSIDE a save window: a fresh "save start" marker,
                # rate-limited so some saves also complete undisturbed
                starts = "".join(chunks).count("] save start at step")
                now = time.monotonic()
                if starts > state["last"]:
                    state["last"] = starts
                    if starts % 2 == 1 and now >= state["next_kill_t"]:
                        state["next_kill_t"] = now + 12.0
                        return True
                return False

        chaos = StoreChaos(spawn_store, *args.store_kill_every,
                           down_s=args.store_down, trigger=trigger)
        time.sleep(2.0)  # let the control plane bind before launchers dial
    else:
        launch_cmd.append("--host-store")
    launch_cmd.append(wl_path)

    proc = subprocess.Popen(
        launch_cmd, cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    # drain stdout continuously: a full 64KB pipe would block the launcher
    # and wedge the very run being measured

    def _drain():
        for line in proc.stdout:
            chunks.append(line)

    reader = threading.Thread(target=_drain, daemon=True)
    reader.start()
    if chaos is not None:
        chaos.start()
    deadline = time.monotonic() + args.seconds
    progress_samples = []
    while time.monotonic() < deadline and proc.poll() is None:
        time.sleep(5.0)
        try:
            progress_samples.append(int(open(ckpt).read().strip() or 0))
        except OSError:
            progress_samples.append(0)
    proc.terminate()
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()  # never leak the launcher tree from the soak itself
        proc.wait(timeout=10)
    if chaos is not None:
        chaos.stop()
    reader.join(timeout=10)
    out = "".join(chunks)

    events = []
    try:
        with open(profile) as f:
            for line in f:
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    except OSError:
        pass
    events.sort(key=lambda e: e.get("mono_ns", 0))
    outer_ms, inner_ms = _ring_latencies(events)

    def med(xs):
        return round(sorted(xs)[len(xs) // 2], 1) if xs else None

    # restart cycles = launcher-recorded worker (re)starts beyond the first
    # (the launcher records worker_started in ITS pid; wrapper copies are
    # worker pids)
    cycles = max(0, sum(
        1 for ev in events
        if ev.get("event") == "worker_started" and ev.get("pid") == proc.pid
    ) - 1)
    injected = {
        "crashes": out.count("] crash at step"),
        "hangs": out.count("] hang at step"),
        "exceptions": out.count("] exception at step"),
        "quorum_stalls": out.count("] quorum stall at step"),
        "collective_wedges": out.count("] collective wedge at step"),
    }
    monotone = all(b >= a for a, b in zip(progress_samples, progress_samples[1:]))
    final = progress_samples[-1] if progress_samples else 0
    bounds_ok = True
    if inner_ms and not (med(inner_ms) <= args.inner_bound_ms):
        bounds_ok = False
    if outer_ms and not (med(outer_ms) <= args.outer_bound_ms):
        bounds_ok = False
    inner_faults = (injected["exceptions"] + injected["quorum_stalls"]
                    + injected["collective_wedges"])
    # faults were injected -> the matching ring must actually have run
    rings_ok = (
        (inner_faults == 0 or inner_ms)
        and (injected["crashes"] + injected["hangs"] == 0 or cycles >= 1)
    )
    # abort-ladder stage outcomes from the profiling stream: every inner
    # trip runs the ladder, whose fingerprint rung must have released
    stage_outcomes: dict = {}
    for ev in events:
        if ev.get("event") == "abort_stage":
            key = f"{ev.get('stage')}/{ev.get('outcome')}"
            stage_outcomes[key] = stage_outcomes.get(key, 0) + 1
    ladder_ok = (
        inner_faults == 0 or not inner_ms
        or stage_outcomes.get("fingerprint/released", 0) >= 1
    )
    # store-outage-mid-save: every save that started must have completed
    # (the unified retry policy rides out the kill); tolerated shortfalls:
    # one save aborted per worker restart (either ring) plus the single
    # save the soak's own deadline may cut off in flight
    saves_started = out.count("] save start at step")
    saves_done = out.count("] save done at step")
    saves_ok = True
    if args.store_kill_mid_save:
        tolerance = cycles + len(inner_ms) + 1
        saves_ok = (
            saves_started >= 1
            and saves_done >= max(1, saves_started - tolerance)
        )
    # checkpoint-integrity campaign (--corrupt-blob): the corrupt blobs must
    # be detected + quarantined and EVERY rank must fallback-restore an
    # iteration strictly older than the corrupted one
    # warm-restore campaign (--peer-mem-kill): every non-serving rank's
    # drill must have been served from its OWN DISK (peer rung timed out
    # against the stalled server) at fallback depth 0 — the stall degrades
    # the restore to a colder source, never to an older iteration
    peer_report: dict = {}
    peer_ok = True
    if args.peer_mem_kill:
        import re as re_mod

        drills = [
            tuple(int(x) for x in m)
            for m in re_mod.findall(
                r"soaklc\[(\d+)\] peer-drill it=(\d+) disk_b=(\d+) "
                r"peer_b=(\d+) depth=(\d+)", out)
        ]
        nonserving = [d for d in drills if d[0] != 0]
        peer_ok = bool(
            drills
            and {d[0] for d in drills} == set(range(args.nproc))
            and nonserving
            and all(disk > 0 and peer == 0 and depth == 0
                    for _r, _it, disk, peer, depth in nonserving)
        )
        peer_report = {
            "peer_mem_kill": True,
            "peer_drills": drills,
            "peer_ok": peer_ok,
        }
        if not args.corrupt_blob:
            # lckpt workloads track progress through checkpoint iterations
            monotone = True
            final = max((d[1] for d in drills), default=0)
    # self-healing-collectives campaign (--link-degrade): every rank must
    # FINISH (no restart of any kind), the armed rank must have walked the
    # degrade ladder (timeouts then degrades both nonzero), the healthy
    # ranks must have degraded nothing, and the launcher ring must have
    # recorded ZERO restart cycles — a single bad link costs one
    # collective's deadline plus a local re-layout, not a pod-wide restart
    coll_report: dict = {}
    coll_ok = True
    if args.link_degrade:
        import re as re_mod

        marks = [
            tuple(int(x) for x in m)
            for m in re_mod.findall(
                r"soakcoll\[(\d+)\] result=done degrades=(\d+) "
                r"timeouts=(\d+)", out)
        ]
        armed = [m for m in marks if m[0] == 0]
        coll_ok = bool(
            marks
            and {m[0] for m in marks} == set(range(args.nproc))
            and armed and armed[0][1] >= 1 and armed[0][2] >= 1
            # healthy ranks may eat a first-call compile-latency timeout
            # (retry rung absorbs it) but must never DEGRADE
            and all(m[1] == 0 for m in marks if m[0] != 0)
            and cycles == 0
        )
        coll_report = {
            "link_degrade": True,
            "coll_marks": marks,
            "coll_degrades": armed[0][1] if armed else 0,
            "coll_timeouts": armed[0][2] if armed else 0,
            "coll_ok": coll_ok,
        }
        monotone = all(
            b >= a for a, b in zip(progress_samples, progress_samples[1:])
        )
        final = len(marks)
    # predict-and-evacuate campaign (--ramp-degrade): the fused rank risk
    # must evacuate the ramping victim BEFORE its hard-fault deadline and
    # never touch a healthy rank, and the victim's slot must warm-join
    # chunk-granular off peer memory (zero disk bytes, no global restore)
    evac_report: dict = {}
    evac_ok = True
    if args.ramp_degrade:
        import re as re_mod

        evacs = [
            (int(r), int(s))
            for r, s in re_mod.findall(
                r"soakev\[0\] EVACUATE rank=(\d+) at step=(\d+)", out)
        ]
        joins = [
            (r, int(it), int(pb), int(db))
            for r, it, pb, db in re_mod.findall(
                r"soakev\[\d+\] JOIN warm=(\w+) iter=(\d+) peer_b=(\d+) "
                r"disk_b=(\d+)", out)
        ]
        hard_faults = out.count("] HARD FAULT at step")
        done = len(re_mod.findall(r"soakev\[\d+\] result=done joined=True",
                                  out))
        victim_rank = 1
        evac_ok = bool(
            evacs
            and {r for r, _s in evacs} == {victim_rank}  # nobody healthy
            and hard_faults == 0                # fired before the deadline
            and joins
            and all(w == "True" and pb > 0 and db == 0
                    for w, _it, pb, db in joins)
            and done == args.nproc
        )
        evac_report = {
            "ramp_degrade": True,
            "evacuations": evacs,
            "evac_joins": joins,
            "hard_faults": hard_faults,
            "evac_ok": evac_ok,
        }
        monotone = True
        final = done
    # interruptible-long-poll campaign (--store-longpoll-abort): every
    # injection must LAND on the parked rank (landed marker for the same
    # episode) within the abort-propagation budget plus 2x the poll quantum
    # — and no rank may ever exit ret=None (the restart completes instead
    # of silently swallowing the raise inside an uninterruptible recv)
    lp_report: dict = {}
    lp_ok = True
    if args.store_longpoll_abort:
        import re as re_mod

        def _marks(kind):
            return {
                int(ep): float(t)
                for ep, t in re_mod.findall(
                    rf"soaklp\[\d+\] {kind} ep=(\d+) t=([0-9.]+)", out)
            }

        parks, injects, landings = (_marks("park"), _marks("inject"),
                                    _marks("landed"))
        land_ms = sorted(
            (landings[ep] - injects[ep]) * 1000.0
            for ep in landings if ep in injects
        )
        # budget: the injector's raise propagates through its wrapper's
        # abort broadcast and the victim's monitor thread before the async
        # raise is even ISSUED; only then does the poll-quantum contract
        # (2x TPURX_STORE_POLL_S) apply to the landing itself
        bound_s = (args.longpoll_bound_s if args.longpoll_bound_s is not None
                   else 4.0 + 2 * lp_poll_s)
        # the last episode may be cut off mid-park by the soak deadline
        complete = [ep for ep in injects if ep in landings]
        lp_ok = bool(
            len(injects) >= 1
            and len(complete) >= max(1, len(injects) - 1)
            and land_ms
            and max(land_ms) <= bound_s * 1000.0
            and "ret=None" not in out
            and "result=None" not in out
        )
        lp_report = {
            "store_longpoll_abort": True,
            "lp_episodes_injected": len(injects),
            "lp_episodes_landed": len(landings),
            "lp_land_ms": [round(x, 1) for x in land_ms],
            "lp_land_ms_median": (round(land_ms[len(land_ms) // 2], 1)
                                  if land_ms else None),
            "lp_bound_ms": bound_s * 1000.0,
            "lp_ret_none": out.count("ret=None") + out.count("result=None"),
            "lp_ok": lp_ok,
        }
        monotone = True  # no progress file in this campaign
        final = len(landings)
    ckpt_report: dict = {}
    ckpt_ok = True
    if args.corrupt_blob:
        import glob as glob_mod
        import re as re_mod

        corrupted = re_mod.findall(
            r"soaklc\[\d+\] corrupted newest mode=\S+ files=(\d+) "
            r"iters=iter_(\d+)", out)
        restores = [
            tuple(int(x) for x in m)
            for m in re_mod.findall(
                r"soaklc\[(\d+)\] restored iter=(\d+) depth=(\d+) "
                r"corrupt=(\d+) quarantined=(\d+) debris=(\d+)", out)
        ]
        # end-of-run debris is best-effort (keep_last pruning legitimately
        # reclaims quarantined iter dirs); the restore-time debris count in
        # each marker is the authoritative check
        end_debris = glob_mod.glob(
            os.path.join(workdir, "lckpt", "**", "*.corrupt"), recursive=True)
        corrupted_iter = int(corrupted[0][1]) if corrupted else None
        fb = [r for r in restores if r[2] >= 1]
        ckpt_ok = bool(
            corrupted and int(corrupted[0][0]) >= 1
            and fb
            and {r[0] for r in fb} == set(range(args.nproc))
            and all(it < corrupted_iter for _r, it, _d, _c, _q, _f in fb)
            and all(c >= 1 and q >= 1 and f >= 1
                    for _r, _it, _d, c, q, f in fb)
        )
        ckpt_report = {
            "corrupt_blob": args.corrupt_blob,
            "corrupted_iter": corrupted_iter,
            "restores": restores,
            "fallback_restores": fb,
            "quarantine_debris_at_exit": len(end_debris),
            "ckpt_ok": ckpt_ok,
        }
        # the lckpt workload tracks progress through checkpoint iterations,
        # not the progress file — those checks don't apply
        monotone = True
        final = max((r[1] for r in restores), default=0)
    if args.store_longpoll_abort:
        ok = bool(lp_ok)
    elif args.ramp_degrade:
        ok = bool(evac_ok)
    elif args.corrupt_blob:
        ok = bool(ckpt_ok and peer_ok and cycles >= 1)
    elif args.link_degrade:
        ok = bool(coll_ok and monotone)
    elif args.peer_mem_kill:
        ok = bool(peer_ok and final > 0)
    else:
        ok = bool(monotone and final > 0 and bounds_ok and rings_ok
                  and ladder_ok and saves_ok)
    print(
        json.dumps(
            {
                "metric": "soak_launcher",
                "seconds": args.seconds,
                "chaos_store": args.chaos_store,
                "store_kills": chaos.kills if chaos else 0,
                "quorum": args.quorum,
                "final_progress": final,
                "progress_samples": progress_samples[-12:],
                "cycles": cycles,
                "injected": injected,
                "inner_ring_recoveries": len(inner_ms),
                "inner_detect_to_recover_ms_median": med(inner_ms),
                "outer_ring_recoveries": len(outer_ms),
                "outer_detect_to_recover_ms_median": med(outer_ms),
                "abort_stage_outcomes": stage_outcomes,
                "saves_started": saves_started,
                "saves_done": saves_done,
                "monotone_progress": monotone,
                "bounds_ok": bounds_ok,
                "ladder_ok": ladder_ok,
                "saves_ok": saves_ok,
                **coll_report,
                **peer_report,
                **evac_report,
                **lp_report,
                **ckpt_report,
                "ok": ok,
            }
        )
    )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
