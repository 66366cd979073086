"""Quick start: the three rings around a real training step.

Run on one host (the launcher hosts the store; one worker drives every chip
the host has — a chip belongs to one process at a time):

    python -m tpu_resiliency.fault_tolerance.launcher \
        --nnodes 1 --nproc-per-node 1 --rdzv-endpoint 127.0.0.1:29500 \
        --host-store --max-restarts 3 --log-dir /tmp/tpurx-logs \
        examples/train_with_launcher.py --ckpt-dir /tmp/tpurx-ckpts

The worker is the whole product around ``make_train_step``:

- outer ring — the launcher respawns a dead worker; ``FaultToleranceCallback``
  heartbeats feed its rank monitor, and the worker resumes from the last
  committed ``AsyncCheckpointer`` save on disk;
- inner ring — ``inprocess.Wrapper`` absorbs an exception or a stall in the
  same process (abort ladder → re-entry → restore, warm from the resident
  copy of the save), with ``NestedRestarterCallback`` telling the outer ring
  that a recovery is in progress;
- detection — the quorum tripwire over the training devices (manual beats:
  ``ping()`` is progress, so a step that stops pinging is a stall), its
  budget calibrated under the real step, and the straggler ``Detector``
  timing every step.

The model is ``TransformerConfig()`` at its declared widths in the dtype the
device selects (bf16 and an fp32 master copy on a TPU); ``--d-model`` etc.
cut it down for a CPU (``tests/test_examples.py`` runs the tiny cut).

``--inject exc:7,stall:12,sigkill:17`` fires each fault once, before that
step of the first launcher cycle: an exception and a ping-less stall for the
inner ring, SIGKILL for the outer one.  An injected fault waits for the drain
of a save in flight, so every recovery has a committed checkpoint to prove
itself against.  ``--report`` appends one JSON line per event, which is what
``chip_smoke.py`` reads.
"""

import argparse
import json
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpu_resiliency.models.transformer import TransformerConfig  # noqa: E402
from tpu_resiliency.utils import compile_cache  # noqa: E402

_DECLARED = TransformerConfig()


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--vocab", type=int, default=_DECLARED.vocab)
    p.add_argument("--d-model", type=int, default=_DECLARED.d_model)
    p.add_argument("--n-heads", type=int, default=_DECLARED.n_heads)
    p.add_argument("--n-layers", type=int, default=_DECLARED.n_layers)
    p.add_argument("--d-ff", type=int, default=_DECLARED.d_ff)
    p.add_argument("--seq", type=int, default=_DECLARED.max_seq)
    p.add_argument("--dtype", default=None, choices=["bfloat16", "float32"],
                   help="default: what the device selects (bf16 on a TPU)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--save-every", type=int, default=20)
    p.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                   help="device mesh, e.g. 2x2 (default: all devices on data)")
    p.add_argument("--ckpt-dir", default="/tmp/tpurx-example-ckpts")
    p.add_argument("--progress-file", default="/tmp/tpurx-example-progress")
    p.add_argument("--inject", default="",
                   help="faults as kind:step[@rank],... (exc | stall | sigkill)")
    p.add_argument("--report", default=None,
                   help="append one JSON line per event to this file")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    rank = int(os.environ.get("TPURX_RANK", "0"))
    world = int(os.environ.get("TPURX_WORLD_SIZE", "1"))
    cycle = int(os.environ.get("TPURX_CYCLE", "0"))
    t_start = time.monotonic()

    def report(ev, **fields):
        rec = {"ev": ev, "t": round(time.monotonic() - t_start, 3),
               "rank": rank, "cycle": cycle, "pid": os.getpid(), **fields}
        print(f"[rank {rank}] {ev} " + " ".join(
            f"{k}={v}" for k, v in fields.items()), flush=True)
        if args.report:
            with open(args.report, "a") as f:
                f.write(json.dumps(rec) + "\n")

    cache_dir = compile_cache.enable()  # before the first jit

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.checkpointing.async_ckpt import device_digest
    from tpu_resiliency.checkpointing.async_ckpt.writer import is_committed
    from tpu_resiliency.fault_tolerance import (
        FaultToleranceConfig,
        RankMonitorClient,
    )
    from tpu_resiliency.fault_tolerance.progress_tracker import (
        write_progress_iteration,
    )
    from tpu_resiliency.inprocess import (
        DeviceProbeHealthCheck,
        ShiftRanks,
        Wrapper,
    )
    from tpu_resiliency.inprocess.nested_restarter import NestedRestarterCallback
    from tpu_resiliency.integrations import (
        CallbackRunner,
        FaultToleranceCallback,
        StragglerDetectionCallback,
    )
    from tpu_resiliency.models.transformer import (
        init_opt_state,
        init_params,
        make_batch,
        make_train_step,
    )
    from tpu_resiliency.parallel import init_distributed, make_mesh
    from tpu_resiliency.store.client import store_from_env
    from tpu_resiliency.straggler.xla_profile import XlaProfileCollector
    from tpu_resiliency.telemetry import get_registry
    from tpu_resiliency.utils import native

    cache_events = compile_cache.CacheEvents()
    init_distributed()  # one JAX process per worker; no-op for one worker
    devices = jax.devices()
    from importlib import metadata

    report(
        "device", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), local_count=jax.local_device_count(),
        process_count=jax.process_count(), cache_dir=cache_dir,
        versions={pkg: metadata.version(pkg)
                  for pkg in ("jax", "jaxlib", "libtpu")},
    )

    # -- model, at the widths asked for, on the mesh asked for --------------
    cfg = TransformerConfig(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_seq=args.seq,
        dtype=args.dtype and jnp.dtype(args.dtype),
    )
    mesh = None
    if len(devices) > 1:
        shape = (tuple(int(s) for s in args.mesh.split("x")) if args.mesh
                 else (len(devices), 1))
        mesh = make_mesh(("data", "model"), shape)
    step_jit = make_train_step(cfg, mesh=mesh)
    batch = make_batch(cfg, args.batch, args.seq, mesh=mesh)
    # The inner ring belongs to a worker that owns its whole JAX job.  When
    # the job spans workers, a rank that restarts in process leaves its peers
    # inside a collective that never completes, and a stall on one rank shows
    # up as late pings on all of them — so there the tripwire stays off and
    # faults go to the outer ring (ROADMAP: in-process restart across a
    # multi-process JAX job).
    quorum_mesh = None
    if world == 1:
        quorum_mesh = jax.sharding.Mesh(np.array(devices), ("quorum",))

    def fresh_state():
        params = init_params(cfg, mesh=mesh)
        return {"params": params, "opt": init_opt_state(params)}

    def fingerprints(state):
        """Per-shard on-device fingerprints of every leaf (exact bit
        patterns, bf16 included): what was saved against what came back."""
        fps = [
            device_digest.shard_fingerprints(shard.data)
            for leaf in jax.tree_util.tree_leaves(state)
            for shard in leaf.addressable_shards
        ]
        return [fp.tolist() for fp in device_digest.read_fingerprints(fps)]

    def latest_checkpoint():
        best = None
        if os.path.isdir(args.ckpt_dir):
            for name in os.listdir(args.ckpt_dir):
                path = os.path.join(args.ckpt_dir, name)
                if name.startswith("step_") and is_committed(path):
                    best = max(best or -1, int(name.split("_")[1]))
        return best

    # -- the rings -----------------------------------------------------------
    client = RankMonitorClient(FaultToleranceConfig(
        # an in-process recovery may take this long before the outer ring
        # treats the quiet rank as hung
        rank_section_timeouts={"inprocess_restart": 120.0},
        skip_section_response=False,
    ))
    client.init_workload_monitoring()
    straggler = StragglerDetectionCallback()
    runner = CallbackRunner([
        FaultToleranceCallback(client, warmup_steps=5, update_interval=20),
        straggler,
    ])
    # always-on completion timing of the step (native op rings)
    step_fn = straggler.detector.wrap_callables({"train_step": step_jit})[
        "train_step"]
    bridge = NestedRestarterCallback(client)
    ckpt = AsyncCheckpointer(
        store=store_from_env() if world > 1 else None,
        rank=rank, world_size=world,
    )
    faults = {}  # step -> (kind, is this rank the victim)
    for item in filter(None, args.inject.split(",")):
        kind, _, where = item.partition(":")
        step, _, only_rank = where.partition("@")
        faults[int(step)] = (kind, not only_rank or int(only_rank) == rank)
    done_once = set()

    def first(key):
        """True the first time in this process: faults fire once, and so do
        the one-off checks, however often a restart re-enters ``train``."""
        if key in done_once:
            return False
        done_once.add(key)
        return True

    def quorum_trips():
        """Trips so far, by detection lane (telemetry registry)."""
        fam = get_registry().snapshot().get("tpurx_quorum_detect_ns", {})
        return {row["labels"]["lane"]: int(row.get("count", 0))
                for row in fam.get("samples", [])}

    def drain(cw, step):
        """Wait for every save in flight to commit: known-long, ping-less."""
        with cw.disable_hang_protection():
            for ticket in ckpt.maybe_finalize(blocking=True):
                report("commit", ticket=ticket, at_step=step)

    def maybe_inject(step, cw):
        if step not in faults or cycle != 0 or not first(("fault", step)):
            return
        drain(cw, step)  # every rank: the commit is a cross-rank agreement
        kind, victim = faults[step]
        if not victim:
            return
        report("inject", kind=kind, step=step, iteration=cw.iteration)
        if kind == "exc":
            raise RuntimeError(f"injected exception before step {step}")
        if kind == "sigkill":
            os.kill(os.getpid(), signal.SIGKILL)
        if kind == "stall":
            # a ping-less wait: the interpreter still runs (the restart raise
            # can land) but progress beats stop — the tripwire must see it
            while True:
                time.sleep(0.02)
        raise ValueError(f"unknown fault kind {kind!r}")

    @Wrapper(
        group=f"train-c{cycle}",
        rank_assignment=ShiftRanks(),
        initialize=bridge.on_initialize,
        abort=bridge.on_abort,
        finalize=bridge.on_finalize,
        health_check=DeviceProbeHealthCheck(timeout=30.0),
        quorum_mesh=quorum_mesh,
        # manual beats: ping() is the progress signal.  Provisional budget
        # (just under the packed-age cap) until calibrate_quorum has seen the
        # real step; nothing to calibrate on an idle interpreter.
        quorum_budget_ms=1000.0,
        # operator floor under the calibrated budget: a false restart costs
        # seconds (restore, re-entry), a quarter second of detection latency
        # does not
        quorum_min_budget_ms=250.0,
        quorum_interval=0.02,
        quorum_auto_beat_interval=None,
        quorum_calibrate=False,
    )
    def train(call_wrapper=None):
        cw = call_wrapper
        report("enter", iteration=cw.iteration, quorum_trips=quorum_trips(),
               last_age_ms=cw.quorum and cw.quorum.monitor.last_max_age,
               step_compiles=step_jit._cache_size())
        # the drain of a save the fault left in flight (it may be the newest
        # checkpoint), then the restore: known-long and ping-less
        drain(cw, None)
        with cw.disable_hang_protection():
            state = fresh_state()
            start = 0
            last = latest_checkpoint()
            if last is not None:
                path = os.path.join(args.ckpt_dir, f"step_{last}")
                stats = {}
                t0 = time.monotonic()
                template, state = state, load_checkpoint(path, state, stats=stats)
                with open(f"{path}.fingerprints.{rank}.json") as f:
                    saved_fp = json.load(f)
                shm, read = stats.get("bytes_shm", 0), stats.get("bytes_read", 0)
                # the committed generation's two warm rungs: the snapshot
                # slot still on the chip, then its staged copy in shm
                device = stats.get("bytes_device", 0)
                report(
                    "restore", step=last, iteration=cw.iteration,
                    source="resident" if read and device + shm == read else "disk",
                    bytes_read=read, bytes_device=device, bytes_shm=shm,
                    bit_equal=fingerprints(state) == saved_fp,
                    same_sharding=all(
                        got.sharding.is_equivalent_to(want.sharding, got.ndim)
                        for got, want in zip(jax.tree_util.tree_leaves(state),
                                             jax.tree_util.tree_leaves(template))),
                    restore_s=round(time.monotonic() - t0, 3),
                )
                del template
                start = last + 1
            params, opt = state["params"], state["opt"]
            if first("model"):
                leaves = jax.tree_util.tree_leaves(state)
                report(
                    "model",
                    widths={k: getattr(cfg, k) for k in (
                        "vocab", "d_model", "n_heads", "n_layers", "d_ff",
                        "max_seq")},
                    batch=args.batch, dtype=str(params["embed"].dtype),
                    n_params=sum(int(x.size) for x in
                                 jax.tree_util.tree_leaves(params)),
                    state_bytes=sum(int(x.nbytes) for x in leaves),
                    has_master="master" in opt,
                    leaf_devices=sorted({len(x.sharding.device_set)
                                         for x in leaves}),
                )
        runner.on_train_start(step=start)

        def run_step(step, params, opt):
            """One step as the loop runs it, host work included: the unit
            the tripwire's budget is calibrated on."""
            cw.ping()
            runner.on_step_start(step=step)
            params, opt, loss = step_fn(params, opt, batch)
            loss_value = float(loss)
            for ticket in ckpt.maybe_finalize():
                report("commit", ticket=ticket, at_step=step)
            if rank == 0:
                write_progress_iteration(args.progress_file, step)
            runner.on_step_end(step=step)
            return params, opt, loss_value

        loss_value = None
        for step in range(start, args.steps):
            maybe_inject(step, cw)
            if step_jit._cache_size() == 0:
                # this process's first call compiles the step (or fetches it
                # from the cache on disk): known-long, ping-less
                hits0, miss0 = cache_events.hits, cache_events.misses
                t0 = time.monotonic()
                with cw.disable_hang_protection():
                    params, opt, loss_value = run_step(step, params, opt)
                report("compile", seconds=round(time.monotonic() - t0, 3),
                       cache_hits=cache_events.hits - hits0,
                       cache_misses=cache_events.misses - miss0)
            else:
                params, opt, loss_value = run_step(step, params, opt)
            if not np.isfinite(loss_value):
                raise FloatingPointError(f"loss {loss_value} at step {step}")
            report("step", step=step, loss=loss_value, iteration=cw.iteration)
            if (step + 1) % args.save_every == 0:
                path = os.path.join(args.ckpt_dir, f"step_{step}")
                state = {"params": params, "opt": opt}
                t0 = time.monotonic()
                # the save call snapshots and hands off; it is host work
                # between two pings that the step budget knows nothing about
                with cw.disable_hang_protection():
                    fp = fingerprints(state)
                    ckpt.async_save(state, path,
                                    extra_metadata={"iteration": step})
                    with open(f"{path}.fingerprints.{rank}.json", "w") as f:
                        json.dump(fp, f)  # of this rank's shards
                report("save", step=step, stage_mode=ckpt.last_stage_mode,
                       call_ms=round((time.monotonic() - t0) * 1e3, 1))
            if step == start + 2 and cw.quorum and first("calibrate"):
                # budget from healthy tick ages under the real step: a
                # shadow copy of the state takes the calibration steps
                with cw.disable_hang_protection():  # compiles the copy
                    shadow = jax.block_until_ready(jax.jit(
                        lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
                    )((params, opt)))
                    # The tripwire recomputes its budget once, from its first
                    # 256 ticks, and counts the ticks of protected phases
                    # among them (the budget is infinite there, so every age
                    # passes for healthy): ages of a ping-less compile put
                    # that budget past the packed-age cap, a tripwire that
                    # can never fire.  Inside a protected phase the exit puts
                    # the budget back, outside it stays.  When the 256th tick
                    # comes depends on the load, so it is made to come here.
                    t0 = time.monotonic()
                    while (not getattr(cw.quorum.monitor, "_recal_done", True)
                           and time.monotonic() - t0 < 20.0):
                        time.sleep(0.05)

                def one_step():
                    nonlocal shadow
                    shadow = run_step(step, *shadow)[:2]

                budget = cw.calibrate_quorum(one_step, n_ticks=12)
                monitor = cw.quorum.monitor
                report("quorum_budget", budget_ms=round(budget, 3),
                       p99_ms=round(monitor.last_calibration_p99_ms, 3),
                       pallas=bool(monitor.use_pallas), lane="collective",
                       devices=int(quorum_mesh.devices.size))
                del shadow
            if step == start + 2 and first("memory"):
                report("memory", bytes_in_use=[
                    (d.memory_stats() or {}).get("bytes_in_use")
                    for d in jax.local_devices()])
            if (step == start + 3 and world == 1 and cycle == 0
                    and len(devices) > 1 and first("ici")):
                # the checkpoint layer's chip-to-chip path: ppermute clique
                # replication, one driver thread per device over an
                # in-thread store, blobs of unequal length
                import __graft_entry__ as dryrun

                with cw.disable_hang_protection():
                    report("ici_replication", devices=len(devices),
                           result=dryrun._dryrun_ici_replication(len(devices)))
            if step == start + 3 and first("profile"):
                # one extra step under the XLA profiler: per-op device
                # durations for the straggler scores
                xla = XlaProfileCollector(straggler.detector.device)
                with cw.disable_hang_protection():
                    with xla.capture():
                        params, opt, loss_value = run_step(step, params, opt)
                report("profile", ops=len(xla.last_capture),
                       source=xla.last_source,
                       top=sorted(xla.last_capture,
                                  key=lambda k: -sum(xla.last_capture[k]))[:5])
        drain(cw, args.steps - 1)
        with cw.disable_hang_protection():  # teardown does not ping either
            runner.on_train_end()
        return loss_value

    final_loss = train()
    report(
        "done", final_loss=final_loss, step_compiles=step_jit._cache_size(),
        cache_hits=cache_events.hits, cache_misses=cache_events.misses,
        quorum_trips=quorum_trips(), native=native.loaded(),
        snapshot_ring=dict(ckpt.snap_ring_stats),
    )
    ckpt.close()
    print(f"[rank {rank}] done: loss={final_loss}", flush=True)


if __name__ == "__main__":
    main()
