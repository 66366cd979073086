"""The benchmark's job: the quick-start worker, copied and cut to what every
job loop shares.

Copied from ``examples/train_with_launcher.py`` (PR 21) and cut: the same
composition around the model's train step — ``inprocess.Wrapper`` with the
quorum tripwire on manual beats (budget calibrated under the real step, 250 ms
operator floor), ``FaultToleranceCallback`` heartbeats to the launcher's rank
monitor, the straggler ``Detector`` around the step, ``NestedRestarterCallback``,
``AsyncCheckpointer.async_save`` / ``load_checkpoint`` — called through the
product's public entry points only.  Started by the launcher CLI; the jax-free
parent ``chipbench/run.py`` starts that.

The model is found by the configuration file's ``model_type``: a module of
``chipbench/families/`` gives the sizes, the seed's draw, the state, the
product's step and the plain reference, and this worker names no model.

What is the benchmark's own, and not the product's: the weights and the feed
(``weights.py``, from ``--seed``, on the device), the step and the save as a
job's loop runs them, with their timestamps, the fingerprints, and everything
after the window (read-back from disk, the plain reference, the comparison).
The step is a job's step: the loss is fetched one step late, ``maybe_finalize``
runs every step, every hook the quick-start arms is armed.

The loop itself is a module of ``chipbench/loops/``, found by the name the
traffic file gives (``steady_save``, ``stall_inproc``); this worker hands it
``run``: the job, the readings and the helpers below.

The worker writes ``readings.json`` into ``--out``; ``run.py`` and the
per-layer readers turn it into metrics.  Nothing here prints a result line.
"""

import argparse
import json
import os
import shutil
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

SPAN_NAMES = ("step.dispatch", "loss.fetch", "finalize", "hooks",
              "save.wait_device", "save.call")  # and the loop's own ``SPANS``


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--out", required=True, help="readings and the trace land here")
    p.add_argument("--work", required=True, help="checkpoints of this run")
    p.add_argument("--started-at", type=float, required=True,
                   help="time.time() when run.py started: set-up counts from there")
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny cut on the CPU backend; never a device number")
    return p.parse_args(argv)


class CompileCounter:
    """Counts every program JAX compiled or fetched from the persistent cache
    (one ``backend_compile`` duration event each)."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def main(argv=None):
    args = parse_args(argv)
    from chipbench import loops

    rank = int(os.environ.get("TPURX_RANK", "0"))
    world = int(os.environ.get("TPURX_WORLD_SIZE", "1"))
    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.rehearsal:
        traffic = {**traffic, **traffic.get("cpu_rehearsal_cut", {})}
    loop = loops.load(traffic["loop"])
    if int(os.environ.get("TPURX_CYCLE", "0")) > 0:
        sys.exit("chipbench worker: the launcher respawned the worker; a "
                 "benchmark run is one process from start to end")
    if world > 1:
        # the launcher starts as many workers as the configuration's file
        # says; what one of several needs is not written yet (PERF.md, Open
        # questions): state and feed placed on a mesh over the workers'
        # chips, and rank 0 merging the ranks' readings
        sys.exit(f"chipbench worker: {world} workers, but the state, the feed "
                 "and the readings are one worker's")
    os.makedirs(args.out, exist_ok=True)
    events_path = os.path.join(args.out, "events.jsonl")
    t_start = time.monotonic()

    def report(ev, **fields):
        rec = {"ev": ev, "t": round(time.monotonic() - t_start, 4), **fields}
        print(f"[chipbench worker] {ev} " + " ".join(
            f"{k}={v}" for k, v in fields.items()), flush=True)
        with open(events_path, "a") as f:
            f.write(json.dumps(rec) + "\n")

    from tpu_resiliency.utils import compile_cache

    cache_dir = compile_cache.enable()  # before the first jit

    import jax
    import numpy as np

    from chipbench import correct, families, trace_reduce, weights
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.checkpointing.async_ckpt import resident
    from tpu_resiliency.fault_tolerance import (
        FaultToleranceConfig,
        RankMonitorClient,
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
    from tpu_resiliency.telemetry import get_registry

    compiles = CompileCounter()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    report("device", cache_dir=cache_dir, **device)
    if not args.rehearsal and (
            device["platform"] != "tpu" or device["count"] < args.chips):
        sys.exit(f"chipbench worker: needs {args.chips} TPU chip(s), JAX found "
                 f"{device['count']} x {device['platform']}")

    family, sizes = families.of_file(args.config, rehearsal=args.rehearsal)
    n_first = 3  # steps the reference follows
    annotate = jax.profiler.TraceAnnotation

    step_jit = family.make_step(sizes)
    key = weights.seed_key(args.seed)
    init_state = weights.make_state_fn(family, sizes)
    fingerprint = weights.make_fingerprint_fn()
    leaf_norms, change_norms = weights.make_norm_fns(family, sizes)

    def memory():
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return {"peak": max((s.get("peak_bytes_in_use", 0) for s in stats), default=0),
                "in_use": max((s.get("bytes_in_use", 0) for s in stats), default=0),
                "limit": max((s.get("bytes_limit", 0) for s in stats), default=0)}

    def save_call_hist():
        fam = get_registry().snapshot().get("tpurx_ckpt_save_call_ns", {})
        rows = fam.get("samples", [])
        return {"count": sum(int(r.get("count", 0)) for r in rows),
                "sum_ns": sum(float(r.get("sum", 0.0)) for r in rows)}

    # -- the rings: as the quick-start arms them -----------------------------
    client = RankMonitorClient(FaultToleranceConfig(
        rank_section_timeouts={"inprocess_restart": 120.0},
        skip_section_response=False,
    ))
    client.init_workload_monitoring()
    straggler = StragglerDetectionCallback()
    # The rank monitor learns its heartbeat timeout as 5x the widest gap seen
    # during warm-up.  The warm-up has to hold the first save: with the step
    # out of the compile cache the first steps' gaps are a second or two, and
    # a timeout learned from them is shorter than the first async_save at
    # this state size (14 s, my chip run, PR 23) — the monitor would kill a
    # healthy rank.  So the warm-up reaches two steps past the first save.
    hb_warmup = n_first + 12 + int(traffic["steps_between_warmup_saves"]) + 2
    runner = CallbackRunner([
        FaultToleranceCallback(client, warmup_steps=hb_warmup, update_interval=20),
        straggler,
    ])
    step_fn = straggler.detector.wrap_callables({"train_step": step_jit})[
        "train_step"]
    bridge = NestedRestarterCallback(client)
    ckpt = AsyncCheckpointer(rank=rank, world_size=world)
    quorum_mesh = jax.sharding.Mesh(np.array(devices), ("quorum",))

    R = {  # the readings
        "config": sizes.name, "traffic": traffic["name"], "loop": traffic["loop"],
        "traced_window": loop.TRACED_WINDOW,
        "config_file": args.config, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace,
        "rehearsal": bool(args.rehearsal), "device": device,
        "tokens_per_step": sizes.tokens_per_step, "state_bytes": sizes.state_bytes,
        "steps_per_save": traffic.get("steps_per_save"),
        "saves": [], "episodes": [], "step_ends": [], "loss_mismatches": [],
        "own_seconds": [], "first_steps": {},
    }

    class Job:
        """What outlives one entry of ``train``: the state, the position in
        the feed, and the marks a re-entry needs."""

        def __init__(self):
            self.state = None        # (params, opt), replaced every step
            self.step = 0            # steps taken = index of the next step
            self.pending = None      # (step, loss array) still on the device
            self.losses = {}         # step -> loss as fetched the first time
            self.entries = 0
            self.saved = None        # the save the fault cell restores from
            self.open_episode = None
            self.window_open = None
            self.deadline = None
            self.tracing = False
            self.trace_done = False
            self.after_save = 0      # steps left under the save's protection

    job = Job()
    feed = None

    def fetch_pending():
        if job.pending is None:
            return
        step, loss = job.pending
        job.pending = None
        with annotate("loss.fetch"):
            value = float(loss)
        if not np.isfinite(value):
            raise FloatingPointError(f"loss {value} at step {step}")
        known = job.losses.setdefault(step, value)
        if np.float32(known).tobytes() != np.float32(value).tobytes():
            R["loss_mismatches"].append(
                {"step": step, "first": known, "again": value})

    shm_names = set()

    def note_shm_segments(path):
        """The product leaves a worker's last staged checkpoint in /dev/shm
        when it exits (``StagedTree.close`` raises on the resident copy's
        exported buffers before it unlinks): the names of the segments this
        worker's saves were staged in go to a file, and ``run.py`` unlinks
        those and no others once the worker has gone."""
        published = resident.lookup(path)
        tree = getattr(published, "tree", None)
        new = set(tree.shm_buffers()) - shm_names if tree is not None else set()
        if new:
            shm_names.update(new)
            with open(os.path.join(args.out, "shm_segments.txt"), "a") as f:
                f.write("".join(f"{name}\n" for name in sorted(new)))

    def note_commits(tickets):
        now = time.monotonic()
        for ticket in tickets:
            for save in R["saves"]:
                if save["ticket"] == ticket and save["commit"] is None:
                    save["commit"] = now
                    note_shm_segments(save["path"])
                    report("commit", step=save["step"],
                           commit_s=round(now - save["call"], 3),
                           shm_segments=len(shm_names))

    def run_step(cw):
        """One step as the loop runs it, hooks included."""
        if job.after_save > 0:
            # the dispatches right after async_save block for up to a second
            # at this state size (the drain's D2H is in their way): known-long
            # and ping-less, as the save call itself
            job.after_save -= 1
            with cw.disable_hang_protection():
                return run_step_armed(cw)
        return run_step_armed(cw)

    def run_step_armed(cw):
        step = job.step
        cw.ping()
        with annotate("hooks"):
            runner.on_step_start(step=step)
        with annotate("step.dispatch"):
            params, opt, loss = step_fn(*job.state, feed[step % len(feed)])
        job.state = (params, opt)
        job.step = step + 1
        fetch_pending()  # the step before this one: never between a step
        job.pending = (step, loss)  # and the dispatch of the next
        with annotate("finalize"):
            note_commits(ckpt.maybe_finalize())
        with annotate("hooks"):
            runner.on_step_end(step=step)
        R["step_ends"].append(time.monotonic())
        return loss

    def save(cw, in_window):
        step = job.step - 1
        path = os.path.join(args.work, f"step_{step}")
        params, opt = job.state
        if traffic["wait_device_before_save"]:
            with cw.disable_hang_protection(), annotate("save.wait_device"):
                # The snapshot copy does not alias the slot it "donates" (the
                # old leaves are an unused argument, which jit prunes), so for
                # the length of the call three copies of the state are live.
                # Beside the temporaries of a step still in flight that is
                # more than the chip has (RESOURCE_EXHAUSTED, my chip run, PR
                # 23), so the job lets the device finish the cycle's last
                # step first.  The wait is inside the cycle and counted.
                fetch_pending()
                jax.block_until_ready(job.state)
        t_call = time.monotonic()
        with cw.disable_hang_protection(), annotate("save.call"):
            fp = fingerprint(job.state)
            ticket = ckpt.async_save({"params": params, "opt": opt}, path,
                                     extra_metadata={"iteration": step})
        t_ret = time.monotonic()
        job.after_save = int(traffic["protected_steps_after_save"])
        rec = {"step": step, "path": path, "ticket": ticket, "call": t_call,
               "ret": t_ret, "commit": None, "in_window": in_window,
               "stage_mode": ckpt.last_stage_mode, "fp": fp}
        R["saves"].append(rec)
        report("save", step=step, call_ms=round((t_ret - t_call) * 1e3, 2),
               stage_mode=ckpt.last_stage_mode, in_window=in_window)
        return rec

    def wait_commits(cw):
        with cw.disable_hang_protection():
            note_commits(ckpt.maybe_finalize(blocking=True))

    def trace_start(cw):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        shutil.rmtree(os.path.join(args.out, "trace"), ignore_errors=True)
        with cw.disable_hang_protection():  # ping-less for a second or more
            jax.profiler.start_trace(os.path.join(args.out, "trace"),
                                     profiler_options=opts)
        job.tracing = True

    def trace_stop(cw):
        if job.tracing:
            with cw.disable_hang_protection():  # collecting takes seconds
                fetch_pending()
                jax.block_until_ready(job.state)
                jax.profiler.stop_trace()
            job.tracing = False
            job.trace_done = True

    # -- set-up: the first entry --------------------------------------------
    def setup(cw):
        nonlocal feed
        with cw.disable_hang_protection():  # compiles; ping-less
            feed = weights.make_feed(sizes, key)
            job.state = init_state(key)
            jax.block_until_ready(job.state)
            report("model", n_params=sizes.n_params, state_bytes=sizes.state_bytes,
                   rows=sizes.rows, seq=sizes.seq,
                   dtypes=",".join(sorted({str(leaf.dtype) for leaf in
                                           jax.tree_util.tree_leaves(job.state)})))
            # the first steps, through the window's own call and feed; the
            # plain reference follows them after the window
            mu_norms = None
            for i in range(n_first):
                run_step(cw)
                if i == 0:
                    mu_norms = leaf_norms(family.first_moment(job.state))
            fetch_pending()
            R["first_steps"] = {
                "loss": [job.losses[i] for i in range(n_first)],
                "grad_norm": (np.asarray(mu_norms, np.float64)
                              / (1.0 - weights.ADAM_B1)).tolist(),
                "change_norm": np.asarray(
                    change_norms(family.master(job.state), key), np.float64).tolist(),
            }
            report("compiled", compiles=compiles.count,
                   step_cache=step_jit._cache_size())
            # The tripwire recomputes its budget once, from its first 256
            # ticks, and counts the ticks of protected phases among them
            # (the budget is infinite there, so every age passes for healthy).
            # Ages of a ping-less compile put that budget past the packed-age
            # cap: a tripwire that can never fire.  Inside a protected phase
            # the exit puts the budget back, outside it stays.  When the 256th
            # tick comes depends on the load, so it is made to come here.
            monitor = cw.quorum.monitor
            t0 = time.monotonic()
            while (not getattr(monitor, "_recal_done", True)
                   and time.monotonic() - t0 < 20.0):
                time.sleep(0.05)
            R["recalibration_wait_s"] = time.monotonic() - t0
        # the tripwire's budget from healthy tick ages under the real step
        budget = cw.calibrate_quorum(lambda: run_step(cw), n_ticks=12)
        R["quorum"] = {"budget_ms": budget,
                       "p99_ms": monitor.last_calibration_p99_ms,
                       "pallas": bool(monitor.use_pallas)}
        report("quorum_budget", **R["quorum"])
        with cw.disable_hang_protection():
            # bare steps: the jitted step alone, no hook, nothing fetched
            fetch_pending()
            params, opt = jax.block_until_ready(job.state)
            n_bare = int(traffic["bare_steps"])
            t0 = time.monotonic()
            for i in range(n_bare):
                params, opt, loss = step_jit(params, opt,
                                             feed[(job.step + i) % len(feed)])
            jax.block_until_ready((params, opt, loss))
            R["bare_step_s"] = (time.monotonic() - t0) / n_bare
            job.state, job.step = (params, opt), job.step + n_bare
            R["memory_after_bare"] = memory()
            report("bare", step_ms=round(R["bare_step_s"] * 1e3, 3),
                   **R["memory_after_bare"])

    def open_window(now):
        job.window_open = now
        job.deadline = now + args.seconds
        R["window_open"] = now
        R["setup_s"] = time.time() - args.started_at
        R["compiles_at_open"] = compiles.count
        R["save_call_hist_at_open"] = save_call_hist()
        report("window_open", setup_s=round(R["setup_s"], 3))

    def close_window():
        fetch_pending()
        jax.block_until_ready(job.state)
        R["window_close"] = time.monotonic()
        R["compiles_at_close"] = compiles.count
        R["save_call_hist_at_close"] = save_call_hist()
        R["memory_after_window"] = memory()
        ring = getattr(ckpt, "_snap_ring", None)
        if ring is not None:  # live slots of the snapshot ring, in bytes
            R["snapshot_ring_bytes"] = sum(
                int(leaf.nbytes) for slot in ring for leaf in slot["leaves"])
        R["snap_ring_stats"] = dict(ckpt.snap_ring_stats)
        report("window_close", seconds=round(R["window_close"] - job.window_open, 3),
               compiles_in_window=R["compiles_at_close"] - R["compiles_at_open"],
               **R["memory_after_window"])

    # -- after the window -------------------------------------------------------
    def read_back_from_disk(last):
        """The last committed save, from disk, against the fingerprint taken
        when it was saved.  The live state is dropped first."""
        template = {"params": job.state[0], "opt": job.state[1]}
        for leaf in jax.tree_util.tree_leaves(template):
            leaf.delete()
        job.state = None
        stats = {}
        t0 = time.monotonic()
        back = load_checkpoint(last["path"], template, stats=stats, resident=False)
        jax.block_until_ready(back)
        seconds = time.monotonic() - t0
        got = np.asarray(fingerprint((back["params"], back["opt"]))).tolist()
        want = np.asarray(last["fp"]).tolist()
        for leaf in jax.tree_util.tree_leaves(back):
            leaf.delete()
        R["read_back"] = {
            "step": last["step"], "bit_equal": got == want, "seconds": seconds,
            "bytes_read": int(stats.get("bytes_read", 0)),
            "bytes_shm": int(stats.get("bytes_shm", 0))}
        report("read_back", **R["read_back"])

    def compare_with_reference():
        """The plain reference follows the first three steps; run after the
        program's state is freed, so the peak stays the program's."""
        t0 = time.monotonic()
        start = weights.make_reference_start_fn(family, sizes)(key)
        ref = family.reference_first_steps(start, feed, sizes, n_steps=n_first)
        found = correct.gaps(R["first_steps"], ref)
        limits = correct.load_limits(sizes.name, rehearsal=args.rehearsal)
        R["reference"] = {"numbers": ref, "gaps": found, "limits": limits,
                          "within": correct.within(found, limits),
                          "seconds": time.monotonic() - t0}
        for name, limit in limits.items():
            report("compared", number=name, value=found[name], limit=limit,
                   ok=found[name] <= limit)

    @Wrapper(
        group="chipbench",
        rank_assignment=ShiftRanks(),
        initialize=bridge.on_initialize,
        abort=bridge.on_abort,
        finalize=bridge.on_finalize,
        health_check=DeviceProbeHealthCheck(timeout=30.0),
        quorum_mesh=quorum_mesh,
        quorum_budget_ms=1000.0,       # provisional, until calibrated
        quorum_min_budget_ms=250.0,    # the quick-start's operator floor
        quorum_interval=0.02,
        quorum_auto_beat_interval=None,
        quorum_calibrate=False,
        max_iterations=200,
    )
    def train(call_wrapper=None):
        try:
            return train_body(call_wrapper)
        except Exception:
            # a fault of the benchmark's own must end the run, not start the
            # inner ring's restart loop
            import traceback

            traceback.print_exc()
            abort("worker_bug", 3)

    def abort(ev, code):
        """End the run here: the inner ring must not start a restart loop."""
        report(ev, entries=job.entries)
        sys.stdout.flush()
        os._exit(code)

    run = types.SimpleNamespace(
        args=args, traffic=traffic, sizes=sizes, R=R, job=job, jax=jax, np=np,
        ckpt=ckpt, runner=runner, step_jit=step_jit, fingerprint=fingerprint,
        load_checkpoint=load_checkpoint, annotate=annotate, report=report,
        abort=abort, fetch_pending=fetch_pending, run_step=run_step, save=save,
        wait_commits=wait_commits, trace_start=trace_start,
        trace_stop=trace_stop, open_window=open_window,
        close_window=close_window)

    def train_body(cw):
        job.entries += 1
        if job.entries == 1:
            setup(cw)
            runner.on_train_start(step=job.step)
        loop.enter(run, cw)
        return "done"

    outcome = train()
    report("loop_done", outcome=str(outcome), entries=job.entries)
    runner.on_train_end()

    last = max((s for s in R["saves"] if s["commit"] is not None),
               key=lambda s: s["step"], default=None)
    ckpt.close()  # drops the ring: the read-back needs the room
    if last is not None:
        read_back_from_disk(last)
    job.state = None
    if job.trace_done:
        t0 = time.monotonic()
        R["trace"] = trace_reduce.load_xplane(
            trace_reduce.find_xplane(os.path.join(args.out, "trace")),
            SPAN_NAMES + tuple(loop.SPANS))
        R["trace_parse_s"] = time.monotonic() - t0
        shutil.rmtree(os.path.join(args.out, "trace"), ignore_errors=True)
    compare_with_reference()
    for save_rec in R["saves"]:
        save_rec.pop("fp", None)
    R["losses"] = {str(k): v for k, v in sorted(job.losses.items())[:8]}
    with open(os.path.join(args.out, "readings.json"), "w") as f:
        json.dump(R, f)
    report("readings_written")
    shutil.rmtree(args.work, ignore_errors=True)


if __name__ == "__main__":
    main()
