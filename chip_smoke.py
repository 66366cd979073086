#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the full
width of the one model the repository supports: the launcher CLI starts the
quick-start worker (``examples/train_with_launcher.py``), which runs
``make_train_step`` for ``TransformerConfig()`` inside ``inprocess.Wrapper``
with heartbeats, the straggler detector and async checkpoints.  In that one
launcher run the worker takes steps with a falling loss, commits an async save
in ``snapshot`` mode, survives an injected exception and a ping-less stall in
the same process (restored bit-equal from the resident copy), is SIGKILLed,
respawned by the launcher, resumes from the checkpoint on disk with the train
step coming out of the compile cache, and finishes with ``rc=0``.

This script never imports jax: the worker is the only process that opens the
chip.  Without a TPU it fails and says so; ``--cpu-rehearsal`` is the only CPU
form (tiny widths, summary marked ``rehearsal``), which tier-1 runs.

    python3 chip_smoke.py                       # one worker over every chip found
    python3 chip_smoke.py --shape worker-per-chip
    python3 chip_smoke.py --cpu-rehearsal

Stdout is two JSON lines: the summary (versions, widths, stage mode, restore
sources, quorum lane, native libraries, compile cache, per-phase pass/fail),
then, last, the verdict ``{"ok": ..., "device": {"platform", "kind",
"count"}}`` with the device as JAX reported it to the worker.  The exit code
is 0 only if every phase passed.  Timings are information, not records.
"""

import argparse
import dataclasses
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import uuid

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

try:
    from tpu_resiliency.health.tpu import visible_tpu_chips
    from tpu_resiliency.models.transformer import TransformerConfig
    from tpu_resiliency.utils import compile_cache, native
except ImportError as exc:
    sys.exit(f"chip_smoke: needs the checkout it sits in ({exc})")

RUN_TAG_VAR = "CHIP_SMOKE_RUN"
FULL = dict(steps=20, save_every=5, batch=8)
TINY = dict(vocab=512, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=32,
            dtype="bfloat16", steps=20, save_every=5, batch=4)
DECLARED = {k: v for k, v in dataclasses.asdict(TransformerConfig()).items()
            if k != "dtype"}  # the widths the only supported model declares
# before these steps of the first cycle; saves land after steps 4, 9, 14, 19
FAULTS_ONE_WORKER = "exc:7,stall:12,sigkill:17"
FAULTS_PER_CHIP = "sigkill:12@1"


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny widths on the CPU backend; never a chip result")
    p.add_argument("--shape", default="one-worker",
                   choices=["one-worker", "worker-per-chip"],
                   help="one worker driving every chip, or one worker per chip")
    p.add_argument("--rehearse-chips", type=int, default=1, metavar="N",
                   help="with --cpu-rehearsal: N virtual CPU devices per host")
    p.add_argument("--deadline", type=float, default=1100.0,
                   help="overall wall-clock limit, seconds")
    p.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "smoke"),
                   help="logs and the worker's event report land here")
    return p.parse_args(argv)


# -- processes ---------------------------------------------------------------

def tagged_pids(tag):
    """Every live process started by this run: the tag rides the environment
    of the launcher and of everything it spawns, own sessions included."""
    needle = f"{RUN_TAG_VAR}={tag}".encode()
    pids = []
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                if needle in f.read().split(b"\0"):
                    pids.append(int(name))
        except OSError:
            continue
    return pids


def maps_libtpu(pid):
    """Does ``pid`` have the TPU runtime mapped?  None if unreadable."""
    try:
        with open(f"/proc/{pid}/maps") as f:
            return any("libtpu" in line for line in f)
    except OSError:
        return None


def cmdline(pid):
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode(errors="replace").strip()
    except OSError:
        return ""


def kill_all(tag, launcher):
    """Stop everything this run started.  SIGTERM lets the launcher sweep its
    workers' process groups; whatever is left is killed by pid."""
    if launcher.poll() is None:
        launcher.terminate()
        try:
            launcher.wait(timeout=20)
        except subprocess.TimeoutExpired:
            launcher.kill()
    for _ in range(3):
        pids = tagged_pids(tag)
        if not pids:
            return
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        time.sleep(0.5)


# -- the run -----------------------------------------------------------------

def read_events(path):
    events = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    events.append(json.loads(line))
                except ValueError:
                    pass  # a line torn by the SIGKILL
    except OSError:
        pass
    return events


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_launcher(args, shape, nproc, mesh, out, work, tag, deadline_t):
    """Start the launcher CLI, watch it to the end (or the deadline); returns
    ``(rc, holders, left)`` — holders: pid -> cmdline of every process of this
    run seen with the TPU runtime mapped while the job ran; left: what was
    still alive a few seconds after the launcher had gone."""
    size = TINY if args.cpu_rehearsal else FULL
    worker = [
        os.path.join(REPO, "examples", "train_with_launcher.py"),
        "--ckpt-dir", os.path.join(work, "ckpts"),
        "--progress-file", os.path.join(work, "progress"),
        "--report", os.path.join(out, "report.jsonl"),
        "--inject",
        FAULTS_PER_CHIP if shape == "worker-per-chip" else FAULTS_ONE_WORKER,
    ]
    for key, value in size.items():
        worker += [f"--{key.replace('_', '-')}", str(value)]
    if mesh:
        worker += ["--mesh", mesh]
    cmd = [
        sys.executable, "-m", "tpu_resiliency.fault_tolerance.launcher",
        "--nnodes", "1", "--nproc-per-node", str(nproc), "--host-store",
        "--rdzv-endpoint", f"127.0.0.1:{free_port()}",
        "--max-restarts", "2", "--log-dir", os.path.join(out, "logs"),
        "--ft-param", f"progress_iteration_file={os.path.join(work, 'progress')}",
        "--", *worker,
    ]
    env = dict(os.environ)
    env[RUN_TAG_VAR] = tag
    env["TPURX_NATIVE_STORE"] = "1"  # the C++ store server: the fourth binary
    if args.cpu_rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        per_worker = args.rehearse_chips // nproc
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={per_worker}")
        # the tiny step compiles in under a second; let the cache keep it
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    log = open(os.path.join(out, "launcher.log"), "w")
    print(f"chip_smoke: {' '.join(cmd)}", file=sys.stderr, flush=True)
    launcher = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=log,
                                stderr=subprocess.STDOUT)
    holders = {}
    try:
        while launcher.poll() is None:
            if time.monotonic() > deadline_t:
                print("chip_smoke: deadline reached, stopping the run",
                      file=sys.stderr, flush=True)
                return None, holders, {}
            for pid in tagged_pids(tag):
                if pid not in holders and maps_libtpu(pid):
                    holders[pid] = cmdline(pid)
            time.sleep(0.5)
        # helpers in their own sessions notice their rank is gone and leave
        left_t = time.monotonic() + 10.0
        while tagged_pids(tag) and time.monotonic() < left_t:
            time.sleep(0.5)
        left = {pid: cmdline(pid) for pid in tagged_pids(tag)}
        return launcher.returncode, holders, left
    finally:
        kill_all(tag, launcher)
        log.close()


def beat_check():
    """The native liveness beater and its futex tripwire, stood up here: the
    worker's tripwire runs on manual beats (``ping()`` is progress), so this
    is where ``libtpurx-beat.so`` proves it loads, stamps and wakes a waiter
    on this host.  jax-free, like the rest of this script."""
    from tpu_resiliency.ops.quorum import NativeBeater, StampTripwire

    hits = []
    beater = NativeBeater(interval_s=0.001)
    if not beater.start():
        return {"started": False}
    trip = StampTripwire(on_stale=hits.append, budget_ms=50.0,
                         beater=beater).start()
    try:
        time.sleep(0.3)
        healthy = {"started": True, "beats": beater.generation,
                   "false_trips": len(hits),
                   "jitter_p99_us": beater.jitter_p99_us()}
        t0 = time.monotonic()
        beater.freeze()
        while not hits and time.monotonic() - t0 < 2.0:
            time.sleep(0.001)
        return {**healthy, "freeze_tripped": bool(hits),
                "freeze_to_trip_ms": round((time.monotonic() - t0) * 1e3, 1)}
    finally:
        trip.stop()
        beater.stop()


# -- the verdict -------------------------------------------------------------

class Phases:
    def __init__(self):
        self.results = {}

    def check(self, name, ok, detail=""):
        self.results[name] = {"pass": bool(ok), "detail": detail}
        print(f"chip_smoke: [{'pass' if ok else 'FAIL'}] {name}: {detail}",
              file=sys.stderr, flush=True)

    @property
    def ok(self):
        return bool(self.results) and all(r["pass"] for r in self.results.values())


def judge(ph, args, shape, nproc, n_chips, built, beat, rc, holders, events,
          launcher_log):
    """Every phase of item 1, from the worker's report and the launcher log."""
    rehearsal = args.cpu_rehearsal
    want_platform = "cpu" if rehearsal else "tpu"
    by = lambda ev, **kw: [e for e in events if e["ev"] == ev and all(
        e.get(k) == v for k, v in kw.items())]
    summary = {}

    ph.check("launcher_rc", rc == 0, f"rc={rc}")

    devs = by("device")
    dev = devs[0] if devs else {}
    summary["device"] = {"platform": dev.get("platform"),
                         "kind": dev.get("kind"), "count": dev.get("count")}
    summary["versions"] = dev.get("versions")
    ph.check("device", bool(devs) and all(
        d["platform"] == want_platform for d in devs),
        f"platform={dev.get('platform')} kind={dev.get('kind')} "
        f"count={dev.get('count')} local={dev.get('local_count')}")
    if not rehearsal:
        ph.check("chips_seen_without_runtime", n_chips == dev.get("count"),
                 f"host exposes {n_chips} chip(s), JAX reports "
                 f"{dev.get('count')} device(s)")
    if shape == "worker-per-chip":
        ph.check("one_job_of_workers", bool(devs) and all(
            d["count"] == nproc and d["local_count"] == 1
            and d["process_count"] == nproc for d in devs),
            f"device_count={dev.get('count')} local_device_count="
            f"{dev.get('local_count')} processes={dev.get('process_count')}")

    models = by("model")
    model = models[0] if models else {}
    summary["model"] = {k: model.get(k) for k in (
        "widths", "batch", "dtype", "n_params", "state_bytes", "has_master")}
    want_widths = ({k: TINY["seq" if k == "max_seq" else k] for k in DECLARED}
                   if rehearsal else DECLARED)
    ph.check("model_at_width", model.get("widths") == want_widths
             and model.get("dtype") == "bfloat16" and model.get("has_master"),
             f"{model.get('widths')} dtype={model.get('dtype')} "
             f"master={model.get('has_master')} params={model.get('n_params')}")

    losses = [e["loss"] for e in by("step", cycle=0, iteration=0, rank=0)]
    ph.check("steps_loss_falls", len(losses) >= 5 and losses[-1] < losses[0]
             and sum(b < a for a, b in zip(losses, losses[1:]))
             >= 2 * (len(losses) - 1) // 3,
             f"{len(losses)} steps, loss {losses[:1]} -> {losses[-1:]}")

    saves, commits = by("save"), by("commit")
    modes = sorted({e.get("stage_mode") for e in saves})
    summary["stage_mode"] = modes[0] if len(modes) == 1 else modes
    ph.check("async_save_commits", bool(saves) and bool(commits)
             and modes == ["sync" if rehearsal else "snapshot"],
             f"{len(saves)} save(s), {len(commits)} commit(s), mode {modes}, "
             f"call_ms {[e.get('call_ms') for e in saves]}")

    restores = by("restore")
    summary["restores"] = [
        {k: e.get(k) for k in ("cycle", "iteration", "step", "source",
                               "bit_equal", "same_sharding", "restore_s")}
        for e in restores]
    pids0 = {e["pid"] for e in events if e["cycle"] == 0}
    if shape != "worker-per-chip":
        r_exc = by("restore", cycle=0, iteration=1)
        ph.check("inprocess_recovery", len(pids0) == 1 and len(r_exc) == 1
                 and r_exc[0]["step"] == 4 and r_exc[0]["bit_equal"]
                 and r_exc[0]["source"] == "resident"
                 and bool(by("step", cycle=0, iteration=1, step=5)),
                 f"exception before step 7 -> same pid {sorted(pids0)}, "
                 f"restore {r_exc[:1]}")
        budgets = by("quorum_budget", cycle=0)
        budget = budgets[0] if budgets else {}
        r_stall = by("restore", cycle=0, iteration=2)
        enter2 = by("enter", cycle=0, iteration=2)
        lanes = sorted((enter2[0].get("quorum_trips") or {})) if enter2 else []
        # the age that tripped, in the tripwire's own line: the monitor's
        # last age at the re-entry is the stall's only if no tick fell
        # between the wrapper's re-arm and the worker's report
        stale = re.findall(r"quorum tripwire: heartbeat stale by ([0-9.]+)ms",
                           launcher_log)
        trips, age = len(stale), float(stale[0]) if stale else None
        summary["quorum"] = {
            "lane": budget.get("lane"), "pallas": budget.get("pallas"),
            "budget_ms": budget.get("budget_ms"),
            "p99_under_load_ms": budget.get("p99_ms"),
            "devices": budget.get("devices"), "trips": trips,
            "trip_lanes": lanes, "trip_age_ms": age,
        }
        ph.check("quorum_trip", bool(budgets)
                 and budget.get("pallas") == (not rehearsal)
                 and trips == 1 and lanes == ["collective"]
                 and age is not None and age > budget.get("budget_ms", 1e9)
                 and len(r_stall) == 1 and r_stall[0]["step"] == 9
                 and r_stall[0]["bit_equal"],
                 f"budget {budget.get('budget_ms')} ms from p99 "
                 f"{budget.get('p99_ms')} ms under load, pallas="
                 f"{budget.get('pallas')}; stall before step 12: {trips} trip(s) "
                 f"in the whole run, lanes {lanes}, age {age} ms; restore "
                 f"{r_stall[:1]}")

    kill_step, resume_from = (12, 9) if shape == "worker-per-chip" else (17, 14)
    r_disk = by("restore", cycle=1)
    pids1 = {e["pid"] for e in events if e["cycle"] == 1}
    done = by("done", cycle=1)
    ph.check("respawn_resumes_from_disk",
             len(r_disk) == nproc and len(pids1) == nproc
             and not (pids0 & pids1)
             and all(r["step"] == resume_from and r["bit_equal"]
                     and r["source"] == "disk" for r in r_disk)
             and len(done) == nproc,
             f"SIGKILL before step {kill_step} -> new pid(s) {sorted(pids1)}, "
             f"restore {r_disk[:1]}")

    compiles1 = by("compile", cycle=1)
    summary["compile_cache"] = {
        "dir": dev.get("cache_dir"),
        "cold_compile_s": [e["seconds"] for e in by("compile", cycle=0)],
        "respawn_compile_s": [e["seconds"] for e in compiles1],
        "respawn_hits": [e["cache_hits"] for e in compiles1],
        "respawn_misses": [e["cache_misses"] for e in compiles1],
        "ranks": [e["rank"] for e in compiles1],
    }
    want_dir = os.environ.get(compile_cache.ENV_VAR) or compile_cache.DEFAULT_DIR
    ph.check("compile_cache_hit_on_respawn",
             dev.get("cache_dir") == want_dir and len(compiles1) == nproc
             and all(e["cache_misses"] == 0 for e in compiles1)
             # (in a multi-process job JAX lets only process 0 write, and
             # whoever reads the entry reports the hit)
             and (all if nproc == 1 else any)(
                 e["cache_hits"] >= 1 for e in compiles1)
             # ... and restored state never made the step compile again
             and all(e["step_compiles"] <= 1 for e in done + by("enter")),
             f"{summary['compile_cache']}")

    seen = sorted(e["step"] for e in by("step", rank=0))
    segments, ok_mono = {}, True
    for e in by("step", rank=0):
        key = (e["cycle"], e["iteration"])
        ok_mono &= e["step"] == segments.get(key, e["step"] - 1) + 1
        segments[key] = e["step"]
    last_steps = [segments[k] for k in sorted(segments)]
    ph.check("progress_monotone", ok_mono and bool(seen)
             and max(seen) == FULL["steps"] - 1
             and last_steps == sorted(last_steps),
             f"segments end at steps {last_steps}")

    loaded = {**(done[0].get("native", {}) if done else {}),
              **native.loaded()}
    store_native = "hosting native C++ store" in launcher_log
    summary["native"] = {"built": built, "loaded": loaded,
                         "store_server": store_native, "beater": beat}
    ph.check("native_built_and_loaded",
             len(built) == 4 and all(built.values()) and store_native
             and all(loaded.values()) and set(loaded) == {
                 n for n in native.TARGETS if n.endswith(".so")}
             and beat.get("beats", 0) > 100 and beat.get("false_trips") == 0
             and beat.get("freeze_tripped"),
             f"built {built}; loaded (worker: op ring and pending-call "
             f"stamper, here: beater) {loaded}; native store server "
             f"{store_native}; beater {beat}")

    profiles = by("profile", cycle=0)
    prof = profiles[0] if profiles else {}
    summary["profile"] = {k: prof.get(k) for k in ("ops", "source", "top")}
    ph.check("profiled_step_has_device_ops", bool(profiles)
             and prof["ops"] > 0
             and prof["source"] == ("host" if rehearsal else "device"),
             f"{prof.get('ops')} op(s) from {prof.get('source')} lanes, "
             f"top {prof.get('top')}")

    worker_pids = pids0 | pids1
    # the launcher's health gate opens the chip too, but between cycles, when
    # no worker holds it (a clash would fail the gate on libtpu's lock)
    strangers = {pid: cmd for pid, cmd in holders.items()
                 if pid not in worker_pids and "TPURX_DEVICE_OK" not in cmd}
    contention = "libtpu_lockfile" in launcher_log
    summary["chip_holders"] = {"workers": sorted(set(holders) & worker_pids),
                               "others": strangers}
    ph.check("one_process_per_chip", not strangers and not contention
             and (rehearsal or worker_pids <= set(holders)),
             f"TPU runtime mapped by worker pid(s) "
             f"{sorted(set(holders) & worker_pids)}; by others: {strangers}; "
             f"lock contention in the log: {contention}")

    gate = [line.split("device health check ", 1)[1] for line in
            launcher_log.splitlines() if "device health check (cycle" in line]
    summary["health_gate"] = gate
    ph.check("gate_reopens_the_chip_after_the_kill", len(gate) >= 2
             and all(f"on platform {want_platform}" in g for g in gate)
             and not any("(cached)" in g for g in gate[1:]),
             f"{gate}")

    ph.check("restored_into_the_same_shardings", bool(restores)
             and all(r.get("same_sharding") for r in restores),
             f"{[r.get('same_sharding') for r in restores]}")
    if dev.get("count", 1) > 1:
        mem = [m["bytes_in_use"] for m in by("memory", cycle=0)]
        leaf_devs = model.get("leaf_devices")
        summary["sharding"] = {"leaf_devices": leaf_devs, "bytes_in_use": mem}
        ph.check("state_spans_every_device",
                 leaf_devs == [dev["count"]] and bool(mem) and (
                     rehearsal or all(b and b > 0 for m in mem for b in m)),
                 f"every leaf on {leaf_devs} device(s); bytes_in_use {mem}")
        if shape != "worker-per-chip":
            ici = by("ici_replication")
            summary["ici"] = {"quorum_devices": summary["quorum"]["devices"],
                              "replication": [e["result"] for e in ici]}
            ph.check("collectives_cross_the_chips",
                     summary["quorum"]["devices"] == dev["count"]
                     and [e["result"] for e in ici] == ["ici_replication_ok"],
                     f"quorum pmax over {summary['quorum']['devices']} "
                     f"device(s); IciReplication ppermute: "
                     f"{[e['result'] for e in ici]}")
    return summary


def main(argv=None):
    args = parse_args(argv)
    t_start = time.monotonic()
    deadline_t = t_start + args.deadline
    try:
        os.setpgrp()  # our own group: the deadline can take it down whole
    except OSError:
        pass
    signal.signal(signal.SIGALRM, lambda *_: os.killpg(0, signal.SIGKILL))
    signal.alarm(int(args.deadline) + 60)  # backstop behind the soft deadline

    chips = ([f"virtual{i}" for i in range(args.rehearse_chips)]
             if args.cpu_rehearsal else visible_tpu_chips())
    if not args.cpu_rehearsal:
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            sys.exit("chip_smoke: no TPU: JAX_PLATFORMS=cpu holds JAX to the "
                     "CPU backend (--cpu-rehearsal is the only CPU form)")
        if not chips:
            sys.exit("chip_smoke: no TPU: this host exposes no chip (no "
                     "/sys/class/accel/accel*, no /dev/accel*, no "
                     "/dev/vfio/<n> behind a Google PCI function)")
    n_chips = len(chips)
    shape = args.shape
    nproc = n_chips if shape == "worker-per-chip" else 1
    if shape == "worker-per-chip" and n_chips < 2:
        sys.exit("chip_smoke: --shape worker-per-chip needs more than one chip")
    mesh = None
    if n_chips > 1:
        mesh = f"{n_chips // 2}x2" if n_chips % 2 == 0 else f"{n_chips}x1"

    out = args.out
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    work = f"/tmp/chip_smoke.{os.getpid()}"  # checkpoints: too big to bring back
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    tag = uuid.uuid4().hex
    ph = Phases()
    summary = {}
    try:
        # from what git would commit: no build product survives into the run
        for name in os.listdir(native.NATIVE_DIR):
            if name.startswith(tuple(native.TARGETS)):
                os.unlink(os.path.join(native.NATIVE_DIR, name))
        built = native.build_all()
        beat = beat_check()

        rc, holders, left = run_launcher(
            args, shape, nproc, mesh, out, work, tag, deadline_t)
        events = read_events(os.path.join(out, "report.jsonl"))
        with open(os.path.join(out, "launcher.log"), errors="replace") as f:
            launcher_log = f.read()
        summary = judge(ph, args, shape, nproc, n_chips, built, beat, rc,
                        holders, events, launcher_log)
        ph.check("nothing_left_running", not left, f"{left}")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    result = {
        "ok": ph.ok,
        "device": summary.get("device"),
        **({"rehearsal": True} if args.cpu_rehearsal else {}),
        "shape": {"name": shape, "workers": nproc, "mesh": mesh},
        **{k: v for k, v in summary.items() if k != "device"},
        "phases": {k: v["pass"] for k, v in ph.results.items()},
        "seconds": round(time.monotonic() - t_start, 1),
        "claim": None,
    }
    with open(os.path.join(out, "result.json"), "w") as f:
        json.dump({**result, "details": ph.results}, f, indent=1)
    if not ph.ok:
        failed = [k for k, v in ph.results.items() if not v["pass"]]
        sys.exit(f"chip_smoke: FAILED phases: {failed} (see {out})")
    print(json.dumps(result))
    # the verdict the driver reads: these keys and no others
    print(json.dumps({"ok": result["ok"], "device": result["device"]}),
          flush=True)


if __name__ == "__main__":
    main()
