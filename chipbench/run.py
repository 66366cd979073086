#!/usr/bin/env python3
"""The benchmark's command: one cell, one run, one result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Never imports jax: the worker is the only process that opens the chip.  A
workload of ``BENCHMARK.json`` names a configuration file and a traffic file;
the configuration's file says how many workers the launcher starts, the
traffic file names its job loop (a module of ``chipbench/loops/``) and the
launcher's settings; this parent starts the launcher CLI (``--host-store``,
the path users run) with ``chipbench/worker.py`` as the worker, waits for it,
reads the readings the worker left in the run's directory, lets each metric's
reader turn them into its number, and prints the contract's object as the
last line of stdout.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the same
window with the profiler over one whole cycle (or episode) and prints the
per-layer metrics and the ``breakdown``.  Earlier lines say what was compared
against which limit.  Without a TPU it fails and prints no result;
``--cpu-rehearsal`` (the self-tests' only CPU form: tiny cut, no device
number, ``--trace 0`` only) says so in its line.
"""

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import uuid

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "worker.py")  # the self-tests put a broken one here
RUN_TAG_VAR = "CHIPBENCH_RUN"


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="self-tests only: tiny cut on the CPU backend")
    p.add_argument("--deadline", type=float, default=1150.0,
                   help="overall wall-clock limit, seconds")
    return p.parse_args(argv)


def fail(message, code=1):
    print(f"chipbench: {message}", file=sys.stderr, flush=True)
    sys.exit(code)


def tagged_pids(tag):
    """Every live process this run started: the tag rides the environment of
    the launcher and of everything it spawns, own sessions included."""
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


def stop_all(tag, launcher):
    """Stop everything this run started and wait until it has gone."""
    if launcher.poll() is None:
        launcher.terminate()
        try:
            launcher.wait(timeout=20)
        except subprocess.TimeoutExpired:
            launcher.kill()
            launcher.wait(timeout=20)
    for _ in range(20):
        pids = tagged_pids(tag)
        if not pids:
            return
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        time.sleep(0.25)


SHM_DIR = "/dev/shm"


def drop_recorded_shm(out):
    """Unlink the shared-memory segments the worker recorded as its own.  A
    worker leaves its last staged checkpoint (a state's worth of /dev/shm)
    when it exits: ``StagedTree.close`` raises on the resident copy's exported
    buffers before it unlinks, and a dozen runs of a cell on one machine
    would fill its memory (my chip run, PR 23: the seventh run met the
    machine's 40 GiB).  Only the names in the run's ``shm_segments.txt``,
    which the worker took from its own checkpointer's staged trees."""
    try:
        with open(os.path.join(out, "shm_segments.txt")) as f:
            names = set(f.read().split())
    except OSError:
        return 0
    dropped = 0
    for name in names:
        if re.fullmatch(r"[A-Za-z0-9_.\-]+", name):
            try:
                os.unlink(os.path.join(SHM_DIR, name))
                dropped += 1
            except OSError:
                pass
    return dropped


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def load_cell(workload):
    """The workload's entry, its configuration's file and its traffic file."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        fail(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})", 2)
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    return bench, cell, os.path.join(ROOT, config["file"]), traffic


def read_json(path):
    with open(path) as f:
        return json.load(f)


def metrics_of(bench, cell, trace, readings):
    """The cell's metrics of this kind of run, by the readers their files name."""
    from chipbench.readers import read_metric

    kind, kind_dir = (("per_layer", "layer_metrics") if trace
                      else ("end_to_end", "end_to_end"))
    out = {}
    for metric in bench[kind]:
        if "workloads" in metric and cell["name"] not in metric["workloads"]:
            continue
        value = read_metric(kind_dir, metric["name"], readings)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def judge(readings):
    """``correct``, ``attempted``, ``failed`` and the reasons, from the
    readings: every condition is checked outside the window.  What an
    operation of the window is, the job loop says (``tally``)."""
    from chipbench import loops

    reasons = []
    ref = readings.get("reference") or {}
    if not ref.get("within"):
        reasons.append("the first steps differ from the plain reference")
    in_window = readings.get("compiles_at_close", 0) - readings.get("compiles_at_open", 0)
    if in_window or "compiles_at_close" not in readings:
        reasons.append(f"{in_window} compilation(s) inside the window")
    if readings.get("loss_mismatches"):
        reasons.append(f"{len(readings['loss_mismatches'])} step(s) whose loss "
                       "differs from the no-fault trajectory")
    back = readings.get("read_back") or {}
    failed = 0 if back.get("bit_equal") else 1
    if failed:
        reasons.append("the last save, read back from disk, is not bit-equal")
    attempted, failed_ops, loop_reasons = loops.load(readings["loop"]).tally(readings)
    if not attempted:
        reasons.append("no whole cycle or episode in the window")
    return not (reasons + loop_reasons), attempted, failed + failed_ops, reasons + loop_reasons


def main(argv=None):
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "tpu_resiliency")):
        fail("needs the checkout it sits in: no tpu_resiliency/ beside chipbench/", 2)
    sys.path.insert(0, ROOT)
    bench, cell, config_file, traffic_file = load_cell(args.workload)
    if args.cpu_rehearsal:
        if args.trace:
            fail("--cpu-rehearsal gives no device metric: a traced run needs a TPU", 3)
    elif os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        fail("no TPU: JAX_PLATFORMS=cpu holds JAX to the CPU backend", 3)

    try:
        os.setpgrp()  # our own group: the backstop can take it down whole
    except OSError:
        pass
    signal.signal(signal.SIGALRM, lambda *_: os.killpg(0, signal.SIGKILL))
    signal.alarm(int(args.deadline) + 60)

    run_name = f"{args.workload}.{args.seed}.t{args.trace}"
    out = os.path.join(HERE, "out", run_name)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    work = tempfile.mkdtemp(prefix="chipbench-ckpt-")  # checkpoints: GBs
    tag = uuid.uuid4().hex

    ft_params = read_json(traffic_file).get("launcher_ft_params", {})
    worker = [
        WORKER, "--config", config_file,
        "--traffic", traffic_file, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", str(args.trace),
        "--chips", str(cell["chips"]), "--out", out, "--work", work,
        "--started-at", repr(T0),
    ]
    if args.cpu_rehearsal:
        worker.append("--rehearsal")
    cmd = [
        sys.executable, "-m", "tpu_resiliency.fault_tolerance.launcher",
        "--nnodes", "1", "--host-store",
        "--nproc-per-node", str(read_json(config_file).get("workers", 1)),
        "--rdzv-endpoint", f"127.0.0.1:{free_port()}",
        "--max-restarts", "1",  # 0 would mean no limit; the worker refuses a respawn
        "--log-dir", os.path.join(out, "logs"),
    ]
    for key, value in ft_params.items():  # launcher settings are the cell's data too
        cmd += ["--ft-param", f"{key}={value}"]
    cmd += ["--", *worker]
    env = dict(os.environ)
    env[RUN_TAG_VAR] = tag
    # one fixed cache inside the checkout unless the machine names another;
    # every program goes in, the small ones too, so a second run compiles nothing
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env.setdefault("TPURX_FLIGHT_DIR", out)  # black boxes beside the run's logs
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if args.cpu_rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    print(f"chipbench: {' '.join(cmd)}", file=sys.stderr, flush=True)
    rc = None
    with open(os.path.join(out, "launcher.log"), "w") as log:
        launcher = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                                    stderr=subprocess.STDOUT)
        try:
            rc = launcher.wait(timeout=args.deadline)
        except subprocess.TimeoutExpired:
            print("chipbench: deadline reached, stopping the run",
                  file=sys.stderr, flush=True)
        finally:
            stop_all(tag, launcher)
            shutil.rmtree(work, ignore_errors=True)
            dropped = drop_recorded_shm(out)
            print(f"chipbench: unlinked {dropped} shared-memory segment(s) the "
                  "worker recorded as its own", file=sys.stderr, flush=True)
    signal.alarm(0)

    readings_path = os.path.join(out, "readings.json")
    if rc != 0 or not os.path.exists(readings_path):
        try:
            with open(os.path.join(out, "launcher.log"), errors="replace") as f:
                sys.stderr.write(f.read()[-6000:])
        except OSError:
            pass
        fail(f"the launcher ended with rc={rc} and "
             f"{'no ' if not os.path.exists(readings_path) else ''}readings "
             f"(see {out})")
    with open(readings_path) as f:
        readings = json.load(f)
    readings["config_file"] = config_file

    dev = readings["device"]
    if not args.cpu_rehearsal and (
            dev["platform"] != "tpu" or dev["count"] < cell["chips"]):
        fail(f"needs {cell['chips']} TPU chip(s), JAX found {dev}", 3)

    correct, attempted, failed, reasons = judge(readings)
    ref = readings.get("reference") or {}
    for name, limit in (ref.get("limits") or {}).items():
        print(f"compared {name}: {ref['gaps'][name]!r} (limit {limit!r})")
    print(f"compared compilations inside the window: "
          f"{readings.get('compiles_at_close', 0) - readings.get('compiles_at_open', 0)}"
          " (limit 0)")
    print(f"compared loss mismatches against the no-fault trajectory: "
          f"{len(readings.get('loss_mismatches', []))} (limit 0)")
    print(f"compared read-back from disk bit-equal: "
          f"{(readings.get('read_back') or {}).get('bit_equal')} (wanted True)")
    print(f"reference: {ref.get('seconds')!r} s after the window; "
          f"own seconds per episode outside product calls: "
          f"{readings.get('own_seconds')}")
    for reason in reasons:
        print(f"not correct: {reason}")

    device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
              "memory_peak_bytes": (readings.get("memory_after_window") or {}).get("peak", 0)}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.cpu_rehearsal:
        # a CPU run proves the flow and the counts, never a device number
        result.update(metrics={}, device=device, rehearsal=True)
    else:
        result["metrics"] = metrics_of(bench, cell, args.trace, readings)
        if args.trace:
            from chipbench.readers import trace as trace_readers

            found = trace_readers.busy_and_window_s(readings)
            if not found or found[0] <= 0:
                fail("the traced run shows no operation on the device")
            device["busy_s"], device["window_s"] = found
            result["breakdown"] = trace_readers.breakdown(readings)
        result["device"] = device
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
