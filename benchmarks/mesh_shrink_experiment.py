"""Measured in-process mesh-shrink: can jax.distributed re-init at N-1?

The open research item behind the abort ladder's ``ShrinkMeshStage``
(SURVEY §7(a), VERDICT r5 'do this' #4): the reference recovers a wedged
collective *in the process* by aborting NCCL communicators; the JAX analog
would be tearing down the ``jax.distributed`` client and re-initializing
at the surviving world size without a respawn.  Whether that works is a
per-JAX-version property of the runtime, not something prose can settle —
so this script MEASURES it:

1. spawn N worker processes, ``jax.distributed.initialize`` at N
   (coordinator on worker 0), prove a cross-process collective;
2. SIGKILL the highest worker (never the coordinator);
3. survivors attempt the in-process shrink, each step timed and deadlined
   exactly like the ladder stage: ``jax.distributed.shutdown()`` →
   ``jax.clear_caches()`` (+ ``clear_backends`` where the version has it) →
   ``jax.distributed.initialize`` at N-1 on a FRESH coordinator port →
   prove a collective at the new world size.

Output: one JSON line per run —
``{"metric": "mesh_shrink", "jax_version": ..., "phases": {...},
"shrink_ok": bool, "verdict": "..."}`` — the per-JAX-version row for the
result matrix in ``docs/inprocess.md``.  A hang in any step is bounded by
``--deadline`` (a wedged runtime blocking ``shutdown()`` in C++ is itself a
finding: it is why the ladder stage carries a deadline and falls through
to the monitor-kill backstop).

Run:    JAX_PLATFORMS=cpu python benchmarks/mesh_shrink_experiment.py
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpu_resiliency.utils.env import force_cpu_env  # noqa: E402

WORKER = r"""
import json, os, sys, threading, time

sys.path.insert(0, os.environ["TPURX_REPO"])

N = int(os.environ["MS_N"])
PID = int(os.environ["MS_PID"])
COORD = os.environ["MS_COORD"]
COORD2 = os.environ["MS_COORD2"]
FLAG_DIR = os.environ["MS_FLAGS"]
DEADLINE = float(os.environ.get("MS_DEADLINE", "30"))


def emit(phase, ok, ms, detail=""):
    print(json.dumps({"pid": PID, "phase": phase, "ok": ok,
                      "ms": round(ms, 1), "detail": str(detail)[:300]}),
          flush=True)


def timed(phase, fn):
    '''Run fn under the stage-style deadline; a hang records timed_out.'''
    box = {}

    def body():
        try:
            box["ret"] = fn()
        except BaseException as exc:
            box["exc"] = exc

    t0 = time.monotonic()
    th = threading.Thread(target=body, daemon=True)
    th.start()
    th.join(timeout=DEADLINE)
    ms = (time.monotonic() - t0) * 1e3
    if th.is_alive():
        emit(phase, False, ms, f"timed_out at {DEADLINE}s deadline")
        return False, None
    if "exc" in box:
        emit(phase, False, ms, repr(box["exc"]))
        return False, None
    emit(phase, True, ms, box.get("ret", ""))
    return True, box.get("ret")


def wait_flag(name, timeout=120.0):
    path = os.path.join(FLAG_DIR, name)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            return True
        time.sleep(0.05)
    return False


def set_flag(name):
    open(os.path.join(FLAG_DIR, name), "w").close()


import jax


def init_at(coord, n, pid):
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=n, process_id=pid)
    return f"procs={jax.process_count()}"


def prove_coordination(n, tag):
    '''Cross-process proof via the coordination service (works on every
    backend; the thing the shrink must re-establish).'''
    from jax._src import distributed

    client = distributed.global_state.client
    client.key_value_set(f"proof/{tag}/{PID}", str(PID))
    client.wait_at_barrier(f"barrier_{tag}", 10_000)
    for p in range(n):
        got = client.blocking_key_value_get(f"proof/{tag}/{p}", 5_000)
        assert got == str(p), f"kv mismatch for {p}: {got!r}"
    return f"kv_barrier_ok n={n}"


def prove_collective(n, tag):
    '''Device-collective proof — records the backend's own capability
    (CPU multiprocess collectives are unimplemented; TPU/GPU run them).'''
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    val = multihost_utils.process_allgather(jnp.float32(PID + 1))
    return f"allgather_sum={float(val.sum())}"


ok, _ = timed("init_n", lambda: init_at(COORD, N, PID))
if ok:
    ok, _ = timed("coordination_n", lambda: prove_coordination(N, "n"))
    timed("collective_n", lambda: prove_collective(N, "n"))  # informational
set_flag(f"ready_{PID}")
if PID == N - 1:
    if os.environ.get("MS_VICTIM") == "clean":
        # clean leave: the victim detaches properly — isolates "can this
        # jax re-init in-process at all" from "does a dead peer wedge it"
        timed("victim_shutdown", lambda: jax.distributed.shutdown())
        emit("victim_left", True, 0.0, "clean shutdown")
        sys.exit(0)
    time.sleep(3600)  # park until the supervisor SIGKILLs us
if not wait_flag("shrink"):
    emit("wait_shrink", False, 0.0, "no shrink flag")
    sys.exit(1)

# --- in-process shrink attempt (the ShrinkMeshStage body, measured) ---
ok, _ = timed("shutdown", lambda: jax.distributed.shutdown())
shrunk = False
if ok:
    def clear():
        jax.clear_caches()
        cleared = "caches"
        try:
            import jax.extend.backend as jeb  # lazy submodule: import, not attr

            jeb.clear_backends()
            cleared += "+backends"
        except Exception as exc:
            cleared += f" (clear_backends unavailable: {type(exc).__name__})"
        from jax._src import xla_bridge as xb

        cleared += f" initialized={xb.backends_are_initialized()}"
        return cleared

    ok, _ = timed("clear", clear)
    # survivors keep their ORIGINAL process ids sans the victim, compacted
    new_pid = PID
    ok2, _ = timed("reinit_n1", lambda: init_at(COORD2, N - 1, new_pid))
    if ok2:
        shrunk, _ = timed(
            "coordination_n1", lambda: prove_coordination(N - 1, "n1")
        )
        timed("collective_n1", lambda: prove_collective(N - 1, "n1"))
emit("shrink_result", bool(shrunk), 0.0,
     "in-process re-init at N-1 succeeded" if shrunk else
     "in-process re-init at N-1 failed")
sys.exit(0 if shrunk else 3)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_experiment(n: int, deadline: float, budget: float,
                   victim_mode: str = "kill") -> dict:
    import jax

    flags = tempfile.mkdtemp(prefix="tpurx-meshshrink-")
    coord = f"127.0.0.1:{_free_port()}"
    coord2 = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    force_cpu_env(env)
    env.update({
        "TPURX_REPO": REPO,
        "MS_N": str(n),
        "MS_COORD": coord,
        "MS_COORD2": coord2,
        "MS_FLAGS": flags,
        "MS_DEADLINE": str(deadline),
        "MS_VICTIM": victim_mode,
        "JAX_PLATFORMS": "cpu",
    })
    workers = []
    for pid in range(n):
        wenv = dict(env)
        wenv["MS_PID"] = str(pid)
        workers.append(subprocess.Popen(
            [sys.executable, "-u", "-c", WORKER], env=wenv,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            start_new_session=True,
        ))

    outputs = {i: [] for i in range(n)}

    def drain(i, proc):
        for line in proc.stdout:
            outputs[i].append(line)

    readers = [threading.Thread(target=drain, args=(i, p), daemon=True)
               for i, p in enumerate(workers)]
    for r in readers:
        r.start()

    t0 = time.monotonic()
    # wait for every worker's ready flag, then kill the victim
    while time.monotonic() - t0 < budget:
        if all(os.path.exists(os.path.join(flags, f"ready_{i}"))
               for i in range(n)):
            break
        if any(p.poll() is not None for p in workers[:-1]):
            break
        time.sleep(0.1)
    victim = workers[-1]
    if victim_mode == "kill":
        try:
            os.killpg(victim.pid, signal.SIGKILL)
        except OSError:
            victim.kill()
    else:
        try:  # clean mode: the victim shuts itself down and exits
            victim.wait(timeout=max(1.0, deadline + 10.0))
        except subprocess.TimeoutExpired:
            os.killpg(victim.pid, signal.SIGKILL)
    open(os.path.join(flags, "shrink"), "w").close()

    deadline_t = t0 + budget
    for i, p in enumerate(workers[:-1]):
        try:
            p.wait(timeout=max(1.0, deadline_t - time.monotonic()))
        except subprocess.TimeoutExpired:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except OSError:
                p.kill()
    victim.wait(timeout=10)
    for r in readers:
        r.join(timeout=5)

    phases: dict = {}
    for i in range(n):
        for raw in outputs[i]:
            raw = raw.strip()
            if not raw.startswith("{"):
                continue
            try:
                ev = json.loads(raw)
            except json.JSONDecodeError:
                continue
            key = ev["phase"]
            cur = phases.setdefault(key, {"ok": True, "ms": [], "detail": ""})
            cur["ok"] = cur["ok"] and bool(ev["ok"])
            cur["ms"].append(ev["ms"])
            if not ev["ok"] and not cur["detail"]:
                cur["detail"] = ev.get("detail", "")
    for v in phases.values():
        v["ms"] = round(max(v["ms"]), 1) if v["ms"] else None

    survivors_rc = [p.returncode for p in workers[:-1]]
    shrink_ok = bool(phases.get("shrink_result", {}).get("ok")) and all(
        rc == 0 for rc in survivors_rc
    )
    if shrink_ok:
        verdict = (
            f"in-process shrink WORKS on jax {jax.__version__} "
            f"({victim_mode} victim): survivors re-initialized at N-1 and "
            "re-established cross-process coordination without a respawn"
        )
    else:
        blocking = next(
            (f"{k}: {v['detail']}" for k, v in phases.items()
             if not v["ok"] and v["detail"]),
            "no failing phase captured",
        )
        verdict = (
            f"in-process shrink FAILS on jax {jax.__version__} "
            f"({victim_mode} victim) — {blocking}; ShrinkMeshStage must keep "
            "its deadline + monitor-kill fallback"
        )
    return {
        "metric": "mesh_shrink",
        "jax_version": jax.__version__,
        "n": n,
        "victim_mode": victim_mode,
        "phases": phases,
        "survivor_rcs": survivors_rc,
        "shrink_ok": shrink_ok,
        "verdict": verdict,
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--n", type=int, default=3,
                   help="initial world size (victim = highest pid)")
    p.add_argument("--deadline", type=float, default=30.0,
                   help="per-step deadline inside each worker (stage analog)")
    p.add_argument("--budget", type=float, default=240.0,
                   help="whole-experiment wall budget")
    p.add_argument("--victim", choices=("kill", "clean", "both"),
                   default="both",
                   help="SIGKILL the victim (failure reality), let it leave "
                        "cleanly (version capability), or measure both")
    args = p.parse_args()
    modes = ["kill", "clean"] if args.victim == "both" else [args.victim]
    results = [
        run_experiment(args.n, args.deadline, args.budget, m) for m in modes
    ]
    for r in results:
        print(json.dumps(r))
    sys.exit(0 if all(r["shrink_ok"] for r in results) else 3)


if __name__ == "__main__":
    main()
