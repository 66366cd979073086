"""Layered restart: in-process ring UNDER the in-job ring.

Reference analog: ``tests/fault_tolerance/unit/test_layered_restart_v1.py``
— the composition contract from SURVEY.md §1: faults the wrapper can absorb
never reach the launcher; faults it cannot (dead process) escalate.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

from tpu_resiliency.utils.env import force_cpu_env

REPO = Path(__file__).resolve().parent.parent
WORKER = str(REPO / "tests" / "workloads" / "layered_worker.py")


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_layered(tmp_path, scenario, timeout=150, extra_env=None):
    env = dict(os.environ)
    force_cpu_env(env)
    env.update(
        {
            "TPURX_REPO": str(REPO),
            "LAYERED_SCENARIO": scenario,
            "TOY_CKPT": str(tmp_path / "progress.txt"),
            "TPURX_FT_ENABLE_DEVICE_HEALTH_CHECK": "0",
            "TPURX_FT_WORKERS_STOP_TIMEOUT": "3.0",
            "TPURX_FT_RDZV_ROUND_TIMEOUT": "30.0",
            "JAX_PLATFORMS": "cpu",
        }
    )
    env.update(extra_env or {})
    proc = subprocess.run(
        [
            sys.executable, "-m", "tpu_resiliency.fault_tolerance.launcher",
            "--nnodes", "1", "--nproc-per-node", "2",
            "--rdzv-endpoint", f"127.0.0.1:{free_port()}",
            "--host-store", "--max-restarts", "3",
            "--monitor-interval", "0.05",
            WORKER,
        ],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=timeout,
    )
    if proc.returncode != 0:
        print("STDOUT:", proc.stdout[-4000:])
        print("STDERR:", proc.stderr[-4000:])
    return proc


def test_inner_fault_absorbed_by_inprocess_ring(tmp_path):
    proc = run_layered(tmp_path, "inner")
    assert proc.returncode == 0
    # the wrapper recovered: both ranks finished at wrapper-iteration 1...
    assert proc.stdout.count("ret=done@1") == 2
    # ...and the LAUNCHER never saw a failure (no new cycle)
    assert "worker failure detected" not in proc.stderr
    assert "cycle=1" not in proc.stdout
    # the nested-restarter protocol surfaced the recovery phases
    assert "[NestedRestarter] name=[InProcess] state=handling_start" in proc.stdout
    assert "[NestedRestarter] name=[InProcess] state=completed" in proc.stdout
    # the abort ladder ran with recorded per-stage outcomes
    blob = proc.stdout + proc.stderr
    assert "abort ladder:" in blob
    assert "fingerprint=released" in blob


def test_inner_fault_with_shrink_mesh_stage_enabled(tmp_path):
    """The opt-in ShrinkMeshStage on the in-process recovery path: with no
    distributed client it releases by clearing caches+backends, recovery
    still completes in-process, and the outcome is recorded — the ladder's
    rung order and gating exercised end to end under the real launcher."""
    proc = run_layered(tmp_path, "inner", extra_env={"TPURX_SHRINK_MESH": "1"})
    assert proc.returncode == 0
    assert proc.stdout.count("ret=done@1") == 2
    assert "worker failure detected" not in proc.stderr
    blob = proc.stdout + proc.stderr
    assert "shrink_mesh=released" in blob


def test_stalled_collective_recovered_through_ladder_with_verdict(tmp_path):
    """The wedged-collective case the ladder absorbs IN-PROCESS: rank 1
    parks ping-less on a 'collective', the quorum tripwire names the stale
    rank, every rank's ladder publishes its dispatch tail, and the
    trace-analyzer verdict cites the in-flight op and the lagging rank
    from the at-abort fingerprints (VERDICT r5 'do this' #5)."""
    proc = run_layered(
        tmp_path, "stall", timeout=240,
        extra_env={
            # host ring stays the distant backstop; quorum owns detection
            "WRAP_SOFT_TIMEOUT": "60", "WRAP_HARD_TIMEOUT": "120",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
    )
    assert proc.returncode == 0
    blob = proc.stdout + proc.stderr
    assert "stalling: parked on a collective" in proc.stdout
    # detection came from the quorum tripwire, not a host timeout
    assert "quorum tripwire: heartbeat stale" in blob
    # both ranks recovered in-process; the launcher never saw a failure
    assert proc.stdout.count("ret=done@1") == 2
    assert "worker failure detected" not in proc.stderr
    # the at-abort fingerprint verdict names the op and the lagging rank
    assert "abort fingerprint verdict" in blob
    assert "unified_allreduce" in blob
    verdict_lines = [
        l for l in blob.splitlines() if "abort fingerprint verdict" in l
    ]
    assert any("culprits=[1]" in l for l in verdict_lines), verdict_lines[:5]


def test_link_degrade_absorbed_below_both_rings(tmp_path):
    """The self-healing collective layer UNDER the layered stack
    (docs/collectives.md): rank 1's primary collective lane is armed to
    stall past its deadline every call (``TPURX_FAULT=coll_stall``), the
    wrapped ``device_max_reduce`` walks retry → re-layout in process, and
    a shrink-only probe trips the Wrapper-installed DegradeToShrink hook
    running the real opt-in ShrinkMeshStage as a TARGETED rung.  Neither
    restart ring fires: both ranks finish at wrapper-iteration 0 and the
    launcher records zero cycles."""
    proc = run_layered(
        tmp_path, "degrade", timeout=240,
        extra_env={
            "LAYERED_STEPS": "8",
            "TPURX_FAULT": "coll_stall",
            "TPURX_FAULT_RANKS": "1",
            "TPURX_COLL_DEADLINE_MS": "500",
            "TPURX_COLL_RETRIES": "1",
            "TPURX_SHRINK_MESH": "1",
        },
    )
    assert proc.returncode == 0
    blob = proc.stdout + proc.stderr
    # absorbed BELOW both rings: no wrapper restart (iteration stays 0),
    # no launcher cycle
    assert proc.stdout.count("ret=done@0") == 2
    assert "worker failure detected" not in proc.stderr
    assert "cycle=1" not in proc.stdout
    # the armed rank walked the ladder: deadline trips and degrades; the
    # healthy rank never degraded
    marks = {}
    for line in proc.stdout.splitlines():
        # worker stdout arrives through the log funnel with an [rN] prefix
        if "colldeg[" in line:
            mark = line[line.index("colldeg["):]
            rank = int(mark.split("[")[1].split("]")[0])
            kv = dict(p.split("=") for p in mark.split()[1:])
            marks[rank] = kv
    assert set(marks) == {0, 1}, blob[-3000:]
    assert int(marks[1]["degrades"]) >= 1, marks
    assert int(marks[1]["timeouts"]) >= 1, marks
    assert int(marks[0]["degrades"]) == 0, marks
    # the re-layout rung engaged on the armed rank's step collective...
    assert "collective degrade: op=device_max_reduce" in blob
    # ...and the shrink probe reached the targeted ShrinkMeshStage through
    # the degrade hook, completing on the fallback lane
    assert "degrade-to-shrink: op=shrink_probe" in blob
    assert "shrink_mesh=released" in blob
    assert "shrink probe -> shrunk" in proc.stdout


def test_outer_fault_escalates_to_launcher(tmp_path):
    proc = run_layered(tmp_path, "outer")
    assert proc.returncode == 0
    # the process death escalated: launcher restarted the group
    assert "worker failure detected" in proc.stderr
    # cycle 1 ran clean to completion on both ranks
    assert proc.stdout.count("cycle=1 ret=done@0") == 2


def test_wedged_device_call_hard_killed_and_ring_recovers(tmp_path):
    """The documented wedged-device contract, exercised END TO END (VERDICT
    r4 'do this' #3 — previously closed only by abort.py's docstring): a
    rank blocks forever inside a real device program (jit'd infinite
    while_loop — stuck in PJRT C++ with the GIL released, exactly how a
    collective with a missing participant presents), its pings and
    pending-call auto-stamps freeze, the exec'd monitor process records
    SOFT_TIMEOUT, the in-process ring's async raise cannot land, the hard
    timeout SIGKILLs the rank, and the launcher's in-job ring
    re-rendezvouses a clean cycle.  Ref: reference
    ``inprocess/monitor_process.py:269-288``, ``nested_restarter.py:36-107``.
    """
    proc = run_layered(
        tmp_path, "wedged", timeout=240,
        extra_env={"WRAP_SOFT_TIMEOUT": "6", "WRAP_HARD_TIMEOUT": "12"},
    )
    assert proc.returncode == 0
    blob = proc.stdout + proc.stderr
    # the wedge engaged, and only the monitor process could break it
    assert "wedging in a device program" in proc.stdout
    assert "killing" in blob, blob[-3000:]  # monitor-process hard-kill fired
    # the launcher ring took over and recovered the job
    assert "worker failure detected" in proc.stderr
    assert proc.stdout.count("cycle=1 ret=done@0") == 2
    # the nested-restarter protocol surfaced the recovery attempt
    assert "[NestedRestarter] name=[InProcess] state=handling_start" in blob
    # the abort ladder still ran on the wedged rank (its monitor THREAD is
    # schedulable even while the main thread is stuck in C) and published
    # the at-abort fingerprint before the hard-kill; the in-flight-op
    # verdict itself is covered by the stall scenario, where a survivor
    # runs the restart path (here rank 0 completed before the escalation)
    assert "abort ladder: fingerprint=released" in blob


def test_abort_ladder_under_lock_order_sanitizer_clean_witness(tmp_path):
    """Soak smoke lane for the runtime lock-order sanitizer: the layered
    restart e2e (inner fault -> full abort ladder -> in-process recovery)
    runs with TPURX_SANITIZE=1.  The sanitizer wraps every lock the wrapper,
    monitor thread, quorum tripwire, and checkpoint machinery create, and
    must observe NO runtime lock-order cycle on the abort-ladder path — a
    cycle would have raised LockOrderViolation and failed the run.  The
    per-process witness files it leaves are the confirm/prune input for
    `tpurx-lint --witness` (see docs/lint.md)."""
    import glob
    import json

    wit_tpl = str(tmp_path / "witness.r%r.p%p.jsonl")
    proc = run_layered(
        tmp_path, "inner",
        extra_env={
            "TPURX_SANITIZE": "1",
            "TPURX_SANITIZE_WITNESS_PATH": wit_tpl,
        },
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    # recovery completed exactly as without the sanitizer
    assert proc.stdout.count("ret=done@1") == 2
    assert "worker failure detected" not in proc.stderr
    assert "abort ladder:" in proc.stdout + proc.stderr

    paths = glob.glob(str(tmp_path / "witness.r*.jsonl"))
    assert paths, "sanitizer produced no witness files"
    edges = 0
    for p in paths:
        for line in open(p):
            rec = json.loads(line)
            assert rec["event"] != "cycle", (
                f"runtime lock-order cycle on the abort path: {rec}")
            if rec["event"] == "edge":
                edges += 1
    assert edges > 0, "sanitizer observed no lock acquisitions at all"
