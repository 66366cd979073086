"""Workload-control exclude flow, orbax interop, init_distributed env logic."""

import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def test_workload_control_exclude_node(tmp_path):
    """A worker asks the launcher to exclude its node (reference
    run_workload_ctrl_test_excl_node.sh): the agent must leave the job."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    worker = tmp_path / "excl_worker.py"
    worker.write_text(
        "import os, sys, time\n"
        f"sys.path.insert(0, {str(REPO)!r})\n"
        "from tpu_resiliency.fault_tolerance import RankMonitorClient\n"
        "from tpu_resiliency.fault_tolerance.data import WorkloadAction\n"
        "c = RankMonitorClient(); c.init_workload_monitoring()\n"
        "c.send_heartbeat()\n"
        "c.send_workload_control_request(WorkloadAction.ExcludeThisNode, 'bad chip')\n"
        "time.sleep(30)\n"  # wait to be stopped by the launcher
    )
    env = dict(os.environ)
    env.update({
        "TPURX_FT_ENABLE_DEVICE_HEALTH_CHECK": "0",
        "TPURX_FT_WORKERS_STOP_TIMEOUT": "2.0",
        "TPURX_FT_RDZV_ROUND_TIMEOUT": "15.0",
    })
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_resiliency.fault_tolerance.launcher",
         "--nnodes", "1", "--nproc-per-node", "1",
         "--rdzv-endpoint", f"127.0.0.1:{port}",
         "--host-store", "--monitor-interval", "0.05", str(worker)],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=90,
    )
    # the only node excluded itself -> the job cannot continue
    assert proc.returncode == 1
    assert "exclude_this_node" in proc.stderr
    assert "not enough healthy nodes" in proc.stderr


def test_init_distributed_env_logic(monkeypatch):
    from tpu_resiliency.parallel.distributed import init_distributed

    # single process: no-op
    monkeypatch.setenv("TPURX_WORLD_SIZE", "1")
    assert init_distributed() is False
    # coordinator derivation (don't actually initialize — just check inputs
    # via a stub)
    calls = {}

    class FakeDist:
        @staticmethod
        def initialize(coordinator_address, num_processes, process_id):
            calls.update(
                addr=coordinator_address, n=num_processes, pid=process_id
            )

    # one JAX process per WORKER: sized by world size and rank, not by nodes
    monkeypatch.setenv("TPURX_NNODES", "1")
    monkeypatch.setenv("TPURX_GROUP_RANK", "0")
    monkeypatch.setenv("TPURX_WORLD_SIZE", "4")
    monkeypatch.setenv("TPURX_RANK", "2")
    monkeypatch.setenv("TPURX_STORE_ADDR", "10.0.0.5")
    monkeypatch.setenv("TPURX_STORE_PORT", "29400")
    monkeypatch.setattr(jax, "distributed", FakeDist)
    assert init_distributed() is True
    assert calls == {"addr": "10.0.0.5:29401", "n": 4, "pid": 2}


def test_orbax_roundtrip_and_migration(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    from tpu_resiliency.checkpointing import load_checkpoint
    from tpu_resiliency.checkpointing.orbax_compat import (
        OrbaxCompatCheckpointer,
        load_orbax_checkpoint,
        migrate_to_tpurx,
    )

    tree = {"w": jnp.arange(12.0).reshape(3, 4), "step": jnp.int32(5)}
    odir = tmp_path / "orbax_ck"
    ck = OrbaxCompatCheckpointer()
    ck.save(tree, str(odir))
    ck.close()
    restored = load_orbax_checkpoint(str(odir), tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    # migrate into tpurx format and load through the native path
    tdir = tmp_path / "tpurx_ck"
    migrate_to_tpurx(str(odir), str(tdir), tree)
    migrated = load_checkpoint(str(tdir), tree)
    np.testing.assert_array_equal(np.asarray(migrated["w"]), np.asarray(tree["w"]))
    assert int(migrated["step"]) == 5
