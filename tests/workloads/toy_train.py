"""Toy training workload for launcher integration tests.

Mirrors the reference's func-test DDP toys
(``tests/fault_tolerance/func/run_local_ddp_test_heartbeats.sh`` workloads):
iterate, heartbeat to the rank monitor, persist progress, optionally inject a
crash or a hang at a given (cycle, rank, iteration).

Env:
  TOY_ITERS       total iterations (default 20)
  TOY_CKPT        progress file path ("checkpoint")
  TOY_FAIL        "cycle:rank:iter" -> crash with rc 17
  TOY_HANG        "cycle:rank:iter" -> stop heartbeating forever
  TOY_QUORUM_HANG "cycle:rank:iter" -> stop quorum-beating (stall) with the
                  on-device quorum tripwire wired to request an in-job
                  restart (WorkloadControlRequest.RestartWorkload)
  TOY_STEP_TIME   seconds per iteration (default 0.05)
"""

import os
import sys
import time

sys.path.insert(0, os.environ.get("TPURX_REPO", "/root/repo"))

from tpu_resiliency.fault_tolerance import FaultToleranceConfig, RankMonitorClient
from tpu_resiliency.fault_tolerance.progress_tracker import write_progress_iteration


def parse_spec(name):
    spec = os.environ.get(name)
    if not spec:
        return None
    return tuple(int(x) for x in spec.split(":"))


def main():
    rank = int(os.environ["TPURX_RANK"])
    cycle = int(os.environ["TPURX_CYCLE"])
    world = int(os.environ["TPURX_WORLD_SIZE"])
    total = int(os.environ.get("TOY_ITERS", "20"))
    step_time = float(os.environ.get("TOY_STEP_TIME", "0.05"))
    ckpt = os.environ.get("TOY_CKPT")
    fail = parse_spec("TOY_FAIL")
    hang = parse_spec("TOY_HANG")
    quorum_hang = parse_spec("TOY_QUORUM_HANG")

    start = 0
    if ckpt and os.path.exists(ckpt):
        with open(ckpt) as f:
            start = int(f.read().strip() or "0")

    client = RankMonitorClient()
    client.init_workload_monitoring()

    quorum = None
    if quorum_hang:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from tpu_resiliency.inprocess import quorum_restart_requester
        from tpu_resiliency.ops import QuorumMonitor

        quorum = QuorumMonitor(
            Mesh(np.array(jax.devices()), ("d",)),
            budget_ms=float(os.environ.get("TOY_QUORUM_BUDGET_MS", "500")),
            interval=0.02,
            auto_beat_interval=None,  # manual beats: progress semantics
            on_stale=quorum_restart_requester(client),
            identify=True,
        )
        quorum.start()

    print(f"toy[{rank}/{world}] cycle={cycle} starting at iter {start}", flush=True)

    for it in range(start, total):
        client.send_heartbeat()
        if quorum is not None:
            quorum.beat()
        time.sleep(step_time)
        if fail and (cycle, rank, it) == fail:
            fail_msg = os.environ.get("TOY_FAIL_MSG")
            if fail_msg:
                print(fail_msg, flush=True)  # e.g. an OOM signature for the gate
            print(f"toy[{rank}] injecting crash at iter {it}", flush=True)
            os._exit(17)
        if hang and (cycle, rank, it) == hang:
            print(f"toy[{rank}] injecting hang at iter {it}", flush=True)
            time.sleep(3600)
        if quorum_hang and (cycle, rank, it) == quorum_hang:
            # keep heartbeating the HOST monitor (its timeout is huge in the
            # test) but stall the quorum beats: only the on-device tripwire
            # can name this hang and request the cycle restart
            print(f"toy[{rank}] injecting quorum-stall at iter {it}", flush=True)
            while True:
                client.send_heartbeat()
                time.sleep(0.1)
        if rank == 0 and ckpt:
            write_progress_iteration(ckpt, it + 1)
    if quorum is not None:
        quorum.stop()
    print(f"toy[{rank}] done ({total} iters)", flush=True)


if __name__ == "__main__":
    main()
