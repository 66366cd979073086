"""Worker for in-process restart tests (reference analog: tests/inprocess/app.py).

Env:
  TPURX_RANK / TPURX_WORLD_SIZE   identity
  TPURX_STORE_ADDR / PORT         store
  SCENARIO                        clean | exception | crash | hang | spare
                                  | tree_crash | tree_hostcrash
  FAIL_RANK                       rank that faults (default 1)
  STEPS                           steps per fn run (default 30)
Prints "RESULT rank=<r> iters=<n> world=<w> ret=<ret>" on success.
"""

import os
import sys
import time

sys.path.insert(0, os.environ.get("TPURX_REPO", "/root/repo"))

from tpu_resiliency.inprocess import (
    Compose,
    Layer,
    LayerFlag,
    MaxActiveWorldSize,
    RankDiscontinued,
    ShiftRanks,
    Tree,
    Wrapper,
)

SCENARIO = os.environ.get("SCENARIO", "clean")
FAIL_RANK = int(os.environ.get("FAIL_RANK", "1"))
STEPS = int(os.environ.get("STEPS", "60"))
INITIAL_RANK = int(os.environ["TPURX_RANK"])

calls = {"n": 0}


def train(call_wrapper=None):
    calls["n"] += 1
    it = call_wrapper.iteration
    state = call_wrapper.state
    rank = state.active_rank
    world = state.active_world_size
    print(
        f"train start rank={rank} world={world} iter={it} call={calls['n']}",
        flush=True,
    )
    for step in range(STEPS):
        call_wrapper.ping()
        time.sleep(0.05)
        if SCENARIO == "late_fault" and it == 0:
            # completion/fault race: rank 0 finishes the job early; the
            # failing rank faults well after — its restart path must see
            # any_completed and EXIT instead of restarting into an
            # iteration barrier the completed rank will never attend
            if INITIAL_RANK == 0 and step == 1:
                return f"done-early@{it}"
            if INITIAL_RANK == FAIL_RANK and step == 30:
                raise RuntimeError("late fault after completion")
        if it == 0 and INITIAL_RANK == FAIL_RANK and step == 3:
            if "exception" in SCENARIO:
                raise RuntimeError("injected exception")
            if "crash" in SCENARIO:
                print("crashing", flush=True)
                os._exit(31)
            if SCENARIO == "quorum_hang":
                # stop beating: the ICI quorum collective must detect the
                # stale stamp and trip the restart ring — the host-side
                # soft/hard/sibling timeouts are set far too large to fire.
                # Python-level stall (not one long C sleep) so the monitor
                # thread's async raise can land and the SAME process recovers.
                print("quorum-hanging", flush=True)
                while True:
                    time.sleep(0.1)
            if "hang" in SCENARIO:
                print("hanging", flush=True)
                time.sleep(3600)  # stops pinging; GIL released
    return f"ok@{it}"


def _tree_assignment():
    """Two-layer pod: root(RESERVE, capped) over N-chip hosts.

    ``tree_crash`` allows partial hosts (spare promotes into a one-chip gap);
    ``tree_hostcrash`` pins min=max=chips so losing one chip terminates the
    whole host and both slots refill from the other host's spares.
    """
    chips = int(os.environ.get("CHIPS_PER_HOST", "2"))
    host_min = 1 if SCENARIO == "tree_crash" else chips
    host_max = 1 if SCENARIO == "tree_crash" else chips
    return Tree(
        [
            Layer(
                min_ranks=1,
                max_ranks=int(os.environ.get("MAX_ACTIVE", "2")),
                key_of_rank="root",
                flag=LayerFlag.RESERVE,
            ),
            Layer(
                min_ranks=host_min,
                max_ranks=host_max,
                key_of_rank=lambda r, c=chips: r // c,
                flag=LayerFlag.RESERVE,
            ),
        ]
    )


def main():
    if SCENARIO.startswith("tree"):
        assignment = _tree_assignment()
    elif SCENARIO.startswith("spare"):
        assignment = Compose(
            ShiftRanks(), MaxActiveWorldSize(int(os.environ.get("MAX_ACTIVE", "2")))
        )
    else:
        assignment = ShiftRanks()
    quorum_kw = {}
    if SCENARIO == "quorum_hang":
        import jax
        import numpy as np
        from jax.sharding import Mesh

        quorum_kw = dict(
            quorum_mesh=Mesh(np.array(jax.devices()), ("d",)),
            quorum_budget_ms=float(os.environ.get("QUORUM_BUDGET_MS", "500")),
            quorum_interval=0.02,
            # manual ping() is the only beat source: a stopped training loop
            # means stale stamps (progress semantics, not just liveness)
            quorum_auto_beat_interval=None,
            quorum_calibrate=False,
        )
    wrapper = Wrapper(
        rank_assignment=assignment,
        # defaults sized for loaded CI hosts: scenarios that TEST hang
        # detection override these via env; for everything else a tight
        # budget risks a load-stall being killed as a "hang"
        soft_timeout=float(os.environ.get("SOFT_TIMEOUT", "5.0")),
        hard_timeout=float(os.environ.get("HARD_TIMEOUT", "10.0")),
        monitor_process_interval=0.2,
        monitor_thread_interval=0.1,
        last_call_wait=0.2,
        heartbeat_interval=0.2,
        sibling_timeout=float(os.environ.get("SIBLING_TIMEOUT", "8.0")),
        barrier_timeout=30.0,
        **quorum_kw,
    )
    wrapped = wrapper(train)
    try:
        ret = wrapped()
    except RankDiscontinued as exc:
        # precisely a policy discontinuation (Tree min_ranks propagation),
        # NOT a generic abort — max_iterations/health aborts must fail loud
        print(f"DISCONTINUED rank={INITIAL_RANK} reason={exc}", flush=True)
        sys.exit(7)
    final_rank = os.environ.get("TPURX_RANK")
    print(
        f"RESULT rank={INITIAL_RANK} calls={calls['n']} "
        f"final_rank={final_rank} ret={ret}",
        flush=True,
    )
    if os.environ.get("TPURX_FLIGHT_DIR"):
        # trip-time black boxes end at the detection instant; the soak tests
        # also want the full episode story (decide..resume), so drop one
        # final dump with the complete ring before exiting
        from tpu_resiliency.telemetry import flight

        flight.dump("worker_exit", min_interval_s=0.0)


if __name__ == "__main__":
    main()
