"""In-process restart example: recover from faults without losing the process.

Start a store, then N ranks (in separate shells or a loop):

    python -m tpu_resiliency.store.server --port 29500 &
    for r in 0 1 2; do
        TPURX_RANK=$r TPURX_WORLD_SIZE=3 \
        TPURX_STORE_ADDR=127.0.0.1 TPURX_STORE_PORT=29500 \
        python examples/inprocess_restart.py &
    done

Kill any rank (kill -9 <pid>): survivors detect it via the sibling/monitor
ring, re-assign ranks with ShiftRanks, and re-enter `train` with a smaller
world — same Python process, no scheduler round trip.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_resiliency.inprocess import (
    AbortLadder,
    Compose,
    DeviceProbeHealthCheck,
    FaultCounter,
    ShiftRanks,
    ShrinkMeshStage,
    Wrapper,
)
from tpu_resiliency.inprocess.abort import ClearJaxCaches


@Wrapper(
    rank_assignment=ShiftRanks(),
    health_check=Compose(FaultCounter(max_faults=5), DeviceProbeHealthCheck(timeout=30)),
    # the staged abort ladder: the wrapper prepends its fingerprint rung
    # automatically; each rung runs with its own deadline and recorded
    # outcome (released / timed_out / escalate) — see docs/inprocess.md
    abort=AbortLadder(ShrinkMeshStage(), ClearJaxCaches()),
    soft_timeout=20.0,
    hard_timeout=40.0,
)
def train(call_wrapper=None):
    state = call_wrapper.state
    print(
        f"train: rank={state.active_rank}/{state.active_world_size} "
        f"iteration={call_wrapper.iteration}",
        flush=True,
    )
    for step in range(200):
        call_wrapper.ping()           # feed the hang watchdog
        time.sleep(0.1)               # "training step"
        if step % 50 == 0:
            print(f"rank {state.active_rank}: step {step}", flush=True)
    return "finished"


if __name__ == "__main__":
    print("pid:", os.getpid(), flush=True)
    print(train())
