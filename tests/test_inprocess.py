"""In-process restart wrapper tests.

Reference analog: ``tests/inprocess/test_wrap.py`` + ``common.py``'s
MultiProcessTestCase: real OS processes, real store, injected faults.
"""

import os
import re
import subprocess
import sys
import time
from pathlib import Path

import pytest

from tpu_resiliency.utils.env import force_cpu_env

from tpu_resiliency.inprocess.rank_assignment import (
    ActivateAllRanks,
    ActiveWorldSizeDivisibleBy,
    FillGaps,
    MaxActiveWorldSize,
    RankAssignmentCtx,
    RankDiscontinued,
    ShiftRanks,
)
from tpu_resiliency.inprocess.state import Mode, State

REPO = Path(__file__).resolve().parent.parent
WORKER = str(REPO / "tests" / "workloads" / "inproc_worker.py")


# ---- pure policy tests (reference test_rank_assignment.py) -----------------

def _state(rank, world):
    return State(rank=rank, world_size=world)


class TestRankAssignment:
    def test_shift_ranks(self):
        ctx = RankAssignmentCtx(_state(3, 4), {1})
        ShiftRanks()(ctx)
        assert ctx.state.rank == 2
        assert ctx.state.world_size == 3
        assert ctx.state.mode == Mode.ACTIVE

    def test_shift_ranks_discontinued(self):
        with pytest.raises(RankDiscontinued):
            ShiftRanks()(RankAssignmentCtx(_state(1, 4), {1}))

    def test_fill_gaps_keeps_survivors(self):
        # world 4, rank 1 dies: rank 3 moves into slot 1; 0 and 2 unchanged
        ctx = RankAssignmentCtx(_state(2, 4), {1})
        FillGaps()(ctx)
        assert ctx.state.rank == 2
        ctx3 = RankAssignmentCtx(_state(3, 4), {1})
        FillGaps()(ctx3)
        assert ctx3.state.rank == 1
        assert ctx3.state.world_size == 3

    def test_max_active_world_size(self):
        ctx = RankAssignmentCtx(_state(2, 3), set())
        MaxActiveWorldSize(2)(ctx)
        assert ctx.state.mode == Mode.INACTIVE
        assert ctx.state.active_world_size == 2
        ctx0 = RankAssignmentCtx(_state(0, 3), set())
        MaxActiveWorldSize(2)(ctx0)
        assert ctx0.state.mode == Mode.ACTIVE

    def test_divisible_by(self):
        ctx = RankAssignmentCtx(_state(6, 7), set())
        ActiveWorldSizeDivisibleBy(4)(ctx)
        assert ctx.state.active_world_size == 4
        assert ctx.state.mode == Mode.INACTIVE
        ctx2 = RankAssignmentCtx(_state(2, 7), set())
        ActiveWorldSizeDivisibleBy(4)(ctx2)
        assert ctx2.state.mode == Mode.ACTIVE

    def test_activate_all(self):
        ctx = RankAssignmentCtx(_state(1, 2), set())
        ActivateAllRanks()(ctx)
        assert ctx.state.mode == Mode.ACTIVE


# ---- multiprocess wrapper tests --------------------------------------------

def run_scenario(store_server, scenario, world=2, extra_env=None, timeout=90):
    procs = []
    for rank in range(world):
        env = dict(os.environ)
        env.update(
            {
                "TPURX_REPO": str(REPO),
                "TPURX_RANK": str(rank),
                "TPURX_WORLD_SIZE": str(world),
                "TPURX_STORE_ADDR": "127.0.0.1",
                "TPURX_STORE_PORT": str(store_server.port),
                "SCENARIO": scenario,
            }
        )
        force_cpu_env(env)
        env.update(extra_env or {})
        procs.append(
            subprocess.Popen(
                [sys.executable, WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                cwd=str(REPO),
            )
        )
    outs = {}
    for rank, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n<TIMEOUT>"
        outs[rank] = out
    return procs, outs


def _dump(outs):
    for r, out in outs.items():
        print(f"===== rank {r} =====\n{out[-2500:]}")


def test_clean_run(store_server):
    procs, outs = run_scenario(store_server, "clean", world=2)
    if any(p.returncode != 0 for p in procs):
        _dump(outs)
    for rank, p in enumerate(procs):
        assert p.returncode == 0
        assert "RESULT" in outs[rank]
        assert "ret=ok@0" in outs[rank]
        assert "calls=1" in outs[rank]


def test_exception_restarts_all_ranks(store_server):
    procs, outs = run_scenario(store_server, "exception", world=2)
    if any(p.returncode != 0 for p in procs):
        _dump(outs)
    for rank, p in enumerate(procs):
        assert p.returncode == 0, f"rank {rank}"
        # iteration 0 faulted; completion at >= 1 (extra legitimate restarts
        # possible on a loaded host)
        m = re.search(r"ret=ok@(\d+)", outs[rank])
        assert m and int(m.group(1)) >= 1, outs[rank][-800:]
    assert "injected exception" in outs[1]


def test_crash_shrinks_world(store_server):
    procs, outs = run_scenario(store_server, "crash", world=3, timeout=120)
    if procs[0].returncode != 0 or procs[2].returncode != 0:
        _dump(outs)
    # rank 1 died hard
    assert procs[1].returncode == 31
    # survivors restarted and finished with world 2
    for rank in (0, 2):
        assert procs[rank].returncode == 0, f"rank {rank}"
        m = re.search(r"ret=ok@(\d+)", outs[rank])
        assert m and int(m.group(1)) >= 1, outs[rank][-800:]
        assert re.search(r"world=2 iter=\d+", outs[rank]), outs[rank][-800:]
    # rank 2 shifted into rank 1's slot
    assert re.search(r"train start rank=1 world=2 iter=\d+", outs[2]), outs[2][-800:]


def test_hang_detected_and_killed(store_server):
    # STEPS=120 (6s of fn) keeps a wide margin between the hang kill
    # (~hard_timeout + interval ≈ 3s) and the survivor finishing its own
    # iteration 0 — on a loaded host a thin margin lets rank 0 complete
    # BEFORE the kill lands and no restart is observed
    procs, outs = run_scenario(
        store_server, "hang", world=2, timeout=150,
        extra_env={"SOFT_TIMEOUT": "1.0", "HARD_TIMEOUT": "2.5",
                   "STEPS": "120"},
    )
    if procs[0].returncode != 0:
        _dump(outs)
    # hung rank was killed by its monitor process
    assert procs[1].returncode != 0
    # survivor restarted alone and completed (iteration >= 1; load stalls
    # can fire extra legitimate restarts on the survivor's own budgets)
    assert procs[0].returncode == 0
    m = re.search(r"ret=ok@(\d+)", outs[0])
    assert m and int(m.group(1)) >= 1, outs[0][-800:]
    assert re.search(r"world=1 iter=\d+", outs[0]), outs[0][-800:]


def test_quorum_tripwire_restarts_without_host_timeouts(store_server):
    """VERDICT r2 #1: the on-device quorum trip must DRIVE recovery.

    Rank 1 stops beating (Python-level stall).  Every host-side detector is
    configured orders of magnitude too slow (soft 300s, hard 600s, sibling
    300s), so the ONLY path to the restart is: quorum collective observes the
    stale stamp -> QUORUM_STALE interruption record -> monitor threads trip
    -> async restart raise -> both ranks restart in-process and complete.
    """
    t0 = time.monotonic()
    procs, outs = run_scenario(
        store_server, "quorum_hang", world=2, timeout=150,
        extra_env={
            "SOFT_TIMEOUT": "300", "HARD_TIMEOUT": "600",
            "SIBLING_TIMEOUT": "300", "QUORUM_BUDGET_MS": "500",
        },
    )
    elapsed = time.monotonic() - t0
    if any(p.returncode != 0 for p in procs):
        _dump(outs)
    # BOTH ranks recovered in the same process (no kill; rc 0) and completed
    # at iteration >= 1 (a loaded host can stall the beater past the budget
    # and fire extra — legitimate — quorum restarts; the invariant is that
    # recovery HAPPENED and came from the quorum, not its exact count)
    for rank in (0, 1):
        assert procs[rank].returncode == 0
        m = re.search(r"ret=ok@(\d+)", outs[rank])
        assert m and int(m.group(1)) >= 1, outs[rank][-800:]
    # detection was the quorum's: the trip and the record kind are logged
    combined = outs[0] + outs[1]
    assert "quorum tripwire" in combined
    assert "quorum_stale" in combined
    # and it was FAST: far under the 300s host-timeout floor (compile +
    # restart dominate; detection itself is sub-second)
    assert elapsed < 120, elapsed


def test_late_fault_after_completion_exits_not_restarts(store_server):
    """Completion wins the completion-vs-fault race: when a peer finished
    the job in the same iteration, a faulted rank's restart path must exit
    (any_completed gate) rather than restart into an iteration barrier the
    completed peer will never attend (review r5 finding)."""
    procs, outs = run_scenario(store_server, "late_fault", world=2, timeout=60)
    if any(p.returncode != 0 for p in procs):
        _dump(outs)
    assert procs[0].returncode == 0
    assert "ret=done-early@0" in outs[0]
    assert procs[1].returncode == 0, outs[1][-800:]
    # the faulted rank exited via the completion gate, not a restart cycle.
    # The gate returns the JOB_COMPLETED sentinel (printed as
    # "ret=job-completed"); "ret=None" no longer exists as an outcome — it
    # used to be ambiguous with the layered-restart flake's lost-result
    # signature, where an async raise couldn't land inside a parked store op.
    assert "job completed" in outs[1], outs[1][-800:]
    assert "ret=job-completed" in outs[1], outs[1][-800:]
    assert "ret=None" not in outs[1], outs[1][-800:]


def test_spare_rank_activated_on_failure(store_server):
    procs, outs = run_scenario(
        store_server, "spare", world=3, timeout=120,
        extra_env={"MAX_ACTIVE": "2", "FAIL_RANK": "1", "SCENARIO2": ""},
    )
    # scenario "spare" with FAIL_RANK crashing? spare scenario only changes
    # assignment; make rank 1 crash via env:
    # (covered by the dedicated run below)
    for rank, p in enumerate(procs):
        if p.returncode != 0:
            _dump(outs)
        assert p.returncode == 0
    # rank 2 was INACTIVE initially, and the job completed
    assert "inactive" in outs[2].lower() or "RESULT" in outs[2]


def test_spare_promoted_after_crash(store_server):
    env = {"MAX_ACTIVE": "2", "FAIL_RANK": "1"}
    procs, outs = run_scenario(
        store_server, "spare_crash", world=3, timeout=150, extra_env=env
    )
    if procs[0].returncode != 0 or procs[2].returncode != 0:
        _dump(outs)
    assert procs[1].returncode == 31      # crashed
    assert procs[0].returncode == 0
    assert procs[2].returncode == 0
    # spare (initial rank 2) became active rank 1 (iteration >= 1)
    assert re.search(r"train start rank=1 world=2 iter=\d+", outs[2]), outs[2][-800:]
    m = re.search(r"ret=ok@(\d+)", outs[0])
    assert m and int(m.group(1)) >= 1, outs[0][-800:]


def test_tree_spare_promoted_into_gap(store_server):
    # 4 ranks = two 2-chip hosts; Tree(root RESERVE max_active=2,
    # host min=1 max=1): actives {0, 2}, spares {1, 3}.  Rank 2 crashes ->
    # its same-host spare (initial rank 3) takes over app rank 1.
    env = {"MAX_ACTIVE": "2", "FAIL_RANK": "2", "CHIPS_PER_HOST": "2"}
    procs, outs = run_scenario(
        store_server, "tree_crash", world=4, timeout=150, extra_env=env
    )
    if procs[0].returncode != 0 or procs[3].returncode != 0:
        _dump(outs)
    assert procs[1].returncode == 0      # parked spare, job completed
    assert procs[2].returncode == 31     # crashed
    assert procs[0].returncode == 0
    assert procs[3].returncode == 0
    # iteration number may exceed 1 under host load (extra legitimate
    # restarts); the invariant is the spare took app rank 1 in a world of 2
    assert re.search(r"train start rank=1 world=2 iter=\d+", outs[3]), outs[3][-800:]
    m = re.search(r"ret=ok@(\d+)", outs[0])
    assert m and int(m.group(1)) >= 1, outs[0][-800:]


def test_tree_host_loss_promotes_whole_spare_host(store_server):
    # host min=max=2: rank 1's crash terminates all of host0 (healthy rank 0
    # is discontinued and must mark itself so peers' barriers don't wait);
    # host1's spares take both slots.
    env = {"MAX_ACTIVE": "2", "FAIL_RANK": "1", "CHIPS_PER_HOST": "2"}
    procs, outs = run_scenario(
        store_server, "tree_hostcrash", world=4, timeout=150, extra_env=env
    )
    if procs[2].returncode != 0 or procs[3].returncode != 0:
        _dump(outs)
    assert procs[1].returncode == 31     # crashed
    assert procs[0].returncode == 7      # healthy but discontinued with host0
    assert "DISCONTINUED rank=0" in outs[0]
    assert procs[2].returncode == 0
    assert procs[3].returncode == 0
    assert re.search(r"train start rank=0 world=2 iter=\d+", outs[2]), outs[2][-800:]
    assert re.search(r"train start rank=1 world=2 iter=\d+", outs[3]), outs[3][-800:]
    m = re.search(r"ret=ok@(\d+)", outs[2])
    assert m and int(m.group(1)) >= 1, outs[2][-800:]


class TestActivateWholeGroups:
    def _policy(self):
        from tpu_resiliency.inprocess.rank_assignment import ActivateWholeGroups

        # 8 ranks, 4 per host
        return ActivateWholeGroups(key_of_rank=lambda r: r // 4, group_size=4)

    def test_all_groups_complete(self):
        p = self._policy()
        ctx = RankAssignmentCtx(_state(5, 8), set())
        p(ctx)
        assert ctx.state.mode == Mode.ACTIVE
        assert ctx.state.active_rank == 5
        assert ctx.state.active_world_size == 8

    def test_broken_group_parks_inactive(self):
        p = self._policy()
        # rank 6 died -> host 1 (ranks 4-7) incomplete; rank 5 parks
        ctx = RankAssignmentCtx(_state(5, 8), {6})
        p(ctx)
        assert ctx.state.mode == Mode.INACTIVE
        assert ctx.state.active_world_size == 4
        # host 0 members stay active with their ranks
        ctx0 = RankAssignmentCtx(_state(2, 8), {6})
        p(ctx0)
        assert ctx0.state.mode == Mode.ACTIVE
        assert ctx0.state.active_rank == 2

    def test_min_groups_enforced(self):
        from tpu_resiliency.inprocess.exceptions import RestartAbort
        from tpu_resiliency.inprocess.rank_assignment import ActivateWholeGroups

        p = ActivateWholeGroups(lambda r: r // 4, 4, min_groups=2)
        with pytest.raises(RestartAbort):
            p(RankAssignmentCtx(_state(0, 8), {6}))


def test_completion_and_terminate_hooks(store_server):
    """Completion transforms the return value; terminate fires on RestartAbort."""
    import threading

    from tpu_resiliency.inprocess import Wrapper
    from tpu_resiliency.inprocess.exceptions import RestartAbort
    from tpu_resiliency.store import StoreClient

    calls = {"completion": 0, "terminate": 0}

    def completion(state, ret):
        calls["completion"] += 1
        return ret + "!"

    def terminate(state):
        calls["terminate"] += 1

    def factory():
        return StoreClient("127.0.0.1", store_server.port, timeout=10.0)

    os.environ["TPURX_RANK"] = "0"
    os.environ["TPURX_WORLD_SIZE"] = "1"
    try:
        w1 = Wrapper(store_factory=factory, group="hooks1", completion=completion,
                     enable_monitor_process=False, enable_sibling_monitor=False)
        assert w1(lambda: "done")() == "done!"
        assert calls["completion"] == 1

        w2 = Wrapper(store_factory=factory, group="hooks2", terminate=terminate,
                     max_iterations=0,
                     enable_monitor_process=False, enable_sibling_monitor=False)
        with pytest.raises(RestartAbort):
            w2(lambda: "never")()
        assert calls["terminate"] == 1
    finally:
        os.environ.pop("TPURX_RANK", None)
        os.environ.pop("TPURX_WORLD_SIZE", None)
