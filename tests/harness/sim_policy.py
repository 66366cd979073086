"""Adaptive-vs-best-fixed goodput under a fault-regime shift.

A seeded discrete-event simulation of a checkpointed training run — work
accrues between saves, each save costs C seconds, each fault destroys
the uncommitted tail and costs a recovery — driving the REAL policy
components end to end:

- the adaptive arm feeds cumulative fault counts into
  :class:`tpu_resiliency.policy.GoodputEstimator` (windowed MTBF, EWMA'd
  C, Young/Daly ``tau_opt``) and applies cadence through the real
  :class:`Actuator` (clamp + hysteresis + runtime knob override), read
  back per save decision exactly as ``SaveScheduler.interval_s`` would;
- restart-rung choice goes through the real :class:`RungLedger`: hangs
  always escalate past in-process and mesh-shrink, so the fixed arm pays
  the full ladder walk on every hang while the adaptive arm's ledger
  learns the terminal rung after a few episodes.

The exception-fault schedule has a regime step (noisy then quiet); no
single fixed cadence serves both phases, and no static rung start serves
a class that always escalates.  The fixed arm sweeps a cadence grid and
reports its BEST goodput; the gate asserts the closed loop beats that
best fixed knob by >= 1.1x (``policy_goodput_gain``).  The sim is
deterministic: same seed, same schedule, same verdict on every host.

``tests/test_policy.py`` runs ``run(seed)`` and holds that verdict; by hand:
python tests/harness/sim_policy.py [--seed N]  (one JSON line).
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import os
import random
import sys

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from tpu_resiliency.policy import (  # noqa: E402
    Actuator, EstimatorInputs, GoodputEstimator, RungLedger,
)
from tpu_resiliency.utils import env  # noqa: E402

# exception regime: a noisy phase (MTBF comparable to the save cost — the
# goodput peak is sharp and sits at a short cadence) followed by a quiet
# one (overhead dominates — the peak sits far to the right)
PHASE1_MTBF_S = 25.0
PHASE2_MTBF_S = 300.0
PHASE1_LEN_S = 2000.0
TOTAL_S = 6000.0
CKPT_COST_S = 8.0

# hangs arrive at a steady slow rate in BOTH phases; their in-process and
# mesh-shrink rungs never release (a wedged collective needs the full
# in-job restart), so a static ladder pays every rung's cost each time
HANG_MTBF_S = 350.0
RUNG_COST_S = {"in_process": 20.0, "mesh_shrink": 45.0, "in_job": 60.0}
RUNG_ORDER = ("in_process", "mesh_shrink", "in_job")
EXC_RECOVERY_S = 5.0  # exceptions: the in-process ring absorbs them

FIXED_GRID_S = (10.0, 14.0, 20.0, 28.0, 40.0, 57.0, 80.0, 120.0, 200.0)


def draw_fault_times(seed: int) -> list:
    """Merged, sorted ``(t, kind)`` stream: exponential interarrivals per
    class, phase-dependent for exceptions.  Deterministic in ``seed``."""
    rng = random.Random(seed)
    events = []
    t = 0.0
    while t < TOTAL_S:
        mtbf = PHASE1_MTBF_S if t < PHASE1_LEN_S else PHASE2_MTBF_S
        t += rng.expovariate(1.0 / mtbf)
        if t < TOTAL_S:
            events.append((t, "exception"))
    t = 0.0
    while t < TOTAL_S:
        t += rng.expovariate(1.0 / HANG_MTBF_S)
        if t < TOTAL_S:
            events.append((t, "hang"))
    events.sort()
    return events


def walk_ladder(start_rung: str) -> float:
    """Recovery cost of a hang when the ladder starts at ``start_rung``:
    every rung below in_job fails (and bills its cost) before in_job
    releases.  Returns (total_cost, [(rung, success, cost), ...])."""
    total = 0.0
    episodes = []
    for rung in RUNG_ORDER[RUNG_ORDER.index(start_rung):]:
        cost = RUNG_COST_S[rung]
        total += cost
        episodes.append((rung, rung == "in_job", cost))
    return total, episodes


class FixedPolicy:
    """One fixed cadence, the static default ladder start."""

    def __init__(self, interval_s: float):
        self.interval_s = interval_s

    def next_interval(self, now: float) -> float:
        return self.interval_s

    def recover(self, now: float, kind: str) -> float:
        if kind == "exception":
            return EXC_RECOVERY_S
        cost, _ = walk_ladder("in_process")
        return cost

    def on_save(self, now: float, cost_s: float) -> None:
        pass


class AdaptivePolicy:
    """The real estimator + actuator + rung ledger closing the loop over
    sim time.  The sim observes what the live stack would: cumulative
    fault counts per class, the measured save cost, per-rung episode
    outcomes.  Cadence comes back out through the runtime knob override —
    the same path ``SaveScheduler.interval_s`` takes in a trainer."""

    def __init__(self, window_s: float, default_interval_s: float):
        self.est = GoodputEstimator(window_s=window_s)
        self.act = Actuator()
        self.led = RungLedger()
        self.default_interval_s = default_interval_s
        self.counts = {"exception": 0, "hang": 0}
        self.ckpt_cost_s = None
        self.recovery_cost_s = None
        self.retunes = 0

    def _observe(self, now: float) -> None:
        self.est.update(
            EstimatorInputs(
                fault_counts={k: float(v) for k, v in self.counts.items()},
                ckpt_cost_s=self.ckpt_cost_s,
                recovery_cost_s=self.recovery_cost_s,
            ),
            now=now,
        )

    def next_interval(self, now: float) -> float:
        self._observe(now)
        tau = self.est.tau_opt()
        if not math.isinf(tau):
            # the controller's rule: never act before a fault is measured
            if self.act.set_cadence(tau, "policy sim") is not None:
                self.retunes += 1
        applied = self.act.current_cadence_s()
        return applied if applied else self.default_interval_s

    def recover(self, now: float, kind: str) -> float:
        self.counts[kind] += 1
        if kind == "exception":
            self.led.record("exception", "in_process", True, EXC_RECOVERY_S)
            self.recovery_cost_s = EXC_RECOVERY_S
            self._observe(now)
            return EXC_RECOVERY_S
        cost, episodes = walk_ladder(self.led.pick_start_rung("hang"))
        for rung, success, rung_cost in episodes:
            self.led.record("hang", rung, success, rung_cost)
        self.recovery_cost_s = cost
        self._observe(now)
        return cost

    def on_save(self, now: float, cost_s: float) -> None:
        self.ckpt_cost_s = cost_s


def simulate(fault_events: list, policy) -> float:
    """Run the save/fault loop; returns goodput (committed work fraction
    of wall time).  Work commits only at a completed save; a fault before
    the save COMPLETES (including inside the save window) wipes the
    uncommitted tail and costs the policy's recovery."""
    t = 0.0
    committed = 0.0
    uncommitted = 0.0
    fi = 0
    while t < TOTAL_S:
        interval = max(1.0, policy.next_interval(t))
        save_end = t + interval + CKPT_COST_S
        if fi < len(fault_events) and fault_events[fi][0] < min(save_end, TOTAL_S):
            tf, kind = fault_events[fi]
            fi += 1
            uncommitted = 0.0
            t = tf + policy.recover(tf, kind)
            continue
        if save_end >= TOTAL_S:
            break  # run ends mid-interval; the tail never committed
        uncommitted += interval
        t = save_end
        committed += uncommitted
        uncommitted = 0.0
        policy.on_save(t, CKPT_COST_S)
    return committed / TOTAL_S


def run_trial(seed: int) -> dict:
    fault_events = draw_fault_times(seed)
    fixed = {}
    for interval in FIXED_GRID_S:
        env.clear_runtime_overrides()
        fixed[interval] = simulate(fault_events, FixedPolicy(interval))
    best_fixed_interval = max(fixed, key=fixed.get)
    best_fixed = fixed[best_fixed_interval]

    env.clear_runtime_overrides()
    # production clamp floors would pin the noisy-phase optimum (~15 s)
    env.set_runtime_override(env.POLICY_CADENCE_MIN_S.name, "2.0")
    env.set_runtime_override(env.POLICY_CADENCE_MAX_S.name, "300.0")
    env.set_runtime_override(env.POLICY_HYSTERESIS_PCT.name, "10.0")
    adaptive_policy = AdaptivePolicy(window_s=200.0, default_interval_s=30.0)
    try:
        adaptive = simulate(fault_events, adaptive_policy)
    finally:
        env.clear_runtime_overrides()

    gain = adaptive / max(best_fixed, 1e-9)
    n_exc = sum(1 for _t, k in fault_events if k == "exception")
    n_hang = sum(1 for _t, k in fault_events if k == "hang")
    return {
        "seed": seed,
        "faults_injected": {"exception": n_exc, "hang": n_hang},
        "adaptive_goodput": round(adaptive, 4),
        "best_fixed_goodput": round(best_fixed, 4),
        "best_fixed_interval_s": best_fixed_interval,
        "fixed_sweep": {str(k): round(v, 4) for k, v in fixed.items()},
        "retunes": adaptive_policy.retunes,
        "hang_start_rung": adaptive_policy.led.pick_start_rung("hang"),
        "gain": round(gain, 3),
    }


def run(seed: int, trials: int = 3) -> dict:
    """Gate on the MEAN gain over ``trials`` derived schedules, so the
    verdict reflects the policy, not one lucky fault draw.  Fully
    deterministic for a given (seed, trials)."""
    # thousands of simulated retunes; keep stdout to the one JSON line
    logging.getLogger("tpurx.policy.actuator").setLevel(logging.WARNING)
    results = [run_trial(seed + 101 * i) for i in range(max(1, trials))]
    mean_gain = sum(r["gain"] for r in results) / len(results)
    return {
        "metric": "sim_policy",
        "seed": seed,
        "trials": len(results),
        "policy_adaptive_goodput": round(
            sum(r["adaptive_goodput"] for r in results) / len(results), 4),
        "policy_best_fixed_goodput": round(
            sum(r["best_fixed_goodput"] for r in results) / len(results), 4),
        "policy_trial_gains": [r["gain"] for r in results],
        "policy_retunes": sum(r["retunes"] for r in results),
        "policy_hang_start_rung": results[-1]["hang_start_rung"],
        "policy_trials": results,
        "policy_goodput_gain": round(mean_gain, 3),
        "policy_ok": bool(mean_gain >= 1.1),
        "ok": bool(mean_gain >= 1.1),
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seed", type=int, default=0xA11CE)
    p.add_argument("--trials", type=int, default=3)
    args = p.parse_args()
    report = run(args.seed, args.trials)
    print(json.dumps(report))
    sys.exit(0 if report["ok"] else 1)


if __name__ == "__main__":
    main()
