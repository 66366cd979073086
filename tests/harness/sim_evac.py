"""Predict-and-evacuate vs react-after-failure goodput (ISSUE 18 gate).

A seeded discrete-event simulation of a training gang where nodes
degrade BEFORE they die — health worsens and step time stretches over a
ramp window, then the node hard-faults — driving the REAL policy stack
end to end:

- each control tick feeds per-rank :class:`RankSignals` (ramping victim
  + noisy healthy ranks) through a real :class:`PolicyController` over a
  scripted feed with ``TPURX_EVAC=1``: the fused
  :class:`RankRiskModel` score, the consecutive-tick streak guard, the
  hysteresis re-arm latch and the one-shot :class:`Actuator` action are
  all the production code paths;
- the **evacuate arm** pays the planned-handoff cost when the controller
  fires before the hard fault (checkpoint-ahead save + spare promotion +
  peer warm join — seconds) and loses NO work; a miss falls back to the
  reactive cost;
- the **react arm** ignores the leading indicators and pays the full
  reactive episode at fault time: detection + restart ladder + cold
  global restore + the uncommitted tail back to the last cadence save.

Gates: mean ``evac_goodput_gain`` >= 1.1 over the trials, ZERO
healthy-rank evacuations (the noisy healthy ranks are the false-positive
bait), and zero missed ramps.  Also reports ``evac_join_mttr_ms`` — the
risk-cross → join-done handoff on the simulated clock.  Deterministic: same
seed, same verdict on every host.

``tests/test_evacuation.py`` runs ``run(seed)`` and holds that verdict; by
hand: python tests/harness/sim_evac.py [--seed N]  (one JSON line).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import random
import sys

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from tpu_resiliency.policy import (  # noqa: E402
    EstimatorInputs, GoodputEstimator, PolicyController, RankSignals,
    set_evacuation_handler,
)
from tpu_resiliency.utils import env  # noqa: E402

TOTAL_S = 6000.0
TICK_S = 5.0
N_HEALTHY = 4           # steady ranks: the false-positive bait
DEGRADE_MTBF_S = 600.0  # mean time between degradation onsets
RAMP_S = 120.0          # onset -> hard fault

# reactive episode: detect + restart ladder + cold global restore, plus
# the uncommitted tail back to the last cadence save (mean interval/2)
REACT_DETECT_S = 10.0
REACT_RESTART_S = 30.0
REACT_COLD_RESTORE_S = 25.0
CKPT_INTERVAL_S = 60.0

# planned handoff: out-of-cadence checkpoint-ahead + CAS'd spare
# promotion + chunk-granular peer warm join (no lost work: the
# checkpoint-ahead committed the tail before the slot went away)
EVAC_CKPT_AHEAD_S = 8.0
EVAC_PROMOTE_S = 1.0
EVAC_JOIN_S = (4.0, 9.0)  # seeded jitter range


def draw_degradations(seed: int) -> list:
    """Sorted onset times of node degradations; each ramps ``RAMP_S``
    then hard-faults.  Deterministic in ``seed``."""
    rng = random.Random(seed)
    onsets = []
    t = 0.0
    while True:
        t += rng.expovariate(1.0 / DEGRADE_MTBF_S)
        if t + RAMP_S >= TOTAL_S:
            return onsets
        onsets.append(t)


def _healthy_signals(rng: random.Random) -> dict:
    """Noisy-but-fine ranks: flutter that must never cross the trigger."""
    return {
        r: RankSignals(
            health_score=rng.uniform(0.0, 0.25),
            straggler_score=rng.uniform(0.9, 1.0),
        )
        for r in range(N_HEALTHY)
    }


def run_trial(seed: int) -> dict:
    """One seeded schedule through both arms; returns the per-trial row."""
    onsets = draw_degradations(seed)
    rng = random.Random(seed ^ 0xE7AC)

    # -- evacuate arm: the real controller over scripted per-rank signals
    evacuated_at: dict = {}

    def on_evacuate(victim_rank, reason):
        evacuated_at[victim_rank] = True

    class _SimFeed:
        """collect() returns the inputs staged for the current tick."""

        inputs = EstimatorInputs()

        def collect(self):
            return self.inputs

    feed = _SimFeed()
    env.set_runtime_override(env.EVAC.name, "1")
    set_evacuation_handler(on_evacuate)
    ctl = PolicyController(
        feed=feed, estimator=GoodputEstimator(window_s=200.0)
    )
    overhead_evac = 0.0
    lead_times = []
    join_ms = []
    false_positives = 0
    missed = 0
    t = 0.0
    ei = 0
    active = None  # (victim_rank, onset)
    next_victim = 1000
    while t < TOTAL_S:
        signals = _healthy_signals(rng)
        if active is None and ei < len(onsets) and t >= onsets[ei]:
            active = (next_victim, onsets[ei])
            next_victim += 1
            ei += 1
        if active is not None:
            victim, onset = active
            frac = min(1.0, (t - onset) / RAMP_S)
            signals[victim] = RankSignals(
                health_score=frac,
                straggler_score=max(0.2, 1.0 - 0.8 * frac),
            )
        feed.inputs = EstimatorInputs(rank_signals=signals)
        ctl.tick(now=t)
        for r in list(evacuated_at):
            if evacuated_at[r] is True:
                evacuated_at[r] = t
                if active is not None and r == active[0]:
                    victim, onset = active
                    lead_times.append(onset + RAMP_S - t)
                    join_s = rng.uniform(*EVAC_JOIN_S)
                    join_ms.append(
                        (EVAC_CKPT_AHEAD_S + EVAC_PROMOTE_S + join_s)
                        * 1000.0
                    )
                    overhead_evac += (
                        EVAC_CKPT_AHEAD_S + EVAC_PROMOTE_S + join_s
                    )
                    ctl.estimator.rank_model.forget(victim)
                    active = None
                else:
                    false_positives += 1
        if active is not None and t >= active[1] + RAMP_S:
            # the model missed: the node died first — reactive episode
            missed += 1
            overhead_evac += (
                REACT_DETECT_S + REACT_RESTART_S + REACT_COLD_RESTORE_S
                + CKPT_INTERVAL_S / 2.0
            )
            ctl.estimator.rank_model.forget(active[0])
            active = None
        t += TICK_S
    set_evacuation_handler(None)
    env.clear_runtime_overrides()

    # -- react arm: every degradation runs to the hard fault
    overhead_react = len(onsets) * (
        REACT_DETECT_S + REACT_RESTART_S + REACT_COLD_RESTORE_S
        + CKPT_INTERVAL_S / 2.0
    )

    evac_goodput = max(0.0, (TOTAL_S - overhead_evac) / TOTAL_S)
    react_goodput = max(0.0, (TOTAL_S - overhead_react) / TOTAL_S)
    return {
        "seed": seed,
        "degradations": len(onsets),
        "evacuations": len(lead_times),
        "missed": missed,
        "false_positives": false_positives,
        "evac_goodput": round(evac_goodput, 4),
        "react_goodput": round(react_goodput, 4),
        "lead_time_s_mean": round(
            sum(lead_times) / len(lead_times), 1) if lead_times else None,
        "join_mttr_ms_mean": round(
            sum(join_ms) / len(join_ms), 1) if join_ms else None,
        "gain": round(evac_goodput / max(react_goodput, 1e-9), 3),
    }


def run(seed: int, trials: int = 3) -> dict:
    """Gate on the MEAN gain over derived schedules (not one lucky fault
    draw); any healthy-rank evacuation or missed ramp fails outright."""
    logging.getLogger("tpurx.policy.actuator").setLevel(logging.ERROR)
    logging.getLogger("tpurx.policy.evacuation").setLevel(logging.ERROR)
    results = [run_trial(seed + 211 * i) for i in range(max(1, trials))]
    mean_gain = sum(r["gain"] for r in results) / len(results)
    joins = [r["join_mttr_ms_mean"] for r in results if r["join_mttr_ms_mean"]]
    false_positives = sum(r["false_positives"] for r in results)
    missed = sum(r["missed"] for r in results)
    ok = bool(mean_gain >= 1.1 and false_positives == 0 and missed == 0)
    return {
        "metric": "sim_evac",
        "seed": seed,
        "trials": len(results),
        "evac_goodput": round(
            sum(r["evac_goodput"] for r in results) / len(results), 4),
        "react_goodput": round(
            sum(r["react_goodput"] for r in results) / len(results), 4),
        "evac_trial_gains": [r["gain"] for r in results],
        "evac_false_positives": false_positives,
        "evac_missed": missed,
        "evac_join_mttr_ms": round(
            sum(joins) / len(joins), 1) if joins else None,
        "evac_trials": results,
        "evac_goodput_gain": round(mean_gain, 3),
        "evac_ok": ok,
        "ok": ok,
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seed", type=int, default=0xE7AC)
    p.add_argument("--trials", type=int, default=3)
    args = p.parse_args()
    report = run(args.seed, args.trials)
    print(json.dumps(report))
    sys.exit(0 if report["ok"] else 1)


if __name__ == "__main__":
    main()
