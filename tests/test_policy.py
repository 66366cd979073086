"""Adaptive policy engine tests: windowed rate views, the goodput
estimator's cadence convergence after a fault-rate step, actuator bounds
(clamp/hysteresis/clear), rung-ledger accounting cross-checked against
the abort ladder's own stage-outcome counters, decision journaling +
``tpurx_policy_*`` metrics, and the per-rank PolicyClient poll/apply
path."""

import math
import os

import pytest

from tpu_resiliency.policy import (
    Action,
    Actuator,
    EstimatorInputs,
    GoodputEstimator,
    K_DECISION_LATEST,
    PolicyController,
    RungLedger,
    TelemetryFeed,
    _reset_ledger_for_tests,
    decisions_from_json,
    ledger,
    young_daly_interval,
)
from tpu_resiliency.telemetry.registry import RateWindow, Registry, get_registry
from tpu_resiliency.utils import env

from harness import sim_policy


@pytest.fixture(autouse=True)
def _clean_policy_state():
    """Every test starts with no runtime overrides and a fresh ledger."""
    env.clear_runtime_overrides()
    _reset_ledger_for_tests()
    yield
    env.clear_runtime_overrides()
    _reset_ledger_for_tests()


# ---- RateWindow / Counter.rate ---------------------------------------------


class TestRateWindow:
    def test_zero_until_baseline(self):
        w = RateWindow()
        assert w.rate(60.0, 0.0, now=0.0) == 0.0
        assert w.rate(60.0, 5.0, now=10.0) == pytest.approx(0.5)

    def test_steady_rate(self):
        w = RateWindow()
        for i in range(7):
            r = w.rate(60.0, float(i), now=float(i * 10))
        assert r == pytest.approx(0.1)

    def test_counter_reset_rebaselines(self):
        """A cumulative-value decrease (rank restart re-created the
        counter) must re-baseline, never report a negative rate."""
        w = RateWindow()
        w.rate(60.0, 100.0, now=0.0)
        w.rate(60.0, 110.0, now=10.0)
        # restart: the series starts over at 2
        assert w.rate(60.0, 2.0, now=20.0) == 0.0  # no baseline again
        assert w.rate(60.0, 4.0, now=30.0) == pytest.approx(0.2)

    def test_window_expiry_drops_stale_baseline(self):
        """The rate is measured against the oldest IN-WINDOW sample; a
        burst that scrolled out of the window stops inflating it."""
        w = RateWindow()
        w.rate(30.0, 0.0, now=0.0)
        w.rate(30.0, 100.0, now=10.0)  # burst
        # 100s later, only quiet samples are in-window
        w.rate(30.0, 100.0, now=90.0)
        assert w.rate(30.0, 100.0, now=100.0) == 0.0

    def test_counter_rate_view(self):
        reg = Registry(enabled=True)
        c = reg.counter("tpurx_policy_test_events_total")
        assert c.rate(60.0, now=0.0) == 0.0
        c.inc(6)
        assert c.rate(60.0, now=12.0) == pytest.approx(0.5)

    def test_disabled_counter_rate_is_zero(self):
        reg = Registry(enabled=False)
        c = reg.counter("tpurx_policy_test_off_total")
        c.inc()
        assert c.rate(60.0, now=1.0) == 0.0


# ---- estimator --------------------------------------------------------------


def _feed_constant_rate(
    est, start_s, end_s, period_s, count0=0.0, tick_s=5.0, ckpt_cost_s=None
):
    """Feed cumulative exception counts growing one per ``period_s``."""
    count = count0
    t = start_s
    while t < end_s:
        count = count0 + (t - start_s) / period_s
        est.update(
            EstimatorInputs(
                fault_counts={"exception": count}, ckpt_cost_s=ckpt_cost_s
            ),
            now=t,
        )
        t += tick_s
    return count


class TestEstimator:
    def test_mtbf_inf_until_first_fault(self):
        est = GoodputEstimator(window_s=100.0)
        est.update(EstimatorInputs(fault_counts={"exception": 0.0}), now=0.0)
        est.update(EstimatorInputs(fault_counts={"exception": 0.0}), now=50.0)
        assert math.isinf(est.mtbf_s())
        assert math.isinf(est.tau_opt())

    def test_quiet_after_faults_relaxes_to_window_bound(self):
        """Once faults HAVE been seen, a quiet window reads as
        ``MTBF >= window_s`` so cadence relaxes instead of pinning at the
        last noisy measurement."""
        est = GoodputEstimator(window_s=100.0)
        _feed_constant_rate(est, 0.0, 200.0, period_s=10.0)
        assert est.mtbf_s() == pytest.approx(10.0, rel=0.3)
        # regime calms: counts stop growing for > window
        count = 20.0
        for t in range(200, 400, 5):
            est.update(
                EstimatorInputs(fault_counts={"exception": count}),
                now=float(t),
            )
        assert est.mtbf_s() == pytest.approx(100.0)  # the window lower bound
        assert not math.isinf(est.tau_opt())

    def test_cadence_tracks_young_daly_after_rate_step(self):
        """Acceptance: after a fault-rate step the Young/Daly target moves
        to the new optimum sqrt(2·C·MTBF)."""
        est = GoodputEstimator(window_s=100.0)
        # phase 1: one fault per 5s, measured save cost 2s
        _feed_constant_rate(est, 0.0, 300.0, period_s=5.0, ckpt_cost_s=2.0)
        tau1 = est.tau_opt()
        assert tau1 == pytest.approx(young_daly_interval(2.0, 5.0), rel=0.25)
        # phase 2 (the step): one fault per 50s, cumulative count continues
        _feed_constant_rate(
            est, 300.0, 800.0, period_s=50.0, count0=60.0, ckpt_cost_s=2.0
        )
        tau2 = est.tau_opt()
        assert tau2 == pytest.approx(young_daly_interval(2.0, 50.0), rel=0.25)
        assert tau2 > tau1 * 2  # the optimum clearly moved with the regime

    def test_costs_ewma_and_defaults(self):
        est = GoodputEstimator(window_s=100.0)
        c0, r0 = est.costs()
        assert c0 == 5.0 and r0 == 30.0  # documented defaults
        est.update(
            EstimatorInputs(ckpt_cost_s=10.0, recovery_cost_s=20.0), now=0.0
        )
        est.update(
            EstimatorInputs(ckpt_cost_s=20.0, recovery_cost_s=40.0), now=10.0
        )
        c, r = est.costs()
        assert 10.0 < c < 20.0  # EWMA between the samples
        assert 20.0 < r < 40.0

    def test_expected_goodput_peaks_near_tau_opt(self):
        est = GoodputEstimator(window_s=1000.0)
        _feed_constant_rate(
            est, 0.0, 1000.0, period_s=100.0, tick_s=20.0, ckpt_cost_s=5.0
        )
        tau = est.tau_opt()
        assert est.expected_goodput(tau) > est.expected_goodput(tau / 5)
        assert est.expected_goodput(tau) > est.expected_goodput(tau * 5)

    def test_telemetry_feed_maps_registry_series(self):
        reg = Registry(enabled=True)
        reg.counter(
            "tpurx_inprocess_interruptions_total", labels=("kind",)
        ).labels(kind="exception").inc(3)
        reg.counter("tpurx_monitor_trips_total").inc(2)
        reg.counter("tpurx_collective_timeouts_total").inc(1)
        reg.gauge("tpurx_health_score", labels=("check",)).labels(
            check="kernel_log"
        ).set(0.75)
        reg.counter("tpurx_kmsg_faults_total", labels=("class",)).labels(
            "hard"
        ).inc(4)
        inputs = TelemetryFeed(registry=reg).collect()
        assert inputs.fault_counts["exception"] == 3
        assert inputs.fault_counts["hang"] == 2
        assert inputs.fault_counts["collective"] == 1
        assert inputs.node_risk == 0.75
        assert inputs.kmsg_hard_total == 4


# ---- actuator ---------------------------------------------------------------


class TestActuator:
    def test_cadence_clamped_and_hysteresis_damped(self):
        act = Actuator()
        lo = env.POLICY_CADENCE_MIN_S.get()
        hi = env.POLICY_CADENCE_MAX_S.get()
        a = act.set_cadence(lo / 100.0, "clamp low")
        assert a is not None and float(a.value) == pytest.approx(lo)
        a = act.set_cadence(hi * 100.0, "clamp high")
        assert float(a.value) == pytest.approx(hi)
        # < hysteresis-pct relative change from the current value: damped
        assert act.set_cadence(hi * 0.99, "noise") is None
        assert env.CKPT_INTERVAL_S.get() == pytest.approx(hi)

    def test_infinite_target_relaxes_to_max(self):
        act = Actuator()
        a = act.set_cadence(math.inf, "no faults ever")
        assert float(a.value) == pytest.approx(env.POLICY_CADENCE_MAX_S.get())

    def test_replication_bounds_and_clear(self):
        act = Actuator(max_replication=4)
        a = act.set_replication(9, "cap")
        assert a.value == "4"
        assert env.LCKPT_REPLICATION.get() == 4
        assert act.set_replication(4, "same") is None  # no-op damped
        a = act.set_replication(None, "clear")
        assert a.value == ""
        assert env.LCKPT_REPLICATION.get() is None
        assert act.set_replication(None, "already clear") is None

    def test_delta_flip_and_clear(self):
        act = Actuator()
        a = act.set_delta(True, "risk")
        assert a is not None and env.CKPT_DELTA.get() is True
        assert act.set_delta(True, "again") is None
        a = act.set_delta(None, "cleared")
        assert a.value == ""
        assert env.runtime_overrides().get(env.CKPT_DELTA.name) is None

    def test_start_rung_arms_ledger_and_shrink_stage(self):
        act = Actuator()
        a = act.set_start_rung("hang", "mesh_shrink", "ledger pick")
        assert a.target == "ledger:hang" and a.value == "mesh_shrink"
        assert ledger().start_rung("hang") == "mesh_shrink"
        assert env.SHRINK_MESH.get()  # the opt-in stage got enabled
        assert act.set_start_rung("hang", "mesh_shrink", "same") is None
        with pytest.raises(ValueError):
            act.set_start_rung("hang", "warp_drive", "nope")

    def test_degrade_ladder_compositions(self):
        act = Actuator()
        a = act.set_degrade_ladder("skip_retry", "timeouts escalate")
        assert a.value == "relayout,shrink"
        assert env.COLL_DEGRADE.get() == "relayout,shrink"
        assert act.set_degrade_ladder("skip_retry", "same") is None
        with pytest.raises(ValueError):
            act.set_degrade_ladder("yolo", "nope")

    def test_apply_replays_remote_actions(self):
        """The per-rank path: a published Action re-applies verbatim —
        set, clear, and ledger arms — without re-deciding."""
        act = Actuator()
        act.apply(Action("set_cadence", env.CKPT_INTERVAL_S.name, "42.0", "r"))
        assert env.CKPT_INTERVAL_S.get() == pytest.approx(42.0)
        act.apply(Action("set_cadence", env.CKPT_INTERVAL_S.name, "", "clear"))
        assert env.runtime_overrides().get(env.CKPT_INTERVAL_S.name) is None
        act.apply(Action("set_start_rung", "ledger:hang", "in_job", "r"))
        assert ledger().start_rung("hang") == "in_job"

    def test_undeclared_knob_rejected(self):
        with pytest.raises(KeyError):
            env.set_runtime_override("TPURX_NOT_A_KNOB", "1")


# ---- rung ledger ------------------------------------------------------------


class TestRungLedger:
    def test_empty_ledger_starts_at_top(self):
        led = RungLedger()
        assert led.pick_start_rung("hang") == "in_process"

    def test_escalating_class_skips_dead_rungs(self):
        """A class whose in-process rung always fails and whose in-job
        rung always recovers should start at in_job once enough episodes
        are recorded."""
        led = RungLedger()
        for _ in range(4):
            led.record("hang", "in_process", False, 10.0)
            led.record("hang", "mesh_shrink", False, 30.0)
            led.record("hang", "in_job", True, 60.0)
        assert led.pick_start_rung("hang") == "in_job"
        assert led.expected_cost("hang", "in_job") < led.expected_cost(
            "hang", "in_process"
        )

    def test_reliable_class_stays_at_top(self):
        led = RungLedger()
        for _ in range(5):
            led.record("exception", "in_process", True, 4.0)
        assert led.pick_start_rung("exception") == "in_process"

    def test_armed_rung_wins_over_pick(self):
        led = RungLedger()
        for _ in range(5):
            led.record("exception", "in_process", True, 4.0)
        led.arm("exception", "in_job", "operator override")
        assert led.start_rung("exception") == "in_job"
        led.disarm("exception")
        assert led.start_rung("exception") == "in_process"

    def test_ledger_accounting_vs_abort_ladder_counters(self, store_server):
        """Satellite cross-check: one real in-process restart episode must
        appear BOTH in the abort ladder's own run counter and as exactly
        one successful in_process episode in the policy ledger."""
        from tpu_resiliency.inprocess import Wrapper
        from tpu_resiliency.store import StoreClient

        reg = get_registry()
        runs_before = reg.value_of("tpurx_abort_ladder_runs_total")

        def factory():
            return StoreClient(
                "127.0.0.1", store_server.port, timeout=10.0
            )

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected fault")
            return "recovered"

        os.environ["TPURX_RANK"] = "0"
        os.environ["TPURX_WORLD_SIZE"] = "1"
        try:
            w = Wrapper(
                store_factory=factory,
                group="policy-ledger",
                enable_monitor_process=False,
                enable_sibling_monitor=False,
            )
            assert w(flaky)() == "recovered"
        finally:
            os.environ.pop("TPURX_RANK", None)
            os.environ.pop("TPURX_WORLD_SIZE", None)
        st = ledger().stats("exception", "in_process")
        assert st.attempts == 1 and st.successes == 1
        assert st.total_cost_s > 0.0  # measured recovery time, not a stub
        runs_after = reg.value_of("tpurx_abort_ladder_runs_total")
        # one restart episode <=> one abort-ladder execution
        assert runs_after - runs_before == st.attempts


# ---- controller loop --------------------------------------------------------


class _ScriptedFeed:
    """A feed whose collect() replays a scripted inputs sequence (the last
    entry repeats once the script runs out)."""

    def __init__(self, script):
        self.script = list(script)
        self.i = 0

    def collect(self):
        inputs = self.script[min(self.i, len(self.script) - 1)]
        self.i += 1
        return inputs


class _FakeStore:
    def __init__(self):
        self.data = {}

    def set(self, key, value):
        self.data[key] = value

    def delete(self, key):
        self.data.pop(key, None)

    def try_get(self, key):
        return self.data.get(key)


def _exception_script(period_s, ticks, tick_s, count0=0.0, ckpt_cost_s=2.0):
    return [
        EstimatorInputs(
            fault_counts={"exception": count0 + i * tick_s / period_s},
            ckpt_cost_s=ckpt_cost_s,
        )
        for i in range(ticks)
    ]


class TestPolicyController:
    def test_no_cadence_action_before_any_fault(self):
        ctl = PolicyController(
            feed=_ScriptedFeed([EstimatorInputs()]),
            estimator=GoodputEstimator(window_s=100.0),
        )
        actions = ctl.tick(now=0.0)
        assert not any(a.kind == "set_cadence" for a in actions)
        assert env.runtime_overrides().get(env.CKPT_INTERVAL_S.name) is None

    def test_cadence_converges_to_young_daly_after_rate_step(self):
        """Acceptance: drive the controller with a synthetic feed whose
        fault rate steps down; the applied cadence must first sit at the
        noisy-phase Young/Daly optimum, then relax toward the quiet one."""
        window = 100.0
        script = _exception_script(period_s=5.0, ticks=40, tick_s=5.0)
        script += _exception_script(
            period_s=50.0, ticks=100, tick_s=5.0, count0=40.0
        )
        ctl = PolicyController(
            feed=_ScriptedFeed(script),
            estimator=GoodputEstimator(window_s=window),
        )
        t = 0.0
        cadences = []
        for _ in range(140):
            ctl.tick(now=t)
            cadences.append(env.CKPT_INTERVAL_S.get())
            t += 5.0
        lo = env.POLICY_CADENCE_MIN_S.get()
        noisy_opt = max(lo, young_daly_interval(2.0, 5.0))
        quiet_opt = young_daly_interval(2.0, 50.0)
        assert cadences[39] == pytest.approx(noisy_opt, rel=0.3)
        assert cadences[-1] == pytest.approx(quiet_opt, rel=0.3)
        assert cadences[-1] > cadences[39]

    def test_decisions_journaled_and_counted(self):
        store = _FakeStore()
        reg = get_registry()
        before = reg.value_of(
            "tpurx_policy_decisions_total", {"action": "set_cadence"}
        )
        ctl = PolicyController(
            feed=_ScriptedFeed(
                _exception_script(period_s=5.0, ticks=30, tick_s=5.0)
            ),
            estimator=GoodputEstimator(window_s=100.0),
            store=store,
        )
        t = 0.0
        for _ in range(30):
            ctl.tick(now=t)
            t += 5.0
        assert ctl.journal, "applied actions must be journaled"
        after = reg.value_of(
            "tpurx_policy_decisions_total", {"action": "set_cadence"}
        )
        assert after - before >= 1
        # every journal record landed in the store, and latest parses back
        for rec in ctl.journal:
            assert store.try_get(f"policy/journal/{rec['seq']}") is not None
        seq, actions = decisions_from_json(store.try_get(K_DECISION_LATEST))
        assert seq == ctl.seq and actions

    def test_journal_keys_are_garbage_collected(self):
        store = _FakeStore()
        ctl = PolicyController(
            feed=_ScriptedFeed(
                _exception_script(period_s=2.0, ticks=200, tick_s=5.0)
            ),
            estimator=GoodputEstimator(window_s=50.0),
            store=store,
            journal_keep=4,
        )
        # force a fresh decision every tick: disable hysteresis damping
        env.set_runtime_override(env.POLICY_HYSTERESIS_PCT.name, "0")
        t = 0.0
        for _ in range(60):
            ctl.tick(now=t)
            t += 5.0
        assert ctl.seq > 8
        journal_keys = [
            k for k in store.data if k.startswith("policy/journal/")
        ]
        assert len(journal_keys) <= 4 + 1  # keep window (+latest in flight)
        assert f"policy/journal/{ctl.seq}" in store.data
        assert "policy/journal/1" not in store.data

    def test_risk_arms_replication_and_delta_then_relaxes(self):
        threshold = env.POLICY_RISK_THRESHOLD.get()
        risky = EstimatorInputs(
            fault_counts={"exception": 1.0}, node_risk=threshold + 0.2
        )
        calm = EstimatorInputs(fault_counts={"exception": 1.0}, node_risk=0.0)
        ctl = PolicyController(
            feed=_ScriptedFeed([risky, risky, calm, calm]),
            estimator=GoodputEstimator(window_s=100.0),
        )
        ctl.tick(now=0.0)
        ctl.tick(now=5.0)
        assert env.LCKPT_REPLICATION.get() == 3
        assert env.CKPT_DELTA.get() is True
        ctl.tick(now=10.0)
        ctl.tick(now=15.0)
        assert env.LCKPT_REPLICATION.get() is None  # override cleared
        assert env.runtime_overrides().get(env.CKPT_DELTA.name) is None

    def test_rung_decision_follows_ledger(self):
        for _ in range(4):
            ledger().record("exception", "in_process", False, 10.0)
            ledger().record("exception", "mesh_shrink", False, 30.0)
            ledger().record("exception", "in_job", True, 60.0)
        ctl = PolicyController(
            feed=_ScriptedFeed(
                _exception_script(period_s=5.0, ticks=10, tick_s=5.0)
            ),
            estimator=GoodputEstimator(window_s=100.0),
        )
        t = 0.0
        actions = []
        for _ in range(10):
            actions += ctl.tick(now=t)
            t += 5.0
        rung_actions = [a for a in actions if a.kind == "set_start_rung"]
        assert rung_actions and rung_actions[-1].value == "in_job"
        assert ledger().start_rung("exception") == "in_job"


# ---- per-rank client --------------------------------------------------------


class TestPolicyClient:
    def test_poll_applies_published_batch_once(self):
        from tpu_resiliency.fault_tolerance.control_plane import PolicyClient

        store = _FakeStore()
        ctl = PolicyController(
            feed=_ScriptedFeed(
                _exception_script(period_s=5.0, ticks=30, tick_s=5.0)
            ),
            estimator=GoodputEstimator(window_s=100.0),
            store=store,
        )
        t = 0.0
        for _ in range(30):
            ctl.tick(now=t)
            t += 5.0
        published_cadence = env.CKPT_INTERVAL_S.get()
        assert published_cadence is not None
        # a "different rank": overrides wiped, then the client re-applies
        env.clear_runtime_overrides()
        assert env.CKPT_INTERVAL_S.get() is None
        client = PolicyClient(store, poll_interval_s=3600.0)
        assert client.poll_once() > 0
        assert env.CKPT_INTERVAL_S.get() == pytest.approx(published_cadence)
        assert client.poll_once() == 0  # same seq: idempotent

    def test_empty_store_is_a_noop(self):
        from tpu_resiliency.fault_tolerance.control_plane import PolicyClient

        client = PolicyClient(_FakeStore(), poll_interval_s=3600.0)
        assert client.poll_once() == 0


# ---- health gauges (fault injection) ---------------------------------------


class TestHealthGauges:
    def test_kmsg_injection_raises_score_and_counter(self, tmp_path):
        from tpu_resiliency.health.kmsg import KernelLogHealthCheck

        reg = get_registry()
        hard_before = reg.value_of(
            "tpurx_kmsg_faults_total", {"class": "hard"}
        )
        log = tmp_path / "kern.log"
        log.write_text("")
        chk = KernelLogHealthCheck(
            source=str(log), window_s=60.0, threshold=2
        )
        assert chk.run().healthy  # attach + baseline on the empty log
        with log.open("a") as f:
            f.write("tpu0: device error, link reset requested\n")
        result = chk.run()
        assert result.healthy  # 1 hard line < threshold 2
        hard_after = reg.value_of(
            "tpurx_kmsg_faults_total", {"class": "hard"}
        )
        assert hard_after - hard_before == 1
        assert reg.value_of(
            "tpurx_health_score", {"check": "kernel_log"}
        ) == pytest.approx(0.5)  # 1 of threshold 2
        # a second hard line crosses the threshold -> unhealthy, score 1.0
        with log.open("a") as f:
            f.write("EDAC MC0: UE page fault\n")
        assert not chk.run().healthy
        assert reg.value_of(
            "tpurx_health_score", {"check": "kernel_log"}
        ) == pytest.approx(1.0)

    def test_health_score_feeds_estimator_risk(self):
        reg = Registry(enabled=True)
        reg.gauge("tpurx_health_score", labels=("check",)).labels(
            check="kernel_log"
        ).set(0.9)
        est = GoodputEstimator(window_s=100.0)
        est.update(TelemetryFeed(registry=reg).collect(), now=0.0)
        assert est.node_risk == pytest.approx(0.9)


# ---- the closed loop against the best fixed knob, on a simulated clock ------


def test_adaptive_policy_beats_best_fixed_cadence_in_simulation():
    """``harness/sim_policy.py`` drives the real estimator, actuator and rung
    ledger through a seeded discrete-event run whose fault regime steps from
    noisy to quiet.  Its docstring's promise, held here: the closed loop's
    goodput is at least the best fixed cadence's of a post-hoc sweep (mean
    gain over the trials >= 1.1), hang episodes learn to start at the rung
    that ends them, and the same seed gives the same report."""
    seed = 0xA11CE
    report = sim_policy.run(seed)
    assert report["policy_ok"], report
    assert report["policy_goodput_gain"] >= 1.1, report
    assert (
        report["policy_adaptive_goodput"] >= report["policy_best_fixed_goodput"]
    ), report
    assert report["policy_hang_start_rung"] == "in_job", report
    assert all(
        t["faults_injected"]["exception"] and t["faults_injected"]["hang"]
        for t in report["policy_trials"]
    ), report
    assert sim_policy.run(seed) == report
