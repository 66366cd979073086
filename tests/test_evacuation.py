"""Predict-and-evacuate tests (ISSUE 18): noisy-OR rank risk fusion and
its damping, the straggler-gauge → feed → estimator attribution path (a
synthetic slow rank must move the estimator's risk output), the
controller's streak/hysteresis evacuation trigger, the one-shot actuator
action and its per-rank replay dispatch, the pipeline's
checkpoint-ahead → promote → shrink stages with bounded store records,
the warm-join deadline, and the merged-trace rendering of evacuation
spans."""

import json
import threading

import pytest

from tpu_resiliency.policy import (
    Action,
    Actuator,
    EstimatorInputs,
    EvacuationPipeline,
    GoodputEstimator,
    PolicyController,
    RankRiskModel,
    RankSignals,
    SnapshotFeed,
    TelemetryFeed,
    set_evacuation_handler,
    _reset_ledger_for_tests,
)
from tpu_resiliency.policy import evacuation as evac_mod
from tpu_resiliency.telemetry import episode as episode_mod
from tpu_resiliency.telemetry import trace
from tpu_resiliency.telemetry.registry import Registry
from tpu_resiliency.utils import env

from harness import sim_evac


@pytest.fixture(autouse=True)
def _clean_evac_state():
    """Fresh overrides/ledger/episode/handler state around every test."""
    env.clear_runtime_overrides()
    _reset_ledger_for_tests()
    set_evacuation_handler(None)
    episode_mod._current = None
    yield
    env.clear_runtime_overrides()
    _reset_ledger_for_tests()
    set_evacuation_handler(None)
    episode_mod._current = None


class _FakeStore:
    def __init__(self):
        self.data = {}
        self.counters = {}

    def set(self, key, value):
        self.data[key] = value

    def add(self, key, amount):
        self.counters[key] = self.counters.get(key, 0) + amount
        return self.counters[key]

    def delete(self, key):
        self.data.pop(key, None)

    def try_get(self, key):
        return self.data.get(key)

    def list_keys(self, prefix):
        return [k for k in self.data if k.startswith(prefix)]


# ---- RankRiskModel ----------------------------------------------------------


class TestRankRiskModel:
    def test_single_saturated_indicator_is_sufficient(self):
        """Noisy-OR: health pegged at 1.0 alone drives the fused score to
        1.0 (damped toward it tick by tick)."""
        m = RankRiskModel(window_s=60.0)
        sig = {1: RankSignals(health_score=1.0)}
        assert m.update(sig, now=0.0)[1] == pytest.approx(0.5)
        assert m.update(sig, now=5.0)[1] == pytest.approx(0.75)
        assert m.update(sig, now=10.0)[1] == pytest.approx(0.875)

    def test_independent_indicators_compound(self):
        """Two moderate signals fuse above either alone: noisy-OR, not
        averaging."""
        both = RankRiskModel.fuse(
            RankSignals(health_score=0.5, straggler_score=0.5), 0.0
        )
        health_only = RankRiskModel.fuse(RankSignals(health_score=0.5), 0.0)
        strag_only = RankRiskModel.fuse(
            RankSignals(straggler_score=0.5), 0.0
        )
        assert both == pytest.approx(0.7)
        assert both > health_only and both > strag_only

    def test_straggler_alone_is_capped(self):
        """A dead-slow rank (score 0) is not certain death: the straggler
        component saturates below 1."""
        raw = RankRiskModel.fuse(RankSignals(straggler_score=0.0), 0.0)
        assert raw == pytest.approx(0.8)

    def test_route_bias_discounted(self):
        raw = RankRiskModel.fuse(RankSignals(route_bias=1.0), 0.0)
        assert raw == pytest.approx(0.6)

    def test_kmsg_hard_fault_saturates_component(self):
        """One hard kmsg fault inside the window pegs that component."""
        m = RankRiskModel(window_s=60.0)
        m.update({0: RankSignals(kmsg_hard_total=0.0)}, now=0.0)
        scores = m.update({0: RankSignals(kmsg_hard_total=1.0)}, now=10.0)
        # raw fused = 1.0, EWMA from 0 → 0.5 on this tick
        assert scores[0] == pytest.approx(0.5)

    def test_absent_rank_decays_and_forget_clears(self):
        m = RankRiskModel(window_s=60.0)
        m.update({2: RankSignals(health_score=1.0)}, now=0.0)
        m.update({2: RankSignals(health_score=1.0)}, now=5.0)
        high = m.scores[2]
        m.update({}, now=10.0)
        m.update({}, now=15.0)
        assert m.scores[2] < high
        m.forget(2)
        assert 2 not in m.scores
        assert m.worst() == (None, 0.0)

    def test_deadband_suppresses_flutter(self):
        m = RankRiskModel(window_s=60.0)
        m.update({0: RankSignals(health_score=0.5)}, now=0.0)
        for t in range(1, 30):
            m.update({0: RankSignals(health_score=0.5)}, now=float(t))
        settled = m.scores[0]
        # a sub-deadband wiggle in the raw signal publishes nothing new
        m.update({0: RankSignals(health_score=0.51)}, now=31.0)
        assert m.scores[0] == settled

    def test_worst_picks_riskiest_rank(self):
        m = RankRiskModel(window_s=60.0)
        m.update(
            {
                0: RankSignals(health_score=0.2),
                3: RankSignals(health_score=0.9),
            },
            now=0.0,
        )
        rank, score = m.worst()
        assert rank == 3 and score == pytest.approx(0.45)


# ---- satellite 1: straggler gauge → feed → estimator risk -------------------


class TestStragglerRiskAttribution:
    def test_synthetic_slow_rank_moves_estimator_risk(self):
        """The published ``tpurx_straggler_score{rank}`` gauge must reach
        the estimator: a synthetic slow rank raises that rank's fused
        risk (and the node risk the hardening rung keys off), attributed
        to the right rank."""
        reg = Registry(enabled=True)
        feed = TelemetryFeed(registry=reg, rank=0)
        est = GoodputEstimator(window_s=60.0)
        est.update(feed.collect(), now=0.0)
        baseline = dict(est.rank_risk)
        assert est.node_risk == 0.0

        score = reg.gauge(
            "tpurx_straggler_score", "individual score", labels=("rank",)
        )
        score.labels("1").set(0.2)   # rank 1 running at 20% of nominal
        score.labels("0").set(1.0)
        for t in (5.0, 10.0, 15.0):
            est.update(feed.collect(), now=t)
        assert est.rank_risk[1] > baseline.get(1, 0.0)
        assert est.rank_risk[1] > 0.5
        assert est.rank_risk.get(0, 0.0) == pytest.approx(0.0)
        assert est.worst_rank()[0] == 1
        assert est.node_risk == pytest.approx(est.rank_risk[1])

    def test_snapshot_feed_attributes_signals_per_rank(self):
        """Cross-rank shape: each rank's snapshot carries its own node
        health; straggler scores ride the {rank} label on the report
        holder's snapshot and are assigned by label, not by holder."""
        snaps = {
            0: {
                "tpurx_straggler_score": {
                    "samples": [
                        {"labels": {"rank": "0"}, "value": 1.0},
                        {"labels": {"rank": "1"}, "value": 0.3},
                    ]
                },
            },
            1: {
                "tpurx_health_score": {
                    "samples": [{"labels": {"check": "ecc"}, "value": 0.9}]
                },
            },
        }
        signals = SnapshotFeed._rank_signals(snaps)
        assert signals[1].health_score == pytest.approx(0.9)
        assert signals[1].straggler_score == pytest.approx(0.3)
        assert signals[0].health_score == 0.0
        assert signals[0].straggler_score == pytest.approx(1.0)

    def test_empty_rank_signals_preserve_node_risk_semantics(self):
        """Backward compatibility: with no per-rank signals the estimator
        carries the legacy gauge-fed node risk unchanged."""
        est = GoodputEstimator(window_s=60.0)
        est.update(EstimatorInputs(node_risk=0.4), now=0.0)
        assert est.node_risk == pytest.approx(0.4)
        assert est.rank_risk == {}


# ---- controller trigger -----------------------------------------------------


def _risky_inputs(rank=1, health=1.0):
    return EstimatorInputs(
        rank_signals={rank: RankSignals(health_score=health)}
    )


class _ScriptedFeed:
    def __init__(self, script):
        self.script = list(script)
        self.i = 0

    def collect(self):
        inputs = self.script[min(self.i, len(self.script) - 1)]
        self.i += 1
        return inputs


class TestControllerEvacuate:
    def test_disabled_by_default(self):
        ctl = PolicyController(
            feed=_ScriptedFeed([_risky_inputs()]),
            estimator=GoodputEstimator(window_s=60.0),
        )
        for t in range(6):
            actions = ctl.tick(now=float(t * 5))
            assert not [a for a in actions if a.kind == "evacuate"]

    def test_fires_after_streak_and_is_one_shot(self):
        env.set_runtime_override(env.EVAC.name, "1")
        fired = []
        set_evacuation_handler(lambda rank, reason: fired.append(rank))
        ctl = PolicyController(
            feed=_ScriptedFeed([_risky_inputs(rank=1)]),
            estimator=GoodputEstimator(window_s=60.0),
        )
        evacs = []
        for t in range(8):
            evacs += [
                a for a in ctl.tick(now=float(t * 5)) if a.kind == "evacuate"
            ]
        # EWMA crosses 0.7 on tick 2; streak guard delays the fire one
        # more tick; the actuator one-shot stops any repeat
        assert len(evacs) == 1
        assert evacs[0].target == "rank:1" and evacs[0].value == "1"
        assert fired == [1]

    def test_streak_resets_on_dip(self):
        """A single over-threshold tick followed by recovery never
        evacuates (false-positive guard)."""
        env.set_runtime_override(env.EVAC.name, "1")
        script = (
            [_risky_inputs(rank=1, health=1.0)] * 2    # risk reaches ~0.75
            + [_risky_inputs(rank=1, health=0.0)] * 10  # decays back down
        )
        ctl = PolicyController(
            feed=_ScriptedFeed(script),
            estimator=GoodputEstimator(window_s=60.0),
        )
        evacs = []
        for t in range(12):
            evacs += [
                a for a in ctl.tick(now=float(t * 5)) if a.kind == "evacuate"
            ]
        assert evacs == []
        assert ctl._evac_streak.get(1, 0) == 0

    def test_healthy_ranks_never_evacuated(self):
        """Moderate, steady signals below threshold must not trigger."""
        env.set_runtime_override(env.EVAC.name, "1")
        inputs = EstimatorInputs(
            rank_signals={
                0: RankSignals(health_score=0.3, straggler_score=0.9),
                1: RankSignals(health_score=0.2),
            }
        )
        ctl = PolicyController(
            feed=_ScriptedFeed([inputs]),
            estimator=GoodputEstimator(window_s=60.0),
        )
        for t in range(20):
            actions = ctl.tick(now=float(t * 5))
            assert not [a for a in actions if a.kind == "evacuate"]

    def test_hardening_armed_at_or_before_evacuation(self):
        """The fused rank risk feeds node risk, so replication/delta
        hardening arms on the same tick the risk crosses — never after
        the evacuation decision."""
        env.set_runtime_override(env.EVAC.name, "1")
        ctl = PolicyController(
            feed=_ScriptedFeed([_risky_inputs(rank=1)]),
            estimator=GoodputEstimator(window_s=60.0),
        )
        seen = []
        for t in range(6):
            for a in ctl.tick(now=float(t * 5)):
                seen.append(a.kind)
        assert "evacuate" in seen
        assert seen.index("set_replication") < seen.index("evacuate")

    def test_rearm_latch_follows_hysteresis_band(self):
        env.set_runtime_override(env.EVAC.name, "1")
        script = (
            [_risky_inputs(rank=1, health=1.0)] * 4
            + [_risky_inputs(rank=1, health=0.0)] * 20
        )
        ctl = PolicyController(
            feed=_ScriptedFeed(script),
            estimator=GoodputEstimator(window_s=60.0),
        )
        for t in range(4):
            ctl.tick(now=float(t * 5))
        assert ctl._evac_armed.get(1) is False  # latched after the fire
        for t in range(4, 24):
            ctl.tick(now=float(t * 5))
        # risk decayed below threshold·(1−hysteresis): latch re-arms
        assert ctl._evac_armed.get(1) is True


# ---- actuator ---------------------------------------------------------------


class TestActuatorEvacuate:
    def test_one_shot_per_rank(self):
        act = Actuator()
        first = act.evacuate(2, "risk 0.9")
        assert first is not None and first.kind == "evacuate"
        assert first.target == "rank:2"
        assert act.evacuate(2, "risk 0.95") is None
        assert act.evacuate(3, "risk 0.9") is not None

    def test_apply_dispatches_to_handler_once(self):
        fired = []
        set_evacuation_handler(lambda rank, reason: fired.append((rank, reason)))
        act = Actuator()
        action = Action("evacuate", "rank:3", "3", "published decision")
        act.apply(action)
        act.apply(action)  # replayed decision must not double-evacuate
        assert fired == [(3, "published decision")]

    def test_apply_knob_actions_unaffected(self):
        act = Actuator()
        act.apply(Action("set_cadence", env.CKPT_INTERVAL_S.name, "42.0", "t"))
        assert env.CKPT_INTERVAL_S.get() == pytest.approx(42.0)

    def test_evacuate_without_handler_is_journal_only(self):
        act = Actuator()
        assert act.evacuate(1, "no handler installed") is not None


# ---- pipeline ---------------------------------------------------------------


class TestEvacuationPipeline:
    def _pipeline(self, store=None, **kw):
        kw.setdefault("save_fn", lambda: kw.setdefault("_saved", True))
        return EvacuationPipeline(store=store, rank=0, **kw)

    def test_stages_run_and_record_published(self):
        store = _FakeStore()
        calls = []
        pipe = EvacuationPipeline(
            store=store,
            rank=0,
            save_fn=lambda: calls.append("save"),
            promote_fn=lambda victim: calls.append("promote") or "h:9",
            shrink_fn=lambda victim: calls.append(f"shrink:{victim}") or "ok",
        )
        record = pipe.evacuate(1, risk=0.84, reason="test")
        assert calls == ["save", "promote", "shrink:1"]
        assert record["victim_rank"] == 1 and record["spare"] == "h:9"
        # checkpoint-ahead bumped replication for the handoff
        assert env.LCKPT_REPLICATION.get() >= 3
        published = json.loads(store.data["evac/1/record"])
        assert published["victim_rank"] == 1
        assert published["episode"].startswith("ep")

    def test_episode_phases_include_evacuate_with_exact_coverage(self):
        store = _FakeStore()
        pipe = EvacuationPipeline(
            store=store, rank=0, shrink_fn=lambda victim: None
        )
        pipe.evacuate(1, risk=0.9)
        summaries = [
            k for k in store.data if k.startswith("episode/ep")
        ]
        assert summaries, "episode summary not published"
        summary = json.loads(store.data[summaries[0]])
        assert summary["fault_class"] == "evacuation"
        assert "evacuate" in summary["phases_ns"]
        assert summary["coverage_pct"] == pytest.approx(100.0, abs=0.5)

    def test_record_window_is_bounded(self):
        store = _FakeStore()
        pipe = EvacuationPipeline(
            store=store, rank=0, shrink_fn=lambda victim: None, keep=2
        )
        for victim in (1, 2, 3):
            episode_mod._current = None
            pipe.evacuate(victim, risk=0.9)
        assert "evac/1/record" not in store.data
        assert "evac/2/record" in store.data and "evac/3/record" in store.data

    def test_failed_stage_raises_and_records_error(self):
        store = _FakeStore()

        def _boom(victim):
            raise RuntimeError("promotion lost the CAS race")

        pipe = EvacuationPipeline(
            store=store, rank=0, promote_fn=_boom,
            shrink_fn=lambda victim: None,
        )
        with pytest.raises(RuntimeError):
            pipe.evacuate(1, risk=0.9)
        published = json.loads(store.data["evac/1/record"])
        assert "promotion lost the CAS race" in published["error"]

    def test_nonvictim_shrink_is_noop(self):
        """Default shrink path: every rank but the victim returns
        immediately (survivors keep training)."""
        pipe = EvacuationPipeline(store=None, rank=0)
        record = pipe.evacuate(1, risk=0.9)  # we are rank 0, victim is 1
        assert record["shrink"] is None


# ---- warm join --------------------------------------------------------------


class _FakeManager:
    def __init__(self, result=("tree", 7), error=None, block=None):
        self.result = result
        self.error = error
        self.block = block

    def load(self, template, iteration=None):
        if self.block is not None:
            self.block.wait()
        if self.error is not None:
            raise self.error
        return self.result


class TestWarmJoin:
    def test_warm_when_no_disk_bytes(self, monkeypatch):
        sources = iter([{}, {"peer_memory": 4096.0}])
        monkeypatch.setattr(
            evac_mod, "_restore_source_bytes", lambda: next(sources)
        )
        pipe = EvacuationPipeline(store=None, rank=2)
        out = pipe.warm_join(_FakeManager(), template={}, timeout=5.0)
        assert out["warm"] is True
        assert out["iteration"] == 7
        assert out["source_bytes"] == {"peer_memory": 4096.0}

    def test_cold_when_disk_rung_served(self, monkeypatch):
        sources = iter([{}, {"peer_memory": 10.0, "peer_disk": 4086.0}])
        monkeypatch.setattr(
            evac_mod, "_restore_source_bytes", lambda: next(sources)
        )
        pipe = EvacuationPipeline(store=None, rank=2)
        out = pipe.warm_join(_FakeManager(), template={}, timeout=5.0)
        assert out["warm"] is False

    def test_deadline_raises_timeout(self):
        gate = threading.Event()
        pipe = EvacuationPipeline(store=None, rank=2)
        try:
            with pytest.raises(TimeoutError):
                pipe.warm_join(
                    _FakeManager(block=gate), template={}, timeout=0.05
                )
        finally:
            gate.set()

    def test_load_error_propagates(self):
        pipe = EvacuationPipeline(store=None, rank=2)
        with pytest.raises(ValueError):
            pipe.warm_join(
                _FakeManager(error=ValueError("no candidates")),
                template={}, timeout=5.0,
            )


# ---- satellite 4: merged trace renders the evacuation span ------------------


def _rec(event, mono_ns, rank, **fields):
    return {"event": event, "mono_ns": mono_ns, "rank": rank, **fields}


class TestEvacuationTrace:
    def test_risk_cross_to_join_renders_one_span(self):
        out = trace.to_chrome_trace([
            _rec("evac.risk_cross", 1_000, 0, victim=1, risk=0.82,
                 episode="ep9"),
            _rec("evac.ckpt_ahead", 2_000, 0, victim=1, episode="ep9"),
            _rec("evac.promote", 3_000, 0, victim=1, spare="h:9",
                 episode="ep9"),
            _rec("evac.join", 9_000, 0, victim=1, source="peer_memory",
                 bytes=4096, dur_ms=1.5, episode="ep9"),
        ])["traceEvents"]
        spans = [e for e in out if e.get("ph") == "X"]
        assert len(spans) == 1
        span = spans[0]
        assert span["name"] == "evacuation" and span["cat"] == "evac"
        assert span["dur"] == pytest.approx(8.0)
        assert span["args"]["source"] == "peer_memory"

    def test_merged_dump_renders_evacuation_span(self, tmp_path):
        path = tmp_path / "flight.jsonl"
        with open(path, "w") as f:
            for rec in [
                {"event": "_flight_meta", "mono_ns": 0, "host": "h0",
                 "rank": 0},
                _rec("evac.risk_cross", 5_000, 0, victim=1, risk=0.9,
                     episode="ep2"),
                _rec("evac.join", 25_000, 0, victim=1, source="peer_memory",
                     bytes=128, dur_ms=0.02, episode="ep2"),
            ]:
                f.write(json.dumps(rec) + "\n")
        merged = trace.to_chrome_trace(
            trace.load_aligned([str(path)], warn=False)
        )
        names = [
            e["name"] for e in merged["traceEvents"] if e.get("ph") == "X"
        ]
        assert "evacuation" in names


# ---- evacuate against react, on a simulated clock --------------------------


def test_evacuation_beats_reacting_with_no_healthy_rank_evacuated():
    """``harness/sim_evac.py`` feeds a ramping victim and noisy healthy ranks
    through the real controller (risk model, streak guard, re-arm latch,
    one-shot actuator) on a seeded simulated clock.  Its docstring's promise,
    held here: evacuating ahead of the hard fault keeps at least the goodput
    of reacting after it (mean gain over the trials >= 1.1), no healthy rank
    is ever evacuated, no ramp is missed, and the same seed gives the same
    report."""
    seed = 0xE7AC
    report = sim_evac.run(seed)
    assert report["evac_ok"], report
    assert report["evac_goodput_gain"] >= 1.1, report
    assert report["evac_goodput"] >= report["react_goodput"], report
    assert report["evac_false_positives"] == 0, report
    assert report["evac_missed"] == 0, report
    assert all(
        t["degradations"] and t["evacuations"] == t["degradations"]
        for t in report["evac_trials"]
    ), report
    assert sim_evac.run(seed) == report
