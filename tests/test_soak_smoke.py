"""CI smoke of the chaos-soak regression gate (tests/harness/soak_launcher.py).

A compressed run of the full-stack gate: launcher + external journaled
control plane (randomly killed mid-run) + in-process ring + quorum
tripwire, randomized fault injection, detect->recover latencies derived
from the shared profiling JSONL with bounds asserted.  The 15-minute gate
is ``python tests/harness/soak_launcher.py --gate``; this smoke keeps the
same machinery honest on every suite run.
"""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SOAK = REPO / "tests" / "harness" / "soak_launcher.py"


def _soak(*flags):
    """Run one campaign of the soak launcher; its report (the last JSON
    line it prints)."""
    proc = subprocess.run(
        [sys.executable, str(SOAK), *flags],
        cwd=str(REPO), capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    last = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert last, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(last[-1])


def test_soak_smoke_chaos_store_and_quorum():
    report = _soak(
        "--seconds", "50", "--chaos-store", "--quorum",
        "--store-kill-every", "18", "28",
        "--exc-p", "0.02", "--qstall-p", "0.012", "--cwedge-p", "0.008",
        # generous bounds: this is a loaded 1-core CI host; the gate run
        # uses the defaults
        "--inner-bound-ms", "15000", "--outer-bound-ms", "60000",
    )
    assert report["ok"], report
    assert report["store_kills"] >= 1, report
    assert report["monotone_progress"], report
    # both rings actually exercised
    assert report["inner_ring_recoveries"] >= 1, report
    # the abort ladder ran on inner trips with recorded stage outcomes
    assert report["ladder_ok"], report
    if report["inner_ring_recoveries"]:
        assert report["abort_stage_outcomes"].get(
            "fingerprint/released", 0
        ) >= 1, report
    total_outer_faults = (
        report["injected"]["crashes"] + report["injected"]["hangs"]
    )
    if total_outer_faults:
        assert report["cycles"] >= 1, report


def test_soak_smoke_corrupt_blob_fallback_restore():
    """The checkpoint-integrity campaign: every copy of the newest local
    checkpoint is bit-flipped mid-run and the gang hard-restarts; the
    restarted ranks must detect + quarantine the corruption and
    fallback-restore the next-oldest valid iteration on all ranks."""
    report = _soak(
        "--seconds", "45", "--corrupt-blob", "bitflip",
    )
    assert report["ok"], report
    assert report["ckpt_ok"], report
    assert report["corrupted_iter"] is not None, report
    assert report["cycles"] >= 1, report
    # every rank fallback-restored an OLDER iteration with nonzero depth,
    # detected corruption, and left quarantine debris
    fb = report["fallback_restores"]
    assert {r[0] for r in fb} == {0, 1}, report
    for _rank, it, depth, corrupt, quarantined, debris in fb:
        assert it < report["corrupted_iter"]
        assert depth >= 1 and corrupt >= 1 and quarantined >= 1 and debris >= 1


def test_soak_smoke_peer_mem_kill_falls_to_disk():
    """The peer-memory-stall fault class: at the drill step the serving
    rank drops every peer-memory chunk request, so each other rank —
    resident copy shed — must time the rung out and restore from its OWN
    disk blob at fallback depth 0 (colder source, same iteration)."""
    report = _soak(
        "--seconds", "35", "--peer-mem-kill",
    )
    assert report["ok"], report
    assert report["peer_ok"], report
    drills = report["peer_drills"]
    assert {d[0] for d in drills} == {0, 1}, report
    for rank, _it, disk_b, peer_b, depth in drills:
        if rank != 0:  # rank 0 serves (and restores warm from its resident)
            assert disk_b > 0 and peer_b == 0 and depth == 0, report


def test_soak_smoke_link_degrade_no_restart():
    """The link_degrade fault class: rank 0's primary collective lane is
    armed to stall past its deadline every call; the resilient wrapper
    must absorb the bad link IN PROCESS (deadline trip -> retry ->
    re-layout), every rank must finish, and the launcher ring must record
    ZERO restart cycles."""
    report = _soak(
        "--seconds", "110", "--link-degrade",
    )
    assert report["ok"], report
    assert report["coll_ok"], report
    # zero pod-wide restarts: the whole point of the degrade ladder
    assert report["cycles"] == 0, report
    # the armed rank walked the ladder: deadline trips AND degrades
    assert report["coll_degrades"] >= 1, report
    assert report["coll_timeouts"] >= 1, report
    # the healthy rank never degraded
    marks = {m[0]: m for m in report["coll_marks"]}
    assert marks[1][1] == 0, report


def test_soak_smoke_store_outage_mid_save():
    """The store-outage-mid-save fault class: targeted store kills inside
    rank 0's store-backed save windows; the unified retry policy must ride
    the save through the outage (saves_done tracks saves_started)."""
    report = _soak(
        "--seconds", "55", "--store-kill-mid-save",
        "--save-every", "30", "--store-down", "2.0",
        # isolate the fault class: no random worker faults
        "--exc-p", "0", "--crash-p", "0", "--hang-p", "0",
        "--qstall-p", "0", "--cwedge-p", "0",
        "--inner-bound-ms", "15000", "--outer-bound-ms", "60000",
    )
    assert report["ok"], report
    assert report["saves_started"] >= 1, report
    assert report["saves_ok"], report
    assert report["store_kills"] >= 1, report
    assert report["monotone_progress"], report


def test_soak_smoke_ramp_degrade_evacuates_before_hard_fault():
    """The predict-and-evacuate campaign: one rank's health/straggler
    scores ramp worse round by round; the fused per-rank risk must
    evacuate it BEFORE its hard-fault deadline (zero HARD FAULT markers),
    never evacuate the healthy rank, and the evacuated slot must
    warm-join from peer memory with zero disk bytes — no global
    restore."""
    report = _soak(
        "--seconds", "120", "--ramp-degrade",
    )
    assert report["ok"], report
    assert report["evac_ok"], report
    assert report["hard_faults"] == 0, report
    # only the ramping victim was evacuated, exactly once
    assert [r for r, _s in report["evacuations"]] == [1], report
    # the slot's replacement joined warm: peer bytes, zero disk bytes
    for warm, _it, peer_b, disk_b in report["evac_joins"]:
        assert warm == "True" and peer_b > 0 and disk_b == 0, report


def test_soak_smoke_store_longpoll_abort_lands():
    """The interruptible-long-poll campaign: every restart episode parks
    one rank deep in a server-held store wait() and injects a sibling
    fault; the async abort must LAND on the parked rank within the
    propagation budget + 2x poll quantum (the historical flake parked the
    raise behind one ~30s uninterruptible recv) and no rank may ever exit
    ret=None."""
    report = _soak(
        "--seconds", "12", "--store-longpoll-abort",
        # loaded 1-core CI host: abort propagation (not the store
        # slicing) eats scheduler latency; the quantum contract itself
        # is asserted tightly by tests/test_store_interrupt.py
        "--longpoll-bound-s", "10.0",
    )
    assert report["ok"], report
    assert report["lp_ok"], report
    assert report["lp_episodes_injected"] >= 1, report
    # every completed episode's abort landed on the parked rank
    assert report["lp_episodes_landed"] >= 1, report
    assert report["lp_ret_none"] == 0, report
    assert report["lp_land_ms_median"] is not None, report


def test_fault_schedule_generation_is_deterministic():
    """Same seed -> byte-identical injection timeline (the property the
    adaptive-vs-fixed A/B rests on); different seed -> different draws;
    the regime shift multiplies fault density after shift_at."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "soak_launcher", str(SOAK))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    kw = dict(shift_at=1000, shift_mult=6.0)
    a = mod._gen_fault_schedule(7, 2, 4000, {"exception": 0.004}, **kw)
    b = mod._gen_fault_schedule(7, 2, 4000, {"exception": 0.004}, **kw)
    c = mod._gen_fault_schedule(8, 2, 4000, {"exception": 0.004}, **kw)
    assert a == b
    assert a["faults"] != c["faults"]
    pre = sum(1 for r in a["faults"].values() for s in r if int(s) < 1000)
    post = sum(1 for r in a["faults"].values() for s in r if int(s) >= 1000)
    # 3000 post-shift steps at 6x density vs 1000 pre-shift at 1x
    assert post > pre, (pre, post)


def test_soak_smoke_fault_shift_goodput_ab():
    """The adaptive-vs-fixed goodput A/B: both arms replay ONE seeded
    fault schedule; the adaptive arm closes the loop (estimator -> Young/
    Daly cadence -> SaveScheduler) on real telemetry.  The mechanics are
    what is held here: both arms finish ok, both make durable progress and
    a finite gain is reported.  Which arm wins is not read off 20 s of a
    shared CPU host; ``test_policy.py`` holds that ordering on the
    simulated clock of ``harness/sim_policy.py``."""
    report = _soak(
        "--fault-shift", "--seconds", "20", "--fault-seed", "11",
    )
    assert report["ok"], report
    assert report["arms_ok"], report
    assert math.isfinite(report["policy_goodput_gain"]), report
    assert report["fixed_progress"] > 0, report
    assert report["adaptive_progress"] > 0, report
