"""Flight recorder, fault episodes, clock alignment, trace merge, exporter.

Covers the observability stack end to end:

- ``telemetry/flight.py``: ring semantics, declaration discipline, the
  ``TPURX_FLIGHT=0`` no-op path, JSONL dumps (meta header, throttling,
  retention, hooks).
- ``telemetry/episode.py``: phase decomposition summing to wall time by
  construction, the store-minted id, cross-rank claim convergence,
  sidecar adoption, ``read_episodes``.
- ``telemetry/clock.py``: RTT-midpoint calibration against a live
  reference recovers a known injected skew.
- ``telemetry/trace.py``: per-file offset alignment, unaligned-host
  warning, span pairing, episode phase spans and cross-rank flows.
- ``telemetry/exporter.py``: OpenMetrics escaping golden, concurrent
  scrape under mutation, ``GET /flight``.
- A two-rank soak (one rank's clock skewed to simulate a second host):
  black-box dumps at trip time, ONE merged aligned timeline with the
  episode's six phases and flow arrows, and ``GET /episodes`` matching
  the store's phase totals.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import types
import urllib.request
from pathlib import Path

import pytest

from tpu_resiliency.telemetry import clock as clock_mod
from tpu_resiliency.telemetry import episode as episode_mod
from tpu_resiliency.telemetry import flight, trace
from tpu_resiliency.telemetry.clock import ClockOffset
from tpu_resiliency.telemetry.exporter import (
    MetricsHTTPServer,
    render_openmetrics,
)
from tpu_resiliency.telemetry.registry import Registry
from tpu_resiliency.utils.env import force_cpu_env

REPO = Path(__file__).resolve().parent.parent
WORKER = str(REPO / "tests" / "workloads" / "inproc_worker.py")

# one test-only event, declared once at import like production call sites
EV_TEST = flight.declare_event("test.unit_event", "k")


@pytest.fixture(autouse=True)
def _reset_telemetry_state():
    """Flight/episode/clock keep process-global state; leave none behind."""
    flight.configure()
    flight.set_current_episode("")
    flight._last_dump_ns.clear()
    clock_mod.set_offset(None)
    with episode_mod._lock:
        episode_mod._current = None
    yield
    flight.configure()
    flight.set_current_episode("")
    flight._last_dump_ns.clear()
    clock_mod.set_offset(None)
    with episode_mod._lock:
        episode_mod._current = None


# ---- the ring ---------------------------------------------------------------


class TestRing:
    def test_capacity_rounds_up_to_power_of_two(self):
        assert flight.FlightRecorder(4).capacity == 4
        assert flight.FlightRecorder(5).capacity == 8
        assert flight.FlightRecorder(0).capacity == 2
        assert flight.FlightRecorder(4096).capacity == 4096

    def test_overwrites_oldest(self):
        ring = flight.FlightRecorder(4)
        for i in range(12):
            ring.record("test.unit_event", i)
        assert len(ring) == 4
        assert [slot[3][0] for slot in ring.snapshot()] == [8, 9, 10, 11]

    def test_snapshot_sorted_by_timestamp(self):
        ring = flight.FlightRecorder(16)
        for i in range(10):
            ring.record("test.unit_event", i)
        stamps = [slot[0] for slot in ring.snapshot()]
        assert stamps == sorted(stamps)

    def test_records_tagged_with_current_episode(self):
        ring = flight.FlightRecorder(4)
        flight.set_current_episode("ep42")
        ring.record("test.unit_event", 1)
        flight.set_current_episode("")
        ring.record("test.unit_event", 2)
        episodes = [slot[2] for slot in ring.snapshot()]
        assert episodes == ["ep42", ""]


class TestDeclaration:
    def test_invalid_names_rejected(self):
        for bad in ("nodot", "Upper.case", "has space.x", "1leading.x", "a."):
            with pytest.raises(ValueError):
                flight.declare_event(bad)

    def test_duplicate_declaration_rejected(self):
        with pytest.raises(ValueError, match="declared twice"):
            flight.declare_event("test.unit_event", "k")

    def test_registered(self):
        assert "test.unit_event" in flight.event_names()
        assert flight.event_fields("test.unit_event") == ("k",)


class TestConfigure:
    def test_disabled_is_noop(self):
        flight.configure(enabled=False)
        assert flight.get_flight() is flight.NOOP
        flight.record(EV_TEST, 1)  # must not raise, must not record
        assert len(flight.get_flight()) == 0
        assert flight.dump("disabled", min_interval_s=0.0) is None

    def test_reenable_rebinds_record(self):
        flight.configure(enabled=False)
        flight.configure(enabled=True, capacity=8)
        flight.record(EV_TEST, 7)
        ring = flight.get_flight()
        assert ring.capacity == 8
        assert len(ring) == 1


# ---- dumps ------------------------------------------------------------------


class TestDump:
    def test_dump_writes_meta_then_sorted_records(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))
        flight.configure(enabled=True, capacity=64)
        for i in range(5):
            flight.record(EV_TEST, i)
        path = flight.dump("unit", min_interval_s=0.0)
        assert path is not None and os.path.exists(path)
        assert os.path.basename(path).startswith("flight-")
        assert path.endswith("-unit.jsonl")
        records = [json.loads(line) for line in open(path)]
        meta, rest = records[0], records[1:]
        assert meta["event"] == "_flight_meta"
        assert meta["reason"] == "unit"
        assert meta["pid"] == os.getpid()
        assert meta["capacity"] == 64
        assert meta["events"] == len(rest)
        stamps = [r["mono_ns"] for r in rest]
        assert stamps == sorted(stamps)
        # declared field names, not positional argN keys
        ks = [r["k"] for r in rest if r["event"] == "test.unit_event"]
        assert ks == [0, 1, 2, 3, 4]

    def test_meta_carries_clock_offset(self):
        flight.configure(enabled=True, capacity=8)
        clock_mod.set_offset(ClockOffset(offset_ns=123, rtt_ns=456))
        meta = json.loads(flight.render_jsonl("request").splitlines()[0])
        assert meta["clock_offset_ns"] == 123
        assert meta["clock_rtt_ns"] == 456
        assert meta["clock_ref"] == "rank0"

    def test_per_reason_throttle(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))
        flight.configure(enabled=True, capacity=8)
        flight.record(EV_TEST, 1)
        assert flight.dump("trip") is not None
        assert flight.dump("trip") is None          # throttled, same reason
        assert flight.dump("other") is not None     # distinct reason passes
        assert flight.dump("trip", min_interval_s=0.0) is not None

    def test_retention(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("TPURX_FLIGHT_DUMP_KEEP", "2")
        flight.configure(enabled=True, capacity=8)
        flight.record(EV_TEST, 1)
        paths = [
            flight.dump(f"keep{i}", min_interval_s=0.0) for i in range(4)
        ]
        assert all(paths)
        assert not os.path.exists(paths[0])
        assert not os.path.exists(paths[1])
        assert os.path.exists(paths[2])
        assert os.path.exists(paths[3])
        assert flight.last_dump_path() == paths[3]

    def test_dump_hooks_fed_parsed_records(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))
        flight.configure(enabled=True, capacity=8)
        flight.record(EV_TEST, 9)
        seen = []
        hook = seen.append
        flight.add_dump_hook(hook)
        try:
            flight.dump("hooked", min_interval_s=0.0)
        finally:
            flight.remove_dump_hook(hook)
        assert len(seen) == 1
        records = seen[0]
        assert records[0]["event"] == "_flight_meta"
        assert any(
            r["event"] == "test.unit_event" and r["k"] == 9 for r in records
        )

    def test_failing_hook_does_not_break_dump(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPURX_FLIGHT_DIR", str(tmp_path))
        flight.configure(enabled=True, capacity=8)
        flight.record(EV_TEST, 1)

        def bad_hook(records):
            raise RuntimeError("hook boom")

        flight.add_dump_hook(bad_hook)
        try:
            assert flight.dump("hooked", min_interval_s=0.0) is not None
        finally:
            flight.remove_dump_hook(bad_hook)


# ---- episodes ---------------------------------------------------------------


class TestEpisode:
    def test_local_fallback_lifecycle(self):
        ep = episode_mod.begin(fault_class="unit")
        assert ep.id.startswith("ep-local-")
        assert flight.current_episode_id() == ep.id
        assert episode_mod.current() is ep
        ep.phase("decide")
        ep.phase("decide")  # idempotent: no duplicate mark
        ep.phase("resume")
        phases = ep.close()
        assert set(phases) == {"detect", "decide", "resume"}
        # transition-based accounting: phases sum to wall exactly
        assert sum(phases.values()) == ep.wall_ns
        assert ep.coverage_pct() == pytest.approx(100.0)
        assert flight.current_episode_id() == ""
        assert episode_mod.current() is None
        assert ep in episode_mod.recent()

    def test_begin_is_idempotent_while_live(self):
        ep = episode_mod.begin(fault_class="unit")
        again = episode_mod.begin(fault_class="refined")
        assert again is ep
        assert ep.fault_class == "refined"
        ep.close()
        assert episode_mod.begin(fault_class="unit") is not ep

    def test_close_is_idempotent(self):
        ep = episode_mod.begin(fault_class="unit")
        first = ep.close()
        assert ep.close() == first

    def test_phase_histogram_observed_on_close(self):
        from tpu_resiliency.telemetry import get_registry

        fam = get_registry().get("tpurx_episode_phase_ns")
        assert fam is not None
        child = fam.labels("detect", "histo_unit")
        before = child.count
        ep = episode_mod.begin(fault_class="histo_unit")
        ep.close()
        assert child.count == before + 1

    def test_store_mint_publish_read(self, store):
        ep = episode_mod.begin(store=store, fault_class="unit")
        assert re.fullmatch(r"ep\d+", ep.id)
        assert store.try_get(episode_mod.CURRENT_KEY) == ep.id.encode()
        ep.phase("decide")
        time.sleep(0.01)
        ep.phase("resume")
        ep.close()
        # rank 0 close clears the job-wide current key
        assert store.try_get(episode_mod.CURRENT_KEY) == b""
        summary = json.loads(store.try_get(f"episode/{ep.id}/rank/0"))
        assert summary["fault_class"] == "unit"
        assert set(summary["phases_ns"]) == {"detect", "decide", "resume"}
        eps = episode_mod.read_episodes(store, n=5)
        assert eps and eps[0]["id"] == ep.id
        assert eps[0]["phase_ns"] == {
            k: int(v) for k, v in summary["phases_ns"].items()
        }
        assert eps[0]["wall_ns"] == summary["wall_ns"]

    def test_claim_converges_on_first_proposal(self, store):
        from tpu_resiliency.inprocess.store_ops import InprocStore

        ops = InprocStore(store)
        assert ops.claim_episode(3, "epA") == "epA"
        assert ops.claim_episode(3, "epB") == "epA"   # loser adopts winner
        assert ops.claim_episode(4, "epB") == "epB"   # new iteration, new claim
        ops.gc_iteration(3)
        assert ops.claim_episode(3, "epC") == "epC"

    def test_adopt_tags_sidecar_without_local_episode(self, store):
        store.set(episode_mod.CURRENT_KEY, "ep7")
        assert episode_mod.adopt(store) == "ep7"
        assert flight.current_episode_id() == "ep7"
        # a process with its own live episode keeps its tag
        flight.set_current_episode("")
        ep = episode_mod.begin(fault_class="unit")
        assert episode_mod.adopt(store) == "ep7"
        assert flight.current_episode_id() == ep.id
        ep.close()

    def test_current_or_store_id(self, store):
        assert episode_mod.current_or_store_id() == ""
        store.set(episode_mod.CURRENT_KEY, "ep9")
        assert episode_mod.current_or_store_id(store) == "ep9"
        ep = episode_mod.begin(fault_class="unit")
        assert episode_mod.current_or_store_id(store) == ep.id
        ep.close()


# ---- clock calibration ------------------------------------------------------


class TestClock:
    def test_calibrate_against_live_reference(self, store):
        ref = clock_mod.ClockReference(store).start()
        try:
            off = clock_mod.calibrate(store, rounds=4, set_global=False)
        finally:
            ref.stop()
        # same process = same clock domain: true offset is 0, error <= RTT
        assert off.rtt_ns > 0
        assert abs(off.offset_ns) <= off.rtt_ns
        assert clock_mod.offset() is None  # set_global=False left it alone

    def test_calibrate_recovers_injected_skew(self, store, monkeypatch):
        skew = 250_000_000  # this "host" reads 250ms ahead of the reference
        monkeypatch.setattr(
            clock_mod, "mono_ns", lambda: time.monotonic_ns() + skew
        )
        ref = clock_mod.ClockReference(store).start()
        try:
            off = clock_mod.calibrate(store, rounds=4, set_global=True)
        finally:
            ref.stop()
        # offset must cancel the skew: local + offset ~ reference domain
        assert abs(off.offset_ns + skew) <= max(off.rtt_ns, 10_000_000)
        assert clock_mod.offset() == off


# ---- trace merge ------------------------------------------------------------


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return str(path)


def _rec(event, mono_ns, rank, **fields):
    return {"event": event, "mono_ns": mono_ns, "rank": rank, **fields}


class TestTrace:
    def test_load_aligned_applies_per_file_offset(self, tmp_path):
        fa = _write_jsonl(tmp_path / "a.jsonl", [
            {"event": "_flight_meta", "mono_ns": 0, "host": "hosta", "rank": 0},
            _rec("monitor.heartbeat", 1_000_000, 0),
        ])
        fb = _write_jsonl(tmp_path / "b.jsonl", [
            {
                "event": "_flight_meta", "mono_ns": 0, "host": "hostb",
                "rank": 1, "clock_offset_ns": -500_000,
            },
            _rec("monitor.heartbeat", 1_600_000, 1),
        ])
        events = trace.load_aligned([fa, fb], warn=False)
        by_rank = {e["rank"]: e["mono_ns"] for e in events}
        assert by_rank[0] == 1_000_000      # reference domain: unshifted
        assert by_rank[1] == 1_100_000      # shifted into the reference

    def test_two_unaligned_hosts_warn(self, tmp_path, capsys):
        fa = _write_jsonl(tmp_path / "a.jsonl", [
            {"event": "_flight_meta", "mono_ns": 0, "host": "ha", "rank": 0},
            _rec("monitor.heartbeat", 1, 0),
        ])
        fb = _write_jsonl(tmp_path / "b.jsonl", [
            {"event": "_flight_meta", "mono_ns": 0, "host": "hb", "rank": 1},
            _rec("monitor.heartbeat", 2, 1),
        ])
        trace.load_aligned([fa, fb])
        err = capsys.readouterr().err
        assert "no clock offset" in err
        assert "ha" in err and "hb" in err

    def test_single_unaligned_host_does_not_warn(self, tmp_path, capsys):
        fa = _write_jsonl(tmp_path / "a.jsonl", [
            {"event": "_flight_meta", "mono_ns": 0, "host": "ha", "rank": 0},
            _rec("monitor.heartbeat", 1, 0),
        ])
        fb = _write_jsonl(tmp_path / "b.jsonl", [
            {
                "event": "_flight_meta", "mono_ns": 0, "host": "hb",
                "rank": 1, "clock_offset_ns": 5,
            },
            _rec("monitor.heartbeat", 2, 1),
        ])
        trace.load_aligned([fa, fb])
        assert "no clock offset" not in capsys.readouterr().err

    def test_flight_span_pairing(self):
        out = trace.to_chrome_trace([
            _rec("monitor.section_begin", 1_000, 0, section="load"),
            _rec("collective.dispatch", 2_000, 0, op="all_reduce", axis="dp"),
            _rec("collective.settle", 9_000, 0,
                 op="all_reduce", axis="dp", status="ok"),
            _rec("monitor.section_end", 11_000, 0, section="load"),
        ])["traceEvents"]
        spans = {e["name"]: e for e in out if e.get("ph") == "X"}
        assert spans["section"]["dur"] == pytest.approx(10.0)
        assert spans["section"]["args"]["section"] == "load"
        assert spans["collective"]["dur"] == pytest.approx(7.0)
        assert spans["collective"]["args"]["status"] == "ok"

    def test_dangling_start_becomes_unfinished_instant(self):
        out = trace.to_chrome_trace([
            _rec("monitor.section_begin", 1_000, 0, section="load"),
            _rec("monitor.heartbeat", 2_000, 0),
        ])["traceEvents"]
        names = [e["name"] for e in out]
        assert "section (unfinished)" in names

    def test_episode_phase_spans_and_cross_rank_flows(self):
        out = trace.to_chrome_trace([
            _rec("episode.begin", 0, 0, episode="ep5", fault_class="x"),
            _rec("episode.phase", 0, 0, episode="ep5", phase="detect"),
            _rec("episode.begin", 1_000, 1, episode="ep5", fault_class="x"),
            _rec("episode.phase", 1_000, 1, episode="ep5", phase="detect"),
            _rec("episode.phase", 10_000, 0, episode="ep5", phase="decide"),
            _rec("episode.close", 20_000, 0,
                 episode="ep5", fault_class="x", wall_ns=20_000),
            _rec("episode.close", 15_000, 1,
                 episode="ep5", fault_class="x", wall_ns=14_000),
        ])["traceEvents"]
        phase_spans = [
            e for e in out if e.get("ph") == "X" and e["cat"] == "episode"
        ]
        by_track = {}
        for e in phase_spans:
            by_track.setdefault(e["pid"], []).append(e["name"])
        assert by_track[0] == ["detect", "decide"]
        assert by_track[1] == ["detect"]
        flows = [e for e in out if e.get("ph") in ("s", "t", "f")]
        assert {e["ph"] for e in flows} == {"s", "f"}
        assert all(e["args"]["episode"] == "ep5" for e in flows)
        assert {e["pid"] for e in flows} == {0, 1}
        assert len({e["id"] for e in flows}) == 1


# ---- exporter ---------------------------------------------------------------


class TestExporter:
    def test_openmetrics_escaping_golden(self):
        reg = Registry(enabled=True)
        c = reg.counter(
            "tpurx_test_esc_total", 'help "q" \\ and\nnewline',
            labels=("path",),
        )
        c.labels('a\\b"c\nd').inc(3)
        assert render_openmetrics(reg) == (
            "# TYPE tpurx_test_esc counter\n"
            "# HELP tpurx_test_esc help \\\"q\\\" \\\\ and\\nnewline\n"
            'tpurx_test_esc_total{path="a\\\\b\\"c\\nd"} 3\n'
            "# EOF\n"
        )

    def test_histogram_rendering_golden(self):
        reg = Registry(enabled=True)
        h = reg.histogram("tpurx_test_hist_ns", buckets=(10.0, 100.0))
        h.observe(5)
        h.observe(50)
        h.observe(5000)
        assert render_openmetrics(reg) == (
            "# TYPE tpurx_test_hist_ns histogram\n"
            'tpurx_test_hist_ns_bucket{le="10"} 1\n'
            'tpurx_test_hist_ns_bucket{le="100"} 2\n'
            'tpurx_test_hist_ns_bucket{le="+Inf"} 3\n'
            "tpurx_test_hist_ns_sum 5055\n"
            "tpurx_test_hist_ns_count 3\n"
            "# EOF\n"
        )

    def test_concurrent_scrape_under_mutation(self):
        reg = Registry(enabled=True)
        c = reg.counter("tpurx_test_conc_total", labels=("worker",))
        h = reg.histogram("tpurx_test_conc_ns")
        server = MetricsHTTPServer(reg, host="127.0.0.1", port=0).start()
        stop = threading.Event()

        def mutate(i):
            while not stop.is_set():
                c.labels(str(i)).inc()
                h.observe(1000.0 * (i + 1))

        threads = [
            threading.Thread(target=mutate, args=(i,), daemon=True)
            for i in range(4)
        ]
        for t in threads:
            t.start()
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            for _ in range(20):
                body = urllib.request.urlopen(url, timeout=10).read().decode()
                assert body.endswith("# EOF\n")
                # every exposition scraped mid-mutation is well-formed:
                # sample lines end in one parseable number
                for line in body.splitlines():
                    if not line or line.startswith("#"):
                        continue
                    float(line.rsplit(" ", 1)[1])
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            server.close()

    def test_get_flight_serves_live_ring(self):
        flight.configure(enabled=True, capacity=16)
        flight.record(EV_TEST, 31)
        server = MetricsHTTPServer(
            Registry(enabled=True), host="127.0.0.1", port=0
        ).start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/flight", timeout=10
            ).read().decode()
        finally:
            server.close()
        records = [json.loads(line) for line in body.splitlines()]
        assert records[0]["event"] == "_flight_meta"
        assert records[0]["reason"] == "http"
        assert any(
            r["event"] == "test.unit_event" and r["k"] == 31 for r in records
        )


# ---- two-rank soak: dumps at trip + merged aligned timeline -----------------

# rank 1's monotonic domain runs 5s ahead — a simulated second host whose
# dumps only line up after calibration-based alignment
_SOAK_SKEW_NS = 5_000_000_000


def _spawn_rank(store_port, rank, world, scenario, extra_env):
    env = dict(os.environ)
    env.update({
        "TPURX_REPO": str(REPO),
        "TPURX_RANK": str(rank),
        "TPURX_WORLD_SIZE": str(world),
        "TPURX_STORE_ADDR": "127.0.0.1",
        "TPURX_STORE_PORT": str(store_port),
        "SCENARIO": scenario,
        "STEPS": "30",
    })
    force_cpu_env(env)
    env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, WORKER],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        cwd=str(REPO),
    )


def _read_dump_meta(path):
    with open(path) as f:
        return json.loads(f.readline())


def test_two_rank_soak_black_boxes_and_aligned_timeline(
    store_server, tmp_path
):
    flight_dir = tmp_path / "flight"
    base = {"TPURX_FLIGHT_DIR": str(flight_dir)}
    procs = [
        _spawn_rank(store_server.port, 0, 2, "exception", base),
        _spawn_rank(
            store_server.port, 1, 2, "exception",
            {**base, "TPURX_CLOCK_TEST_SKEW_NS": str(_SOAK_SKEW_NS)},
        ),
    ]
    outs = {}
    for rank, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n<TIMEOUT>"
        outs[rank] = out
    for rank, p in enumerate(procs):
        assert p.returncode == 0, f"rank {rank}:\n{outs[rank][-2500:]}"
        assert "RESULT" in outs[rank]

    # 1. black boxes: every process dumped, and at least one dump fired at
    #    the detection instant (trip/ladder), not just at exit
    dumps = sorted(str(p) for p in flight_dir.glob("flight-*.jsonl"))
    assert dumps, "no flight dumps written"
    metas = {path: _read_dump_meta(path) for path in dumps}
    assert {m["pid"] for m in metas.values()} == {p.pid for p in procs}
    assert any(
        m["reason"] in ("monitor_trip", "abort_ladder")
        for m in metas.values()
    ), f"no trip-time dump among {[m['reason'] for m in metas.values()]}"
    exit_dumps = {
        m["rank"]: path
        for path, m in metas.items() if m["reason"] == "worker_exit"
    }
    assert set(exit_dumps) == {0, 1}

    # 2. calibration recovered the injected skew: rank 1's dumps carry an
    #    offset that cancels it (error bounded by loopback RTT)
    rank1_meta = metas[exit_dumps[1]]
    assert abs(rank1_meta["clock_offset_ns"] + _SOAK_SKEW_NS) < 1_000_000_000

    # 3. one merged timeline: all six phases of the fault episode appear as
    #    spans, connected across the two ranks' tracks by flow arrows
    merged = trace.to_chrome_trace(trace.load_aligned(dumps, warn=False))
    events = merged["traceEvents"]
    ep_spans = [
        e for e in events
        if e.get("ph") == "X" and e.get("cat") == "episode"
        and e["args"].get("episode") == "ep1"
    ]
    phase_names = {e["name"].replace(" (unfinished)", "") for e in ep_spans}
    assert phase_names >= set(episode_mod.REACTIVE_PHASES), (
        f"episode phases missing from merged trace: "
        f"{set(episode_mod.REACTIVE_PHASES) - phase_names}"
    )
    assert {e["pid"] for e in ep_spans} == {0, 1}
    flows = [
        e for e in events
        if e.get("ph") in ("s", "t", "f") and e["args"].get("episode") == "ep1"
    ]
    assert {e["ph"] for e in flows} >= {"s", "f"}
    assert {e["pid"] for e in flows} == {0, 1}

    # 4. alignment made the timeline causal: both ranks saw the fault within
    #    seconds of each other; unaligned, rank 1 would sit ~5s off
    begin_ts = {}
    for e in events:
        if e.get("name") == "episode.begin":
            begin_ts.setdefault(e["pid"], e["ts"])
    assert set(begin_ts) == {0, 1}
    assert abs(begin_ts[0] - begin_ts[1]) < _SOAK_SKEW_NS / 1e3 / 2, (
        f"episode.begin instants {begin_ts} still ~skew apart — "
        "per-file offset not applied"
    )

    # 5. the store's episode record decomposes MTTR across all six phases,
    #    and GET /episodes serves the same totals
    from tpu_resiliency.services.smonsvc import make_status_server
    from tpu_resiliency.store import StoreClient

    client = StoreClient("127.0.0.1", store_server.port, timeout=10.0)
    try:
        eps = episode_mod.read_episodes(client, n=5)
        assert eps and eps[0]["id"] == "ep1"
        phase_ns = eps[0]["phase_ns"]
        assert set(phase_ns) >= set(episode_mod.REACTIVE_PHASES)
        assert all(v > 0 for v in phase_ns.values())

        monitor = types.SimpleNamespace(episode_store=client)
        server = make_status_server(monitor, "127.0.0.1", 0)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.server_port}/episodes", timeout=10
            ).read()
        finally:
            server.shutdown()
            server.server_close()
            thread.join(timeout=5)
        payload = json.loads(body)
        assert payload["enabled"] is True
        served = {e["id"]: e for e in payload["episodes"]}
        assert served["ep1"]["phase_ns"] == phase_ns
    finally:
        client.close()
