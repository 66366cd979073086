"""Health checks, profiling recorder, log config tests (previously indirect)."""

import json
import logging
import os
import time

import pytest

from tpu_resiliency.health import (
    ChainedHealthCheck,
    DeviceHealthCheck,
    HealthCheck,
    HealthCheckResult,
    NicLinkHealthCheck,
    NodeResourceHealthCheck,
    StoragePathHealthCheck,
)
from tpu_resiliency.utils.profiling import ProfilingEvent, ProfilingRecorder


class _Fail(HealthCheck):
    name = "always_fail"

    def _check(self):
        return HealthCheckResult(False, "nope")


class _Pass(HealthCheck):
    name = "always_pass"

    def _check(self):
        return HealthCheckResult(True, "fine")


class _Boom(HealthCheck):
    name = "crasher"

    def _check(self):
        raise RuntimeError("check exploded")


class TestHealthChecks:
    def test_chained_fail_fast(self):
        result = ChainedHealthCheck([_Pass(), _Fail(), _Pass()]).run()
        assert not result.healthy
        assert result.name == "always_fail"

    def test_chained_collect_all(self):
        result = ChainedHealthCheck([_Fail(), _Boom()], fail_fast=False).run()
        assert not result.healthy
        assert "always_fail" in result.message and "crasher" in result.message

    def test_crashing_check_is_unhealthy(self):
        result = _Boom().run()
        assert not result.healthy
        assert "check exploded" in result.message
        assert result.duration_s >= 0

    def test_node_resources_ok_by_default(self):
        assert NodeResourceHealthCheck().run().healthy

    def test_node_resources_disk_threshold(self, tmp_path):
        result = NodeResourceHealthCheck(
            min_free_disk_mb=10 ** 9, disk_path=str(tmp_path)
        ).run()
        assert not result.healthy
        assert "low disk" in result.message

    def test_storage_probe_roundtrip(self, tmp_path):
        result = StoragePathHealthCheck(str(tmp_path)).run()
        assert result.healthy
        # no probe files left behind
        assert not list(tmp_path.iterdir())

    def test_storage_probe_unwritable(self, tmp_path):
        # a regular file as path parent fails regardless of uid (root
        # ignores permission bits, so chmod-based denial would not)
        blocker = tmp_path / "file"
        blocker.write_text("x")
        result = StoragePathHealthCheck(str(blocker / "sub")).run()
        assert not result.healthy

    def test_nic_link_check_with_fake_sysfs(self, tmp_path):
        for iface, state in (("eth0", "up"), ("eth1", "down")):
            d = tmp_path / iface
            d.mkdir()
            (d / "operstate").write_text(state + "\n")
        ok = NicLinkHealthCheck(["eth0"], sys_net=str(tmp_path)).run()
        assert ok.healthy
        bad = NicLinkHealthCheck(sys_net=str(tmp_path)).run()
        assert not bad.healthy
        assert "eth1=down" in bad.message

    def test_device_probe_via_subprocess(self):
        DeviceHealthCheck.clear_cache()
        result = DeviceHealthCheck(
            timeout=120, env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
        ).run()
        assert result.healthy, result.message
        # cached on second run
        again = DeviceHealthCheck(timeout=1).run()
        assert again.healthy and "cached" in again.message
        DeviceHealthCheck.clear_cache()


class TestProfilingRecorder:
    def test_records_and_latency(self, tmp_path):
        path = str(tmp_path / "prof.jsonl")
        rec = ProfilingRecorder(path=path, cycle=2)
        rec.record(ProfilingEvent.FAILURE_DETECTED, rank=3)
        time.sleep(0.01)
        rec.record(ProfilingEvent.WORKER_STARTED)
        lat = rec.latency_ns(ProfilingEvent.FAILURE_DETECTED, ProfilingEvent.WORKER_STARTED)
        assert lat is not None and lat > 0
        lines = [json.loads(l) for l in open(path)]
        assert lines[0]["event"] == "_flight_meta"  # alignment header
        assert lines[1]["event"] == "failure_detected"
        assert lines[1]["cycle"] == 2
        assert lines[1]["rank"] == 3

    def test_latency_none_when_missing(self):
        rec = ProfilingRecorder()
        assert rec.latency_ns(ProfilingEvent.FAILURE_DETECTED, ProfilingEvent.WORKER_STARTED) is None


def test_log_funnel_gap_detection(tmp_path):
    """A skipped batch sequence is surfaced in the aggregate log."""
    import socket
    import struct

    from tpu_resiliency.utils.log_funnel import RootLogServer

    root = RootLogServer(str(tmp_path / "agg.log"), host="127.0.0.1", flush_age=0.05)
    U32 = struct.Struct("<I")

    def send(batch):
        raw = json.dumps(batch).encode()
        s = socket.create_connection(("127.0.0.1", root.port))
        s.sendall(U32.pack(len(raw)) + raw)
        s.close()

    send({"source": "n1", "seq": 1, "lines": ["a"]})
    # each batch has a connection and a handler thread of its own: the second
    # goes once the first is in the file, or a loaded host may take them in
    # the other order and see no gap
    agg = tmp_path / "agg.log"
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and not (
            agg.exists() and "[n1] a" in agg.read_text()):
        time.sleep(0.02)
    send({"source": "n1", "seq": 4, "lines": ["b"], "dropped": 2})
    time.sleep(0.4)
    root.close()
    content = (tmp_path / "agg.log").read_text()
    assert "[n1] a" in content and "[n1] b" in content
    assert "GAP from n1" in content
    assert "dropped 2 lines" in content


def test_shm_janitor_removes_only_orphans(tmp_path, monkeypatch):
    from multiprocessing import shared_memory

    import tpu_resiliency.utils.shm_janitor as sj

    # held segment: must survive; orphan: must be removed (age forced)
    held = shared_memory.SharedMemory(create=True, size=1024)
    orphan = shared_memory.SharedMemory(create=True, size=1024)
    orphan_name = orphan.name
    orphan.close()  # unmapped by everyone, but still linked in /dev/shm
    try:
        ours = {held.name.lstrip("/"), orphan_name.lstrip("/")}
        monkeypatch.setattr(
            sj, "_age",
            lambda path: 10_000.0 if path.rsplit("/", 1)[1] in ours else 0.0,
        )
        removed = sj.sweep(min_age_s=600.0)
        assert orphan_name.lstrip("/") in [r.lstrip("/") for r in removed]
        assert held.name.lstrip("/") not in [r.lstrip("/") for r in removed]
        # held segment still usable
        held.buf[0] = 7
        assert held.buf[0] == 7
    finally:
        held.close()
        held.unlink()
        try:
            shared_memory.SharedMemory(name=orphan_name).unlink()
        except FileNotFoundError:
            pass


class TestConfig:
    def test_yaml_section_discovery_nested(self, tmp_path):
        from tpu_resiliency.fault_tolerance.config import FaultToleranceConfig

        # the section hides inside an arbitrary trainer config tree
        (tmp_path / "trainer.yaml").write_text(
            "trainer:\n"
            "  devices: 8\n"
            "  plugins:\n"
            "    fault_tolerance:\n"
            "      rank_heartbeat_timeout: 120.5\n"
            "      max_nodes: 4\n"
            "      rank_section_timeouts: {step: 60}\n"
        )
        cfg = FaultToleranceConfig.from_yaml(str(tmp_path / "trainer.yaml"))
        assert cfg.rank_heartbeat_timeout == 120.5
        assert cfg.max_nodes == 4
        assert cfg.rank_section_timeouts == {"step": 60}

    def test_yaml_missing_section(self, tmp_path):
        from tpu_resiliency.fault_tolerance.config import FaultToleranceConfig

        (tmp_path / "c.yaml").write_text("foo: {bar: 1}\n")
        with pytest.raises(ValueError, match="not found"):
            FaultToleranceConfig.from_yaml(str(tmp_path / "c.yaml"))

    def test_unknown_key_rejected(self):
        from tpu_resiliency.fault_tolerance.config import FaultToleranceConfig

        with pytest.raises(ValueError, match="unknown"):
            FaultToleranceConfig.from_dict({"not_a_real_field": 1})

    def test_env_null_disables_timeout(self, monkeypatch):
        from tpu_resiliency.fault_tolerance.config import FaultToleranceConfig

        monkeypatch.setenv("TPURX_FT_RANK_HEARTBEAT_TIMEOUT", "null")
        cfg = FaultToleranceConfig().merged_with_env()
        assert cfg.rank_heartbeat_timeout is None


class TestDataModel:
    def test_timeouts_json_roundtrip(self):
        from tpu_resiliency.fault_tolerance.data import (
            HeartbeatTimeouts,
            SectionTimeouts,
            heartbeat_timeouts_from_dict,
            heartbeat_timeouts_to_dict,
            section_timeouts_from_dict,
            section_timeouts_to_dict,
        )

        hb = HeartbeatTimeouts(initial=10.0, subsequent=None, were_calculated=True)
        assert heartbeat_timeouts_from_dict(heartbeat_timeouts_to_dict(hb)) == hb
        st = SectionTimeouts(
            section={"step": 5.0, "ckpt": None}, out_of_section=9.0,
            calculated_sections=("step",), calculated_out_of_section=True,
        )
        back = section_timeouts_from_dict(section_timeouts_to_dict(st))
        assert back.section == st.section
        assert back.out_of_section == st.out_of_section
        assert back.calculated_sections == st.calculated_sections

    def test_workload_control_roundtrip(self):
        from tpu_resiliency.fault_tolerance.data import (
            WorkloadAction,
            WorkloadControlRequest,
        )

        req = WorkloadControlRequest(WorkloadAction.ExcludeThisNode, "bad hbm")
        back = WorkloadControlRequest.from_json(req.to_json())
        assert back.action == WorkloadAction.ExcludeThisNode
        assert back.reason == "bad hbm"


def test_cycle_log_router_caps_file_size(tmp_path):
    import os

    from tpu_resiliency.fault_tolerance.per_cycle_logs import CycleLogRouter

    router = CycleLogRouter(str(tmp_path), tee_to_stdout=False,
                            max_bytes_per_cycle=200)
    router.start_cycle(0)
    w_fd = router.make_worker_pipe(0, "out")
    with os.fdopen(w_fd, "w") as wf:
        for i in range(100):
            wf.write(f"spam line {i}\n")
    time.sleep(0.3)
    router.close()
    content = (tmp_path / "cycle_0.log").read_text()
    assert "TRUNCATED" in content
    assert len(content) < 1000  # capped, not 100 lines
    assert "spam line 0" in content
