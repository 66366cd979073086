"""Units behind the chip bring-up: nothing on the main path may hide the
device, give two processes one chip, or lose the compile cache."""

import gzip
import json
import logging
import os
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from tpu_resiliency.utils import compile_cache


# -- compile cache ------------------------------------------------------------

def test_compile_cache_env_set_means_no_config_write(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert compile_cache.cache_dir() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself


def test_compile_cache_unset_is_one_fixed_path_in_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.enable() == os.path.join(repo, ".jax_cache")
    assert compile_cache.enable() == compile_cache.cache_dir()  # never moves
    assert calls == [("jax_compilation_cache_dir", compile_cache.DEFAULT_DIR)] * 2


def test_launcher_hands_every_worker_the_same_cache_dir(monkeypatch, tmp_path):
    import subprocess as sp

    from tpu_resiliency.fault_tolerance import launcher as launcher_mod

    seen = []

    class FakePopen:
        pid = 0

        def __init__(self, cmd, env=None, **kw):
            seen.append(env)

        def poll(self):
            return 0

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(sp, "Popen", FakePopen)
    agent = launcher_mod.ElasticAgent(
        launcher_mod.FaultToleranceConfig(), launcher_mod.WorkerSpec(["true"], 2),
        "127.0.0.1", 0,
    )
    agent.monitors = [(None, type("C", (), {"send": lambda s, m: None})(), "s")] * 2
    agent.store_port = 1
    result = launcher_mod.RendezvousResult(
        round_num=0, cycle=0, role=launcher_mod.NodeRole.PARTICIPANT,
        group_rank=0, group_world_size=1, rank_offset=0, global_world_size=2,
        participants=["n"],
    )
    agent._start_workers(result)
    agent.log_router.close()
    assert [e[compile_cache.ENV_VAR] for e in seen] == [compile_cache.DEFAULT_DIR] * 2


# -- no fallback that hides the device ---------------------------------------

def test_on_tpu_raises_when_the_backend_fails_to_initialise(monkeypatch):
    from tpu_resiliency.ops import quorum

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    mesh = Mesh(np.array(jax.devices()), ("d",))
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        quorum._on_tpu()
    # ... so the lane choice cannot quietly come out as jnp.max
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        quorum.QuorumMonitor(mesh)


def test_device_probe_names_its_platform_and_fails_cpu_on_a_tpu_host(monkeypatch):
    from tpu_resiliency.health import device as device_mod
    from tpu_resiliency.health import tpu as tpu_mod

    stats = [{"id": 0, "kind": "cpu", "platform": "cpu",
              "bytes_in_use": None, "bytes_limit": None}]
    line = "TPURX_DEVICE_OK " + json.dumps(stats)
    check = device_mod.DeviceHealthCheck()
    monkeypatch.setattr(tpu_mod, "visible_tpu_chips", lambda: [])
    ok = check._judge_stats(line)
    assert ok.healthy and "on platform cpu" in ok.message
    monkeypatch.setattr(tpu_mod, "visible_tpu_chips", lambda: ["vfio2"])
    bad = check._judge_stats(line)
    assert not bad.healthy and "came up on platform cpu" in bad.message
    stats[0].update(kind="TPU v5 lite", platform="tpu")
    tpu = check._judge_stats("TPURX_DEVICE_OK " + json.dumps(stats))
    assert tpu.healthy and "on platform tpu" in tpu.message


def test_health_gate_logs_the_probe_verdict(monkeypatch):
    from tpu_resiliency import health
    from tpu_resiliency.fault_tolerance import health_gate
    from tpu_resiliency.fault_tolerance.config import FaultToleranceConfig
    from tpu_resiliency.health.base import HealthCheckResult

    monkeypatch.setattr(
        health.DeviceHealthCheck, "_check",
        lambda self: HealthCheckResult(True, "1 device(s) healthy (cpu) on platform cpu"),
    )
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    health_gate.log.addHandler(handler)
    try:
        health_gate.pre_rendezvous_health_check(
            FaultToleranceConfig(), "node-a", current_cycle=3)
    finally:
        health_gate.log.removeHandler(handler)
    assert [r.getMessage() for r in records] == [
        "device health check (cycle 3): 1 device(s) healthy (cpu) on "
        "platform cpu"]


def test_v5e_host_surface_counts_vfio_groups_behind_google_pci(monkeypatch, tmp_path):
    """A v5e host has an empty /sys/class/accel and no /dev/accel*: its chips
    are VFIO group nodes beside PCI functions of vendor 0x1ae0."""
    from tpu_resiliency.health import tpu as tpu_mod

    (tmp_path / "accel").mkdir()
    vfio = tmp_path / "vfio"
    vfio.mkdir()
    for name in ("0", "1", "vfio"):  # the container node is not a chip
        (vfio / name).touch()
    check = tpu_mod.TpuSysHealthCheck(
        sys_accel=str(tmp_path / "accel"), dev_glob=str(tmp_path / "none*"),
        vfio_glob=str(vfio / "[0-9]*"),
    )
    monkeypatch.setattr(tpu_mod, "_google_pci_present", lambda *a: False)
    assert check._list_chips() == []  # somebody else's passthrough device
    monkeypatch.setattr(tpu_mod, "_google_pci_present", lambda *a: True)
    assert check._list_chips() == ["vfio0", "vfio1"]
    assert "2 accel device(s)" in check.run().message

    pci = tmp_path / "pci" / "0000:00:08.0"
    pci.mkdir(parents=True)
    (pci / "vendor").write_text("0x1ae0\n")
    monkeypatch.undo()
    assert tpu_mod._google_pci_present(str(tmp_path / "pci"))
    (pci / "vendor").write_text("0x8086\n")
    assert not tpu_mod._google_pci_present(str(tmp_path / "pci"))


def test_profile_parser_takes_device_ops_not_host_runtime_spans(tmp_path):
    """Layout of a trace taken on a v5e (jax 0.9.0): the device process has
    the ops on its "XLA Ops" lane; the host process's PJRT threads are
    runtime time and used to be counted as ops."""
    from tpu_resiliency.straggler.xla_profile import parse_trace_events

    def meta(kind, pid, name, tid=None):
        e = {"ph": "M", "name": kind, "pid": pid, "args": {"name": name}}
        return e if tid is None else {**e, "tid": tid}

    def span(pid, tid, name, dur):
        return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": 0, "dur": dur}

    events = [
        meta("process_name", 1, "/device:TPU:0"),
        meta("thread_name", 1, "XLA Modules", tid=1),
        meta("thread_name", 1, "XLA Ops", tid=2),
        meta("process_name", 2, "/host:CPU"),
        meta("thread_name", 2, "main/313", tid=1),
        span(1, 1, "jit_step(123)", 900.0),
        span(1, 2, "fusion.9", 400.0), span(1, 2, "fusion.9", 420.0),
        span(1, 2, "copy-start", 10.0),
        span(2, 1, "PJRT_LoadedExecutable_Execute", 567.0),
    ]
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    per_op, source = parse_trace_events(str(tmp_path))
    assert source == "device"
    assert sorted(per_op) == ["copy-start", "fusion.9"]
    assert per_op["fusion.9"] == pytest.approx([400e-6, 420e-6])
    # without a device process (the CPU backend) the host lanes are the ops
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [e for e in events if e["pid"] == 2]}, f)
    per_op, source = parse_trace_events(str(tmp_path))
    assert source == "host" and list(per_op) == ["PJRT_LoadedExecutable_Execute"]


# -- one process per chip -----------------------------------------------------

def test_worker_chip_env_one_worker_per_chip_or_refuses(monkeypatch):
    from tpu_resiliency.parallel.distributed import worker_chip_env

    monkeypatch.delenv("TPU_CHIPS_PER_HOST_BOUNDS", raising=False)
    four = ["vfio0", "vfio1", "vfio2", "vfio3"]
    assert worker_chip_env(2, 0, []) == {}        # a CPU host
    assert worker_chip_env(1, 0, four) == {}      # one worker drives them all
    envs = [worker_chip_env(4, lr, four) for lr in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    with pytest.raises(ValueError, match="a chip belongs to one process"):
        worker_chip_env(2, 0, ["vfio2"])          # the README's old quick start
    with pytest.raises(ValueError, match="--nproc-per-node 4"):
        worker_chip_env(2, 0, four)
    with pytest.raises(ValueError, match="TPU_CHIPS_PER_HOST_BOUNDS"):
        worker_chip_env(3, 0, ["a", "b", "c"])    # no layout known for 3


def test_launcher_refuses_workers_that_would_fight_over_a_chip(monkeypatch):
    from tpu_resiliency.fault_tolerance import launcher as launcher_mod
    from tpu_resiliency.health import tpu as tpu_mod

    monkeypatch.setattr(tpu_mod, "visible_tpu_chips", lambda: ["vfio2"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    args = launcher_mod.parse_args(["--nproc-per-node", "2", "w.py"])
    agent = launcher_mod.build_agent(args)
    with pytest.raises(ValueError, match="2 workers on a host with 1 TPU chip"):
        agent._chip_env(0)
    # pinned to the CPU backend the chips are nobody's
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert agent._chip_env(0) == {}


# -- the tripwire around protected sections and restarts ----------------------

def test_fence_drops_a_tick_whose_stamp_was_read_before_it():
    """On a TPU the dispatch of a tick takes long enough for a beat + fence
    (the exit of a protected section) to land inside it; the tick's age was
    read before the fence and must not fire after it."""
    from tpu_resiliency.ops.quorum import (
        QuorumMonitor, make_quorum_fn, now_stamp_ns,
    )

    mesh = Mesh(np.array(jax.devices()), ("d",))
    hits = []
    mon = QuorumMonitor(mesh, budget_ms=50.0, on_stale=hits.append)
    real = make_quorum_fn(mesh, use_pallas=False, blocking=False)

    def slow_dispatch(stamps):
        out = real(stamps)          # the stale age is in the program now
        mon.resume_auto_beat()      # beat + fence land mid-dispatch
        return out

    slow_dispatch.finish = real.finish
    mon._fn_async = slow_dispatch
    mon._last_beat_ns = now_stamp_ns() - 200_000_000
    assert mon.tick_pipelined() is None
    mon._fn_async = real
    age = mon.tick_pipelined()      # evaluates the pre-fence tick
    assert age > 50.0 and hits == []
    # an age read after the fence still fires
    mon._last_beat_ns = now_stamp_ns() - 200_000_000
    mon.tick_pipelined()
    mon.tick_pipelined()
    assert len(hits) == 1


def test_tripwire_is_suspended_over_the_restart_path(store):
    from tpu_resiliency.inprocess.quorum_tripwire import QuorumTripwire
    from tpu_resiliency.inprocess.store_ops import InprocStore

    mesh = Mesh(np.array(jax.devices()), ("d",))
    trip = QuorumTripwire(mesh, InprocStore(store, "g"), rank=0, budget_ms=40.0,
                          auto_beat_interval=None, calibrate=False,
                          use_pallas=False)
    trip.suspend()
    trip.suspend()  # idempotent: the saved budget is the real one
    assert trip.monitor.budget_ms == float("inf")
    time.sleep(0.06)
    assert trip.monitor.tick() > 40.0 and trip.trip_time is None
    trip.set_iteration(1)
    assert trip.monitor.budget_ms == 40.0
    time.sleep(0.06)
    trip.monitor.tick()
    assert trip.trip_time is not None
