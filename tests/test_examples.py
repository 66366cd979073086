"""Every example script stays runnable (the reference ships its examples as
living documentation; broken examples are worse than none)."""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from tests.test_launcher import free_port

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"


def _scrub_env(env):
    """Keep example subprocesses on the CPU backend."""
    from tpu_resiliency.utils.env import force_cpu_env

    return force_cpu_env(env)


def _run(script, env_extra=None, timeout=180, args=()):
    env = _scrub_env(dict(os.environ))
    env["TPURX_REPO"] = str(REPO)
    env.update(env_extra or {})
    out = subprocess.run(
        [sys.executable, str(script), *args],
        cwd=str(REPO), env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, (
        f"{script} rc={out.returncode}\n{out.stdout[-1500:]}\n{out.stderr[-1500:]}"
    )
    return out


def test_attribution_example():
    out = _run(EXAMPLES / "attribution" / "single_server_example.py")
    assert "category:      oom_hbm" in out.stdout
    assert "should_resume: False" in out.stdout


def test_async_ckpt_example():
    out = _run(EXAMPLES / "checkpointing" / "async_ckpt.py")
    assert "async checkpoint roundtrip OK" in out.stdout


def test_local_ckpt_example():
    out = _run(EXAMPLES / "checkpointing" / "local_ckpt.py")
    assert "recovered from clique buddy" in out.stdout


def test_straggler_example():
    out = _run(EXAMPLES / "straggler" / "example.py")
    assert "always-on collector: 16 samples" in out.stdout


def test_health_example():
    out = _run(EXAMPLES / "utils" / "node_health_check_example.py")
    assert "node is" in out.stdout  # healthy or not — runs either way


def test_inprocess_basic_example(store_server):
    env = {
        "TPURX_STORE_ADDR": "127.0.0.1",
        "TPURX_STORE_PORT": str(store_server.port),
        "TPURX_WORLD_SIZE": "2",
    }
    procs = []
    try:
        for r in range(2):
            e = _scrub_env(
                dict(os.environ, TPURX_REPO=str(REPO), TPURX_RANK=str(r), **env)
            )
            procs.append(subprocess.Popen(
                [sys.executable,
                 str(EXAMPLES / "inprocess" / "basic_example.py")],
                cwd=str(REPO), env=e, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            ))
        outs = [p.communicate(timeout=180)[0] for p in procs]
    finally:
        for p in procs:  # never leak children on timeout/assert failure
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-1500:]
        assert "result: ok@1" in out, out[-1500:]  # restarted past the fault


def test_inprocess_advanced_example(store_server):
    env = {
        "TPURX_STORE_ADDR": "127.0.0.1",
        "TPURX_STORE_PORT": str(store_server.port),
        "TPURX_RANK": "0",
        "TPURX_WORLD_SIZE": "1",
    }
    out = _run(EXAMPLES / "inprocess" / "advanced_example.py", env_extra=env)
    assert "result: done" in out.stdout


@pytest.mark.parametrize("script,cfg", [
    ("basic_ft_example.py", None),
    ("sections_example.py", "ft_cfg_sections.yaml"),
])
def test_ft_examples_under_launcher(tmp_path, script, cfg):
    env = _scrub_env(dict(os.environ))
    env.update({
        "TPURX_REPO": str(REPO),
        "TPURX_FT_ENABLE_DEVICE_HEALTH_CHECK": "0",
        "FT_STATE": str(tmp_path / "state_{}.json"),
    })
    cmd = [
        sys.executable, "-m", "tpu_resiliency.fault_tolerance.launcher",
        "--nnodes", "1", "--nproc-per-node", "2", "--host-store",
        "--rdzv-endpoint", f"127.0.0.1:{free_port()}",
    ]
    if cfg:
        cmd += ["--ft-cfg", str(EXAMPLES / "fault_tolerance" / cfg)]
    cmd += ["--", str(EXAMPLES / "fault_tolerance" / script)]
    out = subprocess.run(
        cmd, cwd=str(REPO), env=env, capture_output=True, text=True,
        timeout=180,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done" in out.stdout


def test_quick_start_worker_under_launcher_tiny_widths(tmp_path):
    """The README quick start: launcher -> Wrapper -> real train step ->
    async save, at a width a CPU takes in seconds (the chip runs the declared
    widths through ``chip_smoke.py``; the fault phases are rehearsed in
    ``tests/test_chip_smoke.py``)."""
    env = _scrub_env(dict(os.environ))
    env.pop("XLA_FLAGS", None)  # one CPU device, like one chip
    report = tmp_path / "report.jsonl"
    cmd = [
        sys.executable, "-m", "tpu_resiliency.fault_tolerance.launcher",
        "--nnodes", "1", "--nproc-per-node", "1", "--host-store",
        "--rdzv-endpoint", f"127.0.0.1:{free_port()}", "--max-restarts", "1",
        "--", str(EXAMPLES / "train_with_launcher.py"),
        "--vocab", "512", "--d-model", "64", "--n-heads", "4",
        "--n-layers", "2", "--d-ff", "128", "--seq", "32", "--batch", "4",
        "--steps", "6", "--save-every", "3",
        "--ckpt-dir", str(tmp_path / "ckpts"),
        "--progress-file", str(tmp_path / "progress"),
        "--report", str(report),
    ]
    out = subprocess.run(
        cmd, cwd=str(REPO), env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    import json

    events = [json.loads(line) for line in report.read_text().splitlines()]
    steps = [e for e in events if e["ev"] == "step"]
    assert [e["step"] for e in steps] == list(range(6))
    assert steps[-1]["loss"] < steps[0]["loss"]
    assert len([e for e in events if e["ev"] == "commit"]) == 2
    done = [e for e in events if e["ev"] == "done"]
    assert done and done[0]["step_compiles"] == 1

