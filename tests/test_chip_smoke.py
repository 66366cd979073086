"""``chip_smoke.py``: the composed restart path, rehearsed on the CPU.

The real run needs a TPU (the driver makes it on every PR).  Tier-1 runs the
``--cpu-rehearsal`` form end to end — launcher CLI, the quick-start worker at
tiny widths with the bf16 + master tree, exception, stall, SIGKILL, respawn —
and checks that without the flag and without a chip the script refuses.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from tpu_resiliency.health.tpu import visible_tpu_chips

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, env=None, cwd=REPO, script=SMOKE, timeout=600):
    return subprocess.run(
        [sys.executable, str(script), *args], cwd=str(cwd),
        env=env if env is not None else dict(os.environ),
        capture_output=True, text=True, timeout=timeout,
    )


def test_cpu_rehearsal_runs_every_phase(tmp_path):
    out = _run(["--cpu-rehearsal", "--out", str(tmp_path / "smoke")])
    assert out.returncode == 0, out.stderr[-3000:]
    summary_line, verdict_line = out.stdout.strip().splitlines()[-2:]
    # the driver reads the last line and accepts these keys and no others
    verdict = json.loads(verdict_line)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["kind"], str)
    assert verdict["device"]["count"] == 1
    result = json.loads(summary_line)
    assert result["device"] == verdict["device"]
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["claim"] is None
    assert result["phases"] and all(result["phases"].values()), result["phases"]
    # the tree the chip checkpoints, not the f32 one a CPU would select
    assert result["model"]["dtype"] == "bfloat16"
    assert result["model"]["has_master"] is True
    assert [r["source"] for r in result["restores"]] == [
        "resident", "resident", "disk"]
    assert all(r["bit_equal"] for r in result["restores"])
    assert result["quorum"]["trips"] == 1
    assert result["compile_cache"]["respawn_hits"] == [1]
    assert all(result["native"]["built"].values())


@pytest.mark.parametrize("jax_platforms", ["cpu", None])
def test_no_flag_and_no_tpu_is_a_failure_that_says_so(jax_platforms):
    if visible_tpu_chips():
        pytest.skip("this host has a TPU")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    if jax_platforms:
        env["JAX_PLATFORMS"] = jax_platforms
    out = _run([], env=env, timeout=60)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""  # no result line


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run([], cwd=tmp_path, script=tmp_path / "chip_smoke.py", timeout=60)
    assert out.returncode != 0
    assert "needs the checkout" in out.stderr
    assert out.stdout.strip() == ""
