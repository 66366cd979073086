"""Self-tests that drive the harness end to end without a chip: the CPU
rehearsal of every cell of ``BENCHMARK.json`` at its tiny cut (the whole flow,
``correct`` present, no device number), the refusal to measure without a TPU, the timed path broken
underneath (``correct`` has to come out false), and the control — the plain
reference in the next lower precision — refused by the same comparison.

    python -m pytest chipbench/tests -q        (about two minutes)
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
RUN = [sys.executable, os.path.join(ROOT, "chipbench", "run.py")]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]  # a later PR's too
GPT2_CELL = "gpt2-xl-1chip.steady-save"  # broken_step_worker.py breaks that family's step


def run(args, cwd=ROOT, env=None, timeout=400):
    env = dict(os.environ if env is None else env)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run([*RUN, *args], cwd=cwd, env=env, timeout=timeout,
                          capture_output=True, text=True)
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    return done.returncode, lines, done.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_cpu_rehearsal_runs_the_whole_flow_and_gives_no_device_number(cell):
    rc, lines, err = run(["--workload", cell, "--seed", str(2**31 + 11),
                          "--seconds", "3", "--trace", "0", "--cpu-rehearsal",
                          "--deadline", "300"])
    assert rc == 0, err[-3000:]
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["correct"] is True, lines
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    compared = [ln for ln in lines if ln.startswith("compared ")]
    assert len(compared) >= 6 and all("limit" in ln or "wanted" in ln for ln in compared)


@pytest.mark.parametrize("extra", [[], ["--cpu-rehearsal"]])
def test_fails_for_want_of_a_chip_when_asked_for_device_metrics(extra):
    """Here JAX is held to the CPU: a run proper, and a traced rehearsal, end
    with another code than 0 and print no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    trace = "1" if extra else "0"
    rc, lines, _ = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", trace, *extra], env=env, timeout=60)
    assert rc != 0 and lines == []


def test_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    done = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    """The harness's look for a chip skipped, the rest of a run driven, with
    the timed path broken underneath (``broken_step_worker.py`` in the
    worker's place): ``correct`` has to come out false."""
    drive = ("import sys; sys.path.insert(0, sys.argv[1]); from chipbench import run; "
             "run.WORKER = sys.argv[2]; run.main(sys.argv[3:])")
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "-c", drive, ROOT, os.path.join(HERE, "broken_step_worker.py"),
         "--workload", GPT2_CELL, "--seed", "5", "--seconds", "2", "--trace", "0",
         "--cpu-rehearsal", "--deadline", "300"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert any("differ from the plain reference" in ln for ln in lines)


def test_the_lower_precision_is_refused_at_the_tiny_cut():
    """The control of ``chipbench/control.py`` at a size a test run can hold.
    The limits here are the tiny cut's own (its gaps are wider than the
    chip's at full width): above the program's largest, below the control's
    smallest, the control failing one number and not each."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import control, correct

    config = os.path.join(ROOT, "chipbench/configs/gpt2-xl-1chip.json")
    rows = control.readings(config, [7, 2**31 + 8, 9], rehearsal=True)
    found = control.summary(rows)
    limits = correct.load_limits("gpt2-xl-1chip", rehearsal=True)
    for row in rows:
        assert correct.within(row["program"], limits), row
        assert not correct.within(row["bf16_everywhere"], limits), row
    # the separating number, by three times and more
    assert found["change_norm_gap"]["bf16_everywhere_smallest"] > (
        3 * found["change_norm_gap"]["program_largest"])
