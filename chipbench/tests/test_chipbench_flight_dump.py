"""The route by which the benchmark reads the product's own intervals, end to
end without a chip: ``run.py`` names the run's directory as
``TPURX_FLIGHT_DIR``, the worker's flight recorder dumps its ring there when
the process ends, and ``chipbench/readers/spans.py`` pairs what it finds.

    python -m pytest chipbench/tests/test_chipbench_flight_dump.py -q   (about a minute)
"""

import glob
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench.readers import spans  # noqa: E402

CELL = "gpt2-xl-1chip.steady-save"


def test_cpu_rehearsal_leaves_an_exit_dump_with_the_saves_intervals():
    seed = 2**31 + 25
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("TPURX_FLIGHT_DIR", None)
    env.pop("TPURX_FLIGHT", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "3", "--trace", "0",
         "--cpu-rehearsal", "--deadline", "300"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True

    out = os.path.join(ROOT, "chipbench", "out", f"{CELL}.{seed}.t0")
    with open(os.path.join(out, "readings.json")) as f:
        readings = json.load(f)
    assert spans.run_dir(readings) == out
    dumps = glob.glob(os.path.join(out, "flight-*-exit.jsonl"))
    assert dumps, os.listdir(out)
    worker = [proc for proc in spans.load_processes(out).values()
              if any(ev["event"] == "ckpt.save_begin" for ev in proc["events"])]
    assert len(worker) == 1  # one process saved: the worker
    assert worker[0]["covered_from_ns"] == float("-inf")  # a ring that never filled
    intervals = spans.pair_intervals(worker[0]["events"])
    tickets = [s["ticket"] for s in readings["saves"]]
    assert tickets == list(range(1, len(tickets) + 1)) and len(tickets) >= 3
    for ticket in tickets:  # every save of the run, each interval once
        for name in ("ckpt.save", "ckpt.save.prepare", "ckpt.save.handoff",
                     "ckpt.stage", "ckpt.stage.d2h", "ckpt.drain"):
            assert len(spans.named(intervals, name, ticket)) == 1, (name, ticket)
        save, = spans.named(intervals, "ckpt.save", ticket)
        stamp = next(s for s in readings["saves"] if s["ticket"] == ticket)
        # inside and outside agree on the same call, on the same clock
        assert stamp["call"] <= save["begin"] <= save["end"] <= stamp["ret"]
        assert spans.coverage(intervals, save) > 0.9
    # the readers see the window's saves through the same route
    assert spans.save_interval(readings, "ckpt.save.handoff", scale=1000.0) > 0
    assert spans.save_interval(readings, "ckpt.save.snapshot") is None  # CPU: sync mode
    assert spans.restore_interval(readings, "ckpt.load.place") is None  # no episode
    assert spans.named(intervals, "ckpt.load")  # the read-back from disk
