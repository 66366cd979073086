"""Self-tests of the model-family seam (``chipbench/families/``): the loader,
that the seam costs the jax-free parent nothing, that every family's
``state_bytes`` is the state it makes, and the proof that the seam is enough:
a family that exists only as new files (``toy_family/``: an untied head, a
float32 leaf with no bfloat16 copy, an int32 buffer no gradient touches) runs
a cell end to end in a copy of the checkout in which no file that was there
has changed.  The ``gpt2`` family's sizes and counts against hand counts are
in ``test_chipbench_units.py``, where they were.

    python -m pytest chipbench/tests/test_chipbench_families.py -q
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import family_trees  # noqa: E402
from chipbench import families  # noqa: E402


def test_the_loader_refuses_a_name_that_is_no_modules_and_names_the_file():
    with pytest.raises(ValueError, match=r"no chipbench/families/mamba9\.py"):
        families.load("mamba9")
    for name in ("GPT2", "gpt-2", "../gpt2", "gpt2.py", ""):
        with pytest.raises(ValueError, match="not a model family's name"):
            families.load(name)


def test_importing_the_seam_loads_no_jax():
    done = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); "
         "import chipbench.families.gpt2, chipbench.readers.setup, chipbench.flops; "
         "from chipbench import families; "
         "family, sizes = families.of_file(sys.argv[2]); "
         "family.train_flops_per_token(sizes); "
         "print(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib')))",
         ROOT, os.path.join(ROOT, "chipbench/configs/gpt2-xl-1chip.json")],
        capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "[]"


def _configs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [c["file"] for c in json.load(f)["configs"]]


@pytest.mark.parametrize("config", _configs())
def test_state_bytes_is_the_state_the_family_makes_and_the_trees_agree(config):
    """At the rehearsal cut, for every configuration of ``BENCHMARK.json`` (a
    later PR's too): ``sizes.state_bytes`` is the summed bytes of the made
    state, ``n_params`` the drawn leaves' elements, and ``first_moment`` and
    ``master`` have the draw's tree."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import weights

    family_trees.check(families, weights, os.path.join(ROOT, config))


# -- a family that exists only as new files ---------------------------------------

TOY = os.path.join(HERE, "toy_family")
TOY_FILES = ["chipbench/configs/toy-1chip.json", "chipbench/families/toy.py",
             "chipbench/limits/toy-1chip.json", "chipbench/reference/toy.py"]
TOY_CONFIG = {"name": "toy-1chip", "source": "chipbench/tests/toy_family",
              "file": "chipbench/configs/toy-1chip.json", "reduced": [],
              "why": "untied head, a float32 leaf with no bfloat16 copy, an int32 buffer"}
TOY_CELL = {"name": "toy-1chip.stall-inproc", "config": "toy-1chip",
            "traffic": "stall-inproc", "chips": 1,
            "why": "the self-tests' own: a family that is only new files"}


def _hashes(top):
    found = {}
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__", ".jax_cache")]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                found[os.path.relpath(path, top)] = hashlib.sha256(f.read()).hexdigest()
    return found


def test_a_family_of_only_new_files_runs_a_cell_end_to_end(tmp_path):
    ignore = shutil.ignore_patterns("out", "__pycache__")
    for package in ("chipbench", "tpu_resiliency"):
        shutil.copytree(os.path.join(ROOT, package), tmp_path / package, ignore=ignore)
    before = _hashes(tmp_path)
    for rel in TOY_FILES:  # only new files ...
        target = tmp_path / rel
        assert not target.exists(), f"{rel} is no new file"
        shutil.copy(os.path.join(TOY, os.path.relpath(rel, "chipbench")), target)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(TOY_CONFIG)  # ... and entries added, none changed
    bench["workloads"].append(TOY_CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    with open(tmp_path / TOY_CONFIG["file"]) as f:
        assert not set(json.load(f)) & {"n_embd", "n_head", "n_layer", "n_inner",
                                        "n_positions", "vocab_size", "batch"}
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("PYTHONPATH", None)  # the copy's own packages, not this checkout's
    trees = subprocess.run(  # the copy's seam finds the toy; this checkout's cannot
        [sys.executable, os.path.join(HERE, "family_trees.py"), str(tmp_path),
         str(tmp_path / TOY_CONFIG["file"])],
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=120)
    assert trees.returncode == 0, trees.stderr[-3000:]
    assert trees.stdout.split() == ["chipbench.families.toy", "977668"]
    with pytest.raises(ValueError, match=r"no chipbench/families/toy\.py"):
        families.load("toy")
    done = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"), "--workload",
         TOY_CELL["name"], "--seed", str(2**31 + 27), "--seconds", "3",
         "--trace", "0", "--cpu-rehearsal", "--deadline", "300"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=400)
    assert done.returncode == 0, done.stderr[-4000:]
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    result = json.loads(lines[-1])
    assert result["correct"] is True, lines
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["rehearsal"] is True and result["device"]["platform"] == "cpu"
    after = _hashes(tmp_path)
    assert {k: after[k] for k in before} == before, "a file that was there changed"
    assert set(after) - set(before) == set(TOY_FILES) | {"BENCHMARK.json"}
