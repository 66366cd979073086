"""``chipbench/readers/inner_ring.py``: the seven per-layer metrics that read
the inner ring's intervals, on one episode of a CPU rehearsal kept as data
(``tests/data/inner_ring/<run>/``: its ``readings.json`` says under ``what``
how it was cut and under ``expect`` what plain sums over the kept events give),
None where there is nothing sound to read, and never a raise: the readers run
over the parent's program too, where one exception would cost the run its
result line.
"""

import ast
import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402
from chipbench.readers import inner_ring, read_metric, spans  # noqa: E402

DATA = os.path.join(ROOT, "tests", "data", "inner_ring")
RUN = "cerebras-gpt-1.3b-1chip.stall-inproc.2147483659.t0"
METRICS = (
    "trip_to_wake_ms", "abort_monitor_ms", "abort_dump_ms", "raise_delivery_ms",
    "restart_main_ms", "restart_collect_ms", "reenter_unattributed_pct")
STALL_CELLS = [f"{config}.stall-inproc" for config in (
    "cerebras-gpt-1.3b-1chip", "kimi-linear-48b-a3b-1chip",
    "qwen3-next-80b-a3b-1chip", "keye-vl-2.0-30b-a3b-1chip", "lfm2-8b-a1b-1chip",
    "mellum2-12b-a2.5b-1chip")]
# what PR 40 added to the program's ring: a program without them is the parent
NEW_EVENTS = ("inproc.abort", "inproc.raise", "inproc.restart", "flight.dump.")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _readings(directory=DATA):
    with open(os.path.join(directory, RUN, "readings.json")) as f:
        return json.load(f)


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(spans, "OUT", DATA)
    return _readings()


@pytest.fixture
def copied(tmp_path, monkeypatch):
    """The run's directory copied where a test may rewrite its dump."""
    shutil.copytree(os.path.join(DATA, RUN), tmp_path / RUN)
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    (dump,) = (tmp_path / RUN).glob("flight-*.jsonl")
    return _readings(str(tmp_path)), dump


def _rewrite(dump, keep=lambda rec: True, meta=None, change=lambda rec: rec):
    lines = [json.loads(line) for line in open(dump)]
    lines[0].update(meta or {})
    with open(dump, "w") as f:
        for rec in [lines[0], *map(change, filter(keep, lines[1:]))]:
            f.write(json.dumps(rec) + "\n")


def _is_new(rec):
    return rec["event"].startswith(NEW_EVENTS)


# ---- the recorded episode, to the number -------------------------------------


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_reads_the_recorded_episode_to_the_number(recorded, name):
    value = read_metric("layer_metrics", name, recorded)
    assert value == pytest.approx(recorded["expect"][name], rel=1e-9, abs=1e-9)
    assert value >= 0.0


def test_the_recorded_episode_is_split_whole(recorded):
    """What the numbers say of one another, episode by episode (never a sum
    of medians against a median): the chain from the trip to the re-entry
    adds up to the episode's own stamps, the restart's children cover it, its
    largest is the collect, and little is in no leaf."""
    (episode,) = recorded["episodes"]
    expect = recorded["expect"]
    (e, ivs), = inner_ring.episodes_of(recorded)
    assert e == episode
    coalesce, = spans.named(ivs, "inproc.coalesce")
    chain = (expect["trip_to_wake_ms"] + (coalesce["end"] - coalesce["begin"]) * 1e3
             + expect["abort_monitor_ms"] + expect["raise_delivery_ms"]
             + expect["restart_main_ms"])
    trip_to_reenter = (episode["reenter"] - episode["trip"]) * 1e3
    assert chain == pytest.approx(trip_to_reenter, rel=0.05)
    restart, = spans.named(ivs, "inproc.restart")
    assert spans.coverage(ivs, restart) >= 0.99
    children = {iv["name"]: iv["end"] - iv["begin"] for iv in ivs
                if iv["parent"] == "inproc.restart"}
    assert set(children) == set(inner_ring.RESTART_PHASES)
    assert max(children, key=children.get) == "inproc.restart.collect"
    assert expect["reenter_unattributed_pct"] < 5.0
    assert 2 * len(ivs) <= 48


def test_a_throttled_dump_reads_zero_not_none(copied):
    readings, dump = copied
    _rewrite(dump, keep=lambda rec: not rec["event"].startswith("flight.dump."))
    assert read_metric("layer_metrics", "abort_dump_ms", readings) == 0.0
    assert read_metric("layer_metrics", "restart_main_ms", readings) == (
        pytest.approx(readings["expect"]["restart_main_ms"]))


def test_an_episode_without_its_restart_is_left_out_of_the_median(copied):
    """Two episodes in the readings, the intervals of one in the dump: the
    median is that one's, not the mean with a zero."""
    readings, _ = copied
    (episode,) = readings["episodes"]
    later = {key: value + 100.0 if isinstance(value, float) else value
             for key, value in episode.items()}
    readings["episodes"].append(later)
    for name in METRICS:
        assert read_metric("layer_metrics", name, readings) == pytest.approx(
            readings["expect"][name])


def test_the_runs_dumps_are_parsed_once_for_the_seven(recorded, monkeypatch):
    calls = []
    parse = spans.load_processes
    monkeypatch.setattr(spans, "load_processes",
                        lambda directory: calls.append(directory) or parse(directory))
    for name in METRICS:
        assert read_metric("layer_metrics", name, recorded) is not None
    assert len(calls) == 1


# ---- None, and never a raise --------------------------------------------------


def _no_dump(readings, dump):
    os.unlink(dump)


def _torn_dump(readings, dump):
    """The writer was killed half-way: whole lines, then half a line."""
    data = open(dump, "rb").read()
    cut = data.index(b"inproc.restart_begin")
    with open(dump, "wb") as f:
        f.write(data[:cut])


def _torn_meta(readings, dump):
    data = open(dump, "rb").read()
    with open(dump, "wb") as f:
        f.write(data[20:])


def _episodes(**changes):
    def case(readings, dump):
        for episode in readings["episodes"]:
            for key, value in changes.items():
                if value is KeyError:
                    episode.pop(key, None)
                else:
                    episode[key] = value
    return case


def _no_episodes(readings, dump):
    readings["episodes"] = []


def _no_episodes_key(readings, dump):
    del readings["episodes"]


def _begins_without_ends(readings, dump):
    _rewrite(dump, keep=lambda rec: not (_is_new(rec) and rec["event"].endswith("_end")))


def _ring_dropped_the_windows_start(readings, dump):
    events = sum(1 for _ in open(dump)) - 1
    _rewrite(dump, meta={"events": events, "capacity": events})


def _the_parents_dumps(readings, dump):
    _rewrite(dump, keep=lambda rec: not _is_new(rec))


def _stamps_that_are_not_numbers(readings, dump):
    _rewrite(dump, change=lambda rec: {**rec, "mono_ns": str(rec["mono_ns"])}
             if _is_new(rec) else rec)


def _readings_of_no_run(readings, dump):
    del readings["seed"]


def _no_window(readings, dump):
    readings["window_open"] = None


def _an_empty_episode(readings, dump):
    """``reenter`` on the ``trip``: nothing to take a share of."""
    for episode in readings["episodes"]:
        episode["reenter"] = episode["trip"]


# what a run can hand the readers, by name
MALFORMED = {
    "no dump": _no_dump,
    "torn dump": _torn_dump,
    "torn meta line": _torn_meta,
    "no trip": _episodes(trip=None),
    "no trip key": _episodes(trip=KeyError),
    "trip is a string": _episodes(trip="60157.9"),
    "no reenter": _episodes(reenter=None),
    "no reenter key": _episodes(reenter=KeyError),
    "episodes that never recovered": _episodes(recovered=None),
    "no episodes": _no_episodes,
    "no episodes key": _no_episodes_key,
    "begins without ends": _begins_without_ends,
    "a ring that dropped the window's start": _ring_dropped_the_windows_start,
    "the parent's dumps": _the_parents_dumps,
    "stamps that are not numbers": _stamps_that_are_not_numbers,
    "readings of no run": _readings_of_no_run,
    "no window_open": _no_window,
    "an empty episode": _an_empty_episode,
}


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_a_malformed_reading_reads_none_and_never_raises(copied, case, name):
    readings, dump = copied
    MALFORMED[case](readings, dump)
    assert read_metric("layer_metrics", name, readings) is None


@pytest.mark.parametrize("name", METRICS)
def test_whatever_a_reader_raises_becomes_none_and_one_line_on_stderr(
        recorded, monkeypatch, capsys, name):
    def broken(R):
        raise RuntimeError("the dumps are on fire")

    monkeypatch.setattr(spans, "product_intervals", broken)
    assert read_metric("layer_metrics", name, recorded) is None
    err = capsys.readouterr().err.splitlines()
    assert len(err) == 1
    assert err[0].startswith(f"chipbench: {name}: ")
    assert "the dumps are on fire" in err[0]


def test_a_number_that_is_not_finite_is_not_printed(recorded, monkeypatch, capsys):
    monkeypatch.setattr(inner_ring, "_median", lambda R, per_episode: float("nan"))
    assert read_metric("layer_metrics", "restart_main_ms", recorded) is None
    assert capsys.readouterr().err.startswith("chipbench: restart_main_ms: ")


@pytest.mark.parametrize("name", METRICS)
def test_every_function_a_metric_file_names_goes_through_the_guard(name):
    with open(os.path.join(ROOT, "chipbench", "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    module, _, function = spec["reader"].partition(":")
    assert module == "inner_ring"
    reader = getattr(inner_ring, function)
    assert getattr(reader, "__wrapped__", None) is not None, "not @_guarded"
    # the guard names the metric in its line: the function's own name, or
    # the file's among its args
    assert spec.get("args", {}).get("metric", function) == name
    # called as read_metric calls it, with nothing to read at all
    assert reader({}, **spec.get("args", {})) is None
    assert reader(None, **spec.get("args", {})) is None


def test_the_reader_imports_nothing_from_the_product():
    with open(inner_ring.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported <= {"functools", "json", "math", "os", "sys", "chipbench",
                        "chipbench.readers"}
    done = subprocess.run(
        [sys.executable, "-c",
         "import sys; import chipbench.readers.inner_ring; "
         "print(sorted(m for m in sys.modules if m.startswith(('tpu_resiliency', 'jax'))))"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == "[]"


# ---- over the parent's program: its own metrics and no others -----------------


def _parent_bench(bench):
    parent = copy.deepcopy(bench)
    parent["per_layer"] = [m for m in bench["per_layer"] if m["name"] not in METRICS]
    return parent


@pytest.mark.parametrize("trace", [1, 0])
@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_over_parent_shaped_readings_the_line_has_the_parents_metrics_only(
        copied, cell, trace):
    """``run.metrics_of`` with this PR's ``BENCHMARK.json`` over the dumps of a
    program that records none of the new intervals returns what the parent's
    own ``BENCHMARK.json`` returns, in all five cells."""
    readings, dump = copied
    _the_parents_dumps(readings, dump)
    bench = _bench()
    (workload,) = [w for w in bench["workloads"] if w["name"] == cell]
    ours = bench_run.metrics_of(bench, workload, trace, readings)
    parents = bench_run.metrics_of(_parent_bench(bench), workload, trace, readings)
    assert ours == parents
    assert not set(ours) & set(METRICS)
    if trace and cell in STALL_CELLS:
        assert {"abort_reenter_ms", "detect_ms", "restore_s"} <= set(ours)


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_over_malformed_readings_the_line_still_has_the_parents_metrics(
        copied, case):
    """No malformed reading makes the new readers cost the line a metric the
    parent's benchmark would have printed from it; where the parent's own
    readers raise on it (a stamp that is a string), so do they here, alike."""
    readings, dump = copied
    MALFORMED[case](readings, dump)
    bench = _bench()
    (workload,) = [w for w in bench["workloads"] if w["name"] == STALL_CELLS[0]]

    def line(bench):
        try:
            return bench_run.metrics_of(bench, workload, 1, readings)
        except Exception as exc:  # noqa: BLE001 - an accepted reader's own
            return repr(exc)

    assert line(bench) == line(_parent_bench(bench))


def test_the_change_side_line_has_all_seven_beside_the_parents(recorded):
    bench = _bench()
    (workload,) = [w for w in bench["workloads"] if w["name"] == STALL_CELLS[0]]
    ours = bench_run.metrics_of(bench, workload, 1, recorded)
    parents = bench_run.metrics_of(_parent_bench(bench), workload, 1, recorded)
    assert set(ours) - set(parents) == set(METRICS)
    assert {k: ours[k] for k in parents} == parents


def test_a_steady_save_cell_is_on_no_new_metrics_list():
    for entry in _bench()["per_layer"]:
        if entry["name"] in METRICS:
            assert entry["workloads"] == STALL_CELLS


@pytest.mark.parametrize("name", METRICS)
def test_the_benchmark_lists_the_metric_for_every_stall_cell(name):
    bench = _bench()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    with open(os.path.join(ROOT, "chipbench", "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert entry["workloads"] == STALL_CELLS
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key]
    assert (entry["layer"], entry["source"], entry["moves"], entry["better"]) == (
        "inner ring", "program_span", "recover_s", "lower")
    # appended: the seven are the list's last, in the table's order
    assert [m["name"] for m in bench["per_layer"][-len(METRICS):]] == list(METRICS)


# ---- the printed summary -------------------------------------------------------


def test_the_printed_summary_has_both_threads_in_time_order():
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.readers.inner_ring",
         os.path.join(DATA, RUN, "readings.json")],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout)
    (episode,) = out["episodes"]
    rows = episode["intervals"]
    assert [r["at_ms"] for r in rows] == sorted(r["at_ms"] for r in rows)
    assert {r["thread"] for r in rows} == {"monitor", "monitor->main", "main"}
    names = [r["interval"] for r in rows]
    # a parent before the child that begins on its stamp
    assert names.index("inproc.restart") < names.index("inproc.restart.abort_wait")
    labelled = [name for name in names if "[" in name]
    assert labelled == [
        "flight.dump.write[monitor_trip]", "flight.dump.hooks[monitor_trip]",
        "flight.dump.write[abort_ladder]", "flight.dump.hooks[abort_ladder]",
        "inproc.abort.stage[fingerprint]", "inproc.abort.stage[on_abort]"]
    assert episode["coverage"]["inproc.restart"] >= 0.99
    assert 0.9 <= episode["coverage"]["inproc.abort"] <= 1.0
    assert out["coverage_min"] == episode["coverage"]
    assert out["median_ms"]["inproc.restart.collect"] == pytest.approx(
        _readings()["expect"]["restart_collect_ms"])
    assert out["median_ms"]["inproc.restart.rearm"] > 0
    assert out["traced_episode_idle_gaps"] is None  # no trace in a rehearsal


def test_the_summary_of_the_parents_dumps_is_null(copied):
    readings, dump = copied
    _the_parents_dumps(readings, dump)
    assert inner_ring.summary(readings) is None


def test_a_traced_episodes_idle_gap_is_apportioned_by_overlap(tmp_path, monkeypatch):
    """A made-up device timeline laid over the recorded episode: busy until
    half-way through ``inproc.restart.health_check`` (the probe's matmul
    ends), idle to the end of ``inproc.restart.collect``.  The gap begins
    under the health check; nearly all of its seconds lie under the collect."""
    monkeypatch.setattr(spans, "OUT", DATA)
    (_, ivs), = inner_ring.episodes_of(_readings())
    # a traced run's directory ends in .t1: the same dump under that name
    shutil.copytree(os.path.join(DATA, RUN), tmp_path / RUN.replace(".t0", ".t1"))
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    readings = _readings()
    (episode,) = readings["episodes"]
    episode["traced"] = True
    offset = 1000.0  # the trace's clock = the monotonic clock + offset
    by_name = {iv["name"]: iv for iv in ivs}
    probe, collect = (by_name[f"inproc.restart.{phase}"]
                      for phase in ("health_check", "collect"))
    busy_until = (probe["begin"] + probe["end"]) / 2
    readings["trace"] = {
        "devices": {"/device:TPU:0": {"modules": [], "ops": [
            ["fusion.1", "jit_step", episode["trip"] + offset - 1.0,
             busy_until - episode["trip"] + 1.0],
            ["fusion.2", "jit_step", collect["end"] + offset, 10.0]]}},
        "spans": [["stall", episode["freeze"] + offset,
                   busy_until - episode["freeze"]],
                  ["reenter", episode["reenter"] + offset, 0.01]]}
    gaps = inner_ring.summary(readings)["traced_episode_idle_gaps"]
    (gap,) = gaps
    assert gap["seconds"] == pytest.approx(collect["end"] - busy_until)
    assert gap["worker_span"] == "(none)"  # what the ledger's breakdown prints
    under = gap["under"]
    assert under["inproc.restart.collect"] == pytest.approx(
        collect["end"] - collect["begin"])
    assert under["inproc.restart.health_check"] == pytest.approx(
        probe["end"] - busy_until)
    assert under["inproc.restart.collect"] > 0.9 * gap["seconds"]
    assert under["inproc.restart"] == pytest.approx(gap["seconds"])  # the parent
    assert gap["under_no_leaf"] == pytest.approx(0.0, abs=1e-9)
