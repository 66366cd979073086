"""``chipbench/readers/spans.py``: from flight dumps to intervals, onto the
trace's clock, and the eight per-layer metrics that read them — on synthetic
events, and on dumps and readings cut from traced runs on a v5e
(``data/<run>/``: my chip run p2, PR 25; each ``readings.json`` says under
``what`` how it was cut, and under ``expect_from_the_uncut_run`` what the whole
run read).
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench.readers import read_metric, spans  # noqa: E402

DATA = os.path.join(HERE, "data")
SAVE_RUN = "gpt2-xl-1chip.steady-save.2590000401.t1"
STALL_RUN = "cerebras-gpt-1.3b-1chip.stall-inproc.2590000402.t1"


def ev(name, ns, ident=None, parent=None, **more):
    rec = {"mono_ns": ns, "event": name, "host": "h", "pid": 7, "rank": 0, **more}
    if ident is not None:
        rec.update(ident=ident, parent=parent)
    return rec


def write_dump(directory, seq, reason, at_ns, events, capacity=8, pid=7):
    meta = {"event": "_flight_meta", "mono_ns": at_ns, "ts": 1.79e9, "host": "h",
            "pid": pid, "rank": 0, "reason": reason, "events": len(events),
            "capacity": capacity}
    path = os.path.join(directory, f"flight-h-{pid}-{seq:04d}-{reason}.jsonl")
    with open(path, "w") as f:
        for rec in [meta, *events]:
            f.write(json.dumps(rec) + "\n")
        f.write('{"mono_ns": 1, "event": "torn')  # a killed process's last line
    return path


@pytest.fixture
def recorded(monkeypatch):
    """The readers look for a run's directory under ``spans.OUT``."""
    monkeypatch.setattr(spans, "OUT", DATA)

    def load(run):
        with open(os.path.join(DATA, run, "readings.json")) as f:
            return json.load(f)
    return load


# ---- pairing ------------------------------------------------------------------


def test_pairs_by_name_and_ident_and_leaves_an_open_begin_out():
    events = [
        ev("ckpt.drain_begin", 10, ident=1),
        ev("ckpt.drain_begin", 20, ident=2),           # two drains in flight
        ev("store.op_issue", 25, op="get"),            # not an interval
        ev("ckpt.drain_end", 30, ident=1),
        ev("ckpt.load.place_begin", 40, ident=5, parent="ckpt.load"),
        ev("ckpt.load.place_end", 50, ident=5, parent="ckpt.load"),
        ev("ckpt.load.place_begin", 60, ident=5, parent="ckpt.load"),
        ev("ckpt.load.place_end", 75, ident=5, parent="ckpt.load"),
        ev("ckpt.stage_begin", 80, ident=2),           # never ended: stuck there
        ev("ckpt.drain_end", 90, ident=2),
        ev("ckpt.save_end", 95, ident=9),              # its begin fell off the ring
    ]
    found = spans.pair_intervals(events)
    assert [(iv["name"], iv["ident"], round(iv["end"] - iv["begin"], 9))
            for iv in found] == [
        ("ckpt.drain", 1, 20e-9), ("ckpt.drain", 2, 70e-9),
        ("ckpt.load.place", 5, 10e-9), ("ckpt.load.place", 5, 15e-9)]
    assert found[2]["parent"] == "ckpt.load" and found[0]["parent"] is None


def test_coverage_is_the_children_s_union_over_the_parent():
    intervals = spans.pair_intervals([
        ev("ckpt.load_begin", 0, ident=1),
        ev("ckpt.load.plan_begin", 0, ident=1, parent="ckpt.load"),
        ev("ckpt.load.plan_end", 100, ident=1, parent="ckpt.load"),
        ev("ckpt.load.place_begin", 50, ident=1, parent="ckpt.load"),   # overlaps
        ev("ckpt.load.place_end", 600, ident=1, parent="ckpt.load"),
        ev("ckpt.load.place_begin", 700, ident=2, parent="ckpt.load"),  # another load's
        ev("ckpt.load.place_end", 900, ident=2, parent="ckpt.load"),
        ev("ckpt.load_end", 1000, ident=1),
    ])
    load, = spans.named(intervals, "ckpt.load")
    assert spans.coverage(intervals, load) == pytest.approx(0.6)


# ---- dumps: merged once, and never a guess ------------------------------------


def test_dumps_of_one_process_merge_and_say_from_when_nothing_is_missing(tmp_path):
    d = str(tmp_path)
    trip = [ev("a.b", t) for t in (10, 20, 30, 40)]
    write_dump(d, 0, "monitor_trip", 45, trip)               # not full: since the start
    late = [ev("a.b", t) for t in (30, 40, 50, 60, 70, 80, 90, 100)]
    write_dump(d, 1, "exit", 105, late)                      # full: oldest is 30
    write_dump(d, 0, "exit", 99, [ev("x.y", 5)], pid=8)      # another process
    procs = spans.load_processes(d)
    assert sorted(procs) == [7, 8]
    assert [e["mono_ns"] for e in procs[7]["events"]] == [
        10, 20, 30, 40, 50, 60, 70, 80, 90, 100]            # each event once
    assert procs[7]["covered_from_ns"] == float("-inf")     # the trip dump reaches back
    assert procs[8]["covered_from_ns"] == float("-inf")


def test_a_hole_between_two_dumps_is_not_bridged(tmp_path):
    d = str(tmp_path)
    write_dump(d, 0, "monitor_trip", 25, [ev("a.b", t) for t in (10, 20)])
    write_dump(d, 1, "exit", 105, [ev("a.b", t) for t in range(30, 110, 10)])
    proc = spans.load_processes(d)[7]
    # the trip dump was taken at 25, the exit dump's oldest event is 30: what
    # happened between them is lost, so nothing before 30 counts
    assert proc["covered_from_ns"] == 30
    assert [e["mono_ns"] for e in proc["events"]][0] == 30


def _readings(tmp_path, monkeypatch, window_open):
    monkeypatch.setattr(spans, "OUT", str(tmp_path))
    R = {"config": "c", "traffic": "t", "seed": 3, "trace": 0,
         "window_open": window_open,
         "saves": [{"ticket": 1, "in_window": False, "call": 0.5},
                   {"ticket": 2, "in_window": True, "call": 2.0}]}
    os.makedirs(spans.run_dir(R))
    return R


def _save(ticket, at_s, snapshot_s):
    t = int(at_s * 1e9)
    return [ev("ckpt.save_begin", t, ident=ticket),
            ev("ckpt.save.snapshot_begin", t + 10, ident=ticket, parent="ckpt.save"),
            ev("ckpt.save.snapshot_end", t + 10 + int(snapshot_s * 1e9),
               ident=ticket, parent="ckpt.save"),
            ev("ckpt.save_end", t + 20 + int(snapshot_s * 1e9), ident=ticket)]


def test_a_reader_returns_none_without_a_dump_and_where_the_ring_lost_the_window(
        tmp_path, monkeypatch):
    R = _readings(tmp_path, monkeypatch, window_open=1.0)
    assert spans.product_intervals(R) is None                     # no dump at all
    assert spans.save_interval(R, "ckpt.save.snapshot") is None
    assert spans.post_save_stall_under_d2h_pct(R, "jit_step") is None
    assert spans.save_interval({"loop": "none"}, "ckpt.save") is None  # no run's readings
    events = _save(1, 0.5, 0.1) + _save(2, 2.0, 0.25)
    write_dump(spans.run_dir(R), 0, "exit", int(9e9), events, capacity=4096)
    assert spans.save_interval(R, "ckpt.save.snapshot") == pytest.approx(0.25)
    assert spans.save_interval(R, "ckpt.save.snapshot", scale=1000.0) == pytest.approx(250)
    # the same events in a ring that was full, its oldest event younger than
    # the window's opening: part of the window is gone, so no number
    write_dump(spans.run_dir(R), 0, "exit", int(9e9), events[4:], capacity=4)
    assert spans.product_intervals(R) is None
    assert spans.save_interval(R, "ckpt.save.snapshot") is None
    # full, but it reaches back past the opening: nothing of the window is lost
    R["window_open"] = 2.5
    assert spans.save_interval(R, "ckpt.save.snapshot") == pytest.approx(0.25)


# ---- the clock ------------------------------------------------------------------


def test_offset_is_the_smallest_stamp_to_span_difference_of_what_was_traced():
    R = {"saves": [], "traced_cycles": None,
         "episodes": [{"traced": False, "in_window": True, "freeze": 50.0,
                       "reenter": 50.5, "restore_start": 50.6},
                      {"traced": True, "in_window": True, "freeze": 100.0,
                       "reenter": 100.5, "restore_start": 100.6}],
         "step_ends": [99.0, 99.9, 111.0, 111.1, 400.0],
         "trace": {"spans": [
             ["hooks", 5.89, 0.0095], ["stall", 6.004, 0.4], ["reenter", 6.5009, 0.1],
             ["restore", 6.60002, 9.0], ["hooks", 16.9, 0.09994],
             ["hooks", 17.05, 0.0499], ["first.step", 17.0, 0.2]]}}
    # stall: +4 ms after its stamp (a report is printed first), reenter +0.9 ms,
    # restore +0.02 ms: the tightest pair is the offset, -94 s
    assert spans.clock_offset(R) == pytest.approx(6.60002 - 100.6)
    lower, upper = spans.clock_bracket(R)
    assert upper == spans.clock_offset(R)
    # hooks spans end 0.5, 0.06 and 0.1 ms before the stamps at 99.9, 111.0,
    # 111.1; the stamp at 99.0 has no span of its own near it
    assert lower == pytest.approx(-94.0 - 0.00006)
    assert 0 < upper - lower < 1e-3
    assert spans.clock_offset({**R, "trace": None}) is None
    assert spans.clock_bracket({**R, "trace": None}) is None


def test_the_traced_save_is_the_stamp_that_pairs_with_save_call():
    R = {"episodes": [], "traced_cycles": [1, 2],
         "saves": [{"in_window": False, "call": 1.0, "ticket": 1},
                   {"in_window": True, "call": 30.0, "ticket": 2},
                   {"in_window": True, "call": 60.0, "ticket": 3}],
         "trace": {"spans": [["save.call", 4.0003, 0.2]]}}
    assert spans.clock_offset(R) == pytest.approx(4.0003 - 60.0)
    spans_on_trace = spans.on_trace_clock(R, [
        {"name": "ckpt.save", "ident": 3, "parent": None, "begin": 60.001, "end": 60.2}])
    assert spans_on_trace == [["ckpt.save", pytest.approx(4.0013), pytest.approx(0.199)]]


# ---- recorded on a v5e: each of the eight readers ------------------------------

RECORDED = [
    (SAVE_RUN, "save_snapshot_ms", 184.729118),
    (SAVE_RUN, "save_handoff_ms", 0.743151),
    (SAVE_RUN, "stage_d2h_s", 2.552932769),
    (SAVE_RUN, "post_save_stall_under_d2h_pct", 99.675767),
    (STALL_RUN, "restore_plan_ms", 0.561465),
    (STALL_RUN, "restore_start_s", 5.6765885495),
    (STALL_RUN, "restore_place_s", 4.409312658),
    (STALL_RUN, "restore_wait_s", 0.0017458755),
]


@pytest.mark.parametrize("run,metric,value", RECORDED)
def test_each_reader_on_the_recorded_run(recorded, run, metric, value):
    R = recorded(run)
    assert read_metric("layer_metrics", metric, R) == pytest.approx(value, rel=1e-6)
    other = recorded(STALL_RUN if run == SAVE_RUN else SAVE_RUN)
    assert read_metric("layer_metrics", metric, other) is None  # nothing to read there


def test_recorded_save_inside_and_outside_agree(recorded):
    """The product's ``ckpt.save`` and the worker's stamps around the same
    call; the children cover the call; the clock's two bounds 10 us apart."""
    R = recorded(SAVE_RUN)
    intervals = spans.product_intervals(R)
    traced = next(s for s in R["saves"] if s["in_window"])
    save, = spans.named(intervals, "ckpt.save", traced["ticket"])
    assert traced["call"] < save["begin"] < save["end"] < traced["ret"]
    assert save["end"] - save["begin"] == pytest.approx(0.194855, abs=1e-5)
    assert spans.coverage(intervals, save) > 0.999
    assert [iv["name"] for iv in intervals
            if iv["ident"] == traced["ticket"] and iv["parent"] == "ckpt.save"] == [
        "ckpt.save.prepare", "ckpt.save.snapshot", "ckpt.save.handoff"]
    lower, upper = spans.clock_bracket(R)
    assert upper == pytest.approx(-104.457009985)
    assert 0 < upper - lower < 50e-6
    # on the trace's clock the product's call lies inside the worker's span
    (a, b), = [(s[1], s[1] + s[2]) for s in R["trace"]["spans"] if s[0] == "save.call"]
    (_, start, dur), = spans.on_trace_clock(R, [save])
    assert a <= start and start + dur <= b
    gap = spans.post_save_gap(R, "jit_step")
    assert gap["hi"] - gap["lo"] == pytest.approx(1.256648, abs=1e-5)
    assert gap["idle_s"] == pytest.approx(1.256647, abs=1e-5)
    assert gap["idle_under_d2h_s"] == pytest.approx(1.252573, abs=1e-5)


def test_recorded_restores_are_the_window_s_clean_episodes(recorded):
    R = recorded(STALL_RUN)
    intervals = spans.product_intervals(R)
    loads = spans.window_loads(R, intervals)
    # of the window's three episodes, the two the profiler did not touch
    assert len(spans.named(intervals, "ckpt.load")) == 5  # four episodes, one read-back
    assert len(loads) == 2 and len({iv["ident"] for iv in loads}) == 2
    for load in loads:
        assert spans.coverage(intervals, load) > 0.999
        assert len(spans.named(intervals, "ckpt.load.place", load["ident"])) == 109
        # one get a leaf and the last one; none of them waited: 1.7 ms in all
        assert len(spans.named(intervals, "ckpt.load.wait", load["ident"])) == 110
    whole = spans.restore_interval(R, "ckpt.load")
    parts = sum(spans.restore_interval(R, f"ckpt.load.{name}")
                for name in ("plan", "start", "wait", "place", "release"))
    assert whole == pytest.approx(10.089913, abs=1e-4)
    assert 0.999 * whole < parts <= whole
    lower, upper = spans.clock_bracket(R)
    assert 0 < upper - lower < 50e-6


def test_recorded_dumps_of_the_worker_merge_to_one_history(recorded):
    """The stall run's worker left a dump at its first trip and one at its
    exit; the exit dump alone is whole (the ring never filled), and merging
    the trip dump in adds no event twice."""
    directory = os.path.join(DATA, STALL_RUN)
    proc, = spans.load_processes(directory).values()
    assert proc["covered_from_ns"] == float("-inf")
    _, alone = spans.read_dump(os.path.join(
        directory, "flight-runsc-737-0008-exit.jsonl"))
    assert len(proc["events"]) == len(alone)
