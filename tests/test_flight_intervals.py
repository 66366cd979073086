"""Flight-recorder intervals: the primitive (``flight.declare_interval`` / ``span`` /
``begin`` / ``end``), the ``exit`` dump, the checkpoint save and restore
intervals a round trip leaves, and their rendering by ``telemetry/trace.py``.
"""

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from tpu_resiliency.telemetry import flight, trace
from tpu_resiliency.utils.env import force_cpu_env

REPO = Path(__file__).resolve().parent.parent

IV_OUTER = flight.declare_interval("test.outer_begin", "test.outer_end")
IV_INNER = flight.declare_interval(
    "test.outer.inner_begin", "test.outer.inner_end", "extra")

SAVE_INTERVALS = ("ckpt.save", "ckpt.save.prepare", "ckpt.save.snapshot",
                  "ckpt.save.handoff", "ckpt.stage", "ckpt.stage.d2h",
                  "ckpt.stage.d2h.first", "ckpt.drain")
LOAD_INTERVALS = ("ckpt.load", "ckpt.load.plan", "ckpt.load.start",
                  "ckpt.load.wait", "ckpt.load.place", "ckpt.load.release")
INNER_RING_INTERVALS = (
    "inproc.coalesce", "inproc.abort", "inproc.abort.on_trip",
    "inproc.abort.ladder", "inproc.abort.stage", "flight.dump.capture",
    "flight.dump.write", "flight.dump.hooks", "inproc.raise", "inproc.restart",
    "inproc.restart.abort_wait", "inproc.restart.finalize",
    "inproc.restart.health_check", "inproc.restart.iteration_barrier",
    "inproc.restart.reassign", "inproc.restart.collect",
    "inproc.restart.rearm", "inproc.restart.initialize")
PARENT_OF = {
    "ckpt.save.prepare": "ckpt.save", "ckpt.save.snapshot": "ckpt.save",
    "ckpt.save.handoff": "ckpt.save", "ckpt.stage.d2h": "ckpt.stage",
    "ckpt.stage.d2h.first": "ckpt.stage.d2h",
    "ckpt.stage.populate": "ckpt.stage",
    "ckpt.load.plan": "ckpt.load", "ckpt.load.start": "ckpt.load",
    "ckpt.load.wait": "ckpt.load", "ckpt.load.place": "ckpt.load",
    "ckpt.load.release": "ckpt.load",
}


@pytest.fixture(autouse=True)
def _fresh_ring():
    flight.configure(enabled=True, capacity=4096)
    flight.set_current_episode("")
    flight._last_dump_ns.clear()
    yield
    flight.configure()
    flight.set_current_episode("")
    flight._last_dump_ns.clear()


def _records():
    """The ring as dump records (field names resolved), meta left out."""
    return [r for r in flight._records("test") if r["event"] != "_flight_meta"]


def _paired(records):
    """{(name, ident): [(begin_ns, end_ns, parent)]}; asserts that nothing is
    left open and that no end comes without its begin."""
    open_, out = {}, {}
    for rec in records:
        if "ident" not in rec:
            continue
        name, _, edge = rec["event"].rpartition("_")
        key = (name, rec["ident"])
        if edge == "begin":
            open_.setdefault(key, []).append(rec)
        else:
            assert edge == "end" and open_.get(key), f"end without begin: {rec}"
            start = open_[key].pop()
            assert start["parent"] == rec["parent"]
            out.setdefault(key, []).append(
                (start["mono_ns"], rec["mono_ns"], rec["parent"]))
    assert not any(open_.values()), f"never ended: {open_}"
    return out


# ---- the primitive -----------------------------------------------------------


class TestPrimitive:
    @pytest.mark.parametrize("how", ["span", "begin_end", "across_threads"])
    def test_records_begin_end_with_ident_parent_and_episode(self, how):
        flight.set_current_episode("ep-7")
        if how == "span":
            with flight.span(IV_OUTER, 41):
                with flight.span(IV_INNER, 41, IV_OUTER):
                    pass
        else:
            flight.begin(IV_OUTER, 41)
            flight.begin(IV_INNER, 41, IV_OUTER, "x")
            if how == "across_threads":
                t = threading.Thread(
                    target=flight.end, args=(IV_INNER, 41, IV_OUTER, "x"))
                t.start()
                t.join(timeout=10)
                assert not t.is_alive()
            else:
                flight.end(IV_INNER, 41, IV_OUTER, "x")
            flight.end(IV_OUTER, 41)
        records = _records()
        assert [r["event"] for r in records] == [
            "test.outer_begin", "test.outer.inner_begin",
            "test.outer.inner_end", "test.outer_end"]
        assert all(r["ident"] == 41 and r["episode"] == "ep-7" for r in records)
        assert [r["parent"] for r in records] == [
            None, "test.outer", "test.outer", None]
        if how != "span":
            assert records[1]["extra"] == records[2]["extra"] == "x"
        stamps = [r["mono_ns"] for r in records]
        assert stamps == sorted(stamps)

    def test_span_ends_when_the_body_raises(self):
        with pytest.raises(KeyError):
            with flight.span(IV_OUTER, 1):
                raise KeyError("boom")
        assert [r["event"] for r in _records()] == [
            "test.outer_begin", "test.outer_end"]

    @pytest.mark.parametrize("call", ["span", "begin", "end"])
    def test_disabled_is_the_shared_noop(self, call):
        flight.configure(enabled=False)
        if call == "span":
            first = flight.span(IV_OUTER, 1)
            assert first is flight.span(IV_INNER, 2, IV_OUTER)
            with first:
                pass
        else:
            assert getattr(flight, call)(IV_OUTER, 1, None, "x") is None
        assert len(flight.get_flight()) == 0
        flight.configure(enabled=True)
        with flight.span(IV_OUTER, 1):
            pass
        assert len(flight.get_flight()) == 2  # re-enabling rebinds all three

    @pytest.mark.parametrize("begin,end", [
        ("test.bad_start", "test.bad_end"),
        ("test.bad2_begin", "test.other_end"),
        ("test.outer_begin", "test.outer_end"),  # declared above: once only
    ])
    def test_malformed_and_repeated_pairs_are_refused(self, begin, end):
        before = flight.event_names()
        with pytest.raises(ValueError):
            flight.declare_interval(begin, end)
        assert flight.event_names() == before

    def test_both_events_carry_ident_and_parent_first(self):
        assert flight.event_fields("test.outer.inner_begin") == (
            "ident", "parent", "extra")
        assert flight.event_fields("test.outer_end") == ("ident", "parent")

    def test_span_enters_a_trace_annotation_only_where_jax_is_loaded(
            self, monkeypatch):
        seen = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        class Profiler:
            TraceAnnotation = Annotation

        monkeypatch.setitem(sys.modules, "jax.profiler", Profiler)
        with flight.span(IV_OUTER, 1):
            pass
        assert seen == [("enter", "test.outer"), ("exit", "test.outer")]
        monkeypatch.delitem(sys.modules, "jax.profiler")
        with flight.span(IV_OUTER, 2):
            pass
        assert len(seen) == 2 and "jax.profiler" not in sys.modules


def test_every_interval_is_a_span_pair_of_the_trace_cli():
    """``telemetry/trace.py`` renders an interval only if ``SPAN_PAIRS``
    names its pair; the span carries the interval's own name."""
    import tpu_resiliency.checkpointing.async_ckpt.checkpointer  # noqa: F401
    import tpu_resiliency.inprocess.wrap  # noqa: F401  (monitor_thread, abort)

    product = [iv for iv in flight.intervals() if not iv.name.startswith("test.")]
    assert {iv.name for iv in product} >= set(
        SAVE_INTERVALS + LOAD_INTERVALS + INNER_RING_INTERVALS)
    for iv in product:
        end, name, _cat = trace.SPAN_PAIRS[iv.begin_event]
        assert end == iv.end_event
        assert name == iv.name or iv.name == "ckpt.drain"  # PR 17's name stays


# ---- the exit dump -----------------------------------------------------------

_EXIT_CHILD = """
import sys
sys.path.insert(0, sys.argv[1])
from tpu_resiliency.telemetry import flight
iv = flight.declare_interval("child.work_begin", "child.work_end")
with flight.span(iv, 3):
    pass
"""


@pytest.mark.parametrize("env,dumped", [
    ({"TPURX_FLIGHT_DIR": "{dir}"}, True),
    ({}, False),
    ({"TPURX_FLIGHT_DIR": "{dir}", "TPURX_FLIGHT": "0"}, False),
])
def test_exit_dump_only_where_a_directory_is_named(tmp_path, env, dumped):
    child_env = force_cpu_env(dict(os.environ))
    child_env.pop("TPURX_FLIGHT_DIR", None)
    child_env["TMPDIR"] = str(tmp_path / "tmp")
    os.makedirs(child_env["TMPDIR"])
    child_env.update({k: v.format(dir=tmp_path / "dumps") for k, v in env.items()})
    done = subprocess.run([sys.executable, "-c", _EXIT_CHILD, str(REPO)],
                          env=child_env, capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr[-2000:]
    assert os.listdir(child_env["TMPDIR"]) == []  # never the temp directory
    found = sorted((tmp_path / "dumps").glob("flight-*-exit.jsonl"))
    assert len(found) == (1 if dumped else 0)
    if dumped:
        records = [json.loads(line) for line in open(found[0])]
        assert records[0]["event"] == "_flight_meta"
        assert records[0]["reason"] == "exit"
        assert {"mono_ns", "ts", "events", "capacity"} <= set(records[0])
        # the dump's own capture has begun and not ended: the next dump has it
        assert [r["event"] for r in records[1:] if "ident" in r] == [
            "child.work_begin", "child.work_end", "flight.dump.capture_begin"]


# ---- the checkpoint's intervals ----------------------------------------------


def _tree(scale=1.0):
    import jax.numpy as jnp

    return {"w": jnp.arange(4096, dtype=jnp.float32) * scale,
            "b": {"m": jnp.ones((64, 64), jnp.bfloat16) * scale},
            "host": np.arange(16, dtype=np.int32)}


def test_snapshot_round_trip_leaves_every_interval_paired(tmp_path):
    """Two ``async_save(stage_mode="snapshot")`` (the CPU default, ``sync``,
    never takes the ring) and two ``load_checkpoint``: every interval of the
    save and the restore path paired, children inside their parents, one
    ident per save and per restore, the ring gauge following the slots."""
    import jax

    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.telemetry import get_registry

    def ring_bytes():
        rows = get_registry().snapshot()["tpurx_ckpt_snap_ring_bytes"]["samples"]
        return rows[0]["value"]

    ckpt = AsyncCheckpointer()
    try:
        tickets = []
        for i in range(2):
            tickets.append(ckpt.async_save(
                _tree(i + 1.0), str(tmp_path / f"s{i}"), stage_mode="snapshot"))
            device_bytes = sum(
                leaf.nbytes for leaf in jax.tree_util.tree_leaves(_tree())
                if isinstance(leaf, jax.Array))
            assert ring_bytes() >= device_bytes
            ckpt.finalize_all()
        before = len(_records())
        for _ in range(100):  # a step's poll with nothing in flight
            assert ckpt.maybe_finalize() == []
        assert len(_records()) == before
        for i in range(2):
            out = load_checkpoint(str(tmp_path / f"s{i}"), _tree())
            np.testing.assert_array_equal(
                np.asarray(out["w"]), np.asarray(_tree(i + 1.0)["w"]))
    finally:
        ckpt.close()
    assert ring_bytes() == 0
    paired = _paired(_records())
    assert tickets == [1, 2]
    for ticket in tickets:
        for name in SAVE_INTERVALS:
            assert len(paired[(name, ticket)]) == 1, (name, ticket)
    loads = sorted(ident for name, ident in paired if name == "ckpt.load")
    assert len(loads) == 2 and len(set(loads)) == 2
    for load in loads:
        for name in LOAD_INTERVALS:
            assert paired[(name, load)], (name, load)
    # the second save donated the first one's ring slot, so the first load is
    # the engine's alone: a place per leaf, a wait per get and the last one.
    # The second is served from its slot: one place and one wait for the two
    # device leaves together, and the engine's for the numpy leaf
    assert [len(paired[("ckpt.load.place", load)]) for load in loads] == [3, 2]
    assert [len(paired[("ckpt.load.wait", load)]) for load in loads] == [4, 3]
    for (name, ident), found in paired.items():
        parent = PARENT_OF.get(name)
        for begin, end, recorded_parent in found:
            assert recorded_parent == parent, name
            if parent is not None:
                (p_begin, p_end, _), = paired[(parent, ident)]
                assert p_begin <= begin <= end <= p_end, (name, ident)
    # the call's three children follow one another and leave little of it out
    for ticket in tickets:
        (s0, s1, _), = paired[("ckpt.save", ticket)]
        inside = sum(paired[(name, ticket)][0][1] - paired[(name, ticket)][0][0]
                     for name in SAVE_INTERVALS[1:4])
        assert inside <= s1 - s0
        assert paired[("ckpt.save.prepare", ticket)][0][1] <= paired[
            ("ckpt.save.snapshot", ticket)][0][0]
        assert paired[("ckpt.save.snapshot", ticket)][0][1] <= paired[
            ("ckpt.save.handoff", ticket)][0][0]


def test_a_blocked_restore_records_its_wait(tmp_path, monkeypatch):
    """``ckpt.load.wait`` is the placing thread in ``engine.ready.get()``:
    with the readers held at a gate until that thread is seen waiting, one
    wait begins before the gate opens and ends after it."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.checkpointing.async_ckpt import writer
    from tpu_resiliency.telemetry.clock import mono_ns

    gate, opened_ns = threading.Event(), []
    real = writer._RestoreEngine._worker

    def held(self):
        gate.wait(timeout=60)
        return real(self)

    def open_once_the_caller_waits():
        deadline = mono_ns() + 60e9
        while mono_ns() < deadline and not any(
                r["event"] == "ckpt.load.wait_begin" for r in _records()):
            gate.wait(timeout=0.002)
        opened_ns.append(mono_ns())
        gate.set()

    ckpt = AsyncCheckpointer(stage_buffers=1)  # no ring slot: the engine's alone
    opener = threading.Thread(target=open_once_the_caller_waits, daemon=True)
    try:
        ckpt.async_save(_tree(), str(tmp_path / "s"), stage_mode="snapshot")
        ckpt.finalize_all()
        monkeypatch.setattr(writer._RestoreEngine, "_worker", held)
        opener.start()
        load_checkpoint(str(tmp_path / "s"), _tree())
    finally:
        gate.set()
        ckpt.close()
    opener.join(timeout=60)
    (opened,) = opened_ns
    waits = [iv for (name, _), found in _paired(_records()).items()
             if name == "ckpt.load.wait" for iv in found]
    assert [1 for begin, end, _ in waits if begin <= opened <= end] == [1]


def test_wrapped_steps_with_no_save_record_no_interval(store_server):
    """100 steps inside ``inprocess.Wrapper`` that poll ``maybe_finalize`` and
    save nothing: not one interval event in the ring."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer
    from tpu_resiliency.inprocess import Wrapper
    from tpu_resiliency.store import StoreClient

    ckpt = AsyncCheckpointer()
    steps = []

    def train(call_wrapper=None):
        for step in range(100):
            call_wrapper.ping()
            steps.append(ckpt.maybe_finalize())
        return "done"

    wrapper = Wrapper(
        store_factory=lambda: StoreClient(
            "127.0.0.1", store_server.port, timeout=10.0),
        group="no-save-steps", soft_timeout=3600.0, hard_timeout=7200.0,
        enable_monitor_process=False, enable_sibling_monitor=False,
        last_call_wait=0.0,
    )
    try:
        assert wrapper(train)() == "done"
    finally:
        ckpt.close()
    assert steps == [[]] * 100
    names = {iv.begin_event for iv in flight.intervals()} | {
        iv.end_event for iv in flight.intervals()}
    assert [r["event"] for r in _records() if r["event"] in names] == []


# ---- rendering ---------------------------------------------------------------


def test_trace_cli_renders_the_new_pairs_as_complete_spans(tmp_path):
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint

    ckpt = AsyncCheckpointer()
    try:
        for i in range(2):  # two drains in flight at once: paired by ident
            ckpt.async_save(_tree(), str(tmp_path / f"s{i}"), stage_mode="snapshot")
        ckpt.finalize_all()
        load_checkpoint(str(tmp_path / "s1"), _tree())
    finally:
        ckpt.close()
    dump = flight.dump("render", path=str(tmp_path / "dump.jsonl"))
    out = tmp_path / "trace.json"
    assert trace.main([dump, "-o", str(out)]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    by_name = {}
    for span in spans:
        by_name.setdefault(span["name"], []).append(span)
    for name in SAVE_INTERVALS[:-1]:
        assert len(by_name[name]) == 2, name
    assert len(by_name["ckpt_drain"]) == 2
    assert {s["args"]["ident"] for s in by_name["ckpt_drain"]} == {1, 2}
    for name in LOAD_INTERVALS:
        assert by_name[name], name
    # nothing is left open but the capture of the very dump that was rendered
    assert [e["name"] for e in events if "(unfinished)" in e.get("name", "")] == [
        "flight.dump.capture (unfinished)"]
    save = by_name["ckpt.save"][0]
    child = next(s for s in by_name["ckpt.save.snapshot"]
                 if s["args"]["ident"] == save["args"]["ident"])
    assert save["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= save["ts"] + save["dur"] + 1e-3
    assert child["args"]["parent"] == "ckpt.save"


def test_a_begin_without_its_end_shows_where_it_was_stuck(tmp_path):
    flight.begin(IV_OUTER, 9)
    dump = flight.dump("stuck", path=str(tmp_path / "dump.jsonl"))
    records = [json.loads(line) for line in open(dump)]
    assert [r["event"] for r in records
            if r.get("ident") == 9 and r["event"].startswith("test.")] == [
        "test.outer_begin"]
