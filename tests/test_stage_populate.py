"""A first save's staging segments, made resident ahead of the copy loop.

A fresh ``stage_pytree`` creates its segments from the plan and hands them to
a helper thread that makes their pages resident by one bulk call a segment
(``staging._populate``); the copy loop waits on a segment's event only if its
turn comes first.  Held here, on the CPU and without reading a clock: what
lands in the segments and streams to the writer is what a staging with the bulk
call failing leaves, byte for byte and in order; a reusing save populates
nothing; a failing call is counted and never fails a save; a staging that
fails mid-plan leaves no segment behind; and the copy loop blocked on a segment
goes on when its event is set."""

import errno
import os
import threading

import jax
import numpy as np
import pytest

from tests.test_stage_window import TREES, WINDOW, _expected_bytes, routed_tree
from tpu_resiliency.checkpointing.async_ckpt import staging
from tpu_resiliency.telemetry import flight, get_registry

POPULATED = "tpurx_ckpt_stage_populated_bytes_total"
FALLBACK = "tpurx_ckpt_stage_populate_fallback_total"
WAIT_S = 60  # a bound on a wait that an event ends, never a pause


@pytest.fixture(autouse=True)
def _fresh_ring():
    flight.configure(enabled=True, capacity=8192)
    yield
    flight.configure()


def _failing(code):
    def populate(shm):
        raise OSError(code, os.strerror(code))
    return populate


class _Calls:
    """Stands where the helper makes the bulk call: the real call, logged."""

    def __init__(self, monkeypatch):
        self.names, self.failed = [], []
        self._real = staging._populate
        monkeypatch.setattr(staging, "_populate", self)

    def __call__(self, shm):
        self.names.append(shm.name)
        try:
            self._real(shm)
        except OSError:  # this kernel has no such advice: still fail-open
            self.failed.append(shm.name)
            raise


def _populate_intervals():
    """[(ident, parent, begin_ns, end_ns)] of the ring's ``ckpt.stage.populate``."""
    open_, out = {}, []
    for rec in flight._records("test"):
        if rec["event"] == "ckpt.stage.populate_begin":
            open_[rec["ident"]] = rec
        elif rec["event"] == "ckpt.stage.populate_end":
            begin = open_.pop(rec["ident"])
            assert begin["parent"] == rec["parent"]
            out.append((rec["ident"], rec["parent"], begin["mono_ns"], rec["mono_ns"]))
    assert not open_, f"left open: {open_}"
    return out


def _fallbacks(reason):
    return get_registry().value_of(FALLBACK, {"reason": reason})


def _payloads(seen):
    """What streams to the writer, in order, less the segments' random names."""
    out = []
    for info in seen:
        p = staging.shard_payload(info)
        assert p.pop("shm_name") == info.shm_name != ""
        out.append(p)
    return out


def _segments(staged):
    bufs = staged.shm_buffers()
    return [bytes(bufs[s.shm_name][:s.nbytes]) for s in staged.shards if s.replica_owner]


@pytest.mark.parametrize("name", list(TREES))
def test_fresh_staging_equals_the_one_whose_bulk_call_fails(name, monkeypatch):
    """Dense and routed shapes, several shards to a leaf, a numpy, a scalar and
    a zero-size leaf, a shard over the window: the same bytes in the same
    segments' order, the same payloads, with and without the bulk call."""
    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW)
    tree = TREES[name][0](5)
    leaves = jax.tree_util.tree_leaves(tree)
    populated_before = get_registry().value_of(POPULATED)

    calls = _Calls(monkeypatch)
    seen, plans = [], []
    live = staging.stage_pytree(tree, process_index=0, on_plan=plans.append,
                                on_shard_staged=seen.append, ident=71)
    try:
        owned = [s for s in live.shards if s.replica_owner]
        total = sum(s.nbytes for s in owned)
        assert plans == [total] and live.bytes_allocated == total
        # one call a segment, in plan order, every one before its copy's end
        assert calls.names == [s.shm_name for s in owned]
        ok_bytes = sum(s.nbytes for s in owned if s.shm_name not in calls.failed)
        assert live.populated_bytes == ok_bytes
        assert live.populate_fallbacks == len(calls.failed)
        assert get_registry().value_of(POPULATED) - populated_before == ok_bytes
        assert live.populate_s > 0 and live.populate_wait_s >= 0
        (ident, parent, begin, end), = _populate_intervals()
        assert (ident, parent) == (71, "ckpt.stage") and begin <= end
        for info, got in zip(owned, _segments(live)):
            assert got == _expected_bytes(leaves[info.leaf_idx], info), info.leaf_idx

        monkeypatch.setattr(staging, "_populate", _failing(errno.EINVAL))
        seen_stub = []
        stub = staging.stage_pytree(tree, process_index=0, on_shard_staged=seen_stub.append)
        try:
            assert stub.populated_bytes == 0 and stub.populate_fallbacks == len(owned)
            assert _segments(stub) == _segments(live)
            assert _payloads(seen_stub) == _payloads(seen)
            assert [(s.leaf_idx, s.shard_idx) for s in seen] == \
                [(s.leaf_idx, s.shard_idx) for s in owned]
        finally:
            stub.close(unlink=True)
    finally:
        live.close(unlink=True)


@pytest.mark.parametrize("stage_mode", ["snapshot", "sync"])
def test_a_reusing_save_populates_nothing(stage_mode, tmp_path, monkeypatch):
    """The first save is fresh, the later ones reuse the pool: no call, no
    bytes counted, no interval; the stats say so save by save."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer
    from tpu_resiliency.checkpointing.async_ckpt import resident

    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW)
    calls = _Calls(monkeypatch)
    tree = routed_tree(11, 24, 4)
    state_bytes = sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))
    ckpt = AsyncCheckpointer()
    reg = get_registry()
    try:
        stats, counted, n_calls = [], [], []
        for i in range(4):
            before = reg.value_of(POPULATED)
            ckpt.async_save(tree, str(tmp_path / f"s{i}"), stage_mode=stage_mode)
            ckpt.finalize_all()
            stats.append(dict(ckpt.last_stage_stats))
            counted.append(reg.value_of(POPULATED) - before)
            n_calls.append(len(calls.names))
    finally:
        ckpt.close()
        resident.invalidate()
    # the first save is fresh and the later ones reuse (how many sets of the
    # pool get filled first is the checkpointer's business)
    fresh = [s["bytes_allocated"] > 0 for s in stats]
    assert fresh[0] and not fresh[-1] and not fresh[-2]
    n_segments = n_calls[0]
    assert n_calls == [n_segments * sum(fresh[:i + 1]) for i in range(4)]
    for s, was_fresh, n in zip(stats, fresh, counted):
        if was_fresh:
            assert s["populated_bytes"] + s["populate_fallbacks"] > 0
            assert s["populated_bytes"] == n
            if not calls.failed:
                assert s["populated_bytes"] == state_bytes == s["bytes_allocated"]
            assert s["populate_s"] > 0
        else:
            assert s["bytes_reused"] == state_bytes
            assert (s["populated_bytes"], s["populate_fallbacks"], n) == (0, 0, 0)
            assert s["populate_s"] == 0 and s["populate_wait_s"] == 0
    # one interval a fresh save, under its ticket, inside its ckpt.stage
    intervals = _populate_intervals()
    assert [i[0] for i in intervals] == [t + 1 for t, f in enumerate(fresh) if f]
    stage = {r["ident"]: r["mono_ns"] for r in flight._records("test")
             if r["event"] == "ckpt.stage_begin"}
    stage_end = {r["ident"]: r["mono_ns"] for r in flight._records("test")
                 if r["event"] == "ckpt.stage_end"}
    for ident, parent, begin, end in intervals:
        assert parent == "ckpt.stage"
        assert stage[ident] <= begin <= end <= stage_end[ident]


def test_a_reusing_stage_pytree_makes_no_call_and_starts_no_thread(monkeypatch):
    tree, other = routed_tree(12, 24, 0), routed_tree(13, 24, 0)
    staged = staging.stage_pytree(tree, process_index=0)
    try:
        assert staged.populated_bytes + staged.populate_fallbacks > 0
        monkeypatch.setattr(staging, "_populate", _failing(errno.EIO))
        started = []
        monkeypatch.setattr(staging, "_Populator",
                            lambda *a, **k: started.append(a) or pytest.fail("populator"))
        eio = _fallbacks("EIO")
        again = staging.stage_pytree(other, process_index=0, reuse=staged)
        assert again is staged and started == [] and _fallbacks("EIO") == eio
        assert (staged.populated_bytes, staged.populate_fallbacks) == (0, 0)
        assert (staged.populate_s, staged.populate_wait_s) == (0.0, 0.0)
        assert len(_populate_intervals()) == 1
        leaves = jax.tree_util.tree_leaves(other)
        owned = [s for s in staged.shards if s.replica_owner]
        for info, got in zip(owned, _segments(staged)):
            assert got == _expected_bytes(leaves[info.leaf_idx], info)
    finally:
        staged.close(unlink=True)


@pytest.mark.parametrize("code", [errno.EINVAL, errno.ENOMEM, None])
def test_a_bulk_call_that_fails_is_counted_and_the_save_lands(code, tmp_path, monkeypatch):
    """``EINVAL`` (a kernel or sandbox without the advice), ``ENOMEM`` (a full
    ``/dev/shm``), or an error with no errno at all: one fallback a segment
    under the errno's name, the copy fills the segments as it always did, and
    the checkpoint reads back bit-equal."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.checkpointing.async_ckpt import resident

    def no_errno(shm):
        raise RuntimeError("no errno")

    reason = errno.errorcode[code] if code else "other"
    monkeypatch.setattr(staging, "_populate", _failing(code) if code else no_errno)
    before = _fallbacks(reason)
    tree = routed_tree(14, 24, 4)
    ckpt = AsyncCheckpointer()
    try:
        ckpt.async_save(tree, str(tmp_path / "s0"))
        ckpt.finalize_all()
        stats = dict(ckpt.last_stage_stats)
        out = load_checkpoint(str(tmp_path / "s0"),
                              jax.tree_util.tree_map(np.zeros_like, tree), resident=False)
    finally:
        ckpt.close()
        resident.invalidate()
    n_segments = len(jax.tree_util.tree_leaves(tree))
    assert stats["populate_fallbacks"] == n_segments and stats["populated_bytes"] == 0
    assert _fallbacks(reason) - before == n_segments
    assert len(_populate_intervals()) == 1
    for want, got in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(out)):
        assert np.asarray(want).tobytes() == np.asarray(got).tobytes()


def _created_segments(monkeypatch):
    names = []
    real = staging.create_shm

    def create(size, name=None):
        shm = real(size, name)
        names.append(shm.name)
        return shm

    monkeypatch.setattr(staging, "create_shm", create)
    return names


@pytest.mark.parametrize("fail_at", [0, 9, "last"])
@pytest.mark.parametrize("bulk", ["real", "EINVAL"])
def test_a_staging_that_fails_mid_plan_leaves_no_segment(fail_at, bulk, monkeypatch):
    """A leaf whose transfer raises: every segment of the plan exists by then
    (they are created ahead), the helper is stopped and joined, and none is
    left in ``/dev/shm``; the interval does not stay open."""
    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW)
    if bulk != "real":
        monkeypatch.setattr(staging, "_populate", _failing(errno.EINVAL))
    tree = routed_tree(15, 48, 0)
    n_device = sum(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(tree))
    fail_at = n_device - 1 if fail_at == "last" else fail_at
    names = _created_segments(monkeypatch)
    real_await, awaits = staging._await_d2h, []

    def failing_await(data):
        if len(awaits) == fail_at:
            raise RuntimeError("transfer failed")
        awaits.append(data)
        return real_await(data)

    monkeypatch.setattr(staging, "_await_d2h", failing_await)
    with pytest.raises(RuntimeError, match="transfer failed"):
        staging.stage_pytree(tree, process_index=0, ident=5)
    assert len(names) == len(jax.tree_util.tree_leaves(tree))
    assert not [n for n in names if os.path.exists(os.path.join("/dev/shm", n.lstrip("/")))]
    assert not [t for t in threading.enumerate() if t.name == "tpurx-ckpt-populate"]
    assert len(_populate_intervals()) == 1


def test_a_plan_whose_size_disagrees_with_the_landed_bytes_is_refused(monkeypatch):
    """The reuse path's size check guards the fresh path too, now that the
    segment is sized from the plan and not from the landed array."""
    tree = {"a": np.arange(8, dtype=np.int64), "b": jax.numpy.ones((16,), jax.numpy.float32)}
    names = _created_segments(monkeypatch)
    monkeypatch.setattr(staging, "_await_d2h", lambda data: np.zeros((3,), np.float32))
    with pytest.raises(ValueError, match="stage size mismatch on leaf 1"):
        staging.stage_pytree(tree, process_index=0)
    assert len(names) == 2
    assert not [n for n in names if os.path.exists(os.path.join("/dev/shm", n.lstrip("/")))]


@pytest.mark.parametrize("held", [0, 3, "last"])
def test_the_copy_loop_waits_for_a_segment_and_goes_on_at_its_event(held, monkeypatch):
    """The bulk call of one segment is held at a gate: the copy loop stages
    every shard before it, waits at that one (seen waiting, by an event), and
    finishes once the gate opens.  Events, no sleeps."""
    tree = routed_tree(16, 12, 0)
    n_segments = len(jax.tree_util.tree_leaves(tree))
    held = n_segments - 1 if held == "last" else held
    gate, waiting, calls = threading.Event(), threading.Event(), []

    def populate(shm):
        calls.append(shm.name)
        if len(calls) == held + 1:
            assert gate.wait(WAIT_S)

    real_wait = staging._Populator.wait

    def wait(self, k):
        if not self.ready[k].is_set():
            waiting.k = k
            waiting.set()
        return real_wait(self, k)

    monkeypatch.setattr(staging, "_populate", populate)
    monkeypatch.setattr(staging._Populator, "wait", wait)
    seen, result = [], []
    stager = threading.Thread(target=lambda: result.append(staging.stage_pytree(
        tree, process_index=0, on_shard_staged=seen.append)))
    stager.start()
    try:
        assert waiting.wait(WAIT_S), "the copy loop never waited for the held segment"
        assert waiting.k == held
        # blocked there: the shards before it are staged, nothing after it
        assert len(seen) == held and stager.is_alive() and not result
    finally:
        gate.set()
    stager.join(WAIT_S)
    assert not stager.is_alive()
    staged, = result
    try:
        assert len(seen) == n_segments == len(calls)
        assert staged.populate_wait_s > 0
        leaves = jax.tree_util.tree_leaves(tree)
        owned = [s for s in staged.shards if s.replica_owner]
        for info, got in zip(owned, _segments(staged)):
            assert got == _expected_bytes(leaves[info.leaf_idx], info)
    finally:
        staged.close(unlink=True)


@pytest.mark.parametrize("done_before", [0, 2, 7])
def test_a_helper_stopped_short_ends_its_interval_with_what_it_did(done_before, monkeypatch):
    """``close()`` while the helper is inside a call: that call ends, no later
    segment is touched, the thread is joined, and the interval's end says how
    many segments and bytes were made resident by then."""
    tree = routed_tree(18, 12, 0)
    entered, gate, calls = threading.Event(), threading.Event(), []

    def populate(shm):
        calls.append(shm.size)
        if len(calls) == done_before + 1:
            entered.set()
            assert gate.wait(WAIT_S)

    monkeypatch.setattr(staging, "_populate", populate)
    monkeypatch.setattr(staging, "_await_d2h", lambda data: entered.wait(WAIT_S) and 1 / 0)
    names = _created_segments(monkeypatch)
    closing, real_close = threading.Event(), staging._Populator.close

    def close(self):
        self._stop = True  # what close() does first; then it joins
        closing.set()
        real_close(self)

    monkeypatch.setattr(staging._Populator, "close", close)
    result = []

    def stage():
        try:
            staging.stage_pytree(tree, process_index=0, ident=9)
        except ZeroDivisionError as exc:
            result.append(exc)

    stager = threading.Thread(target=stage)
    stager.start()
    # the stager fails, and its close() joins the helper that sits in its call
    assert closing.wait(WAIT_S)
    gate.set()
    stager.join(WAIT_S)
    assert not stager.is_alive() and len(result) == 1
    assert len(calls) == done_before + 1
    ends = [r for r in flight._records("test") if r["event"] == "ckpt.stage.populate_end"]
    assert [(r["ident"], r["segments"], r["bytes"]) for r in ends] == \
        [(9, done_before + 1, sum(calls))]
    assert not [n for n in names if os.path.exists(os.path.join("/dev/shm", n.lstrip("/")))]
    assert not [t for t in threading.enumerate() if t.name == "tpurx-ckpt-populate"]


@pytest.mark.parametrize("nbytes", [1, 4096, (1 << 20) + 3])
def test_the_bulk_call_leaves_a_segment_as_it_was_and_free_to_close(nbytes):
    """Whether this kernel takes the advice or answers an errno, the segment's
    bytes are untouched, it is writable, and nothing of the call keeps the
    mapping exported (``close`` would raise ``BufferError``)."""
    shm = staging.create_shm(nbytes)
    try:
        shm.buf[0] = 7
        try:
            staging._populate(shm)
        except OSError as exc:
            assert exc.errno in (errno.EINVAL, errno.ENOSYS, errno.ENOMEM, errno.EPERM)
        assert shm.buf[0] == 7 and bytes(shm.buf[1:nbytes]) == bytes(nbytes - 1)
        shm.buf[nbytes - 1] = 9
    finally:
        shm.close()
        staging.unlink_shm(shm)
