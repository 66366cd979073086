"""Warm restore ladder tests: shm-resident read source, digest-keyed delta
saves, and the local manager's peer-memory rung.

The resident registry (``async_ckpt/resident.py``) promotes the staging
pool's committed generation to a read source; ``load_checkpoint`` must
restore a complete generation without opening ANY checkpoint file.  Delta
saves skip draining chunks whose crc matches the previous committed
generation and record provenance so a cold restore of the delta directory
still covers every byte.  The local manager's ladder tries its own resident
blob, then clique peers' resident copies over the TCP exchange, then disk.
"""

import gc
import json
import os
import shutil
import threading

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_resiliency.checkpointing.async_ckpt import resident as resident_mod
from tpu_resiliency.checkpointing.async_ckpt import checkpointer as ckpt_mod
from tpu_resiliency.checkpointing.async_ckpt import writer as writer_mod
from tpu_resiliency.checkpointing.async_ckpt.checkpointer import (
    AsyncCheckpointer,
    load_checkpoint,
)
from tpu_resiliency.checkpointing.integrity import CheckpointCorruptError
from tpu_resiliency.checkpointing.local.manager import LocalCheckpointManager
from tpu_resiliency.checkpointing.local.replication import (
    CliqueReplication,
    PeerExchange,
)
from tpu_resiliency.store import StoreClient
from tpu_resiliency.telemetry import get_registry

from harness.serial_restore import serial_restore


def _source_bytes(source):
    return get_registry().value_of(
        "tpurx_ckpt_restore_source_total", {"source": source}
    )


def make_tree(seed=0, step=1):
    k = jax.random.PRNGKey(seed)
    return {
        "w": jax.device_put(jax.random.normal(k, (64, 32))),
        "b": jax.device_put(np.arange(256, dtype=np.float32)),
        "step": np.int64(step),
    }


def assert_trees_equal(a, b):
    la, _ = jax.tree_util.tree_flatten(a)
    lb, _ = jax.tree_util.tree_flatten(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(autouse=True)
def _fresh_registry():
    resident_mod.invalidate()
    yield
    resident_mod.invalidate()


def _forbid_file_reads(monkeypatch):
    def _boom(*_a, **_k):
        raise AssertionError("warm restore touched a checkpoint file")

    monkeypatch.setattr(writer_mod, "ChunkReader", _boom)
    monkeypatch.setattr(ckpt_mod, "read_metadata", _boom)
    monkeypatch.setattr(ckpt_mod, "is_committed", _boom)


class TestResidentRestore:
    def test_warm_restore_no_file_opens(self, tmp_path, monkeypatch):
        """In-process-restart smoke: after close(), a complete resident
        generation satisfies the whole restore from memory — metadata
        included — with every chunk verified against the committed index."""
        tree = make_tree(1)
        d = str(tmp_path / "ck")
        cp = AsyncCheckpointer(digest=True, resident=True)
        try:
            cp.save(tree, d, extra_metadata={"iteration": 1})
        finally:
            cp.close()  # the resident generation outlives the checkpointer
        rc = resident_mod.lookup(d)
        assert rc is not None and rc.complete
        _forbid_file_reads(monkeypatch)
        stats = {}
        restored = load_checkpoint(d, tree, threads=2, stats=stats)
        assert_trees_equal(tree, restored)
        assert stats["bytes_shm"] > 0
        assert stats["bytes_shm"] == stats["bytes_read"]  # 100% warm

    def test_resident_opt_out_reads_disk(self, tmp_path):
        tree = make_tree(2)
        d = str(tmp_path / "ck")
        cp = AsyncCheckpointer(digest=True, resident=True)
        try:
            cp.save(tree, d, extra_metadata={"iteration": 1})
        finally:
            cp.close()
        stats = {}
        restored = load_checkpoint(d, tree, stats=stats, resident=False)
        assert_trees_equal(tree, restored)
        assert stats["bytes_shm"] == 0

    def test_serial_path_ignores_resident(self, tmp_path):
        tree = make_tree(3)
        d = str(tmp_path / "ck")
        cp = AsyncCheckpointer(digest=True, resident=True)
        try:
            cp.save(tree, d, extra_metadata={"iteration": 1})
        finally:
            cp.close()
        assert resident_mod.lookup(d) is not None
        stats = {}
        warm = load_checkpoint(d, tree, stats=stats)
        assert stats["bytes_shm"] == stats["bytes_read"]
        assert_trees_equal(warm, serial_restore(d, tree))
        assert_trees_equal(tree, warm)

    def test_sharded_leaves_warm_and_cold(self, tmp_path):
        """Row sharding exercises the direct-into-leaf-buffer path, column
        sharding the scratch-then-place path — both must restore equal from
        the shm source AND from disk after invalidation."""
        devs = jax.devices()
        assert len(devs) == 8
        mesh = Mesh(np.array(devs), ("x",))
        rows = jax.device_put(
            np.arange(64 * 32, dtype=np.float32).reshape(64, 32),
            NamedSharding(mesh, P("x", None)),
        )
        cols = jax.device_put(
            np.arange(16 * 64, dtype=np.float32).reshape(16, 64),
            NamedSharding(mesh, P(None, "x")),
        )
        tree = {"rows": rows, "cols": cols, "step": np.int64(4)}
        d = str(tmp_path / "ck")
        cp = AsyncCheckpointer(digest=True, resident=True)
        try:
            cp.save(tree, d, extra_metadata={"iteration": 1})
        finally:
            cp.close()
        stats = {}
        warm = load_checkpoint(d, tree, threads=2, stats=stats)
        assert stats["bytes_shm"] == stats["bytes_read"] > 0
        assert_trees_equal(tree, warm)
        assert warm["rows"].sharding.is_equivalent_to(rows.sharding, 2)
        assert warm["cols"].sharding.is_equivalent_to(cols.sharding, 2)
        resident_mod.invalidate(d)
        stats = {}
        cold = load_checkpoint(d, tree, threads=2, stats=stats)
        assert stats["bytes_shm"] == 0
        assert_trees_equal(tree, cold)

    def test_layout_change_invalidates_resident(self, tmp_path):
        cp = AsyncCheckpointer(digest=True, resident=True)
        d1, d2 = str(tmp_path / "c1"), str(tmp_path / "c2")
        try:
            cp.save(make_tree(5), d1, extra_metadata={"iteration": 1})
            assert resident_mod.lookup(d1) is not None
            # different leaf set = different plan signature: the staging
            # pool re-shapes, so the old generation must be evicted
            other = {"v": jax.device_put(np.ones((8, 8), dtype=np.float32))}
            cp.save(other, d2, extra_metadata={"iteration": 2})
        finally:
            cp.close()
        assert resident_mod.lookup(d1) is None
        assert resident_mod.lookup(d2) is not None


# -- in place: verified where the bytes lie, placed from that view -------------


def _save_resident(tmp_path, tree):
    d = str(tmp_path / "ck")
    cp = AsyncCheckpointer(digest=True, resident=True)
    try:
        cp.save(tree, d, extra_metadata={"iteration": 1})
    finally:
        cp.close()
    rc = resident_mod.lookup(d)
    assert rc is not None and rc.complete
    return d, rc


def _shard_of(rc, leaf_path):
    """((leaf_idx, shard_idx), index entry) of ``leaf_path``'s lone shard."""
    leaf_idx = rc.leaf_paths.index(leaf_path)
    (found,) = [(k, s) for k, s in rc.shards.items() if k[0] == leaf_idx]
    return found


def _overwrite_segments(rc):
    """What the next save's reuse of the staging tree does to them."""
    for s in rc.shards.values():
        s["buf"][: int(s["nbytes"])] = b"\xa5" * int(s["nbytes"])


def _whole_leaf_shards_verified_where_they_lie(tmp_path, monkeypatch):
    tree = make_tree(11)
    d, _rc = _save_resident(tmp_path, tree)
    _forbid_file_reads(monkeypatch)

    def _no_second_buffer(nbytes):
        raise AssertionError(f"a {nbytes}-byte leaf buffer beside the segment")

    monkeypatch.setattr(writer_mod, "_alloc_aligned", _no_second_buffer)
    before = get_registry().value_of("tpurx_ckpt_restore_in_place_bytes_total")
    stats = {}
    restored = load_checkpoint(d, tree, threads=2, stats=stats)
    assert_trees_equal(tree, restored)
    assert stats["bytes_read"] > 0
    assert stats["bytes_in_place"] == stats["bytes_shm"] == stats["bytes_read"]
    assert get_registry().value_of(
        "tpurx_ckpt_restore_in_place_bytes_total"
    ) - before == stats["bytes_in_place"]


def _a_flipped_resident_byte_names_shard_and_offset(tmp_path, monkeypatch):
    tree = {
        "big": jax.device_put(np.arange(3 * 4096, dtype=np.float32)),
        "ok": jax.device_put(np.ones(64, dtype=np.float32)),
    }
    monkeypatch.setenv("TPURX_CKPT_CHUNK_BYTES", "16384")  # three spans
    d, rc = _save_resident(tmp_path, tree)
    (leaf_idx, shard_idx), s = _shard_of(rc, "['big']")
    assert len(s["chunks"]) == 3
    s["buf"][16384 + 5] ^= 0xFF  # in the second span
    placed = []
    real = ckpt_mod._place_leaf
    monkeypatch.setattr(
        ckpt_mod, "_place_leaf",
        lambda tmpl, arr, path: placed.append(path) or real(tmpl, arr, path),
    )
    with pytest.raises(CheckpointCorruptError) as err:
        load_checkpoint(d, tree, threads=2)
    assert writer_mod.shard_filename(leaf_idx, shard_idx) in str(err.value)
    assert "offset 16384" in str(err.value)
    assert "['big']" not in placed
    # the error's traceback holds the readers' frames, and those views of
    # the segment: gone before the fixture closes the segment under them
    del err, s
    gc.collect()


def _restored_values_outlive_the_segments(tmp_path, monkeypatch):
    """On the CPU backend ``device_put`` of a page-aligned host buffer IS
    that buffer: handing it the resident view would make the restored
    state follow the next save's bytes."""
    tree = make_tree(13)
    expect = jax.tree_util.tree_map(lambda x: np.array(x), tree)
    d, rc = _save_resident(tmp_path, tree)
    stats = {}
    restored = load_checkpoint(d, tree, threads=2, stats=stats)
    assert stats["bytes_in_place"] == stats["bytes_read"] > 0
    _overwrite_segments(rc)
    assert_trees_equal(expect, restored)


def _several_shards_and_numpy_leaves_are_copied(tmp_path, monkeypatch):
    mesh = Mesh(np.array(jax.devices()), ("x",))
    rows = jax.device_put(
        np.arange(64 * 32, dtype=np.float32).reshape(64, 32),
        NamedSharding(mesh, P("x", None)),
    )
    cols = jax.device_put(
        np.arange(16 * 64, dtype=np.float32).reshape(16, 64),
        NamedSharding(mesh, P(None, "x")),
    )
    host = np.arange(512, dtype=np.int32)
    tree = {"cols": cols, "host": host, "rows": rows}
    expect = jax.tree_util.tree_map(lambda x: np.array(x), tree)
    d, rc = _save_resident(tmp_path, tree)
    stats = {}
    restored = load_checkpoint(d, tree, threads=2, stats=stats)
    assert stats["bytes_shm"] == stats["bytes_read"] == (
        rows.nbytes + cols.nbytes + host.nbytes
    )
    # the sharded leaves were assembled in buffers of their own; the numpy
    # leaf was verified where it lay, and what came back is a copy of it
    assert stats["bytes_in_place"] == host.nbytes
    assert isinstance(restored["host"], np.ndarray)
    assert restored["host"].flags.writeable
    (_key, s) = _shard_of(rc, "['host']")
    assert not np.shares_memory(
        restored["host"], np.frombuffer(s["buf"], dtype=np.uint8)
    )
    _overwrite_segments(rc)
    assert_trees_equal(expect, restored)


def _a_reader_copy_lets_the_caller_run(tmp_path, monkeypatch):
    """The copying path's memcpy releases the GIL.  With switching on a
    timer as good as off, the caller gets to run before the reader is done
    only if something in the reader lets go of it: the crc is stubbed out,
    so that something is the copy."""
    import sys
    import zlib

    span, spans = 16 << 20, 8
    nbytes = span * spans
    resident = np.full(nbytes, 7, dtype=np.uint8)
    crc = zlib.crc32(resident[:span]) & 0xFFFFFFFF
    shard = {
        "leaf_idx": 0, "shard_idx": 0, "process_index": 0,
        "index": [[0, nbytes]], "shape": [nbytes], "nbytes": nbytes,
        "chunks": [[i * span, span, crc] for i in range(spans)],
    }
    # half of a leaf of two shards: contiguous there, but not the whole of it
    leaf = writer_mod._LeafRestore(0, (2 * nbytes,), np.dtype(np.uint8), 2)
    source = writer_mod._ShardSource(
        str(tmp_path), shard, leaf, np.dtype(np.uint8),
        res_buf=memoryview(resident),
    )
    assert not source.in_place and source.from_shm
    started, caller_ran, seen = threading.Event(), threading.Event(), []
    monkeypatch.setattr(
        writer_mod, "verify_chunk",
        lambda data, want, *a, **k: seen.append(caller_ran.is_set()) or want,
    )

    def reader():
        started.set()
        for off, length, want in source.spans:
            source.read_span(off, length, want)

    t = threading.Thread(target=reader, daemon=True)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(3600.0)
    try:
        t.start()
        started.wait(timeout=60)
        caller_ran.set()
        t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not t.is_alive() and len(seen) == spans
    assert any(seen), "the caller never ran while the reader copied"
    np.testing.assert_array_equal(leaf.out[:nbytes], resident)


@pytest.mark.parametrize("case", [
    _whole_leaf_shards_verified_where_they_lie,
    _a_flipped_resident_byte_names_shard_and_offset,
    _restored_values_outlive_the_segments,
    _several_shards_and_numpy_leaves_are_copied,
    _a_reader_copy_lets_the_caller_run,
], ids=lambda case: case.__name__.lstrip("_"))
def test_resident_in_place(case, tmp_path, monkeypatch):
    case(tmp_path, monkeypatch)


# -- the device rung: the sealed snapshot slot the chip still holds ------------


def _rejected(reason):
    return get_registry().value_of(
        "tpurx_ckpt_restore_device_rejected_total", {"reason": reason}
    )


def device_tree(seed=0):
    """Device leaves only: what the slot alone can serve."""
    k = jax.random.PRNGKey(seed)
    return {
        "w": jax.device_put(jax.random.normal(k, (64, 32))),
        "h": jax.device_put(jax.random.normal(k, (16, 8)).astype("bfloat16")),
        "b": jax.device_put(np.arange(256, dtype=np.float32)),
    }


def _device_bytes(tree):
    return sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)
    )


@pytest.fixture
def sealed_save(tmp_path):
    """``save(tree) -> (dir, checkpointer)``: a committed single-process save
    in snapshot mode through a ring of two (the CPU default, ``sync``, keeps
    no slot), the checkpointer left open so the slot stays live."""
    made = []

    def save(tree, name="ck", cp=None):
        if cp is None:
            cp = AsyncCheckpointer(digest=True, resident=True,
                                   stage_mode="snapshot", stage_buffers=2)
            made.append(cp)
        d = str(tmp_path / name)
        cp.save(tree, d, extra_metadata={"iteration": 1})
        return d, cp

    yield save
    for cp in made:
        cp.close()


def _slot_serves_a_committed_save(sealed_save, monkeypatch):
    tree = device_tree(21)
    d, _cp = sealed_save(tree)
    rc = resident_mod.lookup(d)
    assert rc.complete and rc.device is not None and rc.buffers()
    assert rc.device.plan_sig == rc.plan_sig
    _forbid_file_reads(monkeypatch)
    monkeypatch.setattr(  # nor a reader thread: the engine is not built
        ckpt_mod, "_RestoreEngine",
        lambda *a, **k: pytest.fail("the slot served every leaf"))
    before = _source_bytes("device"), _source_bytes("shm")
    stats = {}
    restored = load_checkpoint(d, tree, stats=stats)
    assert_trees_equal(tree, restored)
    assert stats["bytes_device"] == stats["bytes_read"] == _device_bytes(tree)
    assert stats["bytes_shm"] == 0 and stats["leaves"] == 3
    assert _source_bytes("device") - before[0] == _device_bytes(tree)
    assert _source_bytes("shm") == before[1]
    for got, want in zip(jax.tree_util.tree_leaves(restored),
                         jax.tree_util.tree_leaves(tree)):
        assert got.dtype == want.dtype and got.sharding == want.sharding
        assert got.committed == want.committed


def _resident_false_reads_disk_and_touches_neither_rung(sealed_save, monkeypatch):
    tree = device_tree(22)
    d, _cp = sealed_save(tree)
    before = {s: _source_bytes(s) for s in ("device", "shm", "disk")}
    stats = {}
    restored = load_checkpoint(d, tree, stats=stats, resident=False)
    assert_trees_equal(tree, restored)
    assert stats["bytes_device"] == 0 and stats["bytes_shm"] == 0
    assert stats["bytes_read"] == _device_bytes(tree)
    assert _source_bytes("device") == before["device"]
    assert _source_bytes("shm") == before["shm"]
    assert _source_bytes("disk") - before["disk"] == _device_bytes(tree)
    assert resident_mod.lookup(d).device is not None  # and stays published


def _numpy_leaves_come_from_shm_in_the_same_call(sealed_save, monkeypatch):
    tree = {**device_tree(23), "host": np.arange(512, dtype=np.int32),
            "step": np.int64(7)}
    d, _cp = sealed_save(tree)
    _forbid_file_reads(monkeypatch)
    stats = {}
    restored = load_checkpoint(d, tree, threads=2, stats=stats)
    assert_trees_equal(tree, restored)
    host_bytes = tree["host"].nbytes + tree["step"].nbytes
    assert stats["bytes_device"] == _device_bytes(tree)
    assert stats["bytes_shm"] == host_bytes
    assert stats["bytes_read"] == _device_bytes(tree) + host_bytes
    assert stats["leaves"] == 5 and isinstance(restored["host"], np.ndarray)


def _restored_leaves_share_no_buffer_with_the_slot(sealed_save, monkeypatch):
    tree = device_tree(24)
    expect = jax.tree_util.tree_map(lambda x: np.array(x), tree)
    d, _cp = sealed_save(tree)
    slot = {leaf.unsafe_buffer_pointer()
            for leaf in resident_mod.lookup(d).device.leaves}
    for _ in range(2):  # the slot serves the next fault too
        stats = {}
        restored = load_checkpoint(d, tree, stats=stats)
        assert stats["bytes_device"] == _device_bytes(tree)
        assert_trees_equal(expect, restored)
        for leaf in jax.tree_util.tree_leaves(restored):
            assert leaf.unsafe_buffer_pointer() not in slot
            leaf.delete()  # what a donating step does to its state
    assert not any(l.is_deleted() for l in resident_mod.lookup(d).device.leaves)


def _the_next_save_takes_the_slot(d, cp, tree, sealed_save, monkeypatch):
    """The call pops the drained slot to donate it, and its stager then takes
    the one pooled staging tree too: the old generation keeps neither part,
    and the new one is served from the slot it was bound to."""
    real = cp._ring_snapshot
    taken = list(resident_mod.lookup(d).device.leaves)

    def popped_first(*args):
        out = real(*args)
        # the call has the slot; the stager has not yet taken the shm tree
        assert resident_mod.lookup(d).device is None
        assert resident_mod.lookup(d).buffers()
        # the slot's memory was released to the new save's copy: a restore
        # of the committed generation between this call and its commit
        # starts at shm, and was never shown the deleted arrays
        assert all(leaf.is_deleted() for leaf in taken)
        before = _rejected("deleted"), _source_bytes("shm")
        stats = {}
        assert_trees_equal(tree, load_checkpoint(d, tree, stats=stats))
        assert stats["bytes_device"] == 0
        assert stats["bytes_shm"] == stats["bytes_read"] == _device_bytes(tree)
        assert _rejected("deleted") == before[0]
        assert _source_bytes("shm") - before[1] == _device_bytes(tree)
        return out

    monkeypatch.setattr(cp, "_ring_snapshot", popped_first)
    later = jax.tree_util.tree_map(lambda x: x + 1, tree)  # other bytes
    d2, _ = sealed_save(later, name="next", cp=cp)
    assert cp.snap_ring_stats["reused"] == 1
    assert resident_mod.lookup(d2).device.slot is cp._snap_ring[-1]
    stats = {}
    assert_trees_equal(later, load_checkpoint(d2, tree, stats=stats))
    assert stats["bytes_device"] == _device_bytes(tree)
    return "disk"


def _close_clears_the_ring(d, cp, tree, sealed_save, monkeypatch):
    cp.close()
    return "shm"


def _the_backends_are_cleared(d, cp, tree, sealed_save, monkeypatch):
    """``ShrinkMeshStage`` with the backends' clearing simulated: the device
    part is gone by the time the arrays would be."""
    import jax.extend.backend as jeb

    from tpu_resiliency.inprocess.abort import ShrinkMeshStage
    from tpu_resiliency.parallel import distributed as dist_mod

    seen = []
    monkeypatch.setattr(dist_mod, "_initialized", dist_mod._initialized)
    monkeypatch.setattr(
        jeb, "clear_backends",
        lambda: seen.append(resident_mod.lookup(d).device))
    assert "backends cleared" in ShrinkMeshStage(enabled=True).release()
    assert seen == [None]
    return "shm"


@pytest.mark.parametrize("reuse", [
    _the_next_save_takes_the_slot, _close_clears_the_ring,
    _the_backends_are_cleared,
], ids=lambda reuse: reuse.__name__.lstrip("_"))
def test_device_part_is_unpublished_on_reuse(reuse, sealed_save, monkeypatch):
    tree = device_tree(25)
    d, cp = sealed_save(tree)
    assert resident_mod.lookup(d).device is not None
    serves = reuse(d, cp, tree, sealed_save, monkeypatch)
    rc = resident_mod.lookup(d)
    if serves == "shm":  # the shm part is untouched
        assert rc.device is None and rc.buffers()
        _forbid_file_reads(monkeypatch)
    else:
        assert rc is None
    stats = {}
    restored = load_checkpoint(d, tree, threads=2, stats=stats)
    assert_trees_equal(tree, restored)
    assert stats["bytes_device"] == 0 and stats["bytes_read"] == _device_bytes(tree)
    assert stats["bytes_shm"] == (_device_bytes(tree) if serves == "shm" else 0)


def _a_swapped_slot_leaf_fails_closed(sealed_save, monkeypatch):
    tree = device_tree(26)
    d, _cp = sealed_save(tree)
    part = resident_mod.lookup(d).device
    row = part.dev_idx.index(sorted(tree).index("w"))
    part.leaves[row] = part.leaves[row] + 1.0  # other bytes, after the seal
    _forbid_file_reads(monkeypatch)
    before = _rejected("seal"), _source_bytes("device")
    stats = {}
    restored = load_checkpoint(d, tree, threads=2, stats=stats)
    assert_trees_equal(tree, restored)  # the saved bytes, from shm
    assert stats["bytes_device"] == 0
    assert stats["bytes_shm"] == stats["bytes_read"] == _device_bytes(tree)
    assert _rejected("seal") - before[0] == 1
    assert _source_bytes("device") == before[1]
    assert resident_mod.lookup(d).device is None
    assert resident_mod.lookup(d).buffers()


def _another_dtype_or_sharding_takes_the_engine_for_that_leaf(sealed_save, monkeypatch):
    mesh = Mesh(np.array(jax.devices()), ("x",))
    rows = NamedSharding(mesh, P("x", None))
    tree = {
        "cast": jax.device_put(np.arange(128, dtype=np.float32)),
        "moved": jax.device_put(
            np.arange(64 * 32, dtype=np.float32).reshape(64, 32), rows),
        "same": jax.device_put(
            np.arange(16 * 64, dtype=np.float32).reshape(16, 64), rows),
    }
    d, _cp = sealed_save(tree)
    template = {
        "cast": jax.device_put(np.zeros(128, dtype=np.float16)),
        "moved": jax.device_put(
            np.zeros((64, 32), np.float32), NamedSharding(mesh, P(None, "x"))),
        "same": tree["same"],
    }
    before = _rejected("template")
    stats = {}
    restored = load_checkpoint(d, template, threads=2, stats=stats)
    assert _rejected("template") - before == 2
    assert stats["bytes_device"] == tree["same"].nbytes
    assert stats["bytes_shm"] == tree["cast"].nbytes + tree["moved"].nbytes
    assert restored["cast"].dtype == np.float16
    np.testing.assert_array_equal(
        np.asarray(restored["cast"]), np.arange(128, dtype=np.float16))
    for name in ("moved", "same"):
        np.testing.assert_array_equal(
            np.asarray(restored[name]), np.asarray(tree[name]))
        assert restored[name].sharding == template[name].sharding
    assert resident_mod.lookup(d).device is not None  # the slot is sound


def _the_four_intervals_are_recorded_once_a_load(sealed_save, monkeypatch):
    from tpu_resiliency.telemetry import flight

    tree = device_tree(28)
    d, _cp = sealed_save(tree)
    flight.configure(enabled=True, capacity=4096)
    try:
        load_checkpoint(d, tree)
        records = [r for r in flight._records("test") if "ident" in r]
    finally:
        flight.configure()
    loads = [r for r in records if r["event"].startswith("ckpt.load")]
    (ident,) = {r["ident"] for r in loads}
    names = [r["event"] for r in loads]
    assert names == [
        "ckpt.load_begin",
        "ckpt.load.plan_begin", "ckpt.load.plan_end",
        "ckpt.load.start_begin", "ckpt.load.start_end",
        "ckpt.load.place_begin", "ckpt.load.place_end",
        "ckpt.load.wait_begin", "ckpt.load.wait_end",
        "ckpt.load_end",
    ]
    assert {r["parent"] for r in loads[1:-1]} == {"ckpt.load"}


@pytest.mark.parametrize("case", [
    _slot_serves_a_committed_save,
    _resident_false_reads_disk_and_touches_neither_rung,
    _numpy_leaves_come_from_shm_in_the_same_call,
    _restored_leaves_share_no_buffer_with_the_slot,
    _a_swapped_slot_leaf_fails_closed,
    _another_dtype_or_sharding_takes_the_engine_for_that_leaf,
    _the_four_intervals_are_recorded_once_a_load,
], ids=lambda case: case.__name__.lstrip("_"))
def test_device_rung(case, sealed_save, monkeypatch):
    case(sealed_save, monkeypatch)


@pytest.mark.parametrize("how", ["sync_mode", "ring_of_one", "resident_off"])
def test_no_slot_no_device_part(how, tmp_path):
    """Where no sealed slot can exist the save publishes as before: shm alone
    (or nothing), and the seal is not even dispatched."""
    kwargs = {
        "sync_mode": dict(stage_mode="sync", resident=True),
        "ring_of_one": dict(stage_mode="snapshot", stage_buffers=1, resident=True),
        "resident_off": dict(stage_mode="snapshot", stage_buffers=2, resident=False),
    }[how]
    tree = device_tree(29)
    d = str(tmp_path / "ck")
    cp = AsyncCheckpointer(digest=True, **kwargs)
    try:
        cp.save(tree, d, extra_metadata={"iteration": 1})
        assert all(slot["seal"] is None for slot in cp._snap_ring)
        rc = resident_mod.lookup(d)
        assert (rc is None) == (how == "resident_off")
        assert rc is None or rc.device is None
        stats = {}
        assert_trees_equal(tree, load_checkpoint(d, tree, stats=stats))
        assert stats["bytes_device"] == 0
    finally:
        cp.close()


def test_seal_is_the_chunk_fingerprint_of_a_whole_leaf():
    """One ``(A, B)`` pair a leaf: the drain's chunk fingerprint over a grid
    of one chunk, against the host oracle, for every lane width."""
    from tpu_resiliency.checkpointing.async_ckpt import device_digest

    rng = np.random.default_rng(5)
    leaves = [
        rng.standard_normal((33, 17)).astype(np.float32),
        rng.integers(0, 1 << 16, (5, 7, 3), dtype=np.uint16),
        rng.integers(0, 255, (1000,), dtype=np.uint8),
        np.float32(3.5),
        np.asarray(jax.random.normal(jax.random.PRNGKey(0), (9, 4)).astype("bfloat16")),
    ]
    seal = np.asarray(device_digest.seal_leaves([jax.device_put(x) for x in leaves]))
    for row, leaf in zip(seal, leaves):
        (want,) = device_digest.host_fingerprints(
            np.asarray(leaf).tobytes(), leaf.dtype, chunk_bytes=1 << 30,
            use_direct=False)
        assert row.tolist() == want.tolist()
    assert device_digest.seal_leaves([jax.device_put(np.ones(3, np.complex64))]) is None


class TestDeltaSaves:
    def test_delta_skips_frozen_chunks_and_restores(self, tmp_path):
        """Save, mutate ONE leaf, delta-save: frozen chunks are recorded by
        provenance (no drain) and both warm and cold restores of the delta
        directory cover every byte."""
        cp = AsyncCheckpointer(digest=True, resident=True, delta=True)
        d1, d2 = str(tmp_path / "c1"), str(tmp_path / "c2")
        t1 = make_tree(6, step=1)
        t2 = dict(t1, step=np.int64(2))  # w and b frozen
        try:
            cp.save(t1, d1, extra_metadata={"iteration": 1})
            cp.save(t2, d2, extra_metadata={"iteration": 2})
        finally:
            cp.close()
        with open(os.path.join(d2, f"process_{cp.process_index}.json")) as f:
            idx = json.load(f)
        based = [
            c
            for s in idx["shards"]
            for c in s.get("chunks", [])
            if len(c) > 3
        ]
        assert based, "delta save recorded no provenance chunks"
        assert any(
            os.path.abspath(d1) in b
            for s in idx["shards"]
            for b in s.get("bases", [])
        )
        # warm restore of the delta generation (resident covers it fully)
        stats = {}
        warm = load_checkpoint(d2, t2, threads=2, stats=stats)
        assert stats["bytes_shm"] == stats["bytes_read"]
        assert_trees_equal(t2, warm)
        # cold restores must resolve provenance across generation dirs
        resident_mod.invalidate()
        assert_trees_equal(t2, load_checkpoint(d2, t2, threads=2))
        assert_trees_equal(t2, serial_restore(d2, t2))

    def test_delta_then_layout_change_invalidates(self, tmp_path):
        """Delta chain then a layout change: the resident generation of the
        old layout is gone and the new layout restores clean."""
        cp = AsyncCheckpointer(digest=True, resident=True, delta=True)
        d1, d2, d3 = (str(tmp_path / n) for n in ("c1", "c2", "c3"))
        t1 = make_tree(7, step=1)
        t2 = dict(t1, step=np.int64(2))
        other = {"v": jax.device_put(np.full((16,), 3.0, dtype=np.float32))}
        try:
            cp.save(t1, d1, extra_metadata={"iteration": 1})
            cp.save(t2, d2, extra_metadata={"iteration": 2})
            assert resident_mod.lookup(d2) is not None
            cp.save(other, d3, extra_metadata={"iteration": 3})
        finally:
            cp.close()
        assert resident_mod.lookup(d1) is None
        assert resident_mod.lookup(d2) is None
        rc = resident_mod.lookup(d3)
        assert rc is not None
        assert_trees_equal(other, load_checkpoint(d3, other, threads=2))
        # the delta dir still restores from disk (provenance, not memory)
        assert_trees_equal(t2, load_checkpoint(d2, t2, threads=2))


# -- peer-memory rung --------------------------------------------------------


def _run_ranks(world, fn):
    errors, results = [], {}

    def wrap(rank):
        try:
            results[rank] = fn(rank)
        except Exception as exc:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            errors.append((rank, exc))

    threads = [threading.Thread(target=wrap, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    return results


def _mgr_tree(rank):
    return {
        "w": np.arange(4096, dtype=np.float32) + rank,
        "rank_marker": np.array([rank], dtype=np.int32),
    }


def test_peer_memory_restore(store_server, tmp_path):
    """Rank 1 loses its disk AND its own resident copy; the ladder serves it
    from rank 0's memory-resident replica over the exchange, then persists a
    durable copy."""
    world = 2
    peer_before = _source_bytes("peer_memory")

    def member(rank):
        store = StoreClient("127.0.0.1", store_server.port, timeout=15.0)
        ex = PeerExchange(store, rank, namespace="pxwm1")
        repl = CliqueReplication(ex, world, replication_factor=2)
        mgr = LocalCheckpointManager(
            str(tmp_path / f"node{rank}"), rank, world,
            store=store, replication=repl,
        )
        try:
            mgr.save(_mgr_tree(rank), iteration=7, is_async=False)
            if rank == 1:
                mgr.drop_resident()
                shutil.rmtree(mgr.root)
            tree, it = mgr.load(_mgr_tree(rank), iteration=7)
            if rank == 1:
                # durability repaired: the warm fetch left a disk copy
                path = mgr._blob_path(7, 1)
                assert os.path.exists(path) and os.path.exists(path + ".done")
            return int(np.asarray(tree["rank_marker"])[0])
        finally:
            mgr.close()
            ex.close()
            store.close()

    results = _run_ranks(world, member)
    assert results == {0: 0, 1: 1}
    assert _source_bytes("peer_memory") > peer_before
    assert _source_bytes("local_resident") > 0


def test_peer_memory_stall_falls_to_disk(store_server, tmp_path, monkeypatch):
    """A stalled serving peer (drops requests) must NOT wedge the restore:
    the rung times out and the ladder falls through to the rank's own disk
    blob with fallback depth 0."""
    monkeypatch.setenv("TPURX_FAULT", "peer_mem_stall")
    monkeypatch.setenv("TPURX_FAULT_RANKS", "0")  # only rank 0 drops requests
    monkeypatch.setenv("TPURX_CKPT_PEER_MEM_TIMEOUT", "1.5")
    world = 2
    disk_before = _source_bytes("local_disk")
    peer_before = _source_bytes("peer_memory")

    def member(rank):
        store = StoreClient("127.0.0.1", store_server.port, timeout=15.0)
        ex = PeerExchange(store, rank, namespace="pxwm2")
        repl = CliqueReplication(ex, world, replication_factor=2)
        mgr = LocalCheckpointManager(
            str(tmp_path / f"node{rank}"), rank, world,
            store=store, replication=repl,
        )
        try:
            mgr.save(_mgr_tree(rank), iteration=9, is_async=False)
            if rank == 1:
                mgr.drop_resident()  # forces the ladder past the memory rung
            tree, _ = mgr.load(_mgr_tree(rank), iteration=9)
            return int(np.asarray(tree["rank_marker"])[0])
        finally:
            mgr.close()
            ex.close()
            store.close()

    results = _run_ranks(world, member)
    assert results == {0: 0, 1: 1}
    assert _source_bytes("peer_memory") == peer_before  # rung never served
    assert _source_bytes("local_disk") > disk_before
    assert get_registry().value_of("tpurx_ckpt_fallback_depth") == 0
