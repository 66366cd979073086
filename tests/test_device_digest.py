"""Device-side digest path tests: on-device fingerprints, D2H-skipping
delta saves, the double-buffered snapshot ring, and sharding-derived save
planning.

Four properties anchor the zero-stall save path:

- the jitted fingerprint kernel and the numpy host oracle compute the SAME
  per-chunk (A, B) rows for every lane-bitcastable dtype — bfloat16
  included — so a device-vs-baseline match means what the drain thinks it
  means;
- a delta save under an active device digest skips the D2H entirely for
  unchanged shards, yet every restore rung (resident shm, peer exchange,
  cold disk) reproduces the bytes exactly, because the skip records
  base-generation provenance instead of bytes;
- device/host verdict disagreement on a transferred chunk is DETECTED
  corruption: the save fails closed, the partial output is quarantined as
  ``*.corrupt``, nothing commits;
- the owner map derived from ``NamedSharding`` assigns every global index
  box to exactly one device cluster-wide, and refuses shardings that
  over- or under-tile the global shape.
"""

import contextlib
import gc
import glob
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_resiliency.checkpointing.async_ckpt import (
    checkpointer as ckpt_mod,
    device_digest as dd,
    resident as resident_mod,
    staging as staging_mod,
    writer as writer_mod,
)
from tpu_resiliency.checkpointing.async_ckpt.checkpointer import (
    AsyncCheckpointer,
    CheckpointSaveError,
    load_checkpoint,
)
from tpu_resiliency.checkpointing.async_ckpt.peer_source import (
    PeerRestoreSource,
)
from tpu_resiliency.checkpointing.local.replication import PeerExchange
from tpu_resiliency.store import StoreClient
from tpu_resiliency.telemetry import get_registry


@pytest.fixture(autouse=True)
def _fresh_registry():
    resident_mod.invalidate()
    yield
    resident_mod.invalidate()


def assert_trees_equal(a, b):
    la, _ = jax.tree_util.tree_flatten(a)
    lb, _ = jax.tree_util.tree_flatten(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        ax, ay = np.asarray(x), np.asarray(y)
        assert ax.dtype == ay.dtype
        assert ax.tobytes() == ay.tobytes()  # byte-identical, not just ==


# -- kernel vs host oracle ---------------------------------------------------


class TestFingerprintKernel:
    CHUNK = 1024  # force multi-chunk grids on small arrays

    @pytest.mark.parametrize(
        "dtype",
        ["float32", "bfloat16", "float16", "int32", "int8", "uint16", "bool"],
    )
    def test_device_matches_host_oracle(self, dtype):
        """The jitted kernel and the numpy oracle agree per chunk, per
        dtype — the exact agreement the drain's cross-check relies on."""
        rng = np.random.default_rng(7)
        host = rng.standard_normal(3001).astype(np.float32)
        x = jnp.asarray(host).astype(dtype)
        host_np = np.asarray(x)  # post-cast bytes (ml_dtypes for bfloat16)

        fp_dev = dd.shard_fingerprints(x, chunk_bytes=self.CHUNK,
                                       use_direct=False)
        assert fp_dev is not None
        (rows_dev,) = dd.read_fingerprints([fp_dev])
        rows_host = dd.host_fingerprints(
            host_np.tobytes(), host_np.dtype, chunk_bytes=self.CHUNK,
            use_direct=False,
        )
        grid = writer_mod.chunk_grid(host_np.nbytes, self.CHUNK, False)
        assert len(grid) > 1, "test must exercise a multi-chunk grid"
        assert rows_dev.shape == (len(grid), 2)
        np.testing.assert_array_equal(rows_dev, rows_host)

    def test_mutation_flips_only_its_chunk(self):
        x = jnp.arange(2048, dtype=jnp.float32)
        y = x.at[700].set(-1.0)  # byte offset 2800 -> second 1 KiB chunk
        (ra,) = dd.read_fingerprints(
            [dd.shard_fingerprints(x, chunk_bytes=self.CHUNK, use_direct=False)]
        )
        (rb,) = dd.read_fingerprints(
            [dd.shard_fingerprints(y, chunk_bytes=self.CHUNK, use_direct=False)]
        )
        changed = [i for i in range(ra.shape[0])
                   if not np.array_equal(ra[i], rb[i])]
        assert changed == [2]  # offset 2800 lands in chunk index 2

    def test_swapped_lanes_change_the_fingerprint(self):
        """The position-mixed lanes make reorderings visible — a plain
        multiset-preserving swap must not fingerprint equal."""
        x = jnp.asarray(np.array([1, 2, 3, 4], dtype=np.uint32))
        y = jnp.asarray(np.array([2, 1, 3, 4], dtype=np.uint32))
        (ra,) = dd.read_fingerprints([dd.shard_fingerprints(x)])
        (rb,) = dd.read_fingerprints([dd.shard_fingerprints(y)])
        assert not np.array_equal(ra, rb)

    def test_uniform_constant_bump_changes_fingerprint(self):
        """Regression: raw Fletcher sums telescope to ZERO on a uniform
        constant delta across a power-of-two-length chunk (`full(0.)` ->
        `full(1.)` fingerprinted equal, silently skipping a changed
        shard).  The avalanche mix must break the telescope."""
        n = 1 << 20
        x = jnp.full((n,), 0.0, jnp.float32)
        y = x + 1.0
        (ra,) = dd.read_fingerprints([dd.shard_fingerprints(x)])
        (rb,) = dd.read_fingerprints([dd.shard_fingerprints(y)])
        assert ra.shape == rb.shape
        for i in range(ra.shape[0]):
            assert not np.array_equal(ra[i], rb[i])

    def test_unsupported_dtype_stays_on_crc_path(self):
        assert dd.shard_fingerprints(jnp.ones(8, jnp.complex64)) is None
        assert dd.host_fingerprints(b"\x00" * 64, np.complex64) is None


# -- delta D2H-skip end to end ----------------------------------------------


def _big_tree(mutate=()):
    """~10 leaves; ``mutate`` names leaves whose values differ."""
    tree = {}
    for i in range(8):
        base = np.full(4096 + 128 * i, float(i + 1), dtype=np.float32)
        if f"f{i}" in mutate:
            base[17] = -99.0
        tree[f"f{i}"] = jnp.asarray(base)
    bf = np.arange(2048, dtype=np.float32) % 7.0
    if "bf" in mutate:
        bf[0] = 5.5
    tree["bf"] = jnp.asarray(bf).astype(jnp.bfloat16)
    tree["host"] = np.arange(33, dtype=np.int64)  # host leaf: never skips
    return tree


class TestDeltaD2HSkip:
    def test_unchanged_shards_skip_the_transfer(self, tmp_path):
        """Mutate ~10% of leaves; every unchanged device shard must skip
        D2H entirely, and all three generations restore byte-identically
        from disk (the sparse files resolve provenance) AND from the
        resident shm source."""
        d = str(tmp_path)
        ck = AsyncCheckpointer(delta=True, digest=True, device_digest=True)
        try:
            t1 = _big_tree()
            ck.save(t1, d + "/g1", {"iteration": 1})
            assert ck.last_stage_stats["d2h_skipped_bytes"] == 0  # no baseline

            t2 = _big_tree(mutate=("f3",))  # 1 of 10 leaves changes
            ck.save(t2, d + "/g2", {"iteration": 2})
            dev_total = sum(
                np.asarray(v).nbytes for k, v in t2.items() if k != "host"
            )
            changed = np.asarray(t2["f3"]).nbytes
            assert ck.last_stage_stats["d2h_skipped_bytes"] == dev_total - changed
            assert ck.last_drain_stats.get("d2h_skipped_bytes") == \
                dev_total - changed

            # provenance rows in the committed index point at g1's files
            idx = json.load(open(d + "/g2/process_0.json"))
            skip_shards = [s for s in idx["shards"] if s.get("bases")]
            assert skip_shards, "no provenance-only shards recorded"
            assert all("g1" in b for s in skip_shards for b in s["bases"])

            # warm (resident) restore of the delta generation
            warm = load_checkpoint(d + "/g2", t2, stats=(st := {}))
            assert_trees_equal(warm, t2)
            assert st.get("bytes_shm", 0) > 0
        finally:
            ck.close()
        # cold restores of every generation, resident source gone
        resident_mod.invalidate()
        for g, ref in (("g1", t1), ("g2", t2)):
            out = load_checkpoint(d + "/" + g, ref, resident=False)
            assert_trees_equal(out, ref)

    def test_fully_frozen_save_writes_nothing(self, tmp_path):
        d = str(tmp_path)
        ck = AsyncCheckpointer(delta=True, digest=True, device_digest=True)
        try:
            t = _big_tree()
            ck.save(t, d + "/g1", {"iteration": 1})
            ck.save(t, d + "/g2", {"iteration": 2})
            dev_total = sum(
                np.asarray(v).nbytes for k, v in t.items() if k != "host"
            )
            assert ck.last_stage_stats["d2h_skipped_bytes"] == dev_total
            assert ck.last_drain_stats.get("bytes_written", 0) == 0
        finally:
            ck.close()
        resident_mod.invalidate()
        assert_trees_equal(load_checkpoint(d + "/g2", t, resident=False), t)

    def test_peer_rung_restores_skipped_generation(self, tmp_path, store_server):
        """Satellite 1: with local files gone, ``load_checkpoint(peers=...)``
        pulls the shards from a peer's resident copy over the exchange —
        including a generation whose save skipped D2H."""
        c0 = StoreClient("127.0.0.1", store_server.port, timeout=10.0)
        c1 = StoreClient("127.0.0.1", store_server.port, timeout=10.0)
        ex0, ex1 = PeerExchange(c0, 0), PeerExchange(c1, 1)
        d = str(tmp_path)
        ck = AsyncCheckpointer(delta=True, digest=True, device_digest=True)
        src0 = src1 = None
        try:
            t1 = _big_tree()
            ck.save(t1, d + "/g1", {"iteration": 1})
            t2 = _big_tree(mutate=("f5",))
            ck.save(t2, d + "/g2", {"iteration": 2})
            assert ck.last_stage_stats["d2h_skipped_bytes"] > 0
            src0 = PeerRestoreSource(ex0, 0, [1]).install()  # serves resident
            src1 = PeerRestoreSource(ex1, 1, [0]).install()  # fetches

            for f in glob.glob(d + "/g2/process_0/*.bin") + \
                    glob.glob(d + "/g1/process_0/*.bin"):
                os.unlink(f)
            out = load_checkpoint(
                d + "/g2", t2, stats=(st := {}), resident=False, peers=src1
            )
            assert_trees_equal(out, t2)
            assert st.get("bytes_peer", 0) > 0
            assert src0.stats["bytes_served"] == st["bytes_peer"]
        finally:
            for h in (src0, src1):
                if h is not None:
                    h.close()
            ck.close()
            ex0.close()
            ex1.close()
            c0.close()
            c1.close()


# -- digest/crc disagreement: detected, quarantined, never committed ---------


class TestDigestDisagreement:
    def test_lying_device_verdict_fails_closed(self, tmp_path, monkeypatch):
        d = str(tmp_path)
        ck = AsyncCheckpointer(delta=True, digest=True, device_digest=True)
        try:
            t1 = _big_tree()
            ck.save(t1, d + "/g1", {"iteration": 1})

            # inject the fault AFTER the baseline exists: the device claims
            # every chunk unchanged while the staged bytes really changed —
            # the model of a torn D2H / stale staging buffer
            def lying_verdict(self, key, nbytes, fp):
                grid = writer_mod.chunk_grid(
                    nbytes, self.chunk_bytes, self.use_direct
                )
                return None, list(grid)

            monkeypatch.setattr(dd.DigestContext, "verdict", lying_verdict)
            t2 = _big_tree(mutate=("f0",))
            with pytest.raises(CheckpointSaveError):
                ck.save(t2, d + "/g2", {"iteration": 2})
        finally:
            with contextlib.suppress(Exception):
                ck.close()
        # the disagreeing shard is quarantined for post-mortem, and the
        # generation never commits (no merged metadata)
        assert glob.glob(d + "/g2/process_0/*.corrupt")
        assert not os.path.exists(d + "/g2/metadata.json")


# -- double-buffered snapshot ring -------------------------------------------


def _slots_taken(since=None):
    """``tpurx_ckpt_snap_slot_total`` by outcome, less an earlier reading."""
    now = {
        outcome: get_registry().value_of(
            "tpurx_ckpt_snap_slot_total", {"outcome": outcome})
        for outcome in ("reused", "fresh")
    }
    return now if since is None else {k: now[k] - since[k] for k in now}


def _copy_sees_the_slot_released(monkeypatch, stale):
    """Wrap the snapshot copy: when it is dispatched, ``stale`` (a list the
    test fills) must hold deleted arrays only.  Returns the dispatch count."""
    real, calls = ckpt_mod._SNAP_FN, []

    def checked(xs):
        assert all(leaf.is_deleted() for leaf in stale)
        calls.append(len(stale))
        return real(xs)

    monkeypatch.setattr(ckpt_mod, "_SNAP_FN", checked)
    return calls


def _live_bytes(known):
    """Bytes of the live device buffers under arrays that ``known`` (ids)
    does not name; a leaf and its ``addressable_shards[0].data`` are two
    arrays over one buffer."""
    gc.collect()
    return sum({
        a.unsafe_buffer_pointer(): a.nbytes
        for a in jax.live_arrays() if id(a) not in known
    }.values())


def _tree_bytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


def _dense_tree(i):
    k = jax.random.PRNGKey(i)
    w = jax.random.normal(k, (96, 128), jnp.float32)
    return {"params": {"w": w.astype(jnp.bfloat16)},
            "opt": {"master": w, "mu": w * 0.1, "nu": w * w}}


def _routed_tree(i):
    """The routed families' leaf kinds at CPU size: a float32-only leaf, an
    int32 buffer no gradient touches and a scalar count beside the usual
    bfloat16 parameter with its float32 shadow."""
    tree = _dense_tree(i)
    tree["params"]["A_log"] = jnp.linspace(0.0, 1.0, 16) + i
    tree["opt"]["load"] = jnp.arange(4 * 64, dtype=jnp.int32).reshape(4, 64) + i
    tree["opt"]["count"] = jnp.asarray(i, jnp.int32)
    return tree


class TestSnapshotRing:
    @pytest.mark.parametrize("make", [_dense_tree, _routed_tree],
                             ids=["dense", "routed"])
    def test_reusing_save_takes_over_the_drained_slot(
            self, make, tmp_path, monkeypatch):
        """A later save's copy gets the memory of the drained slot it took:
        the stale leaves are released before the copy is dispatched, so the
        process holds the live tree and ONE slot at every instant, not
        two."""
        known = {id(a) for a in jax.live_arrays()}
        trees = [make(i) for i in range(2)]
        d = str(tmp_path)
        # no digest, no resident copy: the slot's leaves are then the only
        # device arrays the checkpointer makes
        ck = AsyncCheckpointer(digest=False, resident=False,
                               stage_mode="snapshot", stage_buffers=2)
        try:
            before = _slots_taken()
            ck.save(trees[0], d + "/g0", {"iteration": 0})
            taken = list(ck._snap_ring[0]["leaves"])
            at_rest = _live_bytes(known)
            assert at_rest == _tree_bytes(trees) + _tree_bytes(trees[0])
            calls = _copy_sees_the_slot_released(monkeypatch, taken)
            ck.save(trees[1], d + "/g1", {"iteration": 1})
            assert calls == [len(taken)]  # one program, the first save's
            assert ck.snap_ring_stats == {"reused": 1, "fresh": 1}
            (slot,) = ck._snap_ring
            assert not any(leaf.is_deleted() for leaf in slot["leaves"])
            assert _live_bytes(known) == at_rest
            assert _slots_taken(before) == {"reused": 1, "fresh": 1}
        finally:
            ck.close()
        for i in range(2):
            out = load_checkpoint(d + f"/g{i}", trees[0], resident=False)
            assert_trees_equal(out, trees[i])

    def test_a_host_view_of_a_released_slot_leaf_stays_valid(self, tmp_path):
        """Somebody still holds a host view of a stale leaf (the CPU
        backend's ``np.asarray`` is zero-copy) when the next save releases
        the slot: the view keeps its bytes, and the save is right."""
        trees = [_routed_tree(i) for i in range(2)]
        d = str(tmp_path)
        ck = AsyncCheckpointer(digest=False, resident=False,
                               stage_mode="snapshot", stage_buffers=2)
        try:
            ck.save(trees[0], d + "/g0", {"iteration": 0})
            taken = ck._snap_ring[0]["leaves"][0]
            held = np.asarray(taken)  # an outside reference to its buffer
            ck.save(trees[1], d + "/g1", {"iteration": 1})
            assert ck.snap_ring_stats == {"reused": 1, "fresh": 1}
            assert taken.is_deleted()
            assert held.tobytes() == np.asarray(
                jax.tree_util.tree_leaves(trees[0])[0]).tobytes()
        finally:
            ck.close()
        out = load_checkpoint(d + "/g1", trees[0], resident=False)
        assert_trees_equal(out, trees[1])

    def test_slow_drain_never_reuses_a_live_slot(self, tmp_path, monkeypatch):
        """Inject a slow D2H: with staging stalled, a rapid second save must
        take a FRESH buffer set (the fence holds); once drained, the next
        save takes a slot over. Every generation restores byte-identically —
        the second snapshot never clobbered the first's device buffers."""
        real_stage = ckpt_mod.stage_pytree
        release = threading.Event()

        def slow_stage(*a, **kw):
            release.wait(timeout=30.0)  # D2H stalled until the test says go
            return real_stage(*a, **kw)

        monkeypatch.setattr(ckpt_mod, "stage_pytree", slow_stage)
        d = str(tmp_path)
        known = {id(a) for a in jax.live_arrays()}
        # no resident copy: no seal, so tree and slots are all that is live
        ck = AsyncCheckpointer(digest=True, resident=False,
                               stage_mode="snapshot", stage_buffers=2)
        try:
            trees = [
                {"w": jnp.full((512,), float(i), jnp.float32),
                 "b": jnp.arange(64, dtype=jnp.int32) + i}
                for i in range(3)
            ]
            before = _slots_taken()
            ck.async_save(trees[0], d + "/g0", {"iteration": 0})
            ck.async_save(trees[1], d + "/g1", {"iteration": 1})
            # both issued while staging was stalled: no slot was donatable
            assert ck.snap_ring_stats == {"reused": 0, "fresh": 2}
            release.set()
            ck.finalize_all()
            taken = list(ck._snap_ring[0]["leaves"])
            calls = _copy_sees_the_slot_released(monkeypatch, taken)
            ck.async_save(trees[2], d + "/g2", {"iteration": 2})
            ck.finalize_all()
            # drained ring: the third save took a slot over instead, ...
            assert ck.snap_ring_stats["reused"] == 1
            # ... for real: its leaves were released before the copy was
            # dispatched, so the ring still holds two slots' bytes, not three
            assert calls == [len(taken)]
            assert _live_bytes(known) == (
                _tree_bytes(trees) + 2 * _tree_bytes(trees[0]))
            assert _slots_taken(before) == {"reused": 1, "fresh": 2}
        finally:
            release.set()
            ck.close()
        resident_mod.invalidate()
        for i in range(3):
            out = load_checkpoint(d + f"/g{i}", trees[0], resident=False)
            assert_trees_equal(out, trees[i])

    def test_ring_depth_one_is_legacy_snapshot(self, tmp_path):
        d = str(tmp_path)
        ck = AsyncCheckpointer(stage_mode="snapshot", stage_buffers=1)
        try:
            t = {"w": jnp.ones(256, jnp.float32)}
            ck.save(t, d + "/g1", {"iteration": 1})
            assert ck.snap_ring_stats == {"reused": 0, "fresh": 0}
        finally:
            ck.close()
        resident_mod.invalidate()
        assert_trees_equal(load_checkpoint(d + "/g1", t, resident=False), t)


# -- sharding-derived save planning ------------------------------------------


class _FakeDev:
    def __init__(self, id):  # noqa: A002 - mirrors jax.Device.id
        self.id = id


class _FakeSharding:
    def __init__(self, dmap):
        self._dmap = dmap

    def devices_indices_map(self, shape):
        return self._dmap


class _FakeLeaf:
    def __init__(self, shape, dmap):
        self.shape = shape
        self.sharding = _FakeSharding(dmap)


class TestShardOwnerMap:
    def _mesh(self):
        return Mesh(np.array(jax.devices()).reshape(4, 2), ("x", "y"))

    @pytest.mark.parametrize(
        "spec,n_boxes",
        [(P("x", "y"), 8), (P("x", None), 4), (P(None, "y"), 2), (P(), 1)],
    )
    def test_exactly_once_on_real_mesh(self, spec, n_boxes):
        """Each distinct index box gets ONE owner; summing shard_is_owner
        over all addressable shards equals the box count — exactly-once
        coverage, no replicated-leaf double-drain."""
        mesh = self._mesh()
        leaf = jax.device_put(
            np.arange(64 * 32, dtype=np.float32).reshape(64, 32),
            NamedSharding(mesh, spec),
        )
        owners = staging_mod.shard_owner_map(leaf)
        assert owners is not None and len(owners) == n_boxes
        owned = sum(
            staging_mod.shard_is_owner(leaf, s, 0, owners)
            for s in leaf.addressable_shards
        )
        assert owned == n_boxes
        total = sum(staging_mod._box_volume(b) for b in owners)
        assert total == 64 * 32

    def test_two_host_mesh_single_owner_per_box(self):
        """Simulated 2-host mesh: rows replicated across hosts — the owner
        map picks the lowest device id per box, so each host's planner
        derives the same assignment with no exchange."""
        sl = slice(None)
        dmap = {
            _FakeDev(0): (slice(0, 8), sl),   # host 0
            _FakeDev(4): (slice(0, 8), sl),   # host 1 replica
            _FakeDev(1): (slice(8, 16), sl),  # host 0
            _FakeDev(5): (slice(8, 16), sl),  # host 1 replica
        }
        owners = staging_mod.shard_owner_map(_FakeLeaf((16, 4), dmap))
        assert len(owners) == 2
        assert sorted(d.id for d in owners.values()) == [0, 1]

    def test_overlapping_boxes_rejected(self):
        sl = slice(None)
        dmap = {
            _FakeDev(0): (slice(0, 10), sl),
            _FakeDev(1): (slice(8, 16), sl),  # rows 8..10 double-drained
        }
        with pytest.raises(ValueError, match="exactly once"):
            staging_mod.shard_owner_map(_FakeLeaf((16, 4), dmap))

    def test_gapped_boxes_rejected(self):
        sl = slice(None)
        dmap = {
            _FakeDev(0): (slice(0, 8), sl),
            _FakeDev(1): (slice(8, 12), sl),  # rows 12..16 lost
        }
        with pytest.raises(ValueError, match="exactly once"):
            staging_mod.shard_owner_map(_FakeLeaf((16, 4), dmap))

    def test_host_arrays_fall_back(self):
        assert staging_mod.shard_owner_map(np.ones(8)) is None


# -- drain_progress under delta skips ----------------------------------------


class TestDrainProgressCredit:
    def test_skipped_bytes_credit_immediately(self, tmp_path):
        """Satellite 2: a provenance-only payload's bytes count toward
        drain progress the moment the plan sees it — NOT when a pool
        thread finishes, so a mostly-frozen delta save never reads as
        stalled below 100%."""
        seen = []
        nbytes = 256 * 1024
        eng = writer_mod._WriteEngine(
            str(tmp_path), 0, 2, "s1", "sigX",
            progress_cb=lambda w, t: seen.append((w, t)), digest=True,
        )
        eng.announce_total(nbytes)
        eng.add_payload({
            "leaf_idx": 0, "shard_idx": 0,
            "global_shape": [nbytes // 4], "index": [[0, nbytes // 4]],
            "dtype": "float32", "shm_name": "", "shape": [nbytes // 4],
            "nbytes": nbytes,
            "skip_spans": [[0, nbytes, 123, "/base/g0/process_0/s0.bin"]],
        })
        # credited at enqueue: the LAST report already shows full coverage,
        # before finish() waits on the pool at all
        assert seen and seen[-1] == (nbytes, nbytes)
        stats = eng.finish()
        assert stats["d2h_skipped_bytes"] == nbytes
        assert stats["bytes_written"] == 0
