"""The stager's device-to-host stage, bit for bit and in order.

Trees shaped like the benchmark's configurations at CPU size (109, 199 and 447
leaves; bfloat16 leaves with float32 shadows, float32-only leaves, int32
buffers, a scalar, a zero-size and a numpy leaf, one leaf larger than the
transfer window among runs of leaves far smaller) go through ``stage_pytree``
fresh and then, with other values, into the pooled segments; every shm segment
is compared with its leaf byte for byte, ``on_shard_staged`` with the plan's
order, and the byte counts with the leaves' own.  The window is patched down to
CPU size so that every tree has shards that go alone, shards that share a
window and shards that wait for one."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_resiliency.checkpointing.async_ckpt import staging

WINDOW = 1 << 16  # bytes: the CPU-size stand-in for staging.D2H_WINDOW_BYTES


def _leaf(rng, shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(-2**31, 2**31 - 1, size=shape), dtype=dtype)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


def _with_shadows(rng, shape):
    """A trained leaf as the steps keep it: bfloat16 + float32 master, mu, nu."""
    return {"p": _leaf(rng, shape, jnp.bfloat16), "master": _leaf(rng, shape, jnp.float32),
            "mu": _leaf(rng, shape, jnp.float32), "nu": _leaf(rng, shape, jnp.float32)}


def dense_tree(seed):
    """109 leaves: the dense decoder's shape.  Four vocabulary-sized leaves far
    over the window, 25 x 4 layer leaves from a few bytes to a window's half,
    and the five odd ones."""
    rng = np.random.default_rng(seed)
    tree = {"wte": _with_shadows(rng, (512, 48))}          # 49-98 KB: each alone
    for i in range(25):
        shape = [(48,), (48, 48), (48, 144), (48, 192), (16,)][i % 5]
        tree[f"layer{i:02d}"] = _with_shadows(rng, shape)
    tree.update(_odd_leaves(rng))
    return tree


def routed_tree(seed, n_trained, n_f32_only):
    """4 x n_trained + n_f32_only + 2 buffers + 5 odd leaves: a routed step's
    shape, many small arrays, float32-only leaves and int32 buffers between."""
    rng = np.random.default_rng(seed)
    tree = {}
    for i in range(n_trained):
        shape = [(32, 24), (24,), (8, 32, 24), (32, 96), (4, 16)][i % 5]
        if i == n_trained // 2:
            shape = (768, 64)                               # one leaf over the window
        tree[f"t{i:03d}"] = _with_shadows(rng, shape)
    for i in range(n_f32_only):
        tree[f"f{i:03d}"] = _leaf(rng, (16 + i % 3,), jnp.float32)   # A_log, dt_bias
    tree["router_bias"] = _leaf(rng, (4, 32), jnp.float32)
    tree["load"] = _leaf(rng, (4, 32), jnp.int32)
    tree.update(_odd_leaves(rng))
    return tree


def _odd_leaves(rng):
    return {
        "zz_count": jnp.int32(int(rng.integers(1, 1000))),           # a scalar
        "zz_empty": jnp.zeros((0, 8), jnp.float32),                  # zero-size
        "zz_numpy": rng.integers(0, 255, size=(37,)).astype(np.int64),
        "zz_pyint": int(rng.integers(1, 1000)),                      # non-array leaf
        "zz_big_i32": _leaf(rng, (40_000,), jnp.int32),              # 160 KB int32
    }


def sharded_tree(seed):
    """Several shards a leaf: a leaf split over eight devices, one split over
    four and replicated over two, one replicated over all."""
    rng = np.random.default_rng(seed)
    devs = np.array(jax.devices()[:8])
    mesh8 = Mesh(devs, ("x",))
    mesh42 = Mesh(devs.reshape(4, 2), ("x", "y"))
    put = jax.device_put
    tree = {
        "split8": put(_leaf(rng, (64, 4096), jnp.float32), NamedSharding(mesh8, P("x"))),
        "split4": put(_leaf(rng, (16, 1024), jnp.bfloat16), NamedSharding(mesh42, P("x"))),
        "rep": put(_leaf(rng, (100,), jnp.int32), NamedSharding(mesh8, P())),
    }
    for i in range(12):
        tree[f"small{i:02d}"] = put(_leaf(rng, (8, 64), jnp.float32),
                                    NamedSharding(mesh8, P("x")))
    tree.update(_odd_leaves(rng))
    return tree


TREES = {
    "dense-109": (dense_tree, 109),
    "routed-199": (lambda seed: routed_tree(seed, 48, 0), 199),
    "routed-447": (lambda seed: routed_tree(seed, 100, 40), 447),
    "sharded": (sharded_tree, 20),
}


def _expected_bytes(leaf, info):
    """The bytes shard ``info`` of ``leaf`` has to have in its segment."""
    if isinstance(leaf, jax.Array):
        return np.asarray(leaf.addressable_shards[info.shard_idx].data).tobytes()
    return np.asarray(leaf).tobytes()


def _check(staged, tree, seen, plans):
    leaves = jax.tree_util.tree_leaves(tree)
    owned = [s for s in staged.shards if s.replica_owner]
    # every owned shard streamed once, in the plan's order
    assert [(s.leaf_idx, s.shard_idx) for s in seen] == \
        [(s.leaf_idx, s.shard_idx) for s in owned]
    bufs = staged.shm_buffers()
    assert len(bufs) == len(owned)
    total = 0
    for info in owned:
        want = _expected_bytes(leaves[info.leaf_idx], info)
        assert info.nbytes == len(want), (info.leaf_idx, info.dtype)
        got = bytes(bufs[info.shm_name][:info.nbytes])
        assert got == want, f"leaf {info.leaf_idx} shard {info.shard_idx} ({info.dtype})"
        total += info.nbytes
    assert plans == [total]
    return total


@pytest.mark.parametrize("name", list(TREES))
def test_stage_pytree_bit_for_bit_fresh_then_pooled(name, monkeypatch):
    # raising=False: this one test holds the parent of the window too
    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW, raising=False)
    make, n_leaves = TREES[name]
    first, second = make(1), make(2)
    assert len(jax.tree_util.tree_leaves(first)) == n_leaves
    sizes = [x.nbytes for x in jax.tree_util.tree_leaves(first) if isinstance(x, jax.Array)]
    assert max(sizes) > WINDOW and sum(1 for b in sizes if b < WINDOW // 16) > 10

    seen, plans = [], []
    staged = staging.stage_pytree(first, process_index=0, on_plan=plans.append,
                                  on_shard_staged=seen.append)
    try:
        total = _check(staged, first, seen, plans)
        assert staged.bytes_allocated == total and staged.bytes_reused == 0
        names = [s.shm_name for s in staged.shards]

        # other values into the pooled segments: segment k stays shard k's
        del seen[:], plans[:]
        again = staging.stage_pytree(second, process_index=0, reuse=staged,
                                     on_plan=plans.append, on_shard_staged=seen.append)
        assert again is staged
        assert [s.shm_name for s in staged.shards] == names
        assert _check(staged, second, seen, plans) == total
        assert staged.bytes_allocated == 0 and staged.bytes_reused == total
    finally:
        staged.close(unlink=True)


# ---- the issue policy, without a device --------------------------------------

MiB = 1 << 20
SIZE_LISTS = {
    # the dense configuration's shape: four vocabulary-sized leaves, then layers
    "dense": [161 * MiB, 322 * MiB, 322 * MiB, 322 * MiB] + [15 * MiB, 31 * MiB, 6400, 41 * MiB] * 26,
    # a routed configuration's: hundreds of leaves of 14-36 MB, buffers between
    "routed": ([14 * MiB, 28 * MiB, 28 * MiB, 28 * MiB, 512, 36 * MiB] * 74)[:447],
    "all-tiny": [4096] * 300,
    "all-over": [200 * MiB, 129 * MiB, 500 * MiB],
    "zeros-between": [0, 100 * MiB, 0, 0, 300 * MiB, 0, 28 * MiB, 0],
    "one": [7],
    "none": [],
}


def _drive(sizes, window):
    """Run the stager's loop over ``sizes`` with ``issue_upto`` and return the
    log of ("issue" | "await", k) and the most bytes that were outstanding."""
    cum = [0, *itertools.accumulate(sizes)]
    issued = landed = peak = 0
    events = []

    def top_up():
        nonlocal issued, peak
        upto = staging.issue_upto(cum, issued, landed, window)
        assert issued <= upto <= len(sizes)
        events.extend(("issue", k) for k in range(issued, upto))
        issued = upto
        out = cum[issued] - cum[landed]
        # never over the window, except for one shard alone
        assert out <= window or issued - landed == 1, (issued, landed, out)
        peak = max(peak, out)

    top_up()
    for k in range(len(sizes)):
        assert issued > k, f"transfer {k} awaited before it was issued"
        events.append(("await", k))
        landed += 1
        top_up()
    return events, peak


@pytest.mark.parametrize("window", [1, 64 * MiB, 128 * MiB, 256 * MiB, 1 << 40])
@pytest.mark.parametrize("name", list(SIZE_LISTS))
def test_issue_policy_bounds_the_bytes_outstanding(name, window):
    sizes = SIZE_LISTS[name]
    events, peak = _drive(sizes, window)
    issues = [k for what, k in events if what == "issue"]
    assert issues == list(range(len(sizes)))            # each once, in plan order
    for k in range(len(sizes)):
        assert events.index(("issue", k)) < events.index(("await", k))
    assert peak <= max([window] + sizes)
    if sizes and window >= sum(sizes):
        assert events[:len(sizes)] == [("issue", k) for k in range(len(sizes))]
    if window == 1 and all(sizes):
        # a window nothing fits in: strictly one transfer at a time
        assert events == [e for k in range(len(sizes)) for e in (("issue", k), ("await", k))]


def test_issue_policy_keeps_the_window_full():
    """Whatever fits is issued at once: after every landing the next shard
    that is still waiting would have passed the window."""
    sizes, window = SIZE_LISTS["routed"], 128 * MiB
    cum = [0, *itertools.accumulate(sizes)]
    issued = staging.issue_upto(cum, 0, 0, window)
    for landed in range(1, len(sizes) + 1):
        issued = staging.issue_upto(cum, issued, landed, window)
        if issued < len(sizes):
            assert cum[issued + 1] - cum[landed] > window
            assert cum[issued] - cum[landed] > window - max(sizes)
    assert issued == len(sizes)


# ---- the same policy through the stager's own loop -------------------------------


class _Recorder:
    """Stands where the stager calls the device: logs every issue and await."""

    def __init__(self, monkeypatch, fail_at_await=None, fail_at_issue=None):
        self.events, self.fail_at, self.fail_at_issue = [], fail_at_await, fail_at_issue
        self._issue, self._await = staging.async_d2h, staging._await_d2h
        monkeypatch.setattr(staging, "async_d2h", self.issue)
        monkeypatch.setattr(staging, "_await_d2h", self.wait)

    def issue(self, datas):
        datas = list(datas)
        for d in datas:
            if self.issues() == self.fail_at_issue:
                raise RuntimeError("issue failed")
            self.events.append(("issue", id(d), d.nbytes))
        return self._issue(datas)

    def issues(self):
        return sum(1 for e in self.events if e[0] == "issue")

    def wait(self, data):
        if self.fail_at is not None and self.awaits() == self.fail_at:
            raise RuntimeError("transfer failed")
        self.events.append(("await", id(data), data.nbytes))
        return self._await(data)

    def awaits(self):
        return sum(1 for e in self.events if e[0] == "await")

    def check(self, window, expect_ids):
        """Every expected shard issued once and before its await, in order;
        the bytes outstanding within the window or one shard alone."""
        issues = [e[1] for e in self.events if e[0] == "issue"]
        awaits = [e[1] for e in self.events if e[0] == "await"]
        assert issues == expect_ids and awaits == expect_ids
        out, peak, live = 0, 0, 0
        pos = {}
        for i, (what, ident, nbytes) in enumerate(self.events):
            if what == "issue":
                pos[ident] = i
                out, live = out + nbytes, live + 1
                assert out <= window or live == 1
                peak = max(peak, out)
            else:
                assert pos[ident] < i
                out, live = out - nbytes, live - 1
        return peak


def _device_shard_ids(tree, staged):
    leaves = jax.tree_util.tree_leaves(tree)
    return [id(leaves[s.leaf_idx].addressable_shards[s.shard_idx].data)
            for s in staged.shards
            if s.replica_owner and isinstance(leaves[s.leaf_idx], jax.Array)]


@pytest.mark.parametrize("name", ["routed-199", "sharded"])
def test_stager_issues_each_device_shard_once_within_the_window(name, monkeypatch):
    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW)
    tree = TREES[name][0](3)
    rec = _Recorder(monkeypatch)
    staged = staging.stage_pytree(tree, process_index=0)
    try:
        # numpy, python and scalar-of-numpy leaves are in no event at all
        peak = rec.check(WINDOW, _device_shard_ids(tree, staged))
        assert staged.d2h_window_peak_bytes == peak > WINDOW   # the leaf that goes alone
        assert 0 < staged.d2h_window_waits <= len(rec.events) // 2
        del rec.events[:]
        staging.stage_pytree(TREES[name][0](4), process_index=0, reuse=staged)
        assert staged.d2h_window_peak_bytes == peak
    finally:
        staged.close(unlink=True)


def test_a_window_that_holds_the_tree_issues_everything_first(monkeypatch):
    tree = dense_tree(5)
    rec = _Recorder(monkeypatch)
    staged = staging.stage_pytree(tree, process_index=0)   # the product's window
    try:
        ids = _device_shard_ids(tree, staged)
        assert [e[0] for e in rec.events[:len(ids)]] == ["issue"] * len(ids)
        assert staged.d2h_window_waits == 0
        assert staged.d2h_window_peak_bytes == sum(e[2] for e in rec.events[:len(ids)])
    finally:
        staged.close(unlink=True)


class _SkipSome:
    """A digest context whose verdict skips the shards it is told to."""

    use_direct = None

    def __init__(self, skip_leaves):
        from tpu_resiliency.checkpointing.async_ckpt import device_digest

        self.chunk_bytes = device_digest.default_chunk_bytes()
        self.skip_leaves = skip_leaves

    def verdict(self, key, nbytes, fp):
        if key[0] in self.skip_leaves:
            return [(0, nbytes, 0, "base")], None
        return None, None


def test_skipped_shards_issue_nothing_and_count_for_nothing(monkeypatch):
    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW)
    first, second = routed_tree(6, 8, 2), routed_tree(7, 8, 2)
    staged = staging.stage_pytree(first, process_index=0)
    try:
        leaves = jax.tree_util.tree_leaves(second)
        device = [i for i, x in enumerate(leaves) if isinstance(x, jax.Array)]
        skip = set(device[::2])
        assert max(leaves[i].nbytes for i in skip) > WINDOW   # the big one is skipped
        rec, seen = _Recorder(monkeypatch), []
        staging.stage_pytree(second, process_index=0, reuse=staged,
                             digest_ctx=_SkipSome(skip), on_shard_staged=seen.append)
        expect = [id(leaves[i].addressable_shards[0].data) for i in device if i not in skip]
        peak = rec.check(WINDOW, expect)
        assert staged.d2h_window_peak_bytes == peak
        assert peak == max(leaves[i].nbytes for i in device if i not in skip)
        assert staged.d2h_skipped_bytes == sum(leaves[i].nbytes for i in skip)
        # skipped shards stream first, then the rest in the plan's order
        order = [s.leaf_idx for s in seen]
        assert order[:len(skip)] == sorted(skip)
        assert order[len(skip):] == [s.leaf_idx for s in staged.shards
                                     if s.replica_owner and s.leaf_idx not in skip]
        bufs = staged.shm_buffers()
        old = jax.tree_util.tree_leaves(first)
        for info in staged.shards:
            want = old if info.leaf_idx in skip else leaves
            assert bytes(bufs[info.shm_name][:info.nbytes]) == \
                _expected_bytes(want[info.leaf_idx], info)
    finally:
        staged.close(unlink=True)


@pytest.mark.parametrize("reuse", [False, True])
def test_nothing_is_issued_after_a_failed_transfer(reuse, monkeypatch):
    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW)
    tree = routed_tree(8, 48, 0)
    pooled = staging.stage_pytree(tree, process_index=0) if reuse else None
    try:
        rec = _Recorder(monkeypatch, fail_at_await=9)
        with pytest.raises(RuntimeError, match="transfer failed"):
            staging.stage_pytree(tree, process_index=0, reuse=pooled)
        assert rec.awaits() == 9
        last_await = max(i for i, e in enumerate(rec.events) if e[0] == "await")
        # what followed the last landing was its own top-up, and nothing since
        tail = rec.events[last_await + 1:]
        assert all(e[0] == "issue" for e in tail)
        assert sum(e[2] for e in tail) <= WINDOW
        assert rec.issues() < len(jax.tree_util.tree_leaves(tree)) - 20
    finally:
        if pooled is not None:
            pooled.close(unlink=True)


@pytest.mark.parametrize("reuse", [False, True])
def test_nothing_is_issued_after_an_issue_that_raised(reuse, monkeypatch):
    """``copy_to_host_async`` itself raising, mid-window: the staging ends
    there, a fresh staging leaks no segment and a pooled one keeps its pool."""
    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW)
    tree = routed_tree(10, 48, 0)
    pooled = staging.stage_pytree(tree, process_index=0) if reuse else None
    try:
        rec = _Recorder(monkeypatch, fail_at_issue=60)
        with pytest.raises(RuntimeError, match="issue failed"):
            staging.stage_pytree(tree, process_index=0, reuse=pooled)
        assert rec.issues() == 60
        assert rec.events[-1][0] == "issue" and 0 < rec.awaits() < 60
        if reuse:
            assert len(pooled.shm_buffers()) == len(pooled.shards)
    finally:
        if pooled is not None:
            pooled.close(unlink=True)


# ---- what a save reports of its window -------------------------------------------


@pytest.mark.parametrize("stage_mode", ["snapshot", "sync"])
def test_a_save_reports_its_window_and_reads_back_bit_equal(stage_mode, tmp_path, monkeypatch):
    """Through ``async_save`` in both stage modes: the window's peak and waits
    in ``last_stage_stats`` and the two gauges, and the checkpoint read back
    from disk equal to the tree byte for byte."""
    from tpu_resiliency.checkpointing import AsyncCheckpointer, load_checkpoint
    from tpu_resiliency.checkpointing.async_ckpt import resident
    from tpu_resiliency.telemetry import get_registry

    monkeypatch.setattr(staging, "D2H_WINDOW_BYTES", WINDOW)
    tree = routed_tree(9, 48, 0)
    ckpt = AsyncCheckpointer()
    try:
        for i in range(2):  # fresh segments, then the pooled ones
            ckpt.async_save(tree, str(tmp_path / f"s{i}"), stage_mode=stage_mode)
            ckpt.finalize_all()
            stats = ckpt.last_stage_stats
            sizes = [x.nbytes for x in jax.tree_util.tree_leaves(tree)
                     if isinstance(x, jax.Array)]
            assert stats["d2h_window_peak_bytes"] == max(sizes) > WINDOW
            assert 0 < stats["d2h_window_waits"] <= len(sizes)
            reg = get_registry()
            assert reg.value_of("tpurx_ckpt_stage_d2h_window_peak_bytes") == max(sizes)
            assert reg.value_of("tpurx_ckpt_stage_d2h_window_waits") == stats["d2h_window_waits"]
        out = load_checkpoint(str(tmp_path / "s1"),
                              jax.tree_util.tree_map(np.zeros_like, tree), resident=False)
    finally:
        ckpt.close()
        resident.invalidate()  # the generation left resident owns its segments
    for want, got in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(out)):
        assert np.asarray(want).tobytes() == np.asarray(got).tobytes()
