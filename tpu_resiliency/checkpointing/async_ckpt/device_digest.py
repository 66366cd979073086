"""On-device per-chunk change fingerprints for the checkpoint drain.

The host-bound half of delta saves — per-chunk crc32 AFTER the D2H — can
only ever skip the disk write; the transfer already happened.  This module
computes the change verdict where the bytes live: a jitted fingerprint
kernel reduces every drain chunk of every owned shard to a 64-bit
Fletcher-style fingerprint **on device**, and one small host readback of
the fingerprint rows (8 bytes per 16 MiB chunk — ~2 million times smaller
than the state) is all that crosses the PCIe/ICI link for an unchanged
shard.  ``staging.py`` consults the mask BEFORE issuing
``copy_to_host_async``: a shard whose every chunk matches the committed
baseline never transfers at all (its payload is pure provenance —
``skip_spans``), and chunks that do transfer carry their device verdicts so
the drain can cross-check them against the host crc32.

Kernel contract
---------------

- The chunk layout is ``writer.chunk_grid(nbytes, chunk_bytes,
  use_direct)`` — the SAME grid the drain engine crcs and the delta
  baseline keys.  Device and host therefore judge identical byte ranges.
- Each uint32 lane is first avalanche-mixed with its position
  (``h = fmix32(lane ^ (index * 0x9E3779B9))``, the murmur3 finalizer);
  per chunk the fingerprint is then the pair ``(A, B)`` of uint32
  wraparound sums ``A = sum(h)``, ``B = sum(h * position)`` (1-based
  in-chunk positions).  The mix is load-bearing, not decoration: raw
  Fletcher-style sums telescope to zero on exactly the tensors training
  produces — a uniform constant delta across a power-of-two-length chunk
  (e.g. ``full(c) -> full(c+1)``) contributes ``N * Δlane mod 2^32 = 0``
  whenever ``Δlane``'s trailing zero bits cover ``log2(N)``, silently
  skipping a changed shard.  Mixing makes every (lane, position) pair
  contribute an independent pseudo-random term, so a changed chunk
  collides with probability ~2^-64 regardless of value structure; a
  collision is also *caught* whenever the chunk transfers anyway (the
  host crc disagrees and the save fails closed).
- Lanes are a pure bitcast of the shard's bytes (``itemsize >= 4``), or a
  widening of its natural lanes (``uint16``/``uint8`` -> ``uint32``) for
  16-/8-bit dtypes including bfloat16 — NaN payloads, negative zeros and
  denormals all fingerprint by their exact bit patterns, never by value
  semantics.
- Everything up to the readback is a jitted XLA computation (a couple of
  fused reductions per chunk): it runs on the accelerator for device
  arrays and compiles to the same semantics on the CPU backend, which is
  what the test suite executes.

This module and ``staging.py`` are the ONLY sanctioned device->host
touchpoints for checkpoint state (lint rule TPURX015).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...utils import env as _envknobs
from ...utils.logging import get_logger
from .writer import chunk_grid, default_chunk_bytes

log = get_logger("ckpt.device_digest")

try:
    import jax
    import jax.numpy as jnp
    from jax import lax

    _HAVE_JAX = True
except Exception:  # pragma: no cover
    _HAVE_JAX = False

Grid = Tuple[Tuple[int, int], ...]


def enabled() -> bool:
    """``TPURX_CKPT_DEVICE_DIGEST``, gated on jax being importable."""
    if not _HAVE_JAX:
        return False
    try:
        return bool(_envknobs.CKPT_DEVICE_DIGEST.get())
    except ValueError:
        return False


# jitted fingerprint executables keyed by (shape, dtype, grid): each
# distinct signature compiles once; steady-state saves replay the cache
_FP_CACHE: Dict[Tuple[Tuple[int, ...], str, Grid], Any] = {}


def _lane_bytes(dtype: np.dtype) -> int:
    """Bytes of shard data per uint32 lane: 4 for wide dtypes (pure
    bitcast), the itemsize for 16-/8-bit dtypes (widened lanes).  Chunk
    boundaries are always multiples of the itemsize AND of 4096 (except
    the final tail, which ends at ``nbytes``), so every grid offset is
    lane-aligned for every supported dtype."""
    return 4 if dtype.itemsize >= 4 else dtype.itemsize


def _supported(dtype: Any) -> bool:
    dt = np.dtype(dtype)
    if dt.kind == "c":  # complex: no uint bitcast path; fall back to host
        return False
    return dt.itemsize in (1, 2, 4, 8)


def _as_lanes(x):
    """Flatten a device array to its uint32 lane stream (see module doc)."""
    return _lanes(x).reshape(-1)


def _lanes(x):
    """The uint32 lanes of a device array, in its own shape (8-byte dtypes
    gain a trailing axis of 2): row-major order is byte order."""
    dt = np.dtype(x.dtype)
    if dt == np.bool_:
        lanes = x.astype(jnp.uint8).astype(jnp.uint32)
    elif dt.itemsize >= 4:
        # 8-byte dtypes bitcast to a trailing (..., 2) uint32 axis; the
        # flatten below serializes it in byte order
        lanes = lax.bitcast_convert_type(x, jnp.uint32)
    elif dt.itemsize == 2:
        lanes = lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    else:
        lanes = lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    return lanes


# murmur3 fmix32 constants; the position multiplier is the golden-ratio
# Weyl increment (odd, so index -> index*PHI is a bijection on uint32)
_PHI = 0x9E3779B9
_MIX1 = 0x85EBCA6B
_MIX2 = 0xC2B2AE35


def _mix(lanes, idx):
    """``fmix32(lane ^ (index * PHI))``, lane by lane (see module doc)."""
    h = lanes ^ (idx * jnp.uint32(_PHI))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_MIX1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_MIX2)
    return h ^ (h >> 16)


def _build_fp_fn(shape: Tuple[int, ...], dtype: np.dtype, grid: Grid):
    lb = _lane_bytes(dtype)
    bounds = [(off // lb, (off + length) // lb) for off, length in grid]

    def fp(x):
        lanes = _as_lanes(x)
        h = _mix(lanes, jnp.arange(lanes.shape[0], dtype=jnp.uint32))
        rows = []
        for s, e in bounds:
            seg = h[s:e]
            pos = jnp.arange(1, (e - s) + 1, dtype=jnp.uint32)
            a = jnp.sum(seg, dtype=jnp.uint32)
            b = jnp.sum(seg * pos, dtype=jnp.uint32)
            rows.append(jnp.stack([a, b]))
        if not rows:
            return jnp.zeros((0, 2), jnp.uint32)
        return jnp.stack(rows)

    return jax.jit(fp)


def shard_fingerprints(
    data: Any,
    chunk_bytes: Optional[int] = None,
    use_direct: Optional[bool] = None,
) -> Optional[Any]:
    """Dispatch the fingerprint kernel for one single-device shard array.

    Returns the DEVICE ``(n_chunks, 2) uint32`` result (no host sync — the
    caller batches readbacks via :func:`read_fingerprints`), or None for
    dtypes without a lane bitcast (complex, exotic widths): those shards
    simply stay on the host-crc path."""
    if not _HAVE_JAX or not _supported(data.dtype):
        return None
    if chunk_bytes is None:
        chunk_bytes = default_chunk_bytes()
    shape = tuple(int(s) for s in data.shape)
    dt = np.dtype(data.dtype)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
    grid = tuple(chunk_grid(nbytes, chunk_bytes, use_direct))
    key = (shape, str(dt), grid)
    fn = _FP_CACHE.get(key)
    if fn is None:
        fn = _FP_CACHE[key] = _build_fp_fn(shape, dt, grid)
    return fn(data)


# -- whole-leaf seals: the snapshot ring's slots as a restore source ----------
#
# A ring slot that a committed generation is bound to (``resident.py``,
# "device part") is sealed with one ``(A, B)`` pair a leaf: the chunk
# fingerprint above over a grid of one chunk, the whole leaf.  Two programs a
# tree, each compiled once per tree signature by its ``jax.jit``: the seal
# (dispatched by the save right after the snapshot copy, never waited for)
# and the sealed copy (the restore's device rung).  One program a tree because
# a dispatch costs 0.4 ms on the chip whatever the program's size (447 of them
# 0.17 s); a leaf's part traced through a jit of its own, because tracing is
# paid at every process start, compile cache or not: 10 ms a leaf when the
# tree's program spells out every leaf, once a leaf SIGNATURE through the
# inner jit, of which a tree of 447 leaves has 46 (PERF.md, PR 34).


def _leaf_fingerprint(x):
    """``uint32[2]``: ``(A, B)`` over all of ``x``, as one chunk.  The lane's
    position comes from an iota a dimension, never from a flattened copy, so
    the pass is one fused read of the leaf whatever its tiling, and a leaf
    sharded over devices is reduced where its shards lie."""
    lanes = _lanes(x)
    idx, stride = jnp.zeros(lanes.shape, jnp.uint32), 1
    for axis in reversed(range(lanes.ndim)):
        idx = idx + lax.broadcasted_iota(
            jnp.uint32, lanes.shape, axis) * jnp.uint32(stride & 0xFFFFFFFF)
        stride *= lanes.shape[axis]
    h = _mix(lanes, idx)
    return jnp.stack([jnp.sum(h, dtype=jnp.uint32),
                      jnp.sum(h * (idx + jnp.uint32(1)), dtype=jnp.uint32)])


def _jit(fn):
    return jax.jit(fn) if _HAVE_JAX else fn


_leaf_seal = _jit(_leaf_fingerprint)


@_jit
def _leaf_sealed_copy(x, seal):
    # the barrier keeps the fingerprint on the copy: without it XLA reads the
    # slot twice and vouches for bytes it never handed out
    copy = lax.optimization_barrier(jnp.copy(x))
    return copy, jnp.all(_leaf_seal(copy) == seal)


@_jit
def ckpt_slot_seal(leaves):
    """``(n_leaves, 2) uint32``: the seal of a slot's device leaves."""
    return jnp.stack([_leaf_seal(x) for x in leaves])


@_jit
def ckpt_slot_restore(leaves, seal):
    """ONE program: ``leaves`` copied into fresh buffers (nothing donated) and
    the copies fingerprinted against ``seal`` on the device.  Returns
    ``(copies, verdict)``, a bool a leaf: hand the verdict to
    :func:`read_verdict` before the copies are trusted."""
    pairs = [_leaf_sealed_copy(x, seal[row]) for row, x in enumerate(leaves)]
    return [copy for copy, _ in pairs], jnp.stack([ok for _, ok in pairs])


def seal_leaves(leaves: Sequence[Any]) -> Optional[Any]:
    """Dispatch the seal of a slot's device leaves: the DEVICE
    ``(n_leaves, 2) uint32`` result (no host sync), or None where a leaf has
    no lane bitcast — such a slot is simply never a restore source."""
    if not _HAVE_JAX or not all(_supported(x.dtype) for x in leaves):
        return None
    return ckpt_slot_seal(list(leaves))


def read_verdict(verdict: Any) -> bool:
    """The sealed copy's one small readback: a bool a leaf."""
    return bool(np.all(jax.device_get(verdict)))


def read_fingerprints(fps: Sequence[Optional[Any]]) -> List[Optional[np.ndarray]]:
    """ONE batched host readback of many shards' fingerprint rows — the
    whole point: ~8 bytes cross the link per 16 MiB chunk, instead of the
    chunk."""
    live = [f for f in fps if f is not None]
    got = iter(jax.device_get(live)) if live else iter(())
    return [
        np.asarray(next(got), dtype=np.uint32) if f is not None else None
        for f in fps
    ]


def host_fingerprints(
    buf: Any,
    dtype: Any,
    chunk_bytes: Optional[int] = None,
    use_direct: Optional[bool] = None,
) -> Optional[np.ndarray]:
    """Reference implementation over HOST bytes — the agreement oracle the
    tests pin the kernel against (same lanes, same sums, numpy uint32
    wraparound arithmetic)."""
    dt = np.dtype(dtype)
    if not _supported(dt):
        return None
    raw = np.frombuffer(buf, dtype=np.uint8)
    lb = _lane_bytes(dt)
    lanes = (
        raw.view(np.uint32) if lb == 4 else raw.view(f"u{lb}").astype(np.uint32)
    )
    if chunk_bytes is None:
        chunk_bytes = default_chunk_bytes()
    grid = chunk_grid(len(raw), chunk_bytes, use_direct)
    rows = np.empty((len(grid), 2), dtype=np.uint32)
    with np.errstate(over="ignore"):
        # identical lane mixing to the device kernel, in numpy uint32
        # wraparound arithmetic
        idx = np.arange(len(lanes), dtype=np.uint32)
        h = lanes ^ (idx * np.uint32(_PHI))
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(_MIX1)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(_MIX2)
        h = h ^ (h >> np.uint32(16))
        for i, (off, length) in enumerate(grid):
            seg = h[off // lb : (off + length) // lb]
            pos = np.arange(1, len(seg) + 1, dtype=np.uint32)
            # per-element uint32 wraparound multiply, THEN a masked sum —
            # exactly the device kernel's modular arithmetic
            rows[i, 0] = np.uint32(seg.sum(dtype=np.uint64) & 0xFFFFFFFF)
            rows[i, 1] = np.uint32(
                (seg * pos).sum(dtype=np.uint64) & 0xFFFFFFFF
            )
    return rows


@dataclasses.dataclass
class DigestContext:
    """Everything staging needs to turn device fingerprints into per-shard
    transfer decisions.  Built by the checkpointer per save from the
    committed baseline (``_after_commit``); ``allow_skip`` additionally
    requires the pooled shm tree to HOLD the baseline generation's bytes
    (``StagedTree.content_id``) — a skipped shard's segment is published
    resident as-is, so its bytes must equal the current device bytes, which
    the fingerprint match only proves relative to the baseline."""

    # committed baseline, keyed (leaf_idx, shard_idx):
    base_rows: Dict[Tuple[int, int], Dict[Tuple[int, int], Tuple[int, str]]]
    base_fps: Dict[Tuple[int, int], np.ndarray]
    allow_skip: bool = False
    chunk_bytes: int = dataclasses.field(default_factory=default_chunk_bytes)
    use_direct: Optional[bool] = None

    def verdict(
        self, key: Tuple[int, int], nbytes: int, fp: Optional[np.ndarray]
    ) -> Tuple[Optional[List], Optional[List[Tuple[int, int]]]]:
        """Per-shard decision: ``(skip_spans, dev_unchanged)``.

        ``skip_spans`` non-None => every chunk matched AND skipping is safe:
        the full provenance row list (off, len, crc, base_path).  Otherwise
        ``dev_unchanged`` lists the (off, len) chunks whose fingerprints
        matched (the drain cross-checks them), or None when no comparable
        baseline exists for this shard."""
        base_fp = self.base_fps.get(key)
        rows = self.base_rows.get(key)
        if fp is None or base_fp is None or rows is None:
            return None, None
        grid = chunk_grid(nbytes, self.chunk_bytes, self.use_direct)
        if fp.shape != base_fp.shape or fp.shape[0] != len(grid):
            return None, None  # layout drift: not comparable
        if set(rows.keys()) != set(grid):
            return None, None  # baseline doesn't cover this exact grid
        mask = np.all(fp == base_fp, axis=1)
        if self.allow_skip and bool(mask.all()) and grid:
            return [
                (off, length, rows[(off, length)][0], rows[(off, length)][1])
                for off, length in grid
            ], None
        unchanged = [grid[i] for i in np.flatnonzero(mask)]
        return None, unchanged
