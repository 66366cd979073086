"""Pipelined D2H staging of JAX pytrees into shared memory.

The TPU replacement for the reference's CUDA-stream preload
(``async_ckpt/filesystem_async.py:230-330``): every ``jax.Array`` leaf starts
a non-blocking device→host copy (``copy_to_host_async`` on each addressable
shard), then shards are materialized straight into POSIX shared-memory
buffers.  The training step only pays for the D2H DMA + one memcpy into shm;
file writes happen in the worker process reading the same shm — zero copies
across the process boundary.

Staging is **pipelined per shard under a bounded window**: the full shm plan
(every shard's size and segment) is computed up-front from metadata alone,
owned D2H copies are kicked off in plan order while the bytes issued and not
yet landed stay within ``D2H_WINDOW_BYTES`` (:func:`issue_upto`; a shard
larger than the window goes alone), and each shard is memcpy'd into shm as
soon as *its* transfer lands, the window topped up first — the memcpy of
shard *i* overlaps the in-flight DMA of the shards behind it in the window.
The window is there for the rest of the process: whatever it launches on the
device after a transfer is issued waits behind that transfer, so a whole
state issued at once held every step and quorum tick for as long as the
runtime's queue of transfers took to drain.
Because the plan precedes the bytes, a streaming consumer (``writer.py``'s
chunked multi-writer engine) can start persisting the first shards while
later leaves are still in flight: ``on_plan`` fires once with the total
owned byte count, ``on_shard_staged`` fires per shard the moment its bytes
are in shm.

Shm segments are pooled and **reused across saves** (double-buffered by the
checkpointer): a steady-state save of an unchanged layout allocates zero new
shm bytes and copies into pages that are already resident.  A page of a fresh
tmpfs segment is a write fault when first touched, and a copy that takes them
one 4 KiB page at a time on the stager's thread ran a first save at a sixth of
a later one's rate.  So **a save that stages into fresh segments** (a job's
first, one that finds every pooled set still in use, the first after a layout
change) creates all of them from the plan before the first byte lands and has
a helper thread make their pages resident in bulk, in plan order, ahead of the
copy loop (:class:`_Populator`, :func:`_populate`: one call a segment that
gives up the interpreter lock); the copy loop waits on a segment's event only
if its turn comes before the helper got there, and then copies into resident
pages as every later save does.  A bulk call that fails is counted and the
copy faults that segment in itself, as before: it never fails a save.  The
reusing path runs none of it.

**Save planning is derived from the sharding itself**: for every jax leaf
the global ``device -> index`` map (``NamedSharding.devices_indices_map``)
is reduced to one owning device per distinct index box (lowest device id
wins), and exactly-once global coverage is ASSERTED — the distinct boxes
must tile the global shape with volumes summing to its total, which plain
interval cover would not prove (overlapping boxes can still union to the
shape).  Each host then drains exactly its addressable shards that own
their box: replicated leaves are written once cluster-wide (by whichever
process holds the lowest-id device), never double-drained, with no special
"process 0" case.  Shardings that cannot enumerate the map fall back to
the replica-id ownership rule.

**Device-side change mask** (``device_digest.py``): when a
:class:`~.device_digest.DigestContext` rides along, every owned shard's
per-chunk fingerprints are computed ON DEVICE and one small readback of
the mask decides, per shard and before any ``copy_to_host_async`` is
issued, whether the shard transfers at all.  A shard whose every chunk
matches the committed baseline is recorded as skipped spans with their
base-generation provenance (``ShardInfo.skip_spans``) — no D2H, no memcpy,
its pooled shm segment keeps the (identical) baseline bytes for the
resident publish.  Shards that do transfer carry their per-chunk device
verdicts (``ShardInfo.dev_unchanged``) so the drain can cross-check them
against the host crc32.

A leaf can be a replicated or sharded global array: we stage only
**addressable** shards and record their global index, so multi-host saves
write disjoint data per process.

This module and ``device_digest.py`` are the ONLY sanctioned device->host
touchpoints for checkpoint state (lint rule TPURX015); external capture
paths (``local/state_dict.py``) kick their transfers through
:func:`async_d2h`.
"""

from __future__ import annotations

import ctypes
import dataclasses
import errno
import itertools
import math
import os
import threading
import time
from multiprocessing import shared_memory

from ...utils.shm import create_shm, unlink_shm
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ...telemetry import counter, flight
from ...telemetry.clock import mono_ns
from ...utils.logging import get_logger
from ..coverage import covers

# Flight-recorder intervals of the stager's side of one save (ident = the
# save ticket): the whole staging job, and inside it the device-to-host
# transfer — what a dispatch issued right after ``async_save`` queues behind.
IV_STAGE = flight.declare_interval("ckpt.stage_begin", "ckpt.stage_end")
IV_STAGE_D2H = flight.declare_interval(
    "ckpt.stage.d2h_begin", "ckpt.stage.d2h_end"
)
# ``ckpt.stage.d2h``'s begin to the first transferring shard landed on the
# host: what the first window of transfers costs before anything streams
IV_STAGE_D2H_FIRST = flight.declare_interval(
    "ckpt.stage.d2h.first_begin", "ckpt.stage.d2h.first_end"
)

# A fresh staging's segments made resident ahead of the copy loop: the first
# segment created -> the last one resident (begun on the stager's thread,
# ended on the helper's).  The begin says how many segments
# and bytes the plan has, the end for how many the bulk call succeeded.  No
# reusing save records one.
IV_STAGE_POPULATE = flight.declare_interval(
    "ckpt.stage.populate_begin", "ckpt.stage.populate_end", "segments", "bytes"
)

_POPULATED_BYTES = counter(
    "tpurx_ckpt_stage_populated_bytes_total",
    "Bytes of fresh staging segments made resident by the bulk call, ahead "
    "of the copy loop (0 on a save that reuses pooled segments)",
)
_POPULATE_FALLBACK = counter(
    "tpurx_ckpt_stage_populate_fallback_total",
    "Fresh staging segments whose bulk call failed and whose pages the copy "
    "itself faulted in, as before; reason = the errno's name",
    labels=("reason",),
)

# The most bytes of device-to-host transfer the stager keeps issued and not
# yet landed.  Whatever the process launches on the device after a transfer
# is issued waits behind it, and once the runtime's queue is full the issuing
# call itself blocks, so this bounds what a step or a quorum tick can wait
# for.  A probe on a v5e chip (PR 43: a jitted step in a loop one step ahead,
# a second thread moving a copy of the whole 4.59 GB state, 269 arrays, to the
# host; docs/checkpointing.md has the table): everything issued at once cost
# the steps beside it 1.057 s (periods of 572 and 732 ms where 101.8 is
# normal; one copy_to_host_async call blocked 0.869 s); 64 / 128 / 256 MiB
# outstanding cost 5.2 / 4.6 / 6.0 ms with no period over 126 ms; 512 MiB
# left a 102 ms step alone but cost a 63 ms step 160 ms; 1 GiB cost 122 ms.
# The state moved as fast at 128 MiB as all at once (2.72 s both; 3.23 s at
# 64 MiB).  128 MiB is half the largest window the shorter step bore.  Bytes,
# not a count of transfers: a count bounds nothing that the bytes do not.  A
# constant, no knob: the stager reads shard sizes and nothing else.
D2H_WINDOW_BYTES = 128 << 20

log = get_logger("ckpt.staging")

try:
    import jax

    _HAVE_JAX = True
except Exception:  # pragma: no cover
    _HAVE_JAX = False


def async_d2h(datas: Iterable[Any]) -> int:
    """Kick a non-blocking device→host transfer for each array in ``datas``
    (single-device shard ``.data`` arrays or whole addressable arrays).

    THE sanctioned transfer kick for checkpoint state outside this module:
    lint rule TPURX015 bans raw ``copy_to_host_async``/``jax.device_get``
    on checkpoint bytes elsewhere, so every capture path funnels through
    here (or through the staging pipeline itself) and inherits whatever
    scheduling/accounting this layer grows.  Whatever it is handed is issued
    at once: the stager hands it a window's worth at a time
    (:func:`issue_upto`).  Returns the number of transfers started;
    host-backed arrays are skipped."""
    n = 0
    for d in datas:
        fn = getattr(d, "copy_to_host_async", None)
        if fn is not None:
            fn()
            n += 1
    return n


def issue_upto(cum: Sequence[int], issued: int, landed: int, window: int) -> int:
    """The stager's issue policy, device-free.  Transfers are numbered in plan
    order; ``cum[i]`` is the bytes of transfers ``0..i-1`` (``cum[0] == 0``),
    ``issued`` of them have been kicked off and ``landed`` of those are on the
    host.  Returns how many may be issued by now: the next one goes while the
    bytes issued and not landed, its own included, stay within ``window``;
    with nothing outstanding the next one always goes, so a transfer larger
    than the window travels alone and the count never stalls."""
    n = len(cum) - 1
    while issued < n and (
        issued == landed or cum[issued + 1] - cum[landed] <= window
    ):
        issued += 1
    return issued


def _await_d2h(data: Any) -> np.ndarray:
    """Block until THIS shard's transfer has landed; the host copy."""
    return np.asarray(data)


# How a fresh segment's pages are made resident: ``mlock`` then ``munlock`` over
# the mapping, from ONE helper thread.  Chosen by a probe on the chip's host
# (PR 48; gVisor, a 102 ms jitted step running beside it, 1.21 GB of fresh
# segments of 14-322 MB; docs/checkpointing.md has the table): ``np.copyto``
# into a fresh segment ran at 0.41 GB/s; ``madvise(MADV_POPULATE_WRITE)`` is
# ``EINVAL`` there; writing zeros through the descriptor or
# ``posix_fallocate`` made the pages at 1.6 and 3.5 GB/s but left the copy
# after them at 0.50-0.52 (the mapping still faults a page at a time); a
# thread storing a byte a page ran at the copy's own 0.41 and needed four
# threads to reach 1.36; ``mlock`` made 1.37 GB/s resident from one thread
# and left the copy after it at 10.8 GB/s.  Two and four ``mlock`` helpers
# moved nothing faster (1.36, 1.35 GB/s end to end against 1.31) and held
# the step beside them for 196-407 ms at a time; one left every period at
# 102.  A constant form and no knob: the stager reads ``reusing`` and the
# plan's sizes, nothing else.
_libc = ctypes.CDLL(None, use_errno=True)
_libc.mlock.argtypes = (ctypes.c_void_p, ctypes.c_size_t)
_libc.mlock.restype = ctypes.c_int
_libc.munlock.argtypes = (ctypes.c_void_p, ctypes.c_size_t)
_libc.munlock.restype = ctypes.c_int


def _populate(shm: shared_memory.SharedMemory) -> None:
    """Make every page of a fresh segment resident in this process's mapping
    by one call that gives up the interpreter lock: ``mlock`` faults the whole
    range in inside the kernel, ``munlock`` lets it go again at once (the
    pages stay; nothing is left pinned).  Raises ``OSError`` where the kernel
    will not (``ENOMEM`` / ``EPERM``: ``RLIMIT_MEMLOCK`` without
    ``CAP_IPC_LOCK``, or a full ``/dev/shm``)."""
    anchor = ctypes.c_char.from_buffer(shm.buf)
    try:
        addr = ctypes.addressof(anchor)
        if _libc.mlock(addr, shm.size):
            err = ctypes.get_errno()
            raise OSError(err, os.strerror(err))
        _libc.munlock(addr, shm.size)
    finally:
        del anchor  # the export would hold the segment open past its close


class _Populator:
    """A fresh staging's segments made resident ahead of the copy loop.

    One helper thread takes the segments in plan order and :func:`_populate`s
    each; ``ready[k]`` is set once segment ``k`` needs no more from it,
    whether the call succeeded or not: a segment whose call failed is counted
    and left for the copy to fault in, as every fresh segment was before.  The
    copy loop waits on a segment's event only if its turn comes first
    (:meth:`wait`).  :meth:`close` stops the helper and joins it; no segment
    may be unmapped before that."""

    def __init__(
        self, staged: "StagedTree", nbytes: List[int], ident: Optional[int],
        t0: float, t0_ns: int,
    ):
        """``t0`` / ``t0_ns``: taken before the first segment was created."""
        self._staged, self._nbytes, self._ident = staged, nbytes, ident
        self._shms = list(staged._shms)
        self.ready = [threading.Event() for _ in self._shms]
        self._stop = False
        self._t0 = t0
        flight.begin(IV_STAGE_POPULATE, ident, IV_STAGE, len(nbytes), sum(nbytes),
                     at_ns=t0_ns)
        self._thread = threading.Thread(
            target=self._run, name="tpurx-ckpt-populate", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        staged, resident = self._staged, 0
        for k, shm in enumerate(self._shms):
            if self._stop:
                self._finish(resident)  # stopped short: the interval still ends
                return
            try:
                _populate(shm)
                resident += 1
                staged.populated_bytes += self._nbytes[k]
            except Exception as exc:  # noqa: BLE001 - fail open, never a save
                reason = errno.errorcode.get(getattr(exc, "errno", None), "other")
                staged.populate_fallbacks += 1
                _POPULATE_FALLBACK.labels(reason=reason).inc()
                log.debug("populate of %s failed (%r): the copy faults it in",
                          shm.name, exc)
            if k == len(self._shms) - 1:
                self._finish(resident)  # before the event the copy loop ends on
            self.ready[k].set()

    def _finish(self, resident: int) -> None:
        self._staged.populate_s = time.perf_counter() - self._t0
        _POPULATED_BYTES.inc(self._staged.populated_bytes)
        flight.end(IV_STAGE_POPULATE, self._ident, IV_STAGE, resident,
                   self._staged.populated_bytes)

    def wait(self, k: int) -> float:
        """Block until segment ``k`` is ready; the seconds that took."""
        if self.ready[k].is_set():
            return 0.0
        t0 = time.perf_counter()
        # tpurx: disable=TPURX005 -- the helper sets it after one local call, whether that succeeded or not
        self.ready[k].wait()
        return time.perf_counter() - t0

    def close(self) -> None:
        self._stop = True
        # tpurx: disable=TPURX005 -- _stop is set; the helper ends after the one local call it is in
        self._thread.join()


@dataclasses.dataclass
class ShardInfo:
    leaf_idx: int
    shard_idx: int
    global_shape: Tuple[int, ...]
    index: Tuple[Tuple[int, int], ...]   # (start, stop) per dim in the global array
    dtype: str
    shm_name: str
    nbytes: int
    replica_owner: bool                   # False -> another process owns this data
    # -- per-save device-digest annotations (reset every staging pass) ------
    d2h_skipped: bool = False             # True -> no D2H happened this save
    # full provenance rows (off, len, crc, base_path) for a skipped shard
    skip_spans: Optional[List[Tuple[int, int, int, str]]] = None
    # (off, len) chunks whose device fingerprint matched the baseline, for a
    # shard that transferred anyway (the drain cross-checks host crcs)
    dev_unchanged: Optional[List[Tuple[int, int]]] = None


@dataclasses.dataclass
class StagedTree:
    treedef_repr: str
    leaf_paths: List[str]
    shards: List[ShardInfo]
    plan_sig: str = ""
    bytes_allocated: int = 0              # shm bytes newly created this staging
    bytes_reused: int = 0                 # shm bytes reused from a pooled tree
    # pipelining telemetry for the last staging pass (last_stage_stats)
    stage_wait_s: float = 0.0             # summed per-shard D2H completion waits
    stage_copy_s: float = 0.0             # summed memcpy-into-shm time
    stage_overlap_pct: float = 0.0        # % of memcpy overlapped with live D2H
    d2h_window_peak_bytes: int = 0        # most bytes issued and not landed
    d2h_window_waits: int = 0             # top-ups that left a shard waiting
    # which save's bytes these shm segments hold (the committed-generation
    # identity the D2H-skip gate compares against the delta baseline)
    content_id: str = ""
    # device fingerprints of every owned jax shard from the last staging
    # pass, keyed (leaf_idx, shard_idx) — the next save's skip baseline
    device_fps: Dict[Tuple[int, int], np.ndarray] = dataclasses.field(
        default_factory=dict
    )
    device_digest_s: float = 0.0          # fingerprint dispatch + mask readback
    d2h_skipped_bytes: int = 0            # bytes that never left the device
    # a fresh staging's segments made resident ahead of the copy loop (all 0
    # for a pass that reused pooled segments)
    populate_s: float = 0.0               # first segment created -> last resident
    populate_wait_s: float = 0.0          # the copy loop's waits for a segment
    populated_bytes: int = 0              # segment bytes the bulk call made resident
    populate_fallbacks: int = 0           # segments left to the copy's own faults
    _populator: Optional["_Populator"] = None
    _shms: List[shared_memory.SharedMemory] = dataclasses.field(default_factory=list)

    def close(self, unlink: bool = True) -> None:
        if self._populator is not None:  # no helper may outlive a mapping
            self._populator.close()
            self._populator = None
        for shm in self._shms:
            try:
                shm.close()
                if unlink:
                    unlink_shm(shm)
            except FileNotFoundError:
                pass
        self._shms.clear()

    def shm_buffers(self) -> Dict[str, memoryview]:
        """shm segment name -> its live buffer view (resident read source)."""
        return {shm.name: shm.buf for shm in self._shms}


def _leaf_paths(tree: Any) -> Tuple[Any, List[str], List[Any]]:
    import jax.tree_util as jtu

    leaves_with_paths, treedef = jtu.tree_flatten_with_path(tree)
    paths = [jtu.keystr(path) for path, _ in leaves_with_paths]
    leaves = [leaf for _, leaf in leaves_with_paths]
    return treedef, paths, leaves


def _shard_index(shard, global_shape) -> Tuple[Tuple[int, int], ...]:
    return _norm_box(shard.index, global_shape)


def _norm_box(index, global_shape) -> Tuple[Tuple[int, int], ...]:
    """Normalize a per-dim slice tuple to concrete (start, stop) bounds."""
    out = []
    for dim, sl in enumerate(index):
        start = sl.start if sl.start is not None else 0
        stop = sl.stop if sl.stop is not None else global_shape[dim]
        out.append((int(start), int(stop)))
    return tuple(out)


def plan_signature(tree: Any, process_index: Optional[int] = None) -> str:
    """Cheap metadata-only fingerprint of a save plan: tree structure + per-leaf
    shape/dtype/sharding.  Two trees with the same signature stage into
    identical shm layouts, enabling segment + plan reuse across saves
    (reference: worker data-cache keyed by plan hash, ``core.py:434-438``, and
    ``verify_global_md_reuse``, ``state_dict_saver.py:374``)."""
    import hashlib

    _, paths, leaves = _leaf_paths(tree)
    h = hashlib.sha256()
    h.update(str(process_index).encode())
    for path, leaf in zip(paths, leaves):
        if _HAVE_JAX and isinstance(leaf, jax.Array):
            # hash the SHARD LAYOUT (what determines the shm plan), not the
            # sharding object's repr — jit outputs carry repr-distinct but
            # layout-identical shardings, and steady-state reuse must
            # survive "same state, N steps later"
            global_shape = tuple(leaf.shape)
            sh = ";".join(
                f"{_shard_index(s, global_shape)}r{s.replica_id}"
                for s in leaf.addressable_shards
            )
            replicated = getattr(leaf.sharding, "is_fully_replicated", False)
            sh += f"|rep={bool(replicated)}"
        else:
            sh = "host"
        h.update(
            f"{path}|{tuple(np.shape(leaf))}|{getattr(leaf, 'dtype', type(leaf))}|{sh}\n".encode()
        )
    return h.hexdigest()[:32]


# -- sharding-derived save planning ------------------------------------------


def _dev_key(dev) -> int:
    """Global owner ordering: the lowest device id wins a box.  Device ids
    are cluster-global in JAX, so every process derives the same owner from
    the same sharding without any exchange."""
    return int(getattr(dev, "id", 0))


def _box_volume(box: Tuple[Tuple[int, int], ...]) -> int:
    v = 1
    for a, b in box:
        v *= max(0, b - a)
    return v


def shard_owner_map(leaf) -> Optional[Dict[Tuple[Tuple[int, int], ...], Any]]:
    """Derive the save plan's owner assignment from the sharding itself:
    the global ``device -> index`` map reduced to ONE owning device per
    distinct index box (lowest device id), so replicas — including fully
    replicated leaves, where every device maps to the whole-shape box —
    are written exactly once cluster-wide.

    Asserts exactly-once global coverage before returning: the distinct
    boxes must cover the global shape (interval accounting) AND their
    volumes must sum to its total element count — cover alone tolerates
    overlapping boxes, which would double-drain bytes.

    Returns None when the sharding cannot enumerate the map (host arrays,
    shardings without ``devices_indices_map``); callers fall back to the
    replica-id ownership rule."""
    sharding = getattr(leaf, "sharding", None)
    dmap_fn = getattr(sharding, "devices_indices_map", None)
    if dmap_fn is None:
        return None
    global_shape = tuple(int(s) for s in leaf.shape)
    try:
        dmap = dmap_fn(global_shape)
    except Exception:  # noqa: BLE001 - unenumerable sharding: use fallback
        return None
    owners: Dict[Tuple[Tuple[int, int], ...], Any] = {}
    for dev, index in dmap.items():
        box = _norm_box(index, global_shape)
        cur = owners.get(box)
        if cur is None or _dev_key(dev) < _dev_key(cur):
            owners[box] = dev
    boxes = list(owners)
    total = math.prod(global_shape) if global_shape else 1
    vol = sum(_box_volume(b) for b in boxes)
    if vol != total or not covers(global_shape, boxes):
        raise ValueError(
            f"sharding does not tile the global shape exactly once: shape "
            f"{global_shape} has {total} elements but the {len(boxes)} "
            f"distinct index boxes {'cover' if vol > total else 'reach'} "
            f"{vol} — a save from this plan would "
            f"{'double-drain' if vol > total else 'lose'} data"
        )
    return owners


def _replica_owner(leaf, shard, pidx: int) -> bool:
    """Fallback ownership rule for shardings without an enumerable device
    map: one replica owner per distinct shard; fully-replicated leaves are
    written by process 0 only (avoids N identical writes)."""
    replicated = getattr(leaf.sharding, "is_fully_replicated", False)
    if replicated:
        return pidx == 0 and shard.replica_id == 0
    return shard.replica_id == 0


def shard_is_owner(leaf, shard, pidx: int, owners=None) -> bool:
    """Does THIS process drain this addressable shard?  With a derived
    owner map, yes iff the shard sits on the device that owns its box;
    otherwise the replica-id fallback decides."""
    if owners is None:
        return _replica_owner(leaf, shard, pidx)
    box = _norm_box(shard.index, tuple(leaf.shape))
    own_dev = owners.get(box)
    dev = getattr(shard, "device", None)
    if own_dev is None or dev is None:
        return _replica_owner(leaf, shard, pidx)
    return _dev_key(own_dev) == _dev_key(dev)


@dataclasses.dataclass
class _OwnedWork:
    """One owned shard awaiting its bytes: plan slot + data source."""

    info: ShardInfo
    source: Any          # jax shard (async D2H in flight) or host array
    is_jax: bool


def stage_pytree(
    tree: Any,
    process_index: Optional[int] = None,
    reuse: Optional[StagedTree] = None,
    plan_sig: Optional[str] = None,
    on_plan: Optional[Callable[[int], None]] = None,
    on_shard_staged: Optional[Callable[[ShardInfo], None]] = None,
    digest_ctx: Optional[Any] = None,
    ident: Optional[int] = None,
) -> StagedTree:
    """Stage all array leaves into shared memory.  Scalars / numpy leaves are
    staged too (uniform handling keeps the writer simple).

    With ``reuse`` (a previously staged tree whose ``plan_sig`` matches this
    tree's), existing shm segments are rewritten in place instead of
    allocated: a steady-state save of an unchanged layout creates zero new
    shm bytes and copies into resident pages.  Without it every owned
    shard's segment is created from the plan's sizes up front and made
    resident by a helper thread ahead of the copy loop (``populate_s``,
    ``populate_wait_s``, ``populated_bytes`` and ``populate_fallbacks`` on
    the result say how that went; all 0 after a reusing pass).

    ``on_plan(total_owned_bytes)`` fires once, before any bytes move, as soon
    as the full shard plan is known.  ``on_shard_staged(info)`` fires per
    owned shard the moment its bytes are fully in shm — a streaming writer
    can persist it immediately while later shards are still staging.

    ``digest_ctx`` (a :class:`~.device_digest.DigestContext`) turns on the
    on-device change mask: fingerprints are computed for every owned jax
    shard, and shards the mask proves unchanged are SKIPPED — no D2H, no
    memcpy; their ``on_shard_staged`` fires immediately with provenance-only
    info (``skip_spans`` set).  Skipping additionally requires ``reuse``
    (the pooled segment must keep holding the shard's — identical —
    bytes for the resident publish).

    ``ident`` (the save ticket) tags the ``ckpt.stage.d2h`` flight
    interval: first transfer issued to last byte landed in shm; its child
    ``ckpt.stage.d2h.first`` ends when the first transferring shard is on
    the host (left open by a staging that fails before that).  A fresh
    staging also records ``ckpt.stage.populate`` (child of ``ckpt.stage``):
    first segment created to last one resident."""
    treedef, paths, leaves = _leaf_paths(tree)
    pidx = process_index
    if pidx is None:
        pidx = jax.process_index() if _HAVE_JAX else 0
    sig = plan_sig if plan_sig is not None else plan_signature(tree, pidx)
    reusing = reuse is not None and reuse.plan_sig == sig and reuse._shms
    if reusing:
        staged = reuse
    else:
        staged = StagedTree(
            treedef_repr=str(treedef), leaf_paths=paths, shards=[], plan_sig=sig
        )
    try:
        return _stage_pipelined(staged, leaves, pidx, reusing,
                                on_plan, on_shard_staged, digest_ctx, ident)
    except BaseException:
        if not reusing:
            staged.close(unlink=True)  # partial staging must not leak shm
        raise


def _build_plan(
    staged: StagedTree, leaves: List[Any], pidx: int, reusing: bool
) -> List[_OwnedWork]:
    """Metadata-only pass: the complete shard list (owned + non-owned) before
    a single byte moves.  Fresh plans derive ownership from the sharding
    (``shard_owner_map``, exactly-once asserted); reuse carries the prior
    plan over verbatim — only the data sources are rebound."""
    work: List[_OwnedWork] = []
    if reusing:
        for info in staged.shards:
            if not info.replica_owner:
                continue
            leaf = leaves[info.leaf_idx]
            if _HAVE_JAX and isinstance(leaf, jax.Array):
                shard = leaf.addressable_shards[info.shard_idx]
                if shard.data.nbytes != info.nbytes:
                    raise ValueError(
                        f"restage size mismatch on leaf {info.leaf_idx}: "
                        f"{shard.data.nbytes} != {info.nbytes} "
                        "(stale plan signature?)"
                    )
                work.append(_OwnedWork(info, shard, True))
            else:
                work.append(_OwnedWork(info, leaf, False))
        return work

    for i, leaf in enumerate(leaves):
        if _HAVE_JAX and isinstance(leaf, jax.Array):
            global_shape = tuple(leaf.shape)
            owners = shard_owner_map(leaf)
            for j, shard in enumerate(leaf.addressable_shards):
                owner = shard_is_owner(leaf, shard, pidx, owners)
                index = _shard_index(shard, global_shape)
                info = ShardInfo(
                    leaf_idx=i, shard_idx=j, global_shape=global_shape,
                    index=index, dtype=str(shard.data.dtype),
                    shm_name="", nbytes=int(shard.data.nbytes) if owner else 0,
                    replica_owner=owner,
                )
                staged.shards.append(info)
                if owner:
                    work.append(_OwnedWork(info, shard, True))
        else:
            arr = np.asarray(leaf)
            info = ShardInfo(
                leaf_idx=i, shard_idx=0, global_shape=tuple(arr.shape),
                index=tuple((0, s) for s in arr.shape), dtype=str(arr.dtype),
                shm_name="", nbytes=arr.nbytes if pidx == 0 else 0,
                replica_owner=pidx == 0,
            )
            staged.shards.append(info)
            if info.replica_owner:
                work.append(_OwnedWork(info, arr, False))
    return work


def _fresh_segments(
    staged: StagedTree, work: List[_OwnedWork], ident: Optional[int]
) -> List[shared_memory.SharedMemory]:
    """A fresh staging's segments, one an owned shard in plan order, created
    from the plan's sizes before a byte has landed — each in ``staged._shms``
    the moment it exists, so a staging that fails leaks none — and handed to
    the helper that makes their pages resident ahead of the copy loop."""
    t0, t0_ns = time.perf_counter(), mono_ns()
    for w in work:
        shm = create_shm(max(1, w.info.nbytes))
        staged._shms.append(shm)
        w.info.shm_name = shm.name
    if work:
        staged._populator = _Populator(
            staged, [w.info.nbytes for w in work], ident, t0, t0_ns
        )
    return staged._shms


def _stage_pipelined(
    staged: StagedTree,
    leaves: List[Any],
    pidx: int,
    reusing: bool,
    on_plan: Optional[Callable[[int], None]],
    on_shard_staged: Optional[Callable[[ShardInfo], None]],
    digest_ctx: Optional[Any] = None,
    ident: Optional[int] = None,
) -> StagedTree:
    work = _build_plan(staged, leaves, pidx, reusing)
    total = sum(w.info.nbytes for w in work)
    if on_plan is not None:
        on_plan(total)

    # per-save annotations: pooled infos persist across saves, so clear them
    for w in work:
        w.info.d2h_skipped = False
        w.info.skip_spans = None
        w.info.dev_unchanged = None
    staged.device_fps = {}
    staged.device_digest_s = 0.0
    staged.d2h_skipped_bytes = 0
    staged.populate_s = staged.populate_wait_s = 0.0
    staged.populated_bytes = staged.populate_fallbacks = 0

    if digest_ctx is not None:
        # On-device change mask BEFORE any transfer is issued: fingerprint
        # every owned jax shard where its bytes live, then one batched
        # readback of the tiny mask decides transfer-vs-skip per shard.
        from . import device_digest as dd

        t0 = time.perf_counter()
        fps_dev = [
            dd.shard_fingerprints(
                w.source.data, digest_ctx.chunk_bytes, digest_ctx.use_direct
            )
            if w.is_jax else None
            for w in work
        ]
        fps = dd.read_fingerprints(fps_dev)
        staged.device_digest_s = time.perf_counter() - t0
        for w, fp in zip(work, fps):
            if fp is None:
                continue
            key = (w.info.leaf_idx, w.info.shard_idx)
            staged.device_fps[key] = fp
            skip_rows, unchanged = digest_ctx.verdict(key, w.info.nbytes, fp)
            if skip_rows is not None and reusing:
                # pooled segment k keeps the baseline generation's bytes —
                # identical to the current ones, per the fingerprint match
                w.info.d2h_skipped = True
                w.info.skip_spans = skip_rows
                staged.d2h_skipped_bytes += w.info.nbytes
            elif unchanged is not None:
                w.info.dev_unchanged = unchanged

    # Transferring shards in plan order, and the window over them: a shard is
    # issued only while the bytes issued and not yet landed stay within
    # D2H_WINDOW_BYTES (``issue_upto``).  Skipped and host-backed shards never
    # transfer and count for nothing.
    xfers = [w for w in work if w.is_jax and not w.info.d2h_skipped]
    cum = [0, *itertools.accumulate(w.info.nbytes for w in xfers)]
    issued = landed = peak = waits = 0

    def top_up() -> None:
        nonlocal issued, peak, waits
        upto = issue_upto(cum, issued, landed, D2H_WINDOW_BYTES)
        async_d2h(w.source.data for w in xfers[issued:upto])
        issued = upto
        peak = max(peak, cum[issued] - cum[landed])
        waits += issued < len(xfers)

    with flight.span(IV_STAGE_D2H, ident, IV_STAGE):
        if xfers:
            flight.begin(IV_STAGE_D2H_FIRST, ident, IV_STAGE_D2H)
        top_up()

        # skipped shards complete instantly: stream their provenance-only
        # payloads first so the drain credits their bytes before any wait
        if on_shard_staged is not None:
            for w in work:
                if w.info.d2h_skipped:
                    on_shard_staged(w.info)

        shms = staged._shms if reusing else _fresh_segments(staged, work, ident)
        wait_s = copy_s = hidden_copy_s = 0.0
        for k, w in enumerate(work):
            if w.info.d2h_skipped:
                continue  # slot k's shm keeps the (identical) baseline bytes
            t0 = time.perf_counter()
            arr = _await_d2h(w.source.data) if w.is_jax else np.asarray(w.source)
            wait_s += time.perf_counter() - t0
            if w.is_jax:
                landed += 1
                if landed == 1:
                    flight.end(IV_STAGE_D2H_FIRST, ident, IV_STAGE_D2H)
                top_up()  # the next transfers run under this shard's memcpy
            t1 = time.perf_counter()
            if reusing:
                shm = shms[k]
                if arr.nbytes != w.info.nbytes:
                    raise ValueError(
                        f"restage size mismatch on leaf {w.info.leaf_idx}: "
                        f"{arr.nbytes} != {w.info.nbytes} (stale plan signature?)"
                    )
            else:
                shm = shms[k]
                if arr.nbytes != w.info.nbytes:
                    raise ValueError(
                        f"stage size mismatch on leaf {w.info.leaf_idx}: "
                        f"{arr.nbytes} != {w.info.nbytes} (the plan's metadata)"
                    )
                staged.populate_wait_s += staged._populator.wait(k)
            dst = np.ndarray(arr.shape, dtype=arr.dtype, buffer=shm.buf)
            np.copyto(dst, arr, casting="no")
            t2 = time.perf_counter()
            copy_s += t2 - t1
            if issued > landed:  # this memcpy ran under at least one live DMA
                hidden_copy_s += t2 - t1
            if on_shard_staged is not None:
                on_shard_staged(w.info)

    owned_bytes = sum(w.info.nbytes for w in work)
    staged.bytes_allocated = 0 if reusing else owned_bytes
    staged.bytes_reused = owned_bytes if reusing else 0
    staged.stage_wait_s = wait_s
    staged.stage_copy_s = copy_s
    staged.stage_overlap_pct = 100.0 * hidden_copy_s / copy_s if copy_s > 0 else 0.0
    staged.d2h_window_peak_bytes = peak
    staged.d2h_window_waits = waits
    return staged


def shard_payload(info: ShardInfo) -> Dict[str, Any]:
    """Picklable description handed to the writer process.  Skipped shards
    travel as provenance-only payloads (``skip_spans``, no shm — the bytes
    never left the device); transferred shards under an active device
    digest carry their per-chunk verdicts (``dev_unchanged``) for the
    drain's crc cross-check."""
    shape = tuple(b - a for a, b in info.index)
    p = {
        "leaf_idx": info.leaf_idx,
        "shard_idx": info.shard_idx,
        "global_shape": list(info.global_shape),
        "index": [list(pair) for pair in info.index],
        "dtype": info.dtype,
        "shm_name": info.shm_name,
        "shape": list(shape),
        "nbytes": info.nbytes,
    }
    if info.skip_spans is not None:
        p["shm_name"] = ""
        p["skip_spans"] = [list(r) for r in info.skip_spans]
    elif info.dev_unchanged is not None:
        p["dev_unchanged"] = [list(t) for t in info.dev_unchanged]
    return p
