"""High-level async checkpoint API for JAX pytrees.

Reference analogs: ``TorchAsyncCheckpoint`` (``torch_ckpt.py:32``) +
``save_state_dict_async_plan`` / ``..._finalize`` (``state_dict_saver.py``).

Save pipeline per request (default ``stage_mode="snapshot"``):
  1. (trainer, ~free)  device snapshot: one jitted copy of every jax.Array
                       leaf into fresh device buffers — an async dispatch,
                       so the training step never waits on D2H.  Device
                       ordering makes this donation-safe: the copy is
                       enqueued before the next step can reuse donated
                       input buffers.  The worker's streamed drain call is
                       opened here too, before any bytes move.  The copy is
                       sealed on the device by a second dispatch (a
                       fingerprint a leaf): once the save commits, the slot
                       is the restore's first source (``resident.py``).
  2. (stager thread)   stage_pytree: pipelined D2H of the snapshot into
                       pooled (double-buffered) shm — zero allocation and
                       zero first-touch faults in steady state; each shard
                       is streamed to the worker the moment its bytes land
  3. (worker, async)   write_process_shards_streamed: chunked multi-writer
                       drain (O_DIRECT when possible, batched durability),
                       overlapping file writes with still-staging leaves,
                       reporting bytes-written/total progress up the pipe
  4. (trainer, later)  finalize once ALL ranks' writes are done:
                       coordinator merges process indices -> metadata.json
                       (atomic commit), shm returns to the pool

``stage_mode="sync"`` restores the reference-style behavior (trainer blocks
on D2H at save time, reference ``core.py:547-553`` preload join) for hosts
where the extra device-memory copy is unaffordable.

The metadata-read side has a cache (:class:`CachedMetadataReader`, the
reference's ``CachedMetadataFileSystemReader`` analog); the save-side merge
is cached by plan signature and cross-checked against every process's
reported signature (reference ``verify_global_md_reuse``,
``state_dict_saver.py:374``).
"""

from __future__ import annotations

import dataclasses
import glob
import itertools
import json
import os
import queue as queue_mod
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ...telemetry import counter, flight, gauge, histogram
from ...utils import env
from ...utils.logging import get_logger
from .core import (  # noqa: F401 - CheckpointSaveError re-exported for callers
    AsyncCallsQueue,
    AsyncRequest,
    CheckpointSaveError,
    store_sync_fn,
)
from ...utils.dtypes import coerce_dtype
from . import resident as resident_mod
from .staging import (
    IV_STAGE,
    StagedTree,
    plan_signature,
    shard_payload,
    stage_pytree,
)
from .writer import (
    _RESTORE_SOURCE,
    _RestoreEngine,
    is_committed,
    read_metadata,
    resolve_restore_threads,
    resolve_write_threads,
    shard_filename,
    write_metadata,
    write_process_shards_streamed,
)

log = get_logger("checkpointer")

_SAVES = counter("tpurx_ckpt_saves_total", "async_save requests issued")
_SAVES_FINALIZED = counter(
    "tpurx_ckpt_saves_finalized_total", "Saves fully committed (finalize ran)"
)
_SAVE_CALL_NS = histogram(
    "tpurx_ckpt_save_call_ns",
    "Trainer-visible async_save stall (snapshot + handoff; full staging in "
    "sync mode)",
)
_STAGE_BYTES = counter(
    "tpurx_ckpt_stage_bytes_total", "Bytes staged into shared memory"
)
_STAGE_OVERLAP = gauge(
    "tpurx_ckpt_stage_overlap_pct", "Last staging's D2H/shm-copy overlap (%)"
)
_STAGE_WINDOW_PEAK = gauge(
    "tpurx_ckpt_stage_d2h_window_peak_bytes",
    "Last staging's most D2H bytes issued and not yet landed",
)
_STAGE_WINDOW_WAITS = gauge(
    "tpurx_ckpt_stage_d2h_window_waits",
    "Last staging's top-ups of the D2H window that left a shard waiting",
)
_DRAIN_PROGRESS = gauge(
    "tpurx_ckpt_drain_progress",
    "Fraction (0-1) of in-flight save bytes the worker has written",
)
_SNAP_RING_BYTES = gauge(
    "tpurx_ckpt_snap_ring_bytes",
    "Device bytes held by the live slots of the snapshot ring",
)
_SNAP_SLOT = counter(
    "tpurx_ckpt_snap_slot_total",
    "Snapshot ring slots taken by a save: reused = a drained slot's memory "
    "was released to this save's copy (the ring did not grow), fresh = no "
    "drained slot of the plan signature, so the copy allocated a new one",
    labels=("outcome",),
)
_DEVICE_REJECTED = counter(
    "tpurx_ckpt_restore_device_rejected_total",
    "Times the restore's device rung declined: seal = a restore whose copies' "
    "fingerprints differ from the slot's seal (served from shm instead), "
    "template = a leaf whose template differs from the slot's leaf in shape, "
    "dtype, sharding or committed-ness (that leaf took the engine), deleted = "
    "a restore that found the slot's arrays deleted",
    labels=("reason",),
)

# Flight-recorder intervals of one save (ident = the save ticket).  The call
# itself is prepare + snapshot + handoff; staging runs on the stager thread
# and the drain (core.py) ends at the commit, so those are intervals of
# their own that share the ticket.
IV_SAVE = flight.declare_interval("ckpt.save_begin", "ckpt.save_end")
IV_SAVE_PREPARE = flight.declare_interval(
    "ckpt.save.prepare_begin", "ckpt.save.prepare_end"
)
IV_SAVE_SNAPSHOT = flight.declare_interval(
    "ckpt.save.snapshot_begin", "ckpt.save.snapshot_end"
)
IV_SAVE_HANDOFF = flight.declare_interval(
    "ckpt.save.handoff_begin", "ckpt.save.handoff_end"
)
# ... and of one restore (ident = a process-local load number)
IV_LOAD = flight.declare_interval("ckpt.load_begin", "ckpt.load_end")
IV_LOAD_PLAN = flight.declare_interval(
    "ckpt.load.plan_begin", "ckpt.load.plan_end"
)
IV_LOAD_START = flight.declare_interval(
    "ckpt.load.start_begin", "ckpt.load.start_end"
)
IV_LOAD_WAIT = flight.declare_interval(
    "ckpt.load.wait_begin", "ckpt.load.wait_end"
)
IV_LOAD_PLACE = flight.declare_interval(
    "ckpt.load.place_begin", "ckpt.load.place_end"
)
IV_LOAD_RELEASE = flight.declare_interval(
    "ckpt.load.release_begin", "ckpt.load.release_end"
)
_LOAD_SEQ = itertools.count(1)


_SNAP_FN = None


def _copy_leaves(
    leaves: List[Any], dev_idx: List[int]
) -> Tuple[List[Any], List[Any]]:
    """``leaves`` with every jax.Array (positions ``dev_idx``) copied into
    fresh device buffers by one jitted dispatch and every host ndarray
    np.copy'd; also the device copies alone, in ``dev_idx`` order."""
    import jax
    import jax.numpy as jnp

    global _SNAP_FN
    copies: List[Any] = []
    if dev_idx:
        if _SNAP_FN is None:
            _SNAP_FN = jax.jit(lambda xs: [jnp.copy(x) for x in xs])
        copies = list(_SNAP_FN([leaves[i] for i in dev_idx]))
    by_pos = dict(zip(dev_idx, copies))
    out = [
        by_pos[i] if i in by_pos
        else (l.copy() if isinstance(l, np.ndarray) else l)
        for i, l in enumerate(leaves)
    ]
    return out, copies


def device_snapshot(tree: Any) -> Any:
    """Copy every jax.Array leaf into fresh device buffers with one jitted
    dispatch (host leaves are np.copy'd).  Returns immediately — the copies
    execute on the device stream ahead of any later-dispatched step, so the
    snapshot is consistent even when the training step donates its inputs."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    dev_idx = [i for i, l in enumerate(leaves) if isinstance(l, jax.Array)]
    return jax.tree_util.tree_unflatten(treedef, _copy_leaves(leaves, dev_idx)[0])


@dataclasses.dataclass
class _StagingJob:
    tree: Any
    plan_sig: str
    ticket: int
    stream: Any = None                    # core.StreamHandle feeding the worker
    # delta baseline for this save: {(leaf_idx, shard_idx):
    #   {(off, len): (crc, base_path)}} from the previous committed index
    delta_base: Optional[Dict] = None
    save_id: str = ""
    # device-digest inputs (see device_digest.DigestContext): the committed
    # baseline's on-device fingerprints + the save_id whose bytes they seal
    device_digest: bool = False
    delta_fps: Optional[Dict] = None
    delta_save_id: str = ""
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    staged: Optional[StagedTree] = None
    # `cleaned` guards the staged-tree handoff between the stager thread and
    # cleanup (finalize or abort) — whichever runs second releases the shm
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    cleaned: bool = False


class SaveScheduler:
    """Interval-based save gate that re-reads ``TPURX_CKPT_INTERVAL_S``
    per step, so a runtime override (the policy controller retuning
    cadence toward the Young/Daly optimum) takes effect mid-run without
    restarting the trainer.  ``default_interval_s`` is the cadence when
    the knob is unset; ``<= 0`` disables time-gating (every ``due()``
    call answers True)."""

    def __init__(
        self,
        default_interval_s: float = 300.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.default_interval_s = float(default_interval_s)
        self._clock = clock
        self._last_save_t: Optional[float] = None

    def interval_s(self) -> float:
        knob = env.CKPT_INTERVAL_S.get()
        return self.default_interval_s if knob is None else float(knob)

    def due(self, now: Optional[float] = None) -> bool:
        """True when a save should be issued this step.  Does NOT mark —
        call :meth:`note_saved` after ``async_save`` actually ran, so a
        skipped/failed save retries next step."""
        interval = self.interval_s()
        if interval <= 0:
            return True
        t = self._clock() if now is None else float(now)
        if self._last_save_t is None:
            return True
        return (t - self._last_save_t) >= interval

    def note_saved(self, now: Optional[float] = None) -> None:
        self._last_save_t = self._clock() if now is None else float(now)


class AsyncCheckpointer:
    def __init__(
        self,
        store=None,
        rank: int = 0,
        world_size: int = 1,
        process_index: Optional[int] = None,
        persistent_worker: bool = True,
        write_threads: Optional[int] = None,
        stage_mode: Optional[str] = None,
        pool_size: int = 2,
        digest: Optional[bool] = None,
        delta: Optional[bool] = None,
        resident: Optional[bool] = None,
        device_digest: Optional[bool] = None,
        stage_buffers: Optional[int] = None,
    ):
        if stage_mode not in (None, "snapshot", "sync"):
            raise ValueError(
                f"stage_mode must be None|snapshot|sync, got {stage_mode!r}"
            )
        sync_fn = (
            store_sync_fn(store, rank, world_size) if store is not None else None
        )
        self.queue = AsyncCallsQueue(persistent=persistent_worker, sync_fn=sync_fn)
        self.rank = rank
        self.world_size = world_size
        self.write_threads = resolve_write_threads(write_threads)
        self.stage_mode = stage_mode
        self.pool_size = pool_size
        # chunk-digest recording in the drain (None = env TPURX_CKPT_DIGEST,
        # default on); per-save override via async_save(digest=...)
        self.digest = digest
        # delta saves (None = env TPURX_CKPT_DELTA, default off); per-save
        # override via async_save(delta=...).  Needs digests: the chunk crc
        # is the unchanged-vs-previous-generation match key.
        self.delta = delta
        # shm-resident committed generation as warm restore source
        # (None = env TPURX_CKPT_RESIDENT, default on)
        self.resident = resident
        # on-device change fingerprints (None = env TPURX_CKPT_DEVICE_DIGEST,
        # default off): delta saves skip the D2H itself for unchanged shards,
        # and transferred chunks get a device-vs-host verdict cross-check
        self.device_digest = device_digest
        # device-side snapshot ring depth (None = env TPURX_CKPT_STAGE_BUFFERS,
        # default 2): snapshot-mode saves rotate through this many device
        # buffer sets, taking a slot over only once its staging drained
        self.stage_buffers = stage_buffers
        # previous committed generation's chunk index, for delta matching:
        # {"sig": plan_sig, "chunks": {(leaf, shard): {(off, len):
        #   (crc, physical_path)}}} — provenance-resolved, so chains never
        # form (every entry points at the file that HOLDS the bytes)
        self._delta_baseline: Optional[Dict[str, Any]] = None
        self._published_dirs: set = set()
        if process_index is None:
            try:
                import jax

                process_index = jax.process_index()
            except Exception:  # noqa: BLE001
                process_index = 0
        self.process_index = process_index
        self._merger = _MetadataMerger()
        self._resolved_stage_mode: Optional[str] = None
        self._save_seq = 0
        self._pool: List[StagedTree] = []
        self._pool_lock = threading.Lock()
        self._stage_q: "queue_mod.Queue[Optional[_StagingJob]]" = queue_mod.Queue()
        self._stager: Optional[threading.Thread] = None
        # last staging's byte accounting (tests assert steady-state reuse)
        self.last_stage_stats: Dict[str, int] = {}
        # "snapshot" | "sync": the mode the last async_save really took
        self.last_stage_mode: Optional[str] = None
        # snapshot ring: {"sig", "leaves" (device arrays), "dev_idx" (their
        # positions in the flattened tree), "seal", "job"} slots; a slot is
        # reusable (its buffers releasable) only once its job's staging has
        # drained — job.done is the D2H-consumed fence.  A slot leaves the
        # ring through _drop_slot alone: a committed generation may be
        # serving restores from it (resident.py, "device part")
        self._snap_ring: List[Dict[str, Any]] = []
        self._snap_lock = threading.Lock()
        self.snap_ring_stats: Dict[str, int] = {"reused": 0, "fresh": 0}

    # -- save --------------------------------------------------------------

    def async_save(
        self,
        tree: Any,
        ckpt_dir: str,
        extra_metadata: Optional[Dict] = None,
        save_id: Optional[str] = None,
        stage_mode: Optional[str] = None,
        digest: Optional[bool] = None,
        delta: Optional[bool] = None,
    ) -> int:
        """Snapshot + hand off to the stager (default), or stage inline
        (``stage_mode="sync"``).  Returns a monotonic save ticket.  Call
        :meth:`maybe_finalize` every step.

        The worker's drain is scheduled HERE, before staging runs: the
        streamed plan lets the writer persist the first staged shards while
        later leaves are still staging (no staging/writing barrier).

        ``save_id`` must match across ranks of one save (e.g. the training
        iteration); finalize only merges process indices carrying the same
        id, so stale index files from a previous run into the same directory
        (possibly with a different world size) are never committed."""
        call_t0 = time.monotonic_ns()
        self._save_seq += 1
        ticket = self._save_seq
        with flight.span(IV_SAVE, ticket):
            with flight.span(IV_SAVE_PREPARE, ticket, IV_SAVE):
                mode = (
                    stage_mode or self.stage_mode
                    or self._resolve_stage_mode(tree)
                )
                self.last_stage_mode = mode
                os.makedirs(ckpt_dir, exist_ok=True)
                if save_id is None:
                    save_id = str(
                        (extra_metadata or {}).get("iteration", "default")
                    )
                # drop our own leftovers from any previous save into this
                # directory
                for stale in (
                    os.path.join(ckpt_dir, f"process_{self.process_index}.json"),
                    os.path.join(ckpt_dir, "metadata.json")
                    if self.rank == 0 else None,
                ):
                    if stale and os.path.exists(stale):
                        os.unlink(stale)
                sig = plan_signature(tree, self.process_index)
            snap_slot = None
            if mode == "snapshot":
                # also copies host-only trees: the stager must never hold raw
                # references the trainer can mutate in place after we return
                with flight.span(IV_SAVE_SNAPSHOT, ticket, IV_SAVE):
                    # async; no D2H yet
                    tree, snap_slot = self._ring_snapshot(tree, sig)
            with flight.span(IV_SAVE_HANDOFF, ticket, IV_SAVE):
                job = _StagingJob(tree=tree, plan_sig=sig, ticket=ticket,
                                  save_id=save_id)
                if snap_slot is not None:
                    snap_slot["job"] = job
                    with self._snap_lock:
                        self._snap_ring.append(snap_slot)
                        while len(self._snap_ring) > self._ring_cap():
                            # the evicted slot's buffers just drop
                            self._drop_slot(0)
                        self._note_ring_bytes()
                if digest is None:
                    digest = self.digest
                effective_digest = (
                    digest if digest is not None else env.CKPT_DIGEST.get()
                )
                if delta is None:
                    delta = (
                        self.delta if self.delta is not None
                        else env.CKPT_DELTA.get()
                    )
                from . import device_digest as device_digest_mod

                job.device_digest = bool(effective_digest) and (
                    self.device_digest if self.device_digest is not None
                    else device_digest_mod.enabled()
                )
                base = self._delta_baseline
                if (delta and effective_digest and base is not None
                        and base["sig"] == sig):
                    job.delta_base = base["chunks"]
                    job.delta_fps = base.get("device_fps")
                    job.delta_save_id = str(base.get("save_id") or "")
                finalize_fns: List[Callable] = []
                if self.rank == 0:
                    extra = extra_metadata
                    finalize_fns.append(
                        lambda: self._merger.finalize(
                            ckpt_dir, job.staged, extra, save_id
                        )
                    )
                # every rank: fold the committed index back into the trainer
                # — the delta baseline for the next save, and (when enabled)
                # the resident publish binding index digests to the staged
                # shm buffers
                finalize_fns.append(
                    lambda: self._after_commit(ckpt_dir, job, save_id, sig)
                )
                req = AsyncRequest(
                    async_fn=write_process_shards_streamed,
                    async_fn_args=(
                        ckpt_dir, self.process_index, self.write_threads,
                        save_id, sig, digest,
                    ),
                    finalize_fns=finalize_fns,
                    cleanup_fns=[lambda: self._release_job(job)],
                    ticket=ticket,
                )
                job.stream = self.queue.schedule_streamed_request(req)
                if mode == "sync":
                    self._run_staging(job)
                else:
                    self._ensure_stager()
                    self._stage_q.put(job)
        _SAVES.inc()
        _SAVE_CALL_NS.observe(time.monotonic_ns() - call_t0)
        return ticket

    def save(self, tree: Any, ckpt_dir: str, extra_metadata: Optional[Dict] = None) -> None:
        """Synchronous save (stage + write + commit before returning)."""
        self.async_save(tree, ckpt_dir, extra_metadata)
        self.finalize_all()

    def _resolve_stage_mode(self, tree: Any) -> str:
        """Platform default, resolved from the first device leaf and cached.

        Accelerators get ``snapshot``: the device-side copy is a cheap
        dispatch and lets D2H overlap later training steps.  The CPU backend
        gets ``sync``: there the "device snapshot" is a full host memcpy and
        background staging steals foreground cycles — staging inline in the
        call pays ONE memcpy and is equally donation-safe (the bytes are in
        shm before async_save returns)."""
        if self._resolved_stage_mode is None:
            platform = "cpu"
            try:
                import jax

                for leaf in jax.tree_util.tree_leaves(tree):
                    if isinstance(leaf, jax.Array):
                        platform = list(leaf.devices())[0].platform
                        break
            except (ImportError, AttributeError, IndexError, RuntimeError):
                pass  # host-only trees / backend without device introspection
            self._resolved_stage_mode = "sync" if platform == "cpu" else "snapshot"
        return self._resolved_stage_mode

    # -- snapshot ring -----------------------------------------------------

    def _ring_cap(self) -> int:
        cap = (
            self.stage_buffers if self.stage_buffers is not None
            else env.CKPT_STAGE_BUFFERS.get()
        )
        return max(1, int(cap))

    def _note_ring_bytes(self) -> None:
        """Publish the ring's live device bytes; call with ``_snap_lock``
        held, wherever ``_snap_ring`` changed."""
        _SNAP_RING_BYTES.set(sum(
            int(leaf.nbytes)
            for slot in self._snap_ring for leaf in slot["leaves"]
        ))

    def _drop_slot(self, i: int) -> Dict[str, Any]:
        """Take slot ``i`` out of the ring (``_snap_lock`` held), unpublishing
        first whatever generation serves restores from it."""
        slot = self._snap_ring.pop(i)
        resident_mod.unpublish_device(slot)
        return slot

    def _publishes_resident(self) -> bool:
        return bool(
            env.CKPT_RESIDENT.get() if self.resident is None else self.resident
        )

    def _seals_slots(self) -> bool:
        """Whether a slot of this checkpointer can become a restore source:
        what governs the shm rung governs this one, and only a generation
        that holds the whole tree (one process) gets a device part."""
        return self.world_size == 1 and self._publishes_resident()

    def _ring_snapshot(self, tree: Any, sig: str) -> Tuple[Any, Optional[Dict]]:
        """Device snapshot through the double-buffered ring: with
        ``stage_buffers >= 2``, the copy takes over the memory of a previous
        slot (same plan signature) instead of growing the ring — but only a
        slot whose staging job already drained, so the next step's
        compute/snapshot overlaps the previous slice's D2H without ever
        overwriting bytes still in flight (``job.done`` is the fence,
        sequenced by the committed-generation protocol in ``resident.py``).
        The drained slot's buffers are released BEFORE the copy is
        dispatched, so at no point of a save more than the live state and
        one slot per ring position in use are allocated.

        Returns ``(snapshot_tree, slot)``; the caller binds the new slot to
        its staging job and appends it to the ring.  ``stage_buffers <= 1``
        falls back to :func:`device_snapshot` (slot None)."""
        if self._ring_cap() <= 1:
            return device_snapshot(tree), None
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        dev_idx = [i for i, l in enumerate(leaves) if isinstance(l, jax.Array)]
        slot = None
        if dev_idx:
            with self._snap_lock:
                for i, s in enumerate(self._snap_ring):
                    if (s["sig"] == sig and len(s["leaves"]) == len(dev_idx)
                            and (s["job"] is None or s["job"].done.is_set())):
                        slot = self._drop_slot(i)
                        self._note_ring_bytes()
                        break
            if slot is not None:
                # hand the stale slot's memory back first: the allocator
                # gives it to the copy's outputs.  (Donating it to the copy
                # instead makes the TPU compiler write the largest leaves
                # twice, through HBM temporaries: PERF.md, PR 38.)
                for leaf in slot["leaves"]:
                    leaf.delete()
            outcome = "fresh" if slot is None else "reused"
            self.snap_ring_stats[outcome] += 1
            _SNAP_SLOT.labels(outcome=outcome).inc()
        out, copies = _copy_leaves(leaves, dev_idx)
        seal = None
        if copies and self._seals_slots():
            from . import device_digest as device_digest_mod

            try:
                # its own program, dispatched and never waited for; it reads
                # the slot before the stager's D2H does, so it vouches for
                # the bytes the committed index's crcs will
                seal = device_digest_mod.seal_leaves(copies)
            except Exception:  # noqa: BLE001 - an unsealed slot serves nothing
                log.warning("snapshot slot not sealed; restores of this save "
                            "start at shm", exc_info=True)
        new_slot = {"sig": sig, "leaves": list(copies), "dev_idx": dev_idx,
                    "seal": seal, "job": None}
        return jax.tree_util.tree_unflatten(treedef, out), new_slot

    # -- staging thread ----------------------------------------------------

    def _ensure_stager(self) -> None:
        if self._stager is None or not self._stager.is_alive():
            self._stager = threading.Thread(
                target=self._stager_loop, name="tpurx-ckpt-stager", daemon=True
            )
            self._stager.start()

    def _stager_loop(self) -> None:
        # QoS: on Linux, setpriority on the NATIVE thread id deprioritizes
        # just this thread — staging memcpys then yield the core to the
        # training thread instead of competing with it (the in-process
        # analog of the write worker's nice/ionice, worker_main.py:65).
        # Matters most on core-starved hosts; harmless elsewhere.
        try:
            os.setpriority(
                os.PRIO_PROCESS,
                threading.get_native_id(),
                env.CKPT_STAGER_NICE.get(),
            )
        except (OSError, AttributeError, ValueError):
            pass
        while True:
            # tpurx: disable=TPURX005 -- stager idles for jobs; close() enqueues the None sentinel
            job = self._stage_q.get()
            if job is None:
                return
            self._run_staging(job)

    def _run_staging(self, job: _StagingJob) -> None:
        """Stage ``job.tree`` into shm, streaming the plan then each shard to
        the worker the moment its bytes land — the drain overlaps staging."""
        stream = job.stream
        flight.begin(IV_STAGE, job.ticket)

        def _payload(info):
            p = shard_payload(info)
            if job.delta_base is not None and info.skip_spans is None:
                ent = job.delta_base.get((info.leaf_idx, info.shard_idx))
                if ent:
                    # delta plan frame: the previous generation's chunk crcs
                    # + physical paths ride the shard payload to the worker
                    p["delta"] = ent
            return p

        try:
            pooled = self._pool_acquire(job.plan_sig)
            digest_ctx = None
            if job.device_digest:
                from . import device_digest as device_digest_mod

                # Skipping a shard publishes its pooled shm segment resident
                # AS-IS, so it is only safe when that segment still holds the
                # baseline generation's bytes — which the fingerprint match
                # then proves identical to the current device bytes.  With a
                # deeper pool the acquired tree can lag a generation behind
                # the baseline: content_id is the guard.
                allow_skip = (
                    job.delta_base is not None
                    and pooled is not None
                    and bool(job.delta_save_id)
                    and pooled.content_id == job.delta_save_id
                )
                digest_ctx = device_digest_mod.DigestContext(
                    base_rows=job.delta_base or {},
                    base_fps=job.delta_fps or {},
                    allow_skip=allow_skip,
                )
            try:
                staged = stage_pytree(
                    job.tree,
                    process_index=self.process_index,
                    reuse=pooled,
                    plan_sig=job.plan_sig,
                    on_plan=lambda total: stream.send(("plan", total)),
                    on_shard_staged=lambda info: stream.send(
                        ("shards", [_payload(info)])
                    ),
                    digest_ctx=digest_ctx,
                    ident=job.ticket,
                )
            except BaseException:
                if pooled is not None:
                    pooled.close(unlink=True)  # buffers in unknown state
                raise
            if pooled is not None and staged is not pooled:
                pooled.close(unlink=True)  # sig raced a layout change
            staged.content_id = job.save_id
            self.last_stage_stats = {
                "bytes_allocated": staged.bytes_allocated,
                "bytes_reused": staged.bytes_reused,
                "stage_wait_s": staged.stage_wait_s,
                "stage_copy_s": staged.stage_copy_s,
                "stage_overlap_pct": staged.stage_overlap_pct,
                "d2h_window_peak_bytes": staged.d2h_window_peak_bytes,
                "d2h_window_waits": staged.d2h_window_waits,
                "device_digest_s": staged.device_digest_s,
                "d2h_skipped_bytes": staged.d2h_skipped_bytes,
                "populate_s": staged.populate_s,
                "populate_wait_s": staged.populate_wait_s,
                "populated_bytes": staged.populated_bytes,
                "populate_fallbacks": staged.populate_fallbacks,
            }
            if staged.bytes_allocated:  # a first save, or a layout change
                log.info(
                    "staged %d bytes into fresh segments: %d made resident "
                    "ahead of the copy in %.3f s (%d segments fell back), the "
                    "copy waited %.3f s for them",
                    staged.bytes_allocated, staged.populated_bytes,
                    staged.populate_s, staged.populate_fallbacks,
                    staged.populate_wait_s,
                )
            _STAGE_BYTES.inc(staged.bytes_allocated + staged.bytes_reused)
            _STAGE_OVERLAP.set(staged.stage_overlap_pct)
            _STAGE_WINDOW_PEAK.set(staged.d2h_window_peak_bytes)
            _STAGE_WINDOW_WAITS.set(staged.d2h_window_waits)
            with job.lock:
                if job.cleaned:
                    # cleanup (abort) already ran: nobody else will release
                    self._pool_release(staged)
                else:
                    job.staged = staged
            stream.end()
        except Exception as exc:  # noqa: BLE001
            log.exception("checkpoint staging failed")
            stream.end(error=f"staging failed: {exc!r}")
        finally:
            job.tree = None  # free the device snapshot
            job.done.set()
            flight.end(IV_STAGE, job.ticket)

    def _release_job(self, job: _StagingJob) -> None:
        with job.lock:
            job.cleaned = True
            staged, job.staged = job.staged, None
        if staged is not None:
            self._pool_release(staged)

    def _pool_acquire(self, sig: str) -> Optional[StagedTree]:
        with self._pool_lock:
            for i, st in enumerate(self._pool):
                if st.plan_sig == sig:
                    st = self._pool.pop(i)
                    # the new save is about to overwrite these buffers: any
                    # resident generation still reading them is stale NOW
                    resident_mod.invalidate_tree(st)
                    return st
        return None

    def _pool_release(self, staged: StagedTree) -> None:
        with self._pool_lock:
            if staged.plan_sig and len(self._pool) < self.pool_size:
                self._pool.append(staged)
                return
        # pool declined the tree; if a resident generation still reads from
        # it, the registry takes ownership (closed at invalidation) —
        # closing here would unmap shm under the warm restore source
        if not resident_mod.retire_tree(staged):
            staged.close(unlink=True)

    def _drain_pool(self) -> None:
        with self._pool_lock:
            pool, self._pool = self._pool, []
        for st in pool:
            if not resident_mod.retire_tree(st):
                st.close(unlink=True)

    # -- finalize ---------------------------------------------------------

    def _after_commit(
        self, ckpt_dir: str, job: _StagingJob, save_id: str, sig: str
    ) -> None:
        """Per-rank finalize hook: fold the worker-reported committed index
        (the done frame's ``shards_index``) back into the trainer — it
        becomes the delta baseline for the next save and, when resident
        sourcing is on, the digest seal of the published warm generation.
        Best-effort: a save whose index doesn't surface (digest off, legacy
        worker) simply publishes nothing and clears the baseline."""
        stats = self.queue.caller.stats(job.stream.call_idx) or {}
        shards_idx = stats.get("shards_index") or []
        digested = bool(stats.get("digest")) and all(
            s.get("chunks") is not None for s in shards_idx
        )
        if not shards_idx or not digested:
            self._delta_baseline = None
            return
        pdir = os.path.abspath(
            os.path.join(ckpt_dir, f"process_{self.process_index}")
        )
        base_chunks: Dict[Tuple[int, int], Dict] = {}
        for s in shards_idx:
            own = os.path.join(
                pdir, shard_filename(s["leaf_idx"], s["shard_idx"])
            )
            bases = s.get("bases") or []
            base_chunks[(s["leaf_idx"], s["shard_idx"])] = {
                (int(r[0]), int(r[1])): (
                    int(r[2]), str(bases[r[3]]) if len(r) > 3 else own
                )
                for r in s["chunks"]
            }
        self._delta_baseline = {
            "sig": sig,
            "save_id": save_id,
            "chunks": base_chunks,
            # device fingerprints staged alongside this save: the next
            # save's on-device comparison baseline (empty when the device
            # digest was off — verdict() then degrades to no-skip)
            "device_fps": (
                dict(job.staged.device_fps) if job.staged is not None else {}
            ),
        }
        self._publish_resident(ckpt_dir, job, save_id, sig, shards_idx)

    def _publish_resident(
        self, ckpt_dir: str, job: _StagingJob, save_id: str, sig: str,
        shards_idx: List[Dict],
    ) -> None:
        staged = job.staged
        if not self._publishes_resident() or staged is None:
            return
        bufs = staged.shm_buffers()
        name_of = {
            (i.leaf_idx, i.shard_idx): i.shm_name
            for i in staged.shards
            if i.replica_owner and i.shm_name
        }
        shards: Dict[Tuple[int, int], Dict] = {}
        for s in shards_idx:
            key = (s["leaf_idx"], s["shard_idx"])
            buf = bufs.get(name_of.get(key, ""))
            if buf is None:
                return  # index/staging mismatch: publish nothing
            shards[key] = {**s, "buf": buf}
        rc = resident_mod.ResidentCheckpoint(
            ckpt_dir=ckpt_dir,
            save_id=save_id,
            plan_sig=sig,
            process_index=self.process_index,
            shards=shards,
            leaf_paths=list(staged.leaf_paths),
            treedef_repr=staged.treedef_repr,
            # a single-process save owns every byte of the tree; only then
            # can a restore skip the filesystem (metadata included)
            complete=self.world_size == 1,
            tree=staged,
            device=self._device_part(job),
        )
        resident_mod.publish(rc)
        self._published_dirs.add(os.path.abspath(ckpt_dir))

    def _device_part(self, job: _StagingJob) -> Optional[resident_mod.DevicePart]:
        """The sealed ring slot ``job`` drained from, if the ring still holds
        it: the committed generation's device part."""
        with self._snap_lock:
            for slot in self._snap_ring:
                if slot["job"] is job and slot["seal"] is not None:
                    return resident_mod.DevicePart(slot)
        return None

    def maybe_finalize(self, blocking: bool = False) -> List[int]:
        done = self.queue.maybe_finalize_async_calls(blocking=blocking)
        if done:
            _SAVES_FINALIZED.inc(len(done))
        return done

    @property
    def num_pending_saves(self) -> int:
        """Saves not yet fully committed (staging + drain).  Zero means every
        ``async_save`` issued so far is durable.  (Every save is scheduled
        on the worker at ``async_save`` time — its streamed call completes
        only after staging AND writing finish, so the queue sees both.)"""
        return self.queue.num_unfinalized_calls

    @property
    def last_drain_stats(self) -> Dict[str, Any]:
        """Drain accounting the worker reported for the most recently
        finalized save (bytes_written / shards / drain_ns / crc_ns /
        crc_chunks / digest) — the write-side digest cost is ``crc_ns``,
        the number the bench's verify-overhead gate watches."""
        return self.queue.last_call_stats or {}

    def drain_progress(self) -> Tuple[int, int]:
        """(bytes_written, bytes_total) across in-flight saves, as reported
        by the worker through the drain-progress pipe frames.  Monotonic per
        save; ``(0, 0)`` is the terminal value once finalize empties the
        in-flight set."""
        written, total = self.queue.drain_progress()
        if total > 0:
            _DRAIN_PROGRESS.set(written / total)
        return written, total

    def finalize_all(self, timeout: float = 600.0) -> None:
        self.queue.maybe_finalize_async_calls(blocking=True, timeout=timeout)

    def close(self) -> None:
        try:
            self.finalize_all()
        finally:
            if self._stager is not None and self._stager.is_alive():
                self._stage_q.put(None)
                self._stager.join(timeout=10)
            with self._snap_lock:
                while self._snap_ring:  # drop device snapshot references
                    self._drop_slot(0)
                self._note_ring_bytes()
            self._drain_pool()
            self.queue.close()


class _MetadataMerger:
    """Rank-0 finalize: merge process indices into metadata.json.

    The merged shard list is cached by (plan_sig, save world) and only
    reused after verifying every process index reports the SAME plan
    signature — the reference's ``verify_global_md_reuse``
    (``state_dict_saver.py:374``) against silent plan drift."""

    def __init__(self):
        self._cache_key: Optional[Tuple[str, int]] = None
        self._cache_shards: Optional[List[Dict]] = None
        self.reuse_hits = 0

    def finalize(
        self, ckpt_dir: str, staged: StagedTree, extra: Optional[Dict], save_id: str
    ) -> None:
        indices = []
        for pf in sorted(glob.glob(os.path.join(ckpt_dir, "process_*.json"))):
            with open(pf) as f:
                idx = json.load(f)
            if idx.get("save_id") != save_id:
                log.warning("ignoring stale process index %s (save_id %r != %r)",
                            pf, idx.get("save_id"), save_id)
                continue
            indices.append(idx)
        sigs = {idx.get("plan_sig", "") for idx in indices}
        verified = sigs == {staged.plan_sig}
        key = (staged.plan_sig, len(indices))
        if verified and self._cache_key == key and self._cache_shards is not None:
            all_shards = self._cache_shards
            self.reuse_hits += 1
            # The cached merge covers the content-INDEPENDENT geometry (the
            # plan signature vouches for it).  Content digests change every
            # save — refresh them from this save's process indices, or the
            # reused metadata would vouch for the PREVIOUS save's bytes.
            fresh = {
                (idx["process_index"], s["leaf_idx"], s["shard_idx"]): s
                for idx in indices
                for s in idx["shards"]
            }
            for s in all_shards:
                src = fresh.get(
                    (s["process_index"], s["leaf_idx"], s["shard_idx"])
                )
                for k in ("crc", "chunks", "bases"):
                    if src is not None and k in src:
                        s[k] = src[k]
                    else:
                        s.pop(k, None)
        else:
            if not verified:
                log.warning(
                    "plan signature mismatch across processes (%s vs local %s) — "
                    "full metadata merge", sigs, staged.plan_sig,
                )
            all_shards = []
            for idx in indices:
                for s in idx["shards"]:
                    s["process_index"] = idx["process_index"]
                    all_shards.append(s)
            if verified:
                self._cache_key, self._cache_shards = key, all_shards
        write_metadata(
            ckpt_dir,
            staged.treedef_repr,
            staged.leaf_paths,
            all_shards,
            num_processes=len(indices),
            extra={**(extra or {}), "save_id": save_id, "plan_sig": staged.plan_sig},
        )
        log.info("checkpoint committed: %s (%d shards)", ckpt_dir, len(all_shards))


# -- load --------------------------------------------------------------------

class CachedMetadataReader:
    """Caches metadata.json across loads (reference
    ``cached_metadata_filesystem_reader.py:24``)."""

    def __init__(self):
        self._cache: Dict[str, Dict] = {}

    def read(self, ckpt_dir: str) -> Dict:
        key = os.path.abspath(ckpt_dir)
        if key not in self._cache:
            self._cache[key] = read_metadata(ckpt_dir)
        return self._cache[key]


_default_reader = CachedMetadataReader()


def _place_leaf(tmpl: Any, arr: np.ndarray, leaf_path: str) -> Any:
    """Hand one restored leaf to its template slot.  jax templates get the
    array device_put with the template's sharding — an async dispatch, so
    placing leaf *i* overlaps whatever leaves are still reading — and as
    committed to it as the template is: a jitted step keys its cache on
    that too, and would compile a second time for restored state that is
    pinned where the fresh state it first saw was not.  The dtype cast is
    skipped entirely when the checkpoint dtype already matches (``astype``
    copies unconditionally; ``coerce_dtype`` does not)."""
    import jax

    if isinstance(tmpl, jax.Array):
        if tuple(arr.shape) != tuple(tmpl.shape):
            raise ValueError(
                f"leaf {leaf_path}: shape {arr.shape} != "
                f"template {tmpl.shape}"
            )
        arr = coerce_dtype(arr, tmpl.dtype)
        if not tmpl.committed:
            # uncommitted templates sit on one device (the default one)
            return jax.device_put(arr)
        if tmpl.sharding.is_fully_addressable:
            return jax.device_put(arr, tmpl.sharding)
        # A sharding that spans processes: device_put of host data onto it is
        # a collective (every process checks its value against process 0's),
        # and leaves arrive here in whatever order each process's readers
        # finish.  Placing only this process's shards needs no agreement.
        return jax.make_array_from_callback(
            arr.shape, tmpl.sharding, lambda index: arr[index]
        )
    return np.asarray(arr, dtype=getattr(tmpl, "dtype", None))


def _aliases_host(tmpl: Any) -> bool:
    """Whether ``device_put`` onto ``tmpl``'s placement may hand back an
    array that IS the host buffer it was given.  The CPU backend does that
    (jax 0.9.0: ``unsafe_buffer_pointer()`` of the result equals the address
    of a page-aligned source, read-only or not), and so may a host memory
    kind; a device with memory of its own copies."""
    sharding = tmpl.sharding
    return "host" in (getattr(sharding, "memory_kind", None) or "") or any(
        d.platform == "cpu" for d in sharding.device_set
    )


def _owned_copy(arr: np.ndarray) -> np.ndarray:
    """A copy the caller owns, made with the GIL released (``np.copyto``
    over byte views; a custom dtype's own copy loop may hold it)."""
    out = np.empty(arr.shape, arr.dtype)
    np.copyto(out.reshape(-1).view(np.uint8), arr.reshape(-1).view(np.uint8))
    return out


def _match_slot(rc: Any, leaves: List[Any]) -> Tuple[Any, List[Tuple[int, int]]]:
    """The generation's device part and ``[(leaf_idx, row)]``: the template
    leaves it can serve, each with its row in the part.  A leaf is served
    only where the slot's leaf is what ``_place_leaf`` would make of the
    template: same shape, dtype, sharding and committed-ness (a jitted step
    keys its cache on those).  Every other leaf is the engine's, as without
    a device part."""
    import jax

    part = rc.device if rc is not None and rc.complete else None
    if part is None or part.plan_sig != rc.plan_sig:
        return None, []
    if any(src.is_deleted() for src in part.leaves):
        # somebody deleted the slot's arrays without telling the registry
        resident_mod.unpublish_device(part.slot)
        _DEVICE_REJECTED.labels(reason="deleted").inc()
        return None, []
    matched = []
    for row, (idx, src) in enumerate(zip(part.dev_idx, part.leaves)):
        tmpl = leaves[idx]
        if not isinstance(tmpl, jax.Array):
            continue
        if (tuple(tmpl.shape) == tuple(src.shape) and tmpl.dtype == src.dtype
                and tmpl.committed == src.committed
                and (tmpl.sharding == src.sharding or
                     tmpl.sharding.is_equivalent_to(src.sharding, src.ndim))):
            matched.append((idx, row))
        else:
            _DEVICE_REJECTED.labels(reason="template").inc()
    return part, matched


def _copy_from_slot(
    part: Any, matched: List[Tuple[int, int]], leaves: List[Any], load_id: int
) -> Dict[int, Any]:
    """The device rung: ``{leaf_idx: restored array}`` out of ONE program that
    copies the matched slot leaves into fresh buffers (nothing donated: the
    slot stays for the next fault and the next save) and fingerprints the
    copies against the slot's seal on the device.  A mismatch fails closed:
    the device part is unpublished, the copies are dropped and ``{}`` leaves
    every leaf to the engine."""
    import jax

    from . import device_digest as device_digest_mod

    rows = [row for _, row in matched]
    with flight.span(IV_LOAD_START, load_id, IV_LOAD):
        seal = part.seal
        if len(rows) != len(part.leaves):
            seal = seal[np.asarray(rows)]
        copies, verdict = device_digest_mod.ckpt_slot_restore(
            [part.leaves[row] for row in rows], seal
        )
    with flight.span(IV_LOAD_PLACE, load_id, IV_LOAD):
        jax.block_until_ready(copies)
    with flight.span(IV_LOAD_WAIT, load_id, IV_LOAD):
        sealed = device_digest_mod.read_verdict(verdict)
    if not sealed:
        log.error("snapshot slot fails its seal: restoring from shm")
        resident_mod.unpublish_device(part.slot)
        _DEVICE_REJECTED.labels(reason="seal").inc()
        return {}
    served = {}
    for (idx, _), copy in zip(matched, copies):
        sharding = leaves[idx].sharding
        if copy.sharding != sharding:
            # an equivalent sharding under another name (a jit's output
            # spells its spec its own way): the same buffers, relabelled
            copy = jax.device_put(copy, sharding)
        served[idx] = copy
    return served


def load_checkpoint(
    ckpt_dir: str,
    template: Any,
    reader: Optional[CachedMetadataReader] = None,
    threads: Optional[int] = None,
    stats: Optional[Dict[str, Any]] = None,
    resident: Optional[bool] = None,
    peers: Optional[Any] = None,
) -> Any:
    """Load into the structure (and shardings) of ``template``.

    Template leaves that are jax.Arrays get the restored values placed with
    the template's sharding; numpy/scalar leaves come back as numpy.

    Default is the **parallel verified restore pipeline**: a restore plan
    computed from ``metadata.json`` (size-bucketed shard read spans with
    their recorded ``(off, len, crc)`` digests) executed by a reader pool
    (``threads``, else ``TPURX_CKPT_RESTORE_THREADS``, else write-engine
    sizing) that preads chunks straight into aligned leaf buffers (one
    lazily-faulted mapping per leaf read from disk) — no intermediate
    whole-shard bytes objects, no ``from_bytes`` copy — verifying every
    chunk's crc32 in-flight and the composed digest per shard.  As each
    leaf's shards complete, its ``device_put`` is enqueued while the
    remaining leaves are still reading, so read, verify, and H2D transfer
    pipeline instead of serializing.

    ``stats``, if given, is filled with the restore's accounting
    (``bytes_read``, the total delivered into the tree, / ``bytes_device`` /
    ``bytes_shm`` / ``bytes_in_place`` / ``chunks`` / ``shards`` / ``leaves``
    / ``verify_ns`` / ``restore_ns`` / ``threads``).

    **Warm restore**: when the committed generation for ``ckpt_dir`` is
    still resident (published at finalize, see ``resident.py``) and
    ``resident`` is not False (None = ``TPURX_CKPT_RESIDENT``; False means
    "from disk" and bypasses both warm rungs), shards are sourced from
    memory instead of disk — for a complete (single-process) generation no
    checkpoint file is opened at all, metadata included.  Every chunk is
    still verified against the committed index crcs; ``stats["bytes_shm"]``
    reports how much of the restore came from shm.

    **Device rung**: a complete generation whose snapshot-ring slot is still
    live (no later save has reused it, the backends were not cleared) is
    served from the chip first.  Every template leaf whose slot leaf has its
    shape, dtype, sharding and committed-ness is copied device to device by
    one jitted program that also fingerprints the copies against the seal the
    save took of the slot; the verdict is read before this function returns,
    and a mismatch fails closed (the device part is unpublished, the call
    restores from shm).  The returned tree never aliases the slot.  Leaves
    the slot cannot serve (numpy leaves, another dtype or placement) take
    the engine below, in the same call.  ``stats["bytes_device"]`` and
    ``tpurx_ckpt_restore_source_total{source="device"}`` account it.  The
    rung records the restore's own intervals: ``ckpt.load.plan`` (lookup and
    match), ``.start`` (dispatch), ``.place`` (until the copies are on
    hand), ``.wait`` (the verdict's fetch).

    A resident shard that is the whole of its leaf is not copied on the
    host at all: its spans are verified **where they lie** and, once all of
    them have, the leaf is placed from a read-only view of the segment
    (``stats["bytes_in_place"]``; a leaf of several shards, or one whose
    box is not contiguous in it, is assembled in a buffer of its own as
    from disk).  The segment is the stager's again at the next save, so
    nothing restored may still depend on it when this function returns:
    transfers from such views are waited for before it does, and where the
    placement could alias host memory instead of copying it (the CPU
    backend, a host memory kind, a numpy template leaf) the verified view
    is copied once, outside the GIL, and the copy is placed.

    **Peer-memory sourcing**: ``peers`` (a
    :class:`~.peer_source.PeerRestoreSource`) adds a rung between shm and
    disk — shards whose local files are missing (this host lost its volume,
    or the directory was never local) are fetched from other ranks' resident
    generations over the PR 11 chunk-request exchange, each tile crc-verified
    in flight and every chunk re-verified against the committed index here.
    ``stats["bytes_peer"]`` reports how much came over the wire.
    """
    import jax
    import jax.tree_util as jtu

    load_id = next(_LOAD_SEQ)
    with flight.span(IV_LOAD, load_id):
        with flight.span(IV_LOAD_PLAN, load_id, IV_LOAD):
            use_res = env.CKPT_RESIDENT.get() if resident is None else resident
            rc = resident_mod.lookup(ckpt_dir) if use_res else None
            res_bufs: Optional[Dict[Tuple[int, int, int], memoryview]] = None
            if rc is not None:
                res_bufs = {
                    (rc.process_index, l, s): buf
                    for (l, s), buf in rc.buffers().items()
                }
            if rc is not None and rc.complete and res_bufs:
                meta = rc.as_meta()  # committed index from memory: zero file opens
            else:
                if not is_committed(ckpt_dir):
                    raise FileNotFoundError(
                        f"no committed checkpoint at {ckpt_dir}"
                    )
                meta = (reader or _default_reader).read(ckpt_dir)

            if peers is not None:
                # peer-memory rung: pull shards whose local bytes are missing
                # from other ranks' resident generations, then hand them to the
                # engine as additional in-memory sources (chunk crcs re-verified
                # on copy)
                res_bufs = dict(res_bufs or {})
                peer_bytes = peers.fetch_missing(ckpt_dir, meta, res_bufs)
                if stats is not None:
                    stats["bytes_peer"] = peer_bytes
                if not res_bufs:
                    res_bufs = None

            leaves, treedef = jtu.tree_flatten(template)
            if len(leaves) != len(meta["leaf_paths"]):
                raise ValueError(
                    f"template has {len(leaves)} leaves, checkpoint has "
                    f"{len(meta['leaf_paths'])}"
                )
            part, matched = _match_slot(rc, leaves)
        t0_ns = time.monotonic_ns()
        out_leaves: List[Any] = [None] * len(leaves)
        from_slot = (
            _copy_from_slot(part, matched, leaves, load_id) if matched else {}
        )
        for idx, copy in from_slot.items():
            out_leaves[idx] = copy
        bytes_device = sum(int(copy.nbytes) for copy in from_slot.values())
        if from_slot:
            _RESTORE_SOURCE.labels(source="device").inc(bytes_device)
        if stats is not None:
            stats["bytes_device"] = bytes_device
        if len(from_slot) == len(leaves):
            # no engine: nothing to read, verify or release
            if stats is not None:
                stats.update(
                    bytes_read=bytes_device, bytes_shm=0, bytes_in_place=0,
                    chunks=0, shards=0, leaves=len(leaves), verify_ns=0,
                    restore_ns=time.monotonic_ns() - t0_ns, threads=0,
                )
            return jtu.tree_unflatten(treedef, out_leaves)
        # placed arrays whose transfer may still be reading a resident view
        in_flight: List[Any] = []

        def place(idx: int, arr: np.ndarray, borrowed: bool = False) -> None:
            """``borrowed``: ``arr`` is a view of a resident buffer, which
            outlives this call only until the next save reuses it."""
            with flight.span(IV_LOAD_PLACE, load_id, IV_LOAD):
                tmpl = leaves[idx]
                if borrowed and (
                    not isinstance(tmpl, jax.Array) or _aliases_host(tmpl)
                ):
                    arr, borrowed = _owned_copy(arr), False
                out_leaves[idx] = _place_leaf(
                    tmpl, arr, meta["leaf_paths"][idx]
                )
                if borrowed:
                    in_flight.append(out_leaves[idx])

        with flight.span(IV_LOAD_START, load_id, IV_LOAD):
            engine = _RestoreEngine(
                ckpt_dir, meta, num_threads=resolve_restore_threads(threads),
                leaf_indices=[
                    i for i in range(len(leaves)) if i not in from_slot
                ],
                resident=res_bufs,
            )
        try:
            while True:
                with flight.span(IV_LOAD_WAIT, load_id, IV_LOAD):
                    idx, payload = engine.ready.get()
                if idx is None:
                    if payload is not None:
                        raise payload
                    break
                place(idx, payload, borrowed=idx in engine.in_place)
        finally:
            if in_flight:
                # placement is over when the device has the bytes: only then
                # may the views go, and the segments be written again
                with flight.span(IV_LOAD_PLACE, load_id, IV_LOAD):
                    jax.block_until_ready(in_flight)
            with flight.span(IV_LOAD_RELEASE, load_id, IV_LOAD):
                engine.close()
        if stats is not None:
            stats.update(engine.stats())
            # bytes_read stays the total delivered into the tree
            stats["bytes_read"] += bytes_device
            stats["leaves"] += len(from_slot)
        return jtu.tree_unflatten(treedef, out_leaves)
