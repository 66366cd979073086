"""Checkpointing: async global saves + node-local saves with replication.

Reference: ``checkpointing/`` (async_ckpt + local).  TPU re-design:

- D2H staging uses JAX's async host transfer (``copy_to_host_async`` shard by
  shard in plan order, at most ``staging.D2H_WINDOW_BYTES`` = 128 MiB issued
  and not yet landed, each shard materialized as it lands) into POSIX shared
  memory, so the training step resumes after one device sync instead of
  blocking on file writes, and no launch of the process waits behind more
  than a window of transfers: issued all at once, a 4.59 GB state held every
  step and quorum tick for 1.2-1.5 s after each save on a v5e chip (the
  probe that chose the window is in ``docs/checkpointing.md``; reference
  stages via CUDA streams + pinned buffers,
  ``async_ckpt/filesystem_async.py:230``).
- The persistent writer is a ``spawn``-ed process receiving zero-copy shm
  handles (reference uses CUDA-IPC / CPU-shm handles, ``core.py:434-438``).
- Completion consensus rides the tpurx KV store over DCN instead of a NCCL
  all_reduce (reference ``core.py:279-291``).
- The on-disk format is a process-sharded array layout with a commit-marker
  metadata file (reference leans on torch DCP; we have no torch).
"""

from .async_ckpt.core import AsyncCallsQueue, AsyncRequest
from .async_ckpt.checkpointer import AsyncCheckpointer, load_checkpoint
from .integrity import (
    CheckpointCorruptError,
    ChunkReader,
    read_verified_blob,
    read_verified_shard,
    verify_blob,
    verify_blob_file,
)
from .local.state_dict import TensorAwareTree
from .local.manager import LocalCheckpointManager
from .local.replication import CliqueReplication

__all__ = [
    "AsyncCallsQueue",
    "AsyncRequest",
    "AsyncCheckpointer",
    "load_checkpoint",
    "CheckpointCorruptError",
    "ChunkReader",
    "read_verified_blob",
    "read_verified_shard",
    "verify_blob",
    "verify_blob_file",
    "TensorAwareTree",
    "LocalCheckpointManager",
    "CliqueReplication",
]
