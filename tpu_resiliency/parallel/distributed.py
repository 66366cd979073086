"""Multi-process JAX bootstrap from launcher-provided env.

The reference's workloads call ``torch.distributed.init_process_group`` from
torchelastic env; the JAX analog is ``jax.distributed.initialize`` with a
coordinator address.  The tpurx launcher already exports rank/world/store
env; this helper derives the coordinator from them so workloads need one
line:

    from tpu_resiliency.parallel import init_distributed
    init_distributed()          # no-op single-process; idempotent

One JAX process per *worker*: the job is sized by ``TPURX_WORLD_SIZE`` and
``TPURX_RANK``.  A chip belongs to one process at a time, so a host runs
either one worker that drives all of its chips or one worker per chip;
:func:`worker_chip_env` is what the launcher exports to give each worker of
the second shape its chip.

The coordinator runs on the node hosting the KV store (same machine that
already owns the control plane), port = store port + 1 by default, or
``TPURX_JAX_COORDINATOR`` overrides.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

from ..utils import env as _env
from ..utils.logging import get_logger

log = get_logger("distributed")

_initialized = False


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize jax.distributed from tpurx env. Returns True if initialized
    (False for single-process runs where it is unnecessary)."""
    global _initialized
    if _initialized:
        return True
    if num_processes is None:
        num_processes = _env.WORLD_SIZE.get()
    if process_id is None:
        process_id = _env.RANK.get()
    if num_processes <= 1:
        return False
    if coordinator_address is None:
        coordinator_address = _env.JAX_COORDINATOR.get()
    if coordinator_address is None:
        host = _env.STORE_ADDR.get()
        port = _env.STORE_PORT.get() + 1
        coordinator_address = f"{host}:{port}"
    import jax

    log.info(
        "jax.distributed.initialize(%s, num_processes=%s, process_id=%s)",
        coordinator_address, num_processes, process_id,
    )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    return True


# chips of one host as a process grid, one chip per process; libtpu wants the
# bounds spelled out.  A host that names its own layout wins over the table.
_HOST_CHIP_BOUNDS = {4: "2,2,1"}
_TPU_PROCESS_BASE_PORT = 8476


def worker_chip_env(
    nproc: int, local_rank: int, chips: Sequence[str]
) -> Dict[str, str]:
    """Env that makes worker ``local_rank`` of ``nproc`` on this host open
    only its own chip, as one process of a host-wide libtpu process grid.

    ``chips`` is what the host exposes (``health.tpu.visible_tpu_chips``).
    No chips (a CPU host) or one worker (it drives every chip) need nothing.
    Anything but one worker per chip is refused: workers that share a chip
    do not share it — the second one dies at backend init on libtpu's lock.
    """
    if not chips or nproc == 1:
        return {}
    bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS") or _HOST_CHIP_BOUNDS.get(
        len(chips)
    )
    if nproc != len(chips) or bounds is None:
        raise ValueError(
            f"{nproc} workers on a host with {len(chips)} TPU chip(s): a chip "
            f"belongs to one process at a time, so a host runs one worker "
            f"that drives all its chips (--nproc-per-node 1) or one worker "
            f"per chip (--nproc-per-node {len(chips)}"
            + ("" if bounds else ", and this host's chip layout is unknown: "
               "set TPU_CHIPS_PER_HOST_BOUNDS")
            + ")"
        )
    ports = [_TPU_PROCESS_BASE_PORT + i for i in range(nproc)]
    me = str(local_rank)
    return {
        "TPU_VISIBLE_CHIPS": me,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_ADDRESSES": ",".join(f"localhost:{p}" for p in ports),
        "TPU_PROCESS_PORT": str(ports[local_rank]),
        "CLOUD_TPU_TASK_ID": me,
        # the older spellings of the same facts, which a TPU host's own
        # environment may carry with the one-process-per-host values
        "TPU_VISIBLE_DEVICES": me,
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": bounds,
        "TPU_WORKER_ID": me,
        "TPU_WORKER_HOSTNAMES": ",".join(["localhost"] * nproc),
    }
