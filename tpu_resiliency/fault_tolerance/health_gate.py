"""Pre-rendezvous node health gate.

Capability parity with the reference's pre-join hook health checks
(``ft_rendezvous_barrier.py:1902`` UnhealthyNodeException path) plus the
env-driven failure injector used for spare-node testing
(``testing_utils/health_check_injector.py:17-60``:
``NVRX_INJECT_GPU_FAILURE="cycle:infra_rank"``).

TPURX_INJECT_NODE_FAILURE="<cycle>:<node_id_substring>" makes the gate fail
for a matching node at a matching cycle — simulating device loss so tests can
exercise hot-spare replacement without real hardware faults.
"""

from __future__ import annotations


from ..utils import env
from ..utils.logging import get_logger
from .config import FaultToleranceConfig
from .rendezvous import UnhealthyNodeError

log = get_logger("health_gate")

ENV_INJECT = env.INJECT_NODE_FAILURE.name


def _injected_failure(node_id: str, current_cycle: int) -> bool:
    spec = env.INJECT_NODE_FAILURE.get()
    if not spec:
        return False
    try:
        cycle_s, _, node_sub = spec.partition(":")
        cycle = int(cycle_s)
    except ValueError:
        return False
    # fire at the given cycle or later (a dead node stays dead)
    return current_cycle >= cycle and node_sub in node_id


def pre_rendezvous_health_check(
    cfg: FaultToleranceConfig, node_id: str, current_cycle: int = 0
) -> None:
    """Raise UnhealthyNodeError if this node must not join the round."""
    if _injected_failure(node_id, current_cycle):
        raise UnhealthyNodeError(f"injected node failure for {node_id}")
    if cfg.enable_device_health_check:
        from ..health import DeviceHealthCheck

        check = DeviceHealthCheck()
        result = check.run()
        if not result.healthy:
            raise UnhealthyNodeError(f"device health check failed: {result.message}")
        log.info("device health check (cycle %s): %s", current_cycle, result.message)
    if cfg.enable_storage_health_check and cfg.storage_health_check_path:
        from ..health import StoragePathHealthCheck

        result = StoragePathHealthCheck(cfg.storage_health_check_path).run()
        if not result.healthy:
            raise UnhealthyNodeError(f"storage health check failed: {result.message}")
