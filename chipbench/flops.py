"""The chip's peaks, and the operations and bytes of what is the product's
and no model's: the snapshot copy.

Kept with the benchmark so that no later PR can move the yardstick.  A
model's operations per token are its family's (``chipbench/families/``).
"""

from __future__ import annotations

import json
import os

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind == "source" or device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in {_PEAKS_FILE}")
    return table[device_kind]


def step_mfu_pct(family, sizes, step_seconds: float, device_kind: str) -> float:
    """Model FLOP/s utilisation of one chip at ``step_seconds`` a step, by
    the family's count of a token's forward and backward operations."""
    achieved = (family.train_flops_per_token(sizes) * sizes.tokens_per_step
                / step_seconds)
    return 100.0 * achieved / peaks(device_kind)["bf16_flops_per_s"]


def snapshot_copy_bytes(state_bytes: int) -> int:
    """The snapshot copy reads every byte of the state and writes it once."""
    return 2 * state_bytes


def snapshot_copy_least_s(state_bytes: int, device_kind: str) -> float:
    """Bandwidth-bound: the least time is bytes over peak HBM bytes/s."""
    return snapshot_copy_bytes(state_bytes) / peaks(device_kind)["hbm_bytes_per_s"]


def snapshot_copy_roofline_pct(state_bytes: int, copy_seconds: float,
                               device_kind: str) -> float:
    return 100.0 * snapshot_copy_least_s(state_bytes, device_kind) / copy_seconds
