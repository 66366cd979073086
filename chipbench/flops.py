"""Operations and bytes the algorithm needs, from shapes, and the chip's peaks.

Kept with the benchmark so that no later PR can move the yardstick.  The
functions take a :class:`chipbench.weights.Sizes`.
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


def forward_flops_per_token(sizes) -> float:
    """Multiply-adds counted as two, one token through the forward pass:
    the four attention projections and the two feed-forward matmuls of every
    layer, causal attention over the (T+1)/2 keys an average query sees
    (scores and weighted values), and the tied output head.  Norms, softmax,
    GELU and the embedding gather are not counted."""
    d, f, t = sizes.n_embd, sizes.n_inner, sizes.seq
    per_layer = 2 * (4 * d * d + 2 * d * f) + 2 * 2 * d * (t + 1) / 2
    return sizes.n_layer * per_layer + 2 * sizes.vocab_size * d


def train_flops_per_token(sizes) -> float:
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3 * forward_flops_per_token(sizes)


def step_mfu_pct(sizes, step_seconds: float, device_kind: str) -> float:
    """Model FLOP/s utilisation of one chip at ``step_seconds`` a step."""
    achieved = train_flops_per_token(sizes) * sizes.tokens_per_step / step_seconds
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
