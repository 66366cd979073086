"""Reference workloads the resiliency layer wraps and benchmarks against."""

from . import (
    keye_vl2,
    keye_vl2_reference,
    kimi_linear,
    kimi_linear_reference,
    lfm2_moe,
    lfm2_moe_reference,
    mellum,
    mellum_reference,
    qwen3_next,
    qwen3_next_reference,
)
from .keye_vl2 import KeyeVL2Config
from .kimi_linear import KimiLinearConfig
from .lfm2_moe import Lfm2MoeConfig
from .mellum import MellumConfig
from .qwen3_next import Qwen3NextConfig
from .transformer import TransformerConfig, init_params, forward, loss_fn, make_train_step

__all__ = [
    "KeyeVL2Config",
    "KimiLinearConfig",
    "Lfm2MoeConfig",
    "MellumConfig",
    "Qwen3NextConfig",
    "TransformerConfig",
    "forward",
    "init_params",
    "keye_vl2",
    "keye_vl2_reference",
    "kimi_linear",
    "kimi_linear_reference",
    "lfm2_moe",
    "lfm2_moe_reference",
    "loss_fn",
    "make_train_step",
    "mellum",
    "mellum_reference",
    "qwen3_next",
    "qwen3_next_reference",
]
