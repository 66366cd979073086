"""Reference workloads the resiliency layer wraps and benchmarks against."""

from . import kimi_linear, kimi_linear_reference, qwen3_next, qwen3_next_reference
from .kimi_linear import KimiLinearConfig
from .qwen3_next import Qwen3NextConfig
from .transformer import TransformerConfig, init_params, forward, loss_fn, make_train_step

__all__ = [
    "KimiLinearConfig",
    "Qwen3NextConfig",
    "TransformerConfig",
    "forward",
    "init_params",
    "kimi_linear",
    "kimi_linear_reference",
    "loss_fn",
    "make_train_step",
    "qwen3_next",
    "qwen3_next_reference",
]
