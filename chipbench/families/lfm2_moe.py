"""The ``lfm2_moe`` family: one chip's share of an LFM2-MoE decoder
(``tpu_resiliency/models/lfm2_moe.py``) at the sizes its ``config.json`` gives
(``lfm2-8b-a1b-1chip``): a double-gated short convolution as the mixer of
every ``conv`` layer and grouped-query attention (32 query heads over 8
key/value heads, held whole) as that of every ``full_attention`` layer; a
dense SwiGLU in the leading layers and after them a routed expert layer that
holds ``num_experts`` of the deployment's experts and routes over all of them
with a sigmoid router whose bias picks and does not weigh; no shared expert; a
head tied to the embedding over the held rows of the vocabulary.

The state: every trained leaf is bfloat16 with a float32 master copy and two
moments (14 bytes a parameter); the router's bias (float32) and the last
step's load (int32), one row an expert layer, ride in the optimizer state
untouched by any gradient.  The plain reference is
``chipbench/reference/lfm2_moe.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

# the state's shape is the second family's (moments, a master copy where a
# leaf is not float32 itself), so its two readers serve as they are
from chipbench.families.kimi_linear import first_moment, master  # noqa: F401

CONTROLS = ("bf16_everywhere", "half_batch", "state_unchanged")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One configuration file, as the benchmark uses it."""

    name: str
    hidden_size: int
    layer_types: Tuple[str, ...]     # "conv" or "full_attention", layer by layer
    num_dense_layers: int
    conv_L_cache: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    attn_block: int
    intermediate_size: int
    moe_intermediate_size: int
    router_experts: int              # the deployment's experts: the router's outputs
    experts_held: int
    expert_offset: int
    num_experts_per_token: int
    routed_scaling_factor: float
    vocab_size: int                  # the held rows: the ids the feed draws from
    norm_eps: float
    rows: int
    seq: int
    feed_batches: int

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

    @property
    def n_expert_layers(self) -> int:
        return len(self.layer_types) - self.num_dense_layers

    @property
    def conv_matmul_params(self) -> int:
        return 4 * self.hidden_size * self.hidden_size           # d x 3d in, d x d out

    @property
    def attn_matmul_params(self) -> int:
        d, dh = self.hidden_size, self.head_dim
        return 2 * d * self.num_attention_heads * dh + 2 * d * self.num_key_value_heads * dh

    @property
    def expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    @property
    def n_params(self) -> int:
        d = self.hidden_size
        mixers = sum(self.conv_matmul_params + self.conv_L_cache * d if kind == "conv"
                     else self.attn_matmul_params + 2 * self.head_dim      # q and k norms
                     for kind in self.layer_types)
        dense = self.num_dense_layers * 3 * d * self.intermediate_size
        expert_layers = self.n_expert_layers * (
            d * self.router_experts + self.experts_held * self.expert_params)
        norms = (2 * len(self.layer_types) + 1) * d
        return mixers + dense + expert_layers + self.vocab_size * d + norms   # one embedding: tied

    @property
    def state_bytes(self) -> int:
        # 14 B a parameter (bfloat16, float32 master, mu, nu); the router's bias
        # and the load, 4 B an expert an expert layer each; the step count
        return 14 * self.n_params + 2 * 4 * self.n_expert_layers * self.router_experts + 4


def load_sizes(cfg: dict, rehearsal: bool = False) -> Sizes:
    batch = dict(cfg["batch"])
    deployment = cfg["deployment"]
    if rehearsal:
        cut = cfg["cpu_rehearsal_cut"]
        cfg = {**cfg, **cut}
        deployment = {**deployment, **cut["deployment"]}
        batch.update(cut["batch"])
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {"conv", "full_attention"}:
        raise ValueError("layer_types names the mixer of every layer, conv or full_attention")
    if cfg["conv_bias"] or not (cfg["norm_topk_prob"] and cfg["use_expert_bias"]
                                and cfg["tie_word_embeddings"]):
        raise ValueError("no convolution bias; renormalised top-k weights, a selection-only "
                         "expert bias and a tied head are what the model computes")
    return Sizes(
        name=cfg["name"], hidden_size=cfg["hidden_size"], layer_types=kinds,
        num_dense_layers=cfg["num_dense_layers"], conv_L_cache=cfg["conv_L_cache"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), attn_block=cfg["attn_block"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_experts=deployment["experts"], experts_held=cfg["num_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_token=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        vocab_size=cfg["vocab_size"], norm_eps=cfg["norm_eps"],
        rows=batch["rows"], seq=batch["seq"], feed_batches=batch["feed_batches"],
    )


def model_config(sizes: Sizes, dtype=None):
    """The product's config at these sizes."""
    from tpu_resiliency.models import lfm2_moe

    return lfm2_moe.Lfm2MoeConfig(
        hidden_size=sizes.hidden_size, layer_types=sizes.layer_types,
        num_dense_layers=sizes.num_dense_layers, conv_L_cache=sizes.conv_L_cache,
        num_attention_heads=sizes.num_attention_heads,
        num_key_value_heads=sizes.num_key_value_heads, head_dim=sizes.head_dim,
        rope_theta=sizes.rope_theta, intermediate_size=sizes.intermediate_size,
        moe_intermediate_size=sizes.moe_intermediate_size,
        num_experts=sizes.router_experts, experts_held=sizes.experts_held,
        expert_offset=sizes.expert_offset,
        num_experts_per_token=sizes.num_experts_per_token,
        routed_scaling_factor=sizes.routed_scaling_factor,
        vocab_rows=sizes.vocab_size, norm_eps=sizes.norm_eps,
        attn_block=sizes.attn_block, dtype=dtype)


def reference_dims(sizes: Sizes):
    from chipbench.reference import lfm2_moe

    return lfm2_moe.Dims(
        rope_theta=sizes.rope_theta, experts_per_token=sizes.num_experts_per_token,
        routed_scaling_factor=sizes.routed_scaling_factor,
        expert_offset=sizes.expert_offset, norm_eps=sizes.norm_eps,
        query_block=sizes.attn_block)


def draw_params(sizes: Sizes, key, dtype):
    """Every trained leaf in ``dtype``: the product's own draw
    (``lfm2_moe.init_params``: normal draws scaled by 1/sqrt(fan_in), the
    embedding's as the head it also is, 1/sqrt(hidden); every norm's scale
    1).  Traceable."""
    from tpu_resiliency.models import lfm2_moe

    return lfm2_moe.init_params(model_config(sizes, dtype), key)


def make_state(sizes: Sizes, params):
    """``(params, opt)`` as ``lfm2_moe.make_train_step`` takes them: every
    leaf as drawn with its float32 master copy, the router's bias and the load
    at 0.  Traceable."""
    from tpu_resiliency.models import lfm2_moe

    return params, lfm2_moe.init_opt_state(params, model_config(sizes))


def make_step(sizes: Sizes):
    """The product's fused forward + backward + AdamW + bias-update step."""
    import jax.numpy as jnp

    from tpu_resiliency.models import lfm2_moe

    return lfm2_moe.make_train_step(model_config(sizes, jnp.bfloat16))


def reference_first_steps(start, feed, sizes: Sizes, n_steps: int = 3,
                          precision=None):
    from chipbench.reference import lfm2_moe

    found = lfm2_moe.first_steps(start, feed, reference_dims(sizes), n_steps=n_steps,
                                 precision=precision or "reference")
    return {k: found[k] for k in ("loss", "grad_norm", "change_norm")}


def make_reference_step(sizes: Sizes):
    from chipbench.reference import lfm2_moe

    return lfm2_moe.make_step(reference_dims(sizes))


def forward_flops_per_token(sizes: Sizes) -> float:
    """Multiply-adds counted as two, one token through the forward pass, term
    by term.  Norms, activations, the softmax, the rotation, the gates'
    products, the convolution's three taps and the embedding gather are not
    counted.

    - convolution layer: its two projections (d x 3d in, d x d out).
    - attention layer: ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``; causal
      attention over the (T + 1) / 2 keys an average query sees (the causal
      half, not the blocks the program multiplies), scores and weighted values
      over 64 channels a query head.
    - dense feed-forward: 3 d intermediate.
    - expert layer: the router over all experts and the held routed experts
      at their expected ``experts_per_token x experts_held / router_experts``
      assignments a token (1.0 at the cell's sizes: the held experts' pairs);
      no shared expert.
    - the tied head over the held rows (d vocab), once: the lookup is a gather.
    """
    d, t = sizes.hidden_size, sizes.seq
    attn = (sizes.attn_matmul_params
            + sizes.num_attention_heads * 2 * sizes.head_dim * (t + 1) / 2)
    expected = sizes.num_experts_per_token * sizes.experts_held / sizes.router_experts
    expert_layer = d * sizes.router_experts + expected * sizes.expert_params
    macs = (sum(sizes.conv_matmul_params if kind == "conv" else attn
                for kind in sizes.layer_types)
            + sizes.num_dense_layers * 3 * d * sizes.intermediate_size
            + sizes.n_expert_layers * expert_layer
            + d * sizes.vocab_size)
    return 2.0 * macs


def train_flops_per_token(sizes: Sizes) -> float:
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3 * forward_flops_per_token(sizes)
