"""The ``mellum`` family: one chip's share of a Mellum decoder
(``tpu_resiliency/models/mellum.py``) at the sizes its ``config.json`` gives
(``mellum2-12b-a2.5b-1chip``): grouped-query attention (32 query heads over 4
key/value heads, held whole) in every layer, inside a window of
``sliding_window`` keys with the default rotary frequencies in a
``sliding_attention`` layer and causal with YaRN frequencies in a
``full_attention`` layer; after it a routed expert layer that holds
``num_experts`` of the deployment's experts and routes over all of them with a
softmax router; no shared expert; an untied head over the held rows of the
vocabulary.

The state: every trained leaf is bfloat16 with a float32 master copy and two
moments (14 bytes a parameter); the last step's load (int32, one row a layer)
rides in the optimizer state untouched by any gradient; there is no router
bias.  The plain reference is ``chipbench/reference/mellum.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

# the state's shape is the second family's (moments, a master copy where a
# leaf is not float32 itself), so its two readers serve as they are
from chipbench.families.kimi_linear import first_moment, master  # noqa: F401

CONTROLS = ("bf16_everywhere", "half_batch", "state_unchanged")
KINDS = ("sliding_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One configuration file, as the benchmark uses it."""

    name: str
    hidden_size: int
    layer_types: Tuple[str, ...]     # one of KINDS, layer by layer
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    rope_theta: float                # of both kinds' tables
    yarn_factor: float
    yarn_original_positions: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_attention_factor: float
    attn_block: int
    moe_intermediate_size: int
    router_experts: int              # the deployment's experts: the router's outputs
    experts_held: int
    expert_offset: int
    num_experts_per_token: int
    vocab_size: int                  # the held rows: the ids the feed draws from
    rms_norm_eps: float
    rows: int
    seq: int
    feed_batches: int

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

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
        layer = (self.attn_matmul_params + 2 * self.head_dim          # q and k norms
                 + d * self.router_experts + self.experts_held * self.expert_params
                 + 2 * d)                                             # the layer's two norms
        return len(self.layer_types) * layer + 2 * self.vocab_size * d + d

    @property
    def state_bytes(self) -> int:
        # 14 B a parameter (bfloat16, float32 master, mu, nu); the load, 4 B an
        # expert a layer; the step count
        return 14 * self.n_params + 4 * len(self.layer_types) * self.router_experts + 4

    def keys_seen(self, kind: str) -> float:
        """Keys an average query of a layer of this kind sees: the causal half,
        or, inside the window, ``min(t + 1, window)`` averaged over positions."""
        t, w = self.seq, min(self.sliding_window, self.seq)
        if kind == "full_attention":
            return (t + 1) / 2
        return (w * (w + 1) / 2 + (t - w) * w) / t


def load_sizes(cfg: dict, rehearsal: bool = False) -> Sizes:
    batch = dict(cfg["batch"])
    deployment = cfg["deployment"]
    if rehearsal:
        cut = cfg["cpu_rehearsal_cut"]
        cfg = {**cfg, **cut}
        deployment = {**deployment, **cut["deployment"]}
        batch.update(cut["batch"])
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types names the kind of every layer, one of {KINDS}")
    if set(cfg["mlp_layer_types"][:len(kinds)]) != {"sparse"}:
        raise ValueError("every layer kept has the expert layer (mlp_layer_types: sparse)")
    if (cfg["attention_bias"] or cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu"
            or not (cfg["norm_topk_prob"] and cfg["use_sliding_window"])):
        raise ValueError("no attention bias, an untied head, SiLU experts, renormalised top-k "
                         "weights and a sliding window are what the model computes")
    sliding, full = (cfg["rope_parameters"][kind] for kind in KINDS)
    if (sliding["rope_type"], full["rope_type"]) != ("default", "yarn") or (
            sliding["rope_theta"] != full["rope_theta"]):
        raise ValueError("default frequencies in the sliding layers, YaRN in the full "
                         "layers, one theta: what the model's two tables are")
    return Sizes(
        name=cfg["name"], hidden_size=cfg["hidden_size"], layer_types=kinds,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], rope_theta=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_positions=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]), yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        attn_block=cfg["attn_block"], moe_intermediate_size=cfg["moe_intermediate_size"],
        router_experts=deployment["experts"], experts_held=cfg["num_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_token=cfg["num_experts_per_tok"],
        vocab_size=cfg["vocab_size"], rms_norm_eps=cfg["rms_norm_eps"],
        rows=batch["rows"], seq=batch["seq"], feed_batches=batch["feed_batches"],
    )


def model_config(sizes: Sizes, dtype=None):
    """The product's config at these sizes."""
    from tpu_resiliency.models import mellum

    return mellum.MellumConfig(
        hidden_size=sizes.hidden_size, layer_types=sizes.layer_types,
        num_attention_heads=sizes.num_attention_heads,
        num_key_value_heads=sizes.num_key_value_heads, head_dim=sizes.head_dim,
        sliding_window=sizes.sliding_window, rope_theta=sizes.rope_theta,
        yarn_factor=sizes.yarn_factor,
        yarn_original_positions=sizes.yarn_original_positions,
        yarn_beta_fast=sizes.yarn_beta_fast, yarn_beta_slow=sizes.yarn_beta_slow,
        yarn_attention_factor=sizes.yarn_attention_factor,
        moe_intermediate_size=sizes.moe_intermediate_size,
        num_experts=sizes.router_experts, experts_held=sizes.experts_held,
        expert_offset=sizes.expert_offset,
        num_experts_per_token=sizes.num_experts_per_token,
        vocab_rows=sizes.vocab_size, rms_norm_eps=sizes.rms_norm_eps,
        attn_block=sizes.attn_block, dtype=dtype)


def reference_dims(sizes: Sizes):
    from chipbench.reference import mellum

    return mellum.Dims(
        layer_types=sizes.layer_types, window=sizes.sliding_window,
        rope_theta=sizes.rope_theta, yarn_factor=sizes.yarn_factor,
        yarn_original_positions=sizes.yarn_original_positions,
        yarn_beta_fast=sizes.yarn_beta_fast, yarn_beta_slow=sizes.yarn_beta_slow,
        yarn_attention_factor=sizes.yarn_attention_factor,
        experts_per_token=sizes.num_experts_per_token, expert_offset=sizes.expert_offset,
        rms_norm_eps=sizes.rms_norm_eps, query_block=sizes.attn_block)


def draw_params(sizes: Sizes, key, dtype):
    """Every trained leaf in ``dtype``: the product's own draw
    (``mellum.init_params``: normal draws scaled by 1/sqrt(fan_in), the
    embedding at scale 1, every norm's scale 1).  Traceable."""
    from tpu_resiliency.models import mellum

    return mellum.init_params(model_config(sizes, dtype), key)


def make_state(sizes: Sizes, params):
    """``(params, opt)`` as ``mellum.make_train_step`` takes them: every leaf
    as drawn with its float32 master copy, the load at 0.  Traceable."""
    from tpu_resiliency.models import mellum

    return params, mellum.init_opt_state(params, model_config(sizes))


def make_step(sizes: Sizes):
    """The product's fused forward + backward + AdamW step."""
    import jax.numpy as jnp

    from tpu_resiliency.models import mellum

    return mellum.make_train_step(model_config(sizes, jnp.bfloat16))


def reference_first_steps(start, feed, sizes: Sizes, n_steps: int = 3,
                          precision=None):
    from chipbench.reference import mellum

    found = mellum.first_steps(start, feed, reference_dims(sizes), n_steps=n_steps,
                               precision=precision or "reference")
    return {k: found[k] for k in ("loss", "grad_norm", "change_norm")}


def make_reference_step(sizes: Sizes):
    from chipbench.reference import mellum

    return mellum.make_step(reference_dims(sizes))


def forward_flops_per_token(sizes: Sizes) -> float:
    """Multiply-adds counted as two, one token through the forward pass, term
    by term.  Norms, activations, the softmax, the rotation and the embedding
    gather are not counted.

    - every layer: ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``; scores and
      weighted values over 128 channels a query head and the keys an average
      query SEES (``Sizes.keys_seen``: the causal half in a full layer,
      ``min(t + 1, window)`` in a sliding layer; not the blocks the program
      multiplies); the router over all experts and the held routed experts at
      their expected ``experts_per_token x experts_held / router_experts``
      assignments a token (1.0 at the cell's sizes); no shared expert.
    - the head over the held rows (d vocab).
    """
    d = sizes.hidden_size
    expected = sizes.num_experts_per_token * sizes.experts_held / sizes.router_experts
    shared_by_kinds = (sizes.attn_matmul_params + d * sizes.router_experts
                       + expected * sizes.expert_params)
    macs = sum(shared_by_kinds
               + sizes.num_attention_heads * 2 * sizes.head_dim * sizes.keys_seen(kind)
               for kind in sizes.layer_types) + d * sizes.vocab_size
    return 2.0 * macs


def train_flops_per_token(sizes: Sizes) -> float:
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3 * forward_flops_per_token(sizes)
