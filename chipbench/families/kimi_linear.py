"""The ``kimi_linear`` family: one chip's share of a Kimi-Linear decoder
(``tpu_resiliency/models/kimi_linear.py``) at the sizes its ``config.json``
gives (``kimi-linear-48b-a3b-1chip``): Kimi Delta Attention and latent
attention without positions over the heads held here, a routed expert layer
that holds ``num_experts`` of the deployment's experts and routes over all of
them, an untied head over the held rows of the vocabulary.

The state is no longer "every leaf bfloat16 with three float32 shadows":
``A_log`` and ``dt_bias`` are float32 themselves (12 bytes a parameter: the
leaf and two moments), every other trained leaf is bfloat16 with a float32
master copy and two moments (14 bytes), and the router's bias (float32) and
the last step's load (int32), one row an expert layer, ride in the optimizer
state untouched by any gradient.  The plain reference is
``chipbench/reference/kimi_linear.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

CONTROLS = ("bf16_everywhere",)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One configuration file, as the benchmark uses it."""

    name: str
    hidden_size: int
    layer_kinds: Tuple[str, ...]     # "kda" or "mla", layer by layer
    first_k_dense: int
    intermediate_size: int
    moe_intermediate_size: int
    router_experts: int              # the deployment's experts: the router's outputs
    experts_held: int
    expert_offset: int
    num_experts_per_token: int
    routed_scaling_factor: float
    heads_held: int
    kda_head_dim: int
    short_conv_kernel_size: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    vocab_size: int                  # the held rows: the ids the feed draws from
    rms_norm_eps: float
    rows: int
    seq: int
    feed_batches: int

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

    @property
    def n_expert_layers(self) -> int:
        return len(self.layer_kinds) - self.first_k_dense

    @property
    def kda_params(self) -> int:
        d, dh, heads = self.hidden_size, self.kda_head_dim, self.heads_held
        inner = heads * dh
        gate = d * dh + dh * inner
        return (3 * d * inner + 3 * inner * self.short_conv_kernel_size  # q, k, v and convolutions
                + gate + (gate + inner)                                   # decay gate; output gate and its bias
                + d * heads + heads + inner + dh + inner * d)            # beta, A_log, dt_bias, head norm, out

    @property
    def float32_only_params(self) -> int:
        """``A_log`` and ``dt_bias`` of every KDA layer."""
        per_layer = self.heads_held + self.heads_held * self.kda_head_dim
        return per_layer * sum(kind == "kda" for kind in self.layer_kinds)

    @property
    def mla_params(self) -> int:
        d, heads, rank = self.hidden_size, self.heads_held, self.kv_lora_rank
        nope, rope, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        return (d * heads * (nope + rope) + d * (rank + rope) + rank
                + rank * heads * (nope + dv) + heads * dv * d)

    @property
    def n_params(self) -> int:
        d = self.hidden_size
        attention = sum(self.kda_params if kind == "kda" else self.mla_params
                        for kind in self.layer_kinds)
        dense = self.first_k_dense * 3 * d * self.intermediate_size
        expert_layer = (d * self.router_experts
                        + (self.experts_held + 1) * 3 * d * self.moe_intermediate_size)
        norms = (2 * len(self.layer_kinds) + 1) * d
        return (attention + dense + self.n_expert_layers * expert_layer
                + 2 * self.vocab_size * d + norms)

    @property
    def state_bytes(self) -> int:
        # 14 B a bfloat16 parameter (itself, float32 master, mu, nu), 12 B a
        # float32 one; the router's bias and the load, 4 B an expert an expert
        # layer each; the step counter's 4 bytes
        buffers = 2 * 4 * self.n_expert_layers * self.router_experts
        return (14 * (self.n_params - self.float32_only_params)
                + 12 * self.float32_only_params + buffers + 4)


def load_sizes(cfg: dict, rehearsal: bool = False) -> Sizes:
    batch = dict(cfg["batch"])
    deployment = cfg["deployment"]
    linear = cfg["linear_attn_config"]
    if rehearsal:
        cut = cfg["cpu_rehearsal_cut"]
        cfg = {**cfg, **cut}
        linear = {**linear, **cut["linear_attn_config"]}
        deployment = {**deployment, **cut["deployment"]}
        batch.update(cut["batch"])
    if cfg["num_attention_heads"] != linear["num_heads"]:
        raise ValueError("both attention kinds hold the same share of the heads")
    kinds = tuple("mla" if layer in linear["full_attn_layers"] else "kda"
                  for layer in range(1, cfg["num_hidden_layers"] + 1))
    return Sizes(
        name=cfg["name"], hidden_size=cfg["hidden_size"], layer_kinds=kinds,
        first_k_dense=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_experts=deployment["experts"], experts_held=cfg["num_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_token=cfg["num_experts_per_token"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        heads_held=cfg["num_attention_heads"], kda_head_dim=linear["head_dim"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        vocab_size=cfg["vocab_size"], rms_norm_eps=cfg["rms_norm_eps"],
        rows=batch["rows"], seq=batch["seq"], feed_batches=batch["feed_batches"],
    )


def model_config(sizes: Sizes, dtype=None):
    """The product's config at these sizes."""
    from tpu_resiliency.models import kimi_linear

    return kimi_linear.KimiLinearConfig(
        hidden_size=sizes.hidden_size, layer_kinds=sizes.layer_kinds,
        first_k_dense=sizes.first_k_dense, intermediate_size=sizes.intermediate_size,
        moe_intermediate_size=sizes.moe_intermediate_size,
        num_experts=sizes.router_experts, experts_held=sizes.experts_held,
        expert_offset=sizes.expert_offset,
        num_experts_per_token=sizes.num_experts_per_token,
        routed_scaling_factor=sizes.routed_scaling_factor,
        heads_held=sizes.heads_held, kda_head_dim=sizes.kda_head_dim,
        short_conv_kernel_size=sizes.short_conv_kernel_size,
        kv_lora_rank=sizes.kv_lora_rank, qk_nope_head_dim=sizes.qk_nope_head_dim,
        qk_rope_head_dim=sizes.qk_rope_head_dim, v_head_dim=sizes.v_head_dim,
        vocab_rows=sizes.vocab_size, rms_norm_eps=sizes.rms_norm_eps, dtype=dtype)


def reference_dims(sizes: Sizes):
    from chipbench.reference import kimi_linear

    return kimi_linear.Dims(
        heads=sizes.heads_held, qk_nope_head_dim=sizes.qk_nope_head_dim,
        qk_rope_head_dim=sizes.qk_rope_head_dim, v_head_dim=sizes.v_head_dim,
        experts_per_token=sizes.num_experts_per_token,
        routed_scaling_factor=sizes.routed_scaling_factor,
        expert_offset=sizes.expert_offset, rms_norm_eps=sizes.rms_norm_eps)


def draw_params(sizes: Sizes, key, dtype):
    """Every trained leaf in ``dtype``, ``A_log`` and ``dt_bias`` too (the
    state widens them): the product's own draw
    (``kimi_linear.init_params``: normal draws scaled by 1/sqrt(fan_in), 0.02
    for the embedding, norm scales 1, the decay parameters as the family's
    public code draws them).  Traceable."""
    import jax

    from tpu_resiliency.models import kimi_linear

    drawn = kimi_linear.init_params(model_config(sizes, dtype), key)
    return jax.tree_util.tree_map(lambda p: p.astype(dtype), drawn)


FLOAT32_LEAVES = ("A_log", "dt_bias")


def make_state(sizes: Sizes, params):
    """``(params, opt)`` as ``kimi_linear.make_train_step`` takes them: the
    decay parameters widened to float32, every other leaf as drawn with its
    float32 master copy.  Traceable."""
    import jax
    import jax.numpy as jnp

    from tpu_resiliency.models import kimi_linear

    def widen(path, p):
        name = getattr(path[-1], "key", None)
        return p.astype(jnp.float32) if name in FLOAT32_LEAVES else p

    params = jax.tree_util.tree_map_with_path(widen, params)
    return params, kimi_linear.init_opt_state(params, model_config(sizes))


def first_moment(state):
    return state[1]["mu"]


def master(state):
    """The master copy where a leaf has one, the leaf itself where it is
    float32."""
    import jax

    params, opt = state
    return jax.tree_util.tree_map(
        lambda p, m: p if m is None else m, params, opt["master"],
        is_leaf=lambda x: x is None)


def make_step(sizes: Sizes):
    """The product's fused forward + backward + AdamW + bias-update step."""
    import jax.numpy as jnp

    from tpu_resiliency.models import kimi_linear

    return kimi_linear.make_train_step(model_config(sizes, jnp.bfloat16))


def reference_first_steps(start, feed, sizes: Sizes, n_steps: int = 3,
                          precision=None):
    from chipbench.reference import kimi_linear

    found = kimi_linear.first_steps(start, feed, reference_dims(sizes), n_steps=n_steps,
                                    precision=precision or "reference")
    return {k: found[k] for k in ("loss", "grad_norm", "change_norm")}


def make_reference_step(sizes: Sizes):
    from chipbench.reference import kimi_linear

    return kimi_linear.make_step(reference_dims(sizes))


def forward_flops_per_token(sizes: Sizes) -> float:
    """Multiply-adds counted as two, one token through the forward pass, term
    by term.  Norms, activations, softmax, the convolutions' 4 taps, the
    decays and the embedding gather are not counted.

    - KDA layer: the projections q, k, v (3 d inner), the two low-rank gates
      (2 (d dh + dh inner)), beta (d heads), the output projection (inner d);
      and the delta rule by its recurrence, three dk x dv products a head a
      token (k^T S, the rank-one update, q^T S) — what the chunked form adds
      to that (pair scores, the triangular solve) is not counted.
    - MLA layer: q (d heads 192), the latent (d 576), its expansion (512 heads
      256), the output projection (heads 128 d); causal attention over the
      (T+1)/2 keys an average query sees, scores over 192 channels and
      weighted values over 128.
    - dense feed-forward: 3 d intermediate.
    - expert layer: the router over all experts (d router_experts), the
      shared expert (3 d moe_intermediate), and the held routed experts at
      their expected ``experts_per_token x experts_held / router_experts``
      assignments a token (0.25 at the cell's sizes).
    - the untied head over the held rows (d vocab).
    """
    d, t = sizes.hidden_size, sizes.seq
    heads, dh = sizes.heads_held, sizes.kda_head_dim
    inner = heads * dh
    kda = (3 * d * inner + 2 * (d * dh + dh * inner) + d * heads + inner * d
           + heads * 3 * dh * dh)
    nope, rope, dv = sizes.qk_nope_head_dim, sizes.qk_rope_head_dim, sizes.v_head_dim
    mla = (d * heads * (nope + rope) + d * (sizes.kv_lora_rank + rope)
           + sizes.kv_lora_rank * heads * (nope + dv) + heads * dv * d
           + heads * (nope + rope + dv) * (t + 1) / 2)
    expected = sizes.num_experts_per_token * sizes.experts_held / sizes.router_experts
    expert_layer = (d * sizes.router_experts
                    + (1 + expected) * 3 * d * sizes.moe_intermediate_size)
    macs = (sum(kda if kind == "kda" else mla for kind in sizes.layer_kinds)
            + sizes.first_k_dense * 3 * d * sizes.intermediate_size
            + sizes.n_expert_layers * expert_layer
            + d * sizes.vocab_size)
    return 2.0 * macs


def train_flops_per_token(sizes: Sizes) -> float:
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3 * forward_flops_per_token(sizes)

