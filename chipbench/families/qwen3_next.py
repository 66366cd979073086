"""The ``qwen3_next`` family: one chip's share of a Qwen3-Next decoder
(``tpu_resiliency/models/qwen3_next.py``) at the sizes its ``config.json``
gives (``qwen3-next-80b-a3b-1chip``): Gated DeltaNet (a scalar-gate delta
rule, 16 key heads serving 32 value heads) and gated rotary attention (16
query heads over 2 key/value heads), both held whole, a routed expert layer
that holds ``num_experts`` of the deployment's experts and routes over all of
them with a softmax router, a sigmoid-gated shared expert, an untied head
over the held rows of the vocabulary.

The state: ``A_log`` and ``dt_bias`` are float32 themselves (12 bytes a
parameter: the leaf and two moments), every other trained leaf is bfloat16
with a float32 master copy and two moments (14 bytes), and the last step's
load (int32, one row a layer) rides in the optimizer state untouched by any
gradient; there is no router bias.  The plain reference is
``chipbench/reference/qwen3_next.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

# the state's shape is the second family's (moments, a master copy where a
# leaf is not float32 itself), so its two readers serve as they are
from chipbench.families.kimi_linear import first_moment, master  # noqa: F401

CONTROLS = ("bf16_everywhere",)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One configuration file, as the benchmark uses it."""

    name: str
    hidden_size: int
    layer_kinds: Tuple[str, ...]     # "gdn" or "attn", layer by layer
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_head_dim: int             # of keys and values alike
    linear_conv_kernel_dim: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
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
    def gdn_matmul_params(self) -> int:
        d, dh = self.hidden_size, self.linear_head_dim
        nk, nv = self.linear_num_key_heads, self.linear_num_value_heads
        return d * 2 * (nk + nv) * dh + d * 2 * nv + nv * dh * d   # qkvz, ba, out

    @property
    def gdn_params(self) -> int:
        dh, nk, nv = self.linear_head_dim, self.linear_num_key_heads, self.linear_num_value_heads
        return (self.gdn_matmul_params + (2 * nk + nv) * dh * self.linear_conv_kernel_dim
                + 2 * nv + dh)                       # the conv; A_log, dt_bias; the head norm

    @property
    def float32_only_params(self) -> int:
        """``A_log`` and ``dt_bias`` of every Gated DeltaNet layer."""
        return 2 * self.linear_num_value_heads * sum(k == "gdn" for k in self.layer_kinds)

    @property
    def attn_matmul_params(self) -> int:
        d, dh = self.hidden_size, self.head_dim
        nq, nkv = self.num_attention_heads, self.num_key_value_heads
        return d * 2 * nq * dh + 2 * d * nkv * dh + nq * dh * d    # q and gate, k, v, out

    @property
    def attn_params(self) -> int:
        return self.attn_matmul_params + 2 * self.head_dim         # q and k norms

    @property
    def expert_layer_params(self) -> int:
        d = self.hidden_size
        return (d * self.router_experts + self.experts_held * 3 * d * self.moe_intermediate_size
                + 3 * d * self.shared_expert_intermediate_size + d)

    @property
    def n_params(self) -> int:
        d = self.hidden_size
        mixers = sum(self.gdn_params if kind == "gdn" else self.attn_params
                     for kind in self.layer_kinds)
        norms = (2 * len(self.layer_kinds) + 1) * d
        return (mixers + len(self.layer_kinds) * self.expert_layer_params
                + 2 * self.vocab_size * d + norms)

    @property
    def state_bytes(self) -> int:
        # 14 B a bfloat16 parameter (itself, float32 master, mu, nu), 12 B a
        # float32 one; the load, 4 B an expert a layer; the step counter's 4
        load = 4 * len(self.layer_kinds) * self.router_experts
        return (14 * (self.n_params - self.float32_only_params)
                + 12 * self.float32_only_params + load + 4)


def load_sizes(cfg: dict, rehearsal: bool = False) -> Sizes:
    batch = dict(cfg["batch"])
    deployment = cfg["deployment"]
    if rehearsal:
        cut = cfg["cpu_rehearsal_cut"]
        cfg = {**cfg, **cut}
        deployment = {**deployment, **cut["deployment"]}
        batch.update(cut["batch"])
    if cfg["linear_key_head_dim"] != cfg["linear_value_head_dim"]:
        raise ValueError("keys and values of a Gated DeltaNet head have one width here")
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every layer has the expert layer")
    kinds = tuple("attn" if (layer + 1) % cfg["full_attention_interval"] == 0 else "gdn"
                  for layer in range(cfg["num_hidden_layers"]))
    return Sizes(
        name=cfg["name"], hidden_size=cfg["hidden_size"], layer_kinds=kinds,
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_head_dim=cfg["linear_key_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        router_experts=deployment["experts"], experts_held=cfg["num_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_token=cfg["num_experts_per_tok"],
        vocab_size=cfg["vocab_size"], rms_norm_eps=cfg["rms_norm_eps"],
        rows=batch["rows"], seq=batch["seq"], feed_batches=batch["feed_batches"],
    )


def model_config(sizes: Sizes, dtype=None):
    """The product's config at these sizes."""
    from tpu_resiliency.models import qwen3_next

    return qwen3_next.Qwen3NextConfig(
        hidden_size=sizes.hidden_size, layer_kinds=sizes.layer_kinds,
        linear_num_key_heads=sizes.linear_num_key_heads,
        linear_num_value_heads=sizes.linear_num_value_heads,
        linear_head_dim=sizes.linear_head_dim,
        linear_conv_kernel_dim=sizes.linear_conv_kernel_dim,
        num_attention_heads=sizes.num_attention_heads,
        num_key_value_heads=sizes.num_key_value_heads, head_dim=sizes.head_dim,
        rotary_dim=sizes.rotary_dim, rope_theta=sizes.rope_theta,
        moe_intermediate_size=sizes.moe_intermediate_size,
        shared_expert_intermediate_size=sizes.shared_expert_intermediate_size,
        num_experts=sizes.router_experts, experts_held=sizes.experts_held,
        expert_offset=sizes.expert_offset,
        num_experts_per_token=sizes.num_experts_per_token,
        vocab_rows=sizes.vocab_size, rms_norm_eps=sizes.rms_norm_eps, dtype=dtype)


def reference_dims(sizes: Sizes):
    from chipbench.reference import qwen3_next

    return qwen3_next.Dims(
        rotary_dim=sizes.rotary_dim, rope_theta=sizes.rope_theta,
        experts_per_token=sizes.num_experts_per_token,
        expert_offset=sizes.expert_offset, rms_norm_eps=sizes.rms_norm_eps)


def draw_params(sizes: Sizes, key, dtype):
    """Every trained leaf in ``dtype``, ``A_log`` and ``dt_bias`` too (the
    state widens them): the product's own draw (``qwen3_next.init_params``:
    normal draws scaled by 1/sqrt(fan_in), 0.02 for the embedding, the ``1 +
    w`` norm scales 0, the head norm's 1, the decay parameters as the
    family's public code draws them).  Traceable."""
    import jax

    from tpu_resiliency.models import qwen3_next

    drawn = qwen3_next.init_params(model_config(sizes, dtype), key)
    return jax.tree_util.tree_map(lambda p: p.astype(dtype), drawn)


FLOAT32_LEAVES = ("A_log", "dt_bias")


def make_state(sizes: Sizes, params):
    """``(params, opt)`` as ``qwen3_next.make_train_step`` takes them: the
    decay parameters widened to float32, every other leaf as drawn with its
    float32 master copy.  Traceable."""
    import jax
    import jax.numpy as jnp

    from tpu_resiliency.models import qwen3_next

    def widen(path, p):
        name = getattr(path[-1], "key", None)
        return p.astype(jnp.float32) if name in FLOAT32_LEAVES else p

    params = jax.tree_util.tree_map_with_path(widen, params)
    return params, qwen3_next.init_opt_state(params, model_config(sizes))


def make_step(sizes: Sizes):
    """The product's fused forward + backward + AdamW step."""
    import jax.numpy as jnp

    from tpu_resiliency.models import qwen3_next

    return qwen3_next.make_train_step(model_config(sizes, jnp.bfloat16))


def reference_first_steps(start, feed, sizes: Sizes, n_steps: int = 3,
                          precision=None):
    from chipbench.reference import qwen3_next

    found = qwen3_next.first_steps(start, feed, reference_dims(sizes), n_steps=n_steps,
                                   precision=precision or "reference")
    return {k: found[k] for k in ("loss", "grad_norm", "change_norm")}


def make_reference_step(sizes: Sizes):
    from chipbench.reference import qwen3_next

    return qwen3_next.make_step(reference_dims(sizes))


def forward_flops_per_token(sizes: Sizes) -> float:
    """Multiply-adds counted as two, one token through the forward pass, term
    by term.  Norms, activations, softmaxes, the convolution's 4 taps, the
    rotation, the gates' and decays' arithmetic and the embedding gather are
    not counted.

    - Gated DeltaNet layer: the projections (``in_proj_qkvz``, ``in_proj_ba``,
      ``out_proj``); and the delta rule by its recurrence, three dk x dv
      products a value head a token (k^T S, the rank-one update, q^T S) —
      what the chunked form adds to that (the key products, the triangular
      solve) is not counted.
    - gated attention layer: ``q_proj`` with its gate, ``k_proj``, ``v_proj``,
      ``o_proj``; causal attention over the (T+1)/2 keys an average query
      sees, scores and weighted values over 256 channels a query head.
    - expert layer: the router over all experts, the shared expert and its
      gate, and the held routed experts at their expected ``experts_per_token
      x experts_held / router_experts`` assignments a token (0.3125 at the
      cell's sizes).
    - the untied head over the held rows.
    """
    d, t = sizes.hidden_size, sizes.seq
    dh, nv = sizes.linear_head_dim, sizes.linear_num_value_heads
    gdn = sizes.gdn_matmul_params + nv * 3 * dh * dh
    attn = (sizes.attn_matmul_params
            + sizes.num_attention_heads * 2 * sizes.head_dim * (t + 1) / 2)
    expected = sizes.num_experts_per_token * sizes.experts_held / sizes.router_experts
    expert_layer = (d * sizes.router_experts + d
                    + 3 * d * sizes.shared_expert_intermediate_size
                    + expected * 3 * d * sizes.moe_intermediate_size)
    macs = (sum(gdn if kind == "gdn" else attn for kind in sizes.layer_kinds)
            + len(sizes.layer_kinds) * expert_layer + d * sizes.vocab_size)
    return 2.0 * macs


def train_flops_per_token(sizes: Sizes) -> float:
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3 * forward_flops_per_token(sizes)
