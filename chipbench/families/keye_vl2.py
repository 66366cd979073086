"""The ``keye_vl2`` family: one chip's share of the language model of a
Keye-VL-2.0 decoder (``tpu_resiliency/models/keye_vl2.py``) at the sizes its
``config.json`` gives (``keye-vl-2.0-30b-a3b-1chip``): every layer
grouped-query attention (32 query heads over 4 key/value heads, held whole)
over the ``topk`` keys a lightning indexer selects (16 index heads of 64
against one index key head), and a routed expert layer that holds
``num_experts`` of the deployment's experts and routes over all of them with a
softmax router; no shared expert; an untied head over the held rows of the
vocabulary.  The step minimises the next-token loss plus the indexer's KL
loss, on disjoint gradient paths.

The state: every trained leaf is bfloat16 with a float32 master copy and two
moments (14 bytes a parameter); three buffers ride in the optimizer state
untouched by any gradient (the last step's load, int32, and by layer the
indexer's KL and the selected keys' share of the dense attention mass,
float32).  The plain reference is ``chipbench/reference/keye_vl2.py``.
"""

from __future__ import annotations

import dataclasses

# the state's shape is the second family's (moments, a master copy where a
# leaf is not float32 itself), so its two readers serve as they are
from chipbench.families.kimi_linear import first_moment, master  # noqa: F401

CONTROLS = ("bf16_everywhere", "half_batch", "state_unchanged")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One configuration file, as the benchmark uses it."""

    name: str
    hidden_size: int
    num_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    indexer_num_heads: int
    indexer_head_dim: int
    index_topk: int
    attn_block: int                  # the source's q_chunk_size
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
    def indexer_matmul_params(self) -> int:
        ni, di = self.indexer_num_heads, self.indexer_head_dim
        return self.hidden_size * (ni * di + di + ni)            # q, the one key head, w

    @property
    def layer_params(self) -> int:
        d = self.hidden_size
        return (self.attn_matmul_params + 2 * self.head_dim      # q and k norms
                + self.indexer_matmul_params + 2 * self.indexer_head_dim   # LayerNorm
                + d * self.router_experts
                + self.experts_held * 3 * d * self.moe_intermediate_size + 2 * d)

    @property
    def n_params(self) -> int:
        d = self.hidden_size
        return self.num_layers * self.layer_params + 2 * self.vocab_size * d + d

    @property
    def state_bytes(self) -> int:
        # 14 B a parameter (bfloat16, float32 master, mu, nu); the load, 4 B an
        # expert a layer; KL and selected mass, 4 B a layer each; the step count
        return (14 * self.n_params + 4 * self.num_layers * self.router_experts
                + 8 * self.num_layers + 4)

    @property
    def selected_pairs_per_token(self) -> float:
        """(query, key) pairs the attention reads, a query in the mean: every
        causal key while a query sees no more than ``index_topk``, then that many."""
        t, k = self.seq, min(self.index_topk, self.seq)
        return (k * (k + 1) / 2 + (t - k) * k) / t


def load_sizes(cfg: dict, rehearsal: bool = False) -> Sizes:
    batch = dict(cfg["batch"])
    deployment = cfg["deployment"]
    if rehearsal:
        cut = cfg["cpu_rehearsal_cut"]
        cfg = {**cfg, **cut}
        deployment = {**deployment, **cut["deployment"]}
        batch.update(cut["batch"])
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every layer has the expert layer")
    if cfg["num_experts"] != cfg["num_local_experts"]:
        raise ValueError("num_experts and num_local_experts both count the experts held here")
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or sa["q_chunk_size"] != sa["kv_chunk_size"]:
        raise ValueError("one index key head, and one tile for queries and keys")
    return Sizes(
        name=cfg["name"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        indexer_num_heads=sa["indexer_num_heads"], indexer_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], attn_block=sa["q_chunk_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_experts=deployment["experts"], experts_held=cfg["num_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_token=cfg["num_experts_per_tok"],
        vocab_size=cfg["vocab_size"], rms_norm_eps=cfg["rms_norm_eps"],
        rows=batch["rows"], seq=batch["seq"], feed_batches=batch["feed_batches"],
    )


def model_config(sizes: Sizes, dtype=None):
    """The product's config at these sizes."""
    from tpu_resiliency.models import keye_vl2

    return keye_vl2.KeyeVL2Config(
        hidden_size=sizes.hidden_size, num_layers=sizes.num_layers,
        num_attention_heads=sizes.num_attention_heads,
        num_key_value_heads=sizes.num_key_value_heads, head_dim=sizes.head_dim,
        rope_theta=sizes.rope_theta, indexer_num_heads=sizes.indexer_num_heads,
        indexer_head_dim=sizes.indexer_head_dim, index_topk=sizes.index_topk,
        moe_intermediate_size=sizes.moe_intermediate_size,
        num_experts=sizes.router_experts, experts_held=sizes.experts_held,
        expert_offset=sizes.expert_offset,
        num_experts_per_token=sizes.num_experts_per_token,
        vocab_rows=sizes.vocab_size, rms_norm_eps=sizes.rms_norm_eps,
        attn_block=sizes.attn_block, dtype=dtype)


def reference_dims(sizes: Sizes):
    from chipbench.reference import keye_vl2

    return keye_vl2.Dims(
        rope_theta=sizes.rope_theta, index_topk=sizes.index_topk,
        experts_per_token=sizes.num_experts_per_token,
        expert_offset=sizes.expert_offset, rms_norm_eps=sizes.rms_norm_eps,
        query_block=sizes.attn_block)


def draw_params(sizes: Sizes, key, dtype):
    """Every trained leaf in ``dtype``: the product's own draw
    (``keye_vl2.init_params``: normal draws scaled by 1/sqrt(fan_in), 1 for
    the embedding, every norm's scale 1, the LayerNorm's bias 0).  Traceable."""
    from tpu_resiliency.models import keye_vl2

    return keye_vl2.init_params(model_config(sizes, dtype), key)


def make_state(sizes: Sizes, params):
    """``(params, opt)`` as ``keye_vl2.make_train_step`` takes them: every
    leaf as drawn with its float32 master copy, the three buffers at 0.
    Traceable."""
    from tpu_resiliency.models import keye_vl2

    return params, keye_vl2.init_opt_state(params, model_config(sizes))


def make_step(sizes: Sizes):
    """The product's fused forward + backward (two losses) + AdamW step."""
    import jax.numpy as jnp

    from tpu_resiliency.models import keye_vl2

    return keye_vl2.make_train_step(model_config(sizes, jnp.bfloat16))


def reference_first_steps(start, feed, sizes: Sizes, n_steps: int = 3,
                          precision=None):
    from chipbench.reference import keye_vl2

    found = keye_vl2.first_steps(start, feed, reference_dims(sizes), n_steps=n_steps,
                                 precision=precision or "reference")
    return {k: found[k] for k in ("loss", "grad_norm", "change_norm")}


def make_reference_step(sizes: Sizes):
    from chipbench.reference import keye_vl2

    return keye_vl2.make_step(reference_dims(sizes))


def forward_flops_per_token(sizes: Sizes) -> float:
    """Multiply-adds counted as two, one token through the forward pass, term
    by term.  Norms, activations, softmaxes, the rotation, the selection (no
    multiply in it), the KL and the embedding gather are not counted.

    - attention: ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``; scores and
      weighted values over 128 channels a query head for the SELECTED pairs
      (every causal key while a query sees no more than ``topk``, then
      ``topk``: 1536.4 a query in the mean at 4096 tokens, where the dense
      causal blocks the program multiplies hold 2304).
    - indexer: its three projections, and the index scores over 64 channels an
      index head for every causal pair, (T + 1) / 2 a query in the mean: the
      indexer scores every key to choose among them.
    - expert layer: the router over all experts and the held routed experts
      at their expected ``experts_per_token x experts_held / router_experts``
      assignments a token (0.5 at the cell's sizes); no shared expert.
    - the untied head over the held rows.
    """
    d, t = sizes.hidden_size, sizes.seq
    attn = (sizes.attn_matmul_params + sizes.num_attention_heads * 2 * sizes.head_dim
            * sizes.selected_pairs_per_token)
    indexer = (sizes.indexer_matmul_params
               + sizes.indexer_num_heads * sizes.indexer_head_dim * (t + 1) / 2)
    expected = sizes.num_experts_per_token * sizes.experts_held / sizes.router_experts
    expert_layer = d * sizes.router_experts + expected * 3 * d * sizes.moe_intermediate_size
    macs = sizes.num_layers * (attn + indexer + expert_layer) + d * sizes.vocab_size
    return 2.0 * macs


def train_flops_per_token(sizes: Sizes) -> float:
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3 * forward_flops_per_token(sizes)
