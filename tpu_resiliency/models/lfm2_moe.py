"""LFM2-MoE-style decoder, one chip's share of it (pure JAX).

The fifth reference workload beside ``transformer.py``, ``kimi_linear.py``,
``qwen3_next.py`` and ``keye_vl2.py``, and like them NOT part of the
resiliency capability surface: it exists so that the wrapper, the tripwire,
the straggler detector and the checkpoint paths meet the largest state of the
five in the fewest arrays (49 trained leaves), a step whose most common mixer
is memory-bound elementwise work and no matmul, and one leaf, the embedding,
that two gradient paths reach.

The layers, after LiquidAI/LFM2-8B-A1B (equations, and every departure from
the published model: ``lfm2_moe_reference.py``):

- **Double-gated short convolution** (``conv.mix``): one projection split
  into B, C and x; a causal depthwise convolution of ``conv_L_cache`` taps over
  ``B * x``; ``C *`` its output; one projection out.  No activation anywhere
  in it.  The gates' products and the convolution are float32 arithmetic.
- **Grouped-query attention** (``attn``): 32 query heads of 64 over 8
  key/value heads, an RMSNorm with a scale over every q and k head, the whole
  head rotated (``keye_vl2._rotate``), in blocks of ``attn_block`` queries
  against the keys up to the block's end
  (``qwen3_next.causal_attention_in_blocks``).
- **The routed expert layer** of ``kimi_linear.py`` (``held_experts`` and
  ``route``, by import): sigmoid scores of all 32 experts, the top 4 of score
  + bias, weights of the unbiased scores renormalised (1e-6 added to the
  denominator, as the family's public code has it); no shared expert.  The
  bias picks and does not weigh; no gradient touches it, and after every step
  it moves towards the experts this step's tokens chose less often.
- A dense SwiGLU in the layers before ``num_dense_layers``, RMSNorm (``w x /
  rms``), and a head TIED to the embedding over the held rows of the
  vocabulary: ``embed`` gets the lookup's scatter-add and the head's matmul
  through one bfloat16 leaf with one float32 master copy.

The layer kinds have different leaves, so the layers are not one ``lax.scan``
over stacked leaves: the step is unrolled, every layer under a
``jax.checkpoint`` that keeps the results of the layer's matmuls and computes
what is elementwise again in the backward pass.

bfloat16 parameters and matmuls; float32 master copy and moments, the gates'
products and the convolution, router scores, norm statistics, the q and k
norms, the rotation, softmaxes and the loss.  The router's bias and the last
step's load ride in the optimizer state and no gradient touches them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

from ..telemetry import gauge
from .adamw import adamw_tree
from .keye_vl2 import _rotate
from .kimi_linear import (_rmsnorm, _swiglu, held_experts, init_opt_state, moved_bias,
                          next_token_loss, route)
from .kimi_linear import routing_stats as held_load_stats
from .qwen3_next import causal_attention_in_blocks

__all__ = ["Lfm2MoeConfig", "init_params", "forward", "loss_fn", "init_opt_state",
           "make_train_step", "routing_stats"]

_HELD_SHARE_MIN = gauge(
    "tpurx_model_held_share_min",
    "least over expert layers of the held experts' share of the last step's assignments")

ROUTE_EPS = 1e-6  # the public code's, on the renormalised weights' denominator
# One size of pair buffer, all tokens x 4 pairs.  A quarter of the experts are
# held, so the expected load (a quarter of the pairs) is ``kimi_linear``'s
# middle size itself: under its ladder every layer flipped between that size and
# the whole by a few dozen pairs, the step's length moved by 5 ms a layer from
# step to step (120-141 ms, PERF.md section 6, PR 42), and with it the beat
# period the tripwire's budget is calibrated from.  The whole buffer is four
# times the expected load, not thirty-two.
PAIR_BUFFER_LADDER = (1,)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    hidden_size: int = 2048
    # mixer of every layer held here; the first ``num_dense_layers`` have a
    # dense feed-forward, the others the expert layer
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv", "conv", "conv")
    num_dense_layers: int = 1
    conv_L_cache: int = 3           # taps of the short convolution
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32           # the router's outputs: every expert of the deployment
    experts_held: int = 8           # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    num_experts_per_token: int = 4
    routed_scaling_factor: float = 1.0
    vocab_rows: int = 16384         # rows of the embedding, which is the head too, held here
    norm_eps: float = 1e-5
    bias_update_rate: float = 1e-3
    attn_block: int = 512           # queries a block of scores
    dtype: Any = None               # resolved to bf16 on TPU, f32 elsewhere

    def resolved_dtype(self):
        import jax
        import jax.numpy as jnp

        if self.dtype is not None:
            return self.dtype
        return jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32

    @property
    def n_expert_layers(self) -> int:
        return len(self.layer_types) - self.num_dense_layers


def init_params(cfg: Lfm2MoeConfig, key=None) -> Dict:
    """Normal draws scaled by 1/sqrt(fan_in), every norm's scale 1.  The
    embedding is the head too and is drawn as one: 1/sqrt(hidden), so the
    logits start at unit spread (at scale 1 they would start at
    sqrt(hidden)).  A convolution of three taps does not pool a sequence, so
    a token's hidden state stays its own at this scale and the routers
    spread their tokens (PERF.md section 2, the sixth configuration)."""
    import jax
    import jax.numpy as jnp

    key = key if key is not None else jax.random.PRNGKey(0)
    dt = cfg.resolved_dtype()
    d, dh = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    keys = iter(jax.random.split(key, 1 + 8 * len(cfg.layer_types)))

    def dense(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * scale).astype(dt)

    ones = lambda n: jnp.ones((n,), dtype=dt)  # noqa: E731

    def swiglu(width, experts=()):
        return {"w_gate": dense((*experts, d, width)), "w_up": dense((*experts, d, width)),
                "w_down": dense((*experts, width, d))}

    def conv():
        return {"in_proj": dense((d, 3 * d)),        # the columns: B, then C, then x
                "conv": dense((cfg.conv_L_cache, d)),
                "out_proj": dense((d, d))}

    def attn():
        return {"q_proj": dense((d, nq * dh)), "k_proj": dense((d, nkv * dh)),
                "v_proj": dense((d, nkv * dh)), "o_proj": dense((nq * dh, d)),
                "q_norm": ones(dh), "k_norm": ones(dh)}

    params: Dict[str, Any] = {
        "embed": dense((cfg.vocab_rows, d), scale=1.0 / math.sqrt(d)),
        "layers": [],
        "embedding_norm": ones(d),
    }
    for i, kind in enumerate(cfg.layer_types):
        name, mixer = {"conv": ("conv", conv), "full_attention": ("attn", attn)}[kind]
        layer = {"operator_norm": ones(d), "ffn_norm": ones(d), name: mixer()}
        if i < cfg.num_dense_layers:
            layer["ffn"] = swiglu(cfg.intermediate_size)
        else:
            layer["moe"] = {"router": dense((d, cfg.num_experts)),
                            "experts": swiglu(cfg.moe_intermediate_size, (cfg.experts_held,))}
        params["layers"].append(layer)
    return params


# -- the double-gated short convolution ---------------------------------------------

def causal_conv(z, w):
    """The causal depthwise convolution of ``z`` [rows, T, channels] float32
    with ``w`` [taps, channels]: ``out_t = sum_j w[j] z_{t - (taps - 1) + j}``,
    zeros before position 0 (the last tap meets the current token); no
    activation."""
    import jax.numpy as jnp

    taps, t = w.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(padded[:, j:j + t] * w[j] for j in range(taps))


def conv_block(u, p):
    """``W_out (C * conv(B * x))`` for ``[B, C, x] = split_3(W_in u)``: the
    projection comes out of its bfloat16 matmul in float32, and both products
    and the convolution are float32 arithmetic."""
    import jax.numpy as jnp

    bcx = jnp.matmul(u, p["in_proj"], preferred_element_type=jnp.float32)
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return (c * causal_conv(b * x, p["conv"])).astype(u.dtype) @ p["out_proj"]


# -- grouped-query attention ----------------------------------------------------------

def attn_block(u, p, cfg: Lfm2MoeConfig):
    """Causal grouped-query attention over ``u``: q and k normed a head in
    float32, then the whole head rotated; query head j reads key/value head
    ``j // group``."""
    import jax.numpy as jnp

    rows, t, _ = u.shape
    dh, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    f32 = jnp.float32

    def head_norm(z, w):
        return _rotate(_rmsnorm(z.astype(f32), w.astype(f32), cfg.norm_eps), cfg).astype(u.dtype)

    q = head_norm((u @ p["q_proj"]).reshape(rows, t, nq, dh), p["q_norm"])
    k = head_norm((u @ p["k_proj"]).reshape(rows, t, nkv, dh), p["k_norm"])
    v = (u @ p["v_proj"]).reshape(rows, t, nkv, dh)
    out = causal_attention_in_blocks(
        q.reshape(rows, t, nkv, nq // nkv, dh), k, v, cfg.attn_block)
    return out.reshape(rows, t, nq * dh) @ p["o_proj"]


# -- the expert layer ---------------------------------------------------------------

def moe_block(x, p, bias, cfg: Lfm2MoeConfig):
    """``(output, load)`` of the expert layer for ``x`` [tokens, d]: the held
    experts' part and nothing else (no shared expert)."""
    import jax

    with jax.named_scope("moe.route"):
        chosen, weights, load = route(x, p["router"], bias, cfg, eps=ROUTE_EPS)
    with jax.named_scope("moe.experts"):
        return held_experts(x, chosen, weights, p["experts"], cfg, PAIR_BUFFER_LADDER), load


# -- the model ----------------------------------------------------------------------

def _layer(h, p, bias, cfg: Lfm2MoeConfig):
    """``(h after the layer, load or None)``: ``h + mixer(norm(h))``, then
    ``+ ffn(norm(.))``; which mixer and which feed-forward, the leaves say."""
    import jax

    rows, t, _ = h.shape
    u = _rmsnorm(h, p["operator_norm"], cfg.norm_eps)
    if "conv" in p:
        with jax.named_scope("conv.mix"):
            h = h + conv_block(u, p["conv"])
    else:
        with jax.named_scope("attn"):
            h = h + attn_block(u, p["attn"], cfg)
    x = _rmsnorm(h, p["ffn_norm"], cfg.norm_eps)
    if "moe" not in p:
        with jax.named_scope("ffn.dense"):
            return h + _swiglu(x, p["ffn"]), None
    out, load = moe_block(x.reshape(rows * t, -1), p["moe"], bias, cfg)
    return h + out.reshape(h.shape), load


def forward(params: Dict, tokens, cfg: Lfm2MoeConfig, router_bias=None):
    """``(logits [rows, T, vocab_rows], load [expert layers, num_experts])``.
    ``router_bias`` [expert layers, num_experts] float32; zeros if None.
    Of every layer the backward pass finds its input and its matmuls' results
    kept and computes the rest again (the attention's scores and the held
    experts' buffers by their own rules); the head is the embedding,
    ``logits = n @ embed^T``."""
    import jax
    import jax.numpy as jnp

    if router_bias is None:
        router_bias = jnp.zeros((cfg.n_expert_layers, cfg.num_experts), jnp.float32)
    h = params["embed"][tokens]
    loads = []
    for p in params["layers"]:
        bias = router_bias[len(loads)] if "moe" in p else None
        h, load = jax.checkpoint(
            lambda h, p, bias: _layer(h, p, bias, cfg),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)(h, p, bias)
        if load is not None:
            loads.append(load)
    with jax.named_scope("head.loss"):
        logits = _rmsnorm(h, params["embedding_norm"], cfg.norm_eps) @ params["embed"].T
    return logits, jnp.stack(loads)


def loss_fn(params, batch, cfg: Lfm2MoeConfig, router_bias=None):
    """``(mean next-token cross-entropy over the held rows of the
    vocabulary, load)``."""
    import jax

    tokens, targets = batch
    logits, load = forward(params, tokens, cfg, router_bias)
    with jax.named_scope("head.loss"):
        return next_token_loss(logits, targets), load


def make_train_step(cfg: Lfm2MoeConfig, lr: float = 1e-3):
    """Fused jitted train step: ``(params, opt, (tokens, targets)) -> (params,
    opt, loss)``: forward, backward, AdamW on every trained leaf, then the
    router's bias moved by ``bias_update_rate`` towards the experts that this
    step's tokens chose less often than the mean (``kimi_linear.moved_bias``).
    The state is ``kimi_linear.init_opt_state``'s: moments, master copies, the
    step count, the router's bias and the last step's load."""
    import jax

    def step(params, opt, batch):
        (loss, load), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, opt["router_bias"]), has_aux=True)(params)
        params, new_opt = adamw_tree(params, grads, opt, lr)
        new_opt.update(
            router_bias=moved_bias(opt["router_bias"], load, cfg.bias_update_rate),
            router_load=load)
        return params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))


def routing_stats(opt, cfg: Lfm2MoeConfig) -> Dict[str, float]:
    """``kimi_linear.routing_stats`` (the held experts' largest and mean load
    and their share of all assignments, with its two gauges) and, beside
    them, ``held_share_min``: the least, over expert layers, of the held
    experts' share of the layer's assignments, as the gauge
    ``tpurx_model_held_share_min``.  Where it falls far under ``experts_held /
    num_experts`` a layer's routers send their tokens elsewhere and a held
    expert's gradient hangs on a handful of tokens."""
    import numpy as np

    stats = held_load_stats(opt, cfg)
    load = np.asarray(opt["router_load"])
    mine = load[:, cfg.expert_offset:cfg.expert_offset + cfg.experts_held].sum(axis=-1)
    stats["held_share_min"] = float(np.min(mine / np.maximum(load.sum(axis=-1), 1)))
    _HELD_SHARE_MIN.set(stats["held_share_min"])
    return stats
