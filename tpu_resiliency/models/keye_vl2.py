"""Keye-VL-2.0-style decoder, one chip's share of its language model (pure JAX).

The fourth reference workload beside ``transformer.py``, ``kimi_linear.py``
and ``qwen3_next.py``, and like them NOT part of the resiliency capability
surface: it exists so that the wrapper, the tripwire, the straggler detector
and the checkpoint paths meet a step whose attention reads a set of keys the
step itself chooses, a step with two losses on disjoint gradient paths (a
``stop_gradient`` in the wrong place still returns a plausible loss), and a
state of 356 arrays with float32 buffers no gradient touches.

Every layer, after Kwai-Keye/Keye-VL-2.0-30B-A3B (equations, and every
departure from the published model: ``keye_vl2_reference.py``):

- **Grouped-query attention over the keys an indexer selects** (``attn.*``):
  32 query heads of 128 over 4 key/value heads, RMSNorm on every q and k head,
  the whole head rotated.  A lightning indexer (16 index heads of 64 against
  one shared index key head, a LayerNorm on the key, its own rotation) scores
  every causal (query, key) pair from the DETACHED layer input, ``I[t, s] =
  sum_j w[t, j] relu(qI[t, j] . kI[s])``, and each query attends to the
  ``index_topk`` keys of largest score (all of them while it sees no more;
  ties to the lower position).  Computed in blocks of ``attn_block`` queries
  against the keys up to the block's end, the selection as one more mask on
  the block's dense scores (``select_keys``: the k-th largest score by
  bisection on the scores' bits, no sort and no gather), each block recomputed
  in the backward pass, so that no [32, T, T] float32 matrix is ever whole.
- **The indexer's loss** (``index.loss``): per query the KL divergence from
  the main attention's distribution over the selected keys (summed over the
  heads, renormalised, detached) to the softmax of the index scores over the
  same keys.  The step minimises ``L_LM + L_I``: the indexer reads a detached
  input and the selection is discrete, so the indexer's five leaves a layer
  get the gradient of ``L_I`` alone and every other leaf that of ``L_LM``
  alone.
- **The routed expert layer** of ``kimi_linear.py`` (``held_experts``, by
  import) under ``qwen3_next.route`` (softmax over all experts, the top 8,
  renormalised), no shared expert.
- RMSNorm computed in float32, an untied head over the held rows of the
  vocabulary.

bfloat16 parameters and matmuls; float32 master copy and moments, index
scores, both softmaxes, the KL, router scores, norms, the rotation and the
loss.  Three buffers ride in the optimizer state and no gradient touches them:
the last step's load, the indexer's KL by layer and, by layer, the share of
the dense attention mass that fell on the selected keys (a by-product of
masking dense blocks).
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Dict

from ..telemetry import gauge
from .adamw import adamw_tree, init_adamw_state
from .kimi_linear import held_experts, next_token_loss, routing_stats
from .qwen3_next import _rope, route

__all__ = ["KeyeVL2Config", "init_params", "forward", "loss_fn", "init_opt_state",
           "make_train_step", "routing_stats", "selection_stats"]

_INDEX_KL = gauge(
    "tpurx_model_index_kl", "the indexer's KL loss in the last step, mean over layers")
_SELECTED_MASS_MIN = gauge(
    "tpurx_model_selected_mass_min",
    "least over layers of the dense attention mass on the selected keys in the last step")


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    hidden_size: int = 2048
    num_layers: int = 5             # all alike: sparse attention, then the expert layer
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64      # one index key head of this width, shared by the index heads
    index_topk: int = 2048          # keys a query attends to
    moe_intermediate_size: int = 768
    num_experts: int = 128          # the router's outputs: every expert of the deployment
    experts_held: int = 8           # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    num_experts_per_token: int = 8
    vocab_rows: int = 18992         # rows of the embedding and columns of the head held here
    rms_norm_eps: float = 1e-6
    attn_block: int = 512           # queries a block of scores
    dtype: Any = None               # resolved to bf16 on TPU, f32 elsewhere

    def resolved_dtype(self):
        import jax
        import jax.numpy as jnp

        if self.dtype is not None:
            return self.dtype
        return jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32


def init_params(cfg: KeyeVL2Config, key=None) -> Dict:
    """Normal draws scaled by 1/sqrt(fan_in), the embedding's by 1; every
    norm's scale 1, the index key's LayerNorm bias 0.

    The embedding is drawn at the residual stream's scale and not at 0.02: a
    token's hidden state is then its own at every layer.  At 0.02 the
    attention's pooled values, much the same for every query, outweigh the
    token's row from the second layer on, the router sends nearly every token
    to the same few experts, and a held expert's gradient hangs on a handful
    of tokens (PERF.md section 2, the fifth configuration's readings)."""
    import jax
    import jax.numpy as jnp

    key = key if key is not None else jax.random.PRNGKey(0)
    dt = cfg.resolved_dtype()
    d, dh = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    ni, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    keys = iter(jax.random.split(key, 2 + 11 * cfg.num_layers))

    def dense(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * scale).astype(dt)

    ones = lambda n: jnp.ones((n,), dtype=dt)  # noqa: E731
    width = cfg.moe_intermediate_size
    params: Dict[str, Any] = {
        "embed": dense((cfg.vocab_rows, d), scale=1.0),
        "layers": [],
        "final_norm": ones(d),
        "head": dense((d, cfg.vocab_rows)),
    }
    for _ in range(cfg.num_layers):
        params["layers"].append({
            "attn_norm": ones(d), "ffn_norm": ones(d),
            "attn": {
                "q_proj": dense((d, nq * dh)), "k_proj": dense((d, nkv * dh)),
                "v_proj": dense((d, nkv * dh)), "o_proj": dense((nq * dh, d)),
                "q_norm": ones(dh), "k_norm": ones(dh),
            },
            "indexer": {
                "q_proj": dense((d, ni * di)), "k_proj": dense((d, di)),
                "w_proj": dense((d, ni)),
                "k_norm": ones(di), "k_norm_bias": jnp.zeros((di,), dtype=dt),
            },
            "moe": {
                "router": dense((d, cfg.num_experts)),
                "experts": {"w_gate": dense((cfg.experts_held, d, width)),
                            "w_up": dense((cfg.experts_held, d, width)),
                            "w_down": dense((cfg.experts_held, width, d))},
            },
        })
    return params


def _norm(x, w, eps):
    """``x rsqrt(mean(x^2) + eps) w`` in float32, returned in ``x``'s dtype."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


_Rotary = collections.namedtuple("_Rotary", "rotary_dim rope_theta")


def _rotate(x, cfg: KeyeVL2Config):
    """``qwen3_next._rope`` over the whole width of ``x`` [rows, T, heads, width]."""
    return _rope(x, _Rotary(x.shape[-1], cfg.rope_theta))


# -- the indexer and the selection ---------------------------------------------------

def indexer_inputs(u, p, cfg: KeyeVL2Config):
    """``(qI [rows, T, index heads, 64], kI [rows, T, 64], w [rows, T, index
    heads] float32)`` of the layer input ``u``, which the caller has detached:
    the index key through a LayerNorm with scale and bias, both sides rotated,
    the head weights scaled by ``heads^-1/2 x width^-1/2``."""
    import jax
    import jax.numpy as jnp

    rows, t, _ = u.shape
    ni, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    f32 = jnp.float32
    q = _rotate((u @ p["q_proj"]).reshape(rows, t, ni, di), cfg)
    k = jnp.matmul(u, p["k_proj"], preferred_element_type=f32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True) + cfg.rms_norm_eps)
    k = k * p["k_norm"].astype(f32) + p["k_norm_bias"].astype(f32)
    k = _rotate(k.astype(u.dtype)[:, :, None, :], cfg)[:, :, 0]
    w = jnp.matmul(u, p["w_proj"], preferred_element_type=f32) / math.sqrt(ni * di)
    return q, k, w


def index_scores(q, k, w):
    """``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` in float32 for ``q``
    [rows, Q, heads, width], ``k`` [rows, S, width], ``w`` [rows, Q, heads];
    an exact zero is +0, so that equal scores are equal bits."""
    import jax
    import jax.numpy as jnp

    dots = jnp.einsum("rqjd,rsd->rjqs", q, k, preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(dots) * jnp.moveaxis(w, -1, 1)[..., None], axis=1)
    return jnp.where(scores == 0, 0.0, scores)


def select_keys(scores, seen, k: int):
    """bool like ``scores`` [..., Q, S]: per query the ``k`` positions of
    largest score among those it sees (``seen``), every one it sees while
    those are no more than ``k``; of equal scores the lower position first —
    the set ``jax.lax.top_k`` returns, as a mask and without a sort.

    The k-th largest score is found bit by bit: float32 bits, flipped so that
    they order as the numbers do, and 32 counts of the elements at or above a
    candidate.  What is above it is taken, and of what equals it the first
    ``k - (number above)`` by position."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    ordered = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    ordered = jnp.where(seen, ordered, jnp.uint32(0))
    count = lambda hit: jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)  # noqa: E731

    def one_bit(i, kth):
        candidate = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(ordered >= candidate) >= k, candidate, kth)

    kth = jax.lax.fori_loop(0, 32, one_bit, jnp.zeros(ordered.shape[:-1] + (1,), jnp.uint32))
    above, equal = ordered > kth, ordered == kth
    first = jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= k - count(above)
    return (above | (equal & first)) & seen


# -- sparse attention, with the indexer's loss -----------------------------------------

def sparse_attention_in_blocks(q, k, v, q_idx, k_idx, w_idx, cfg: KeyeVL2Config):
    """``(out [rows, T, kv heads, group, width], KL [rows, T], mass [rows, kv
    heads, group, T])`` for ``q`` [rows, T, kv heads, group, width], ``k, v``
    [rows, T, kv heads, width] and the indexer's ``q_idx, k_idx, w_idx``:
    ``cfg.attn_block`` queries at a time against the keys up to the block's
    last query.  A block's index scores pick its chosen set; the attention is
    the softmax over that set; the KL is from the heads' mean distribution
    over the set (detached) to the softmax of the index scores over it; the
    mass is the set's share of the softmax over every key the query sees.
    Scores and softmaxes in float32.  Each block is recomputed in the backward
    pass but for its chosen set, which is kept meanwhile (a byte a pair)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    t, dh = q.shape[1], q.shape[-1]
    stop = jax.lax.stop_gradient

    def one_block(q_blk, k_seen, v_seen, qi_blk, ki_seen, wi_blk):
        n_q, n_s = q_blk.shape[1], k_seen.shape[1]
        seen = ((n_s - n_q + jnp.arange(n_q))[:, None] >= jnp.arange(n_s)[None, :])
        with jax.named_scope("attn.index"):
            index = index_scores(qi_blk, ki_seen, wi_blk)
        # else every query of the block sees no more keys than it may choose
        selects = n_s > cfg.index_topk
        with jax.named_scope("attn.select"):
            chosen = (checkpoint_name(select_keys(stop(index), seen, cfg.index_topk), "attn.chosen")
                      if selects else jnp.broadcast_to(seen, index.shape))
        with jax.named_scope("attn.sparse"):
            scores = jnp.einsum("rqkgd,rskd->rkgqs", q_blk, k_seen,
                                preferred_element_type=jnp.float32) / math.sqrt(dh)
            picked = jnp.where(chosen[:, None, None], scores, -1e9)
            norm = jax.nn.logsumexp(picked, axis=-1, keepdims=True)
            probs = jnp.exp(picked - norm)
            out = jnp.einsum("rkgqs,rskd->rqkgd", probs.astype(v_seen.dtype), v_seen)
            if selects:
                dense = jax.nn.logsumexp(jnp.where(seen, stop(scores), -1e9), axis=-1)
                mass = jnp.exp(stop(norm)[..., 0] - dense)
            else:
                mass = jnp.ones(scores.shape[:-1], jnp.float32)
        with jax.named_scope("index.loss"):
            target = stop(jnp.mean(probs, axis=(1, 2)))
            log_q = jax.nn.log_softmax(jnp.where(chosen, index, -1e9), axis=-1)
            live = chosen & (target > 0)
            kl = jnp.sum(jnp.where(
                live, target * (jnp.log(jnp.where(live, target, 1.0)) - log_q), 0.0), axis=-1)
        return out, kl, mass

    one_block = jax.checkpoint(
        one_block, policy=jax.checkpoint_policies.save_only_these_names("attn.chosen"))
    out, kl, mass = zip(*(
        one_block(q[:, lo:hi], k[:, :hi], v[:, :hi], q_idx[:, lo:hi], k_idx[:, :hi],
                  w_idx[:, lo:hi])
        for lo, hi in ((lo, min(lo + cfg.attn_block, t)) for lo in range(0, t, cfg.attn_block))))
    return (checkpoint_name(jnp.concatenate(out, axis=1), "attn.blocks"),
            jnp.concatenate(kl, axis=1), jnp.concatenate(mass, axis=-1))


def attn_block(u, p, p_idx, cfg: KeyeVL2Config):
    """``(output [rows, T, d], KL, mass)`` of the attention over ``u`` =
    norm1(x): the indexer reads ``u`` detached."""
    import jax

    rows, t, _ = u.shape
    dh, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    q = (u @ p["q_proj"]).reshape(rows, t, nq, dh)
    k = (u @ p["k_proj"]).reshape(rows, t, nkv, dh)
    v = (u @ p["v_proj"]).reshape(rows, t, nkv, dh)
    q = _rotate(_norm(q, p["q_norm"], cfg.rms_norm_eps), cfg)
    k = _rotate(_norm(k, p["k_norm"], cfg.rms_norm_eps), cfg)
    with jax.named_scope("attn.index"):
        q_idx, k_idx, w_idx = indexer_inputs(jax.lax.stop_gradient(u), p_idx, cfg)
    # query head j reads key/value head j // group
    out, kl, mass = sparse_attention_in_blocks(
        q.reshape(rows, t, nkv, nq // nkv, dh), k, v, q_idx, k_idx, w_idx, cfg)
    return out.reshape(rows, t, nq * dh) @ p["o_proj"], kl, mass


# -- the expert layer ---------------------------------------------------------------

def moe_block(x, p, cfg: KeyeVL2Config):
    """``(output, load)`` of the expert layer for ``x`` [tokens, d]: the held
    experts' part and nothing else (no shared expert)."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    with jax.named_scope("moe.route"):
        chosen, weights, load = route(x, p["router"], cfg)
    with jax.named_scope("moe.experts"):
        mine = held_experts(x, chosen, weights, p["experts"], cfg)
    return checkpoint_name(mine, "moe.experts"), load


# -- the model ----------------------------------------------------------------------

def forward(params: Dict, tokens, cfg: KeyeVL2Config):
    """``(logits [rows, T, vocab_rows], {"router_load" [layers, num_experts]
    int32, "index_kl" [layers], "selected_mass" [layers]})``: by layer the
    load, the indexer's KL (mean over positions) and the selected keys' share
    of the dense attention mass (mean over heads and positions).

    The layers are alike, so they are one ``lax.scan`` over their stacked
    leaves: one layer's program, not one a layer (unrolled, six layers of
    eight block shapes made an executable of 113 MB, PERF.md section 6, PR
    37).  In the backward pass a layer keeps its matmuls' results, its
    attention blocks' and its held experts' outputs and computes the
    elementwise rest again from the layer's input."""
    import jax
    import jax.numpy as jnp

    rows, t = tokens.shape

    def layer(h, p):
        u = _norm(h, p["attn_norm"], cfg.rms_norm_eps)
        out, kl, mass = attn_block(u, p["attn"], p["indexer"], cfg)
        h = h + out
        x = _norm(h, p["ffn_norm"], cfg.rms_norm_eps)
        out, load = moe_block(x.reshape(rows * t, -1), p["moe"], cfg)
        return h + out.reshape(h.shape), (load, jnp.mean(kl), jnp.mean(mass))

    keep = jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names("attn.blocks", "moe.experts"))
    stacked = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *params["layers"])
    h, (loads, kls, masses) = jax.lax.scan(
        jax.checkpoint(layer, policy=keep), params["embed"][tokens], stacked)
    with jax.named_scope("head.loss"):
        logits = _norm(h, params["final_norm"], cfg.rms_norm_eps) @ params["head"]
    return logits, {"router_load": loads, "index_kl": kls, "selected_mass": masses}


def loss_fn(params, batch, cfg: KeyeVL2Config):
    """``(L_LM + L_I, buffers)``: the mean next-token cross-entropy over the
    held rows of the vocabulary plus the indexer's KL, mean over layers;
    ``buffers`` as ``forward`` gives them, with ``"lm_loss"`` beside them."""
    import jax
    import jax.numpy as jnp

    tokens, targets = batch
    logits, buffers = forward(params, tokens, cfg)
    with jax.named_scope("head.loss"):
        lm = next_token_loss(logits, targets)
    return lm + jnp.mean(buffers["index_kl"]), {**buffers, "lm_loss": lm}


BUFFERS = ("router_load", "index_kl", "selected_mass")


def init_opt_state(params, cfg: KeyeVL2Config):
    """``adamw.init_adamw_state`` (moments, master copies, the step count)
    and the last step's buffers: load, KL and selected mass by layer."""
    import jax.numpy as jnp

    return {
        **init_adamw_state(params),
        "router_load": jnp.zeros((cfg.num_layers, cfg.num_experts), jnp.int32),
        "index_kl": jnp.zeros((cfg.num_layers,), jnp.float32),
        "selected_mass": jnp.zeros((cfg.num_layers,), jnp.float32),
    }


def make_train_step(cfg: KeyeVL2Config, lr: float = 1e-3):
    """Fused jitted train step: ``(params, opt, (tokens, targets)) -> (params,
    opt, loss)``: forward, backward of ``L_LM + L_I``, AdamW on every trained
    leaf; the step's buffers replace the state's; the loss is the sum."""
    import jax

    def step(params, opt, batch):
        (loss, buffers), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        params, new_opt = adamw_tree(params, grads, opt, lr)
        new_opt.update({name: buffers[name] for name in BUFFERS})
        return params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))


def selection_stats(opt, cfg: KeyeVL2Config) -> Dict[str, float]:
    """The indexer's last step, from the state (no callback in the step): its
    KL (mean over layers) and the least, over layers, of the selected keys'
    share of the dense attention mass; sets the gauges ``tpurx_model_index_kl``
    and ``tpurx_model_selected_mass_min``."""
    import numpy as np

    stats = {"index_kl": float(np.mean(np.asarray(opt["index_kl"]))),
             "selected_mass_min": float(np.min(np.asarray(opt["selected_mass"])))}
    _INDEX_KL.set(stats["index_kl"])
    _SELECTED_MASS_MIN.set(stats["selected_mass_min"])
    return stats
