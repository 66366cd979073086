"""Qwen3-Next-style decoder, one chip's share of it (pure JAX).

The third reference workload beside ``transformer.py`` and ``kimi_linear.py``,
and like them NOT part of the resiliency capability surface: it exists so that
the wrapper, the tripwire, the straggler detector and the checkpoint paths
meet a step twice as long as the second model's at the same tokens (``PERF.md``
has the times), a state of 276 arrays whose largest are 100-155 MB, and a
second routed model on the shared expert layer.

The layers, after Qwen/Qwen3-Next-80B-A3B-Instruct (equations, and every
departure from the published model: ``qwen3_next_reference.py``):

- **Gated DeltaNet** (``gdn``): the gated delta rule with ONE decay a value
  head (a scalar, where Kimi Delta Attention has one a key channel), 16 key
  heads serving 32 value heads, q, k and v through one short convolution, a
  SiLU-gated head norm.  Computed in chunks (``gdn_chunked``): with a scalar
  decay the decay leaves the key products, so a chunk needs one 64 x 64 decay
  matrix a value head beside ``k k^T`` and ``q k^T`` a key head; inside a
  chunk the rule's WY form (one triangular solve), across chunks a
  ``lax.scan`` over the running state; differentiated as written.  The
  chunk's products are float32 at ``Precision.HIGHEST``.
- **Gated attention** (``attn``): 16 query heads of 256 over 2 key/value
  heads, ``1 + w`` norms on q and k, the first 64 channels rotated, a sigmoid
  output gate from the doubled ``q_proj``; the scores in blocks of
  ``attn_block`` queries against the keys up to the block's end (each block
  recomputed in the backward pass), so that no [16, T, T] float32 matrix is
  ever whole and the masked half of it is never computed.
- **The routed expert layer** of ``kimi_linear.py`` (``held_experts``, by
  import) under a softmax router without bias: the top 10 of 512,
  renormalised; a shared expert behind a sigmoid gate.
- ``1 + w`` RMSNorms computed in float32, an untied head over the held rows of
  the vocabulary.

What is recomputed in the backward pass: every Gated DeltaNet block
(``jax.checkpoint`` around the block), inside it the chunked scan of each of
``GDN_HEAD_GROUPS`` groups of key heads, which run one after the other (the
chunk arithmetic of all 32 value heads at once and its cotangents were 2.4 of
the step's 4.5 GB of temporaries at 4096 tokens), and every block of attention
scores; nothing else.

bfloat16 parameters and matmuls; float32 master copy and moments, router
scores, softmaxes, norms, gates and decays, the scan's state and the loss.
``A_log`` and ``dt_bias`` are float32 themselves (no master copy); the last
step's load rides in the optimizer state and no gradient touches it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

from .adamw import adamw_tree, init_adamw_state
from .kimi_linear import _conv_silu, _swiglu, held_experts, next_token_loss, routing_stats

__all__ = ["Qwen3NextConfig", "init_params", "forward", "loss_fn", "init_opt_state",
           "make_train_step", "routing_stats"]


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    hidden_size: int = 2048
    # mixer of every layer held here; every layer has the expert layer
    layer_kinds: Tuple[str, ...] = ("gdn", "gdn", "gdn", "attn")
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_head_dim: int = 128      # of keys and values alike
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64
    rope_theta: float = 1e7
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512          # the router's outputs: every expert of the deployment
    experts_held: int = 16          # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    num_experts_per_token: int = 10
    vocab_rows: int = 18992         # rows of the embedding and columns of the head held here
    rms_norm_eps: float = 1e-6
    gdn_chunk: int = 64
    attn_block: int = 512           # queries a block of scores
    dtype: Any = None               # resolved to bf16 on TPU, f32 elsewhere

    def resolved_dtype(self):
        import jax
        import jax.numpy as jnp

        if self.dtype is not None:
            return self.dtype
        return jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32


def init_params(cfg: Qwen3NextConfig, key=None) -> Dict:
    """Normal draws scaled by 1/sqrt(fan_in) (0.02 for the embedding); the
    ``1 + w`` norm scales 0 and the Gated DeltaNet head norm's 1; ``A_log`` =
    log of a uniform draw from (0, 16) and ``dt_bias`` the inverse softplus
    of a log-uniform draw from [1e-3, 1e-1), both float32 whatever the dtype."""
    import jax
    import jax.numpy as jnp

    key = key if key is not None else jax.random.PRNGKey(0)
    dt = cfg.resolved_dtype()
    d = cfg.hidden_size
    keys = iter(jax.random.split(key, 2 + 16 * len(cfg.layer_kinds)))

    def dense(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * scale).astype(dt)

    zeros = lambda n: jnp.zeros((n,), dtype=dt)  # noqa: E731

    def swiglu(width, experts=()):
        return {"w_gate": dense((*experts, d, width)), "w_up": dense((*experts, d, width)),
                "w_down": dense((*experts, width, d))}

    def gdn():
        dh, nk, nv = cfg.linear_head_dim, cfg.linear_num_key_heads, cfg.linear_num_value_heads
        rate = jax.random.uniform(next(keys), (nv,), jnp.float32, 1e-4, 16.0)
        step = jnp.exp(jax.random.uniform(
            next(keys), (nv,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "in_proj_qkvz": dense((d, 2 * (nk + nv) * dh)),
            "in_proj_ba": dense((d, 2 * nv)),
            "conv": dense((cfg.linear_conv_kernel_dim, (2 * nk + nv) * dh)),
            "A_log": jnp.log(rate), "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "head_norm": jnp.ones((dh,), dtype=dt),
            "out_proj": dense((nv * dh, d)),
        }

    def attn():
        dh, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        return {
            "q_proj": dense((d, 2 * nq * dh)),    # per head: the query, then its gate
            "k_proj": dense((d, nkv * dh)), "v_proj": dense((d, nkv * dh)),
            "q_norm": zeros(dh), "k_norm": zeros(dh),
            "o_proj": dense((nq * dh, d)),
        }

    params: Dict[str, Any] = {
        "embed": dense((cfg.vocab_rows, d), scale=0.02),
        "layers": [],
        "final_norm": zeros(d),
        "head": dense((d, cfg.vocab_rows)),
    }
    for kind in cfg.layer_kinds:
        params["layers"].append({
            "attn_norm": zeros(d), "ffn_norm": zeros(d),
            kind: {"gdn": gdn, "attn": attn}[kind](),
            "moe": {
                "router": dense((d, cfg.num_experts)),
                "experts": swiglu(cfg.moe_intermediate_size, (cfg.experts_held,)),
                "shared": swiglu(cfg.shared_expert_intermediate_size),
                "shared_gate": dense((d, 1)),
            },
        })
    return params


def _norm(x, w, eps):
    """``x rsqrt(mean(x^2) + eps) (1 + w)`` in float32 (a bfloat16 ``1 + w``
    would round the scale's step away), returned in ``x``'s dtype."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# -- Gated DeltaNet ---------------------------------------------------------------

def gdn_chunked(q, k, v, g, beta, chunk: int):
    """The gated delta rule with a scalar decay, ``S_t = (I - b_t k_t k_t^T)
    exp(g_t) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t``, in chunks of
    ``chunk`` tokens: ``q, k`` [rows, T, key heads, dk], ``v`` [rows, T, key
    heads, per, dv] and ``g, beta`` [rows, T, key heads, per], all float32;
    key head j serves its ``per`` value heads.

    With ``G_t`` the summed log-decay from the chunk's first token to t,
    ``D_ts = exp(G_t - G_s)`` for s <= t (one matrix a value head) and ``S``
    the state the chunk starts from, the rule's updates are ``S_t = exp(G_t)
    S + sum_{s<=t} D_ts k_s u_s^T`` for pseudo-values ``U = X_v - X_k S``,
    where ``(I + A) [X_v, X_k] = diag(b) [V, K exp G]`` and ``A = diag(b) (K
    K^T * D)`` below the diagonal: one unit-triangular solve a chunk, outside
    the scan, and ``K K^T``, ``Q K^T`` once a key head.  Every exponent is a
    difference of a later and an earlier sum, so none is positive.  A
    sequence that is no multiple of the chunk is padded with tokens that
    leave the state as it is."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    rows, t, nk, dk = q.shape
    per, dv = v.shape[-2:]
    pad = (-t) % chunk
    if pad:
        widen = lambda z: jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))  # noqa: E731
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    n = (t + pad) // chunk
    # the scan runs over the first axis: [chunks, rows, key heads, (per,) chunk, ...]
    key_side = lambda z: jnp.moveaxis(  # noqa: E731
        z.reshape(rows, n, chunk, nk, dk), (1, 3), (0, 2))
    value_side = lambda z: jnp.moveaxis(  # noqa: E731
        z.reshape(rows, n, chunk, nk, per, -1), (1, 3, 4), (0, 2, 3))
    q, k = key_side(q)[..., None, :, :], key_side(k)[..., None, :, :]
    v = value_side(v)
    g, beta = value_side(g), value_side(beta)                # [..., chunk, 1]
    decay = jnp.cumsum(g, axis=-2)                           # G_t, inclusive
    later = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))
    rel = jnp.exp(jnp.where(later, decay - jnp.swapaxes(decay, -1, -2), -jnp.inf))
    k_t = jnp.swapaxes(k, -1, -2)
    kk = jnp.matmul(k, k_t, precision=hi)                    # once a key head
    qk = jnp.matmul(q, k_t, precision=hi) * rel
    below = jnp.tril(jnp.ones((chunk, chunk), dtype=bool), -1)
    system = jnp.where(below, beta * kk * rel, 0.0) + jnp.eye(chunk, dtype=kk.dtype)
    solved = jax.lax.linalg.triangular_solve(
        system, beta * jnp.concatenate([v, k * jnp.exp(decay)], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    x_v, x_k = solved[..., :dv], solved[..., dv:]
    q_in = q * jnp.exp(decay)                                # q_t exp G_t
    last = decay[..., -1:, :]                                # G at the chunk's end
    k_out = k * jnp.exp(last - decay)                        # k_s exp(G_end - G_s)

    def one_chunk(state, xs):
        x_v, x_k, q_in, qk, k_out, last = xs
        u = x_v - jnp.matmul(x_k, state, precision=hi)
        o = jnp.matmul(q_in, state, precision=hi) + jnp.matmul(qk, u, precision=hi)
        state = jnp.exp(last) * state + jnp.matmul(
            jnp.swapaxes(k_out, -1, -2), u, precision=hi)
        return state, o

    start = jnp.zeros((rows, nk, per, dk, dv), jnp.float32)
    _, o = jax.lax.scan(one_chunk, start, (x_v, x_k, q_in, qk, k_out, last))
    o = jnp.moveaxis(o, (0, 2, 3), (1, 3, 4)).reshape(rows, n * chunk, nk, per, dv)
    return o[:, :t]


# step temporaries at 4096 tokens by rehearsal: 4.49 GB with one group, 2.24 with two, 2.07 with four
GDN_HEAD_GROUPS = 4


def gdn_in_groups(q, k, v, g, beta, chunk: int):
    """``gdn_chunked`` over ``GDN_HEAD_GROUPS`` groups of key heads, one after the
    other (``lax.map``), each recomputed in the backward pass: the heads share
    nothing, and the chunk arithmetic of all of them at once (a dozen [T,
    heads, 64 + 128 + 256] float32 arrays and as many cotangents) is what a
    Gated DeltaNet layer's backward pass would hold."""
    import jax
    import jax.numpy as jnp

    rows, t, nk = q.shape[:3]
    groups = math.gcd(GDN_HEAD_GROUPS, nk)
    by_group = lambda z: jnp.moveaxis(  # noqa: E731
        z.reshape(rows, t, groups, nk // groups, *z.shape[3:]), 2, 0)
    o = jax.lax.map(jax.checkpoint(lambda args: gdn_chunked(*args, chunk)),
                    tuple(map(by_group, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2).reshape(rows, t, nk, *o.shape[4:])


def gdn_block(x, p, cfg: Qwen3NextConfig):
    import jax
    import jax.numpy as jnp

    rows, t, _ = x.shape
    dh, nk, nv = cfg.linear_head_dim, cfg.linear_num_key_heads, cfg.linear_num_value_heads
    per = nv // nk
    f32 = jnp.float32

    def l2norm(z):
        return z * jax.lax.rsqrt(jnp.sum(jnp.square(z), axis=-1, keepdims=True) + 1e-6)

    # bfloat16 matmuls that come out in float32: what follows them (the
    # convolution, the normalisation, the gates and decays) is float32
    wide = lambda a, b: jnp.matmul(a, b, preferred_element_type=f32)  # noqa: E731
    # the columns lie key head by key head: q, k, its value heads' v, their z
    qkvz = wide(x, p["in_proj_qkvz"]).reshape(rows, t, nk, (2 + 2 * per) * dh)
    ba = wide(x, p["in_proj_ba"]).reshape(rows, t, nk, 2 * per)
    flat = lambda z: z.reshape(rows, t, -1)  # noqa: E731
    mixed = _conv_silu(jnp.concatenate([
        flat(qkvz[..., :dh]), flat(qkvz[..., dh:2 * dh]),
        flat(qkvz[..., 2 * dh:(2 + per) * dh])], axis=-1), p["conv"])
    z = qkvz[..., (2 + per) * dh:].reshape(rows, t, nk, per, dh)
    q = l2norm(mixed[..., :nk * dh].reshape(rows, t, nk, dh)) / math.sqrt(dh)
    k = l2norm(mixed[..., nk * dh:2 * nk * dh].reshape(rows, t, nk, dh))
    v = mixed[..., 2 * nk * dh:].reshape(rows, t, nk, per, dh)
    shape = (nk, per)
    g = -jnp.exp(p["A_log"].astype(f32)).reshape(shape) * jax.nn.softplus(
        ba[..., per:] + p["dt_bias"].astype(f32).reshape(shape))
    beta = jax.nn.sigmoid(ba[..., :per])
    o = gdn_in_groups(q, k, v, g, beta, cfg.gdn_chunk)
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * p["head_norm"].astype(f32) * jax.nn.silu(z)
    return o.astype(x.dtype).reshape(rows, t, nv * dh) @ p["out_proj"]


# -- gated attention ----------------------------------------------------------------

def _rope(x, cfg: Qwen3NextConfig):
    """The first ``rotary_dim`` channels of ``x`` [rows, T, heads, width]
    rotated as two halves, positions 0..T-1, in float32."""
    import jax.numpy as jnp

    t, rot = x.shape[1], cfg.rotary_dim
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    turn, keep = x32[..., :rot], x32[..., rot:]
    half = jnp.concatenate([-turn[..., rot // 2:], turn[..., :rot // 2]], axis=-1)
    return jnp.concatenate(
        [turn * jnp.cos(angle) + half * jnp.sin(angle), keep], axis=-1).astype(x.dtype)


def causal_attention_in_blocks(q, k, v, block: int):
    """``softmax(q k^T / sqrt(width)) v`` under the causal mask for ``q``
    [rows, T, kv heads, group, width] and ``k, v`` [rows, T, kv heads,
    width]: ``block`` queries at a time against the keys up to the block's
    last query (the keys after it are masked for every query of the block, so
    they are left out: half the products of the whole matrix), the scores and
    their softmax in float32, each block recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp

    t, dh = q.shape[1], q.shape[-1]

    @jax.checkpoint
    def one_block(q_blk, k_seen, v_seen):
        scores = jnp.einsum("rqkgd,rskd->rkgqs", q_blk, k_seen,
                            preferred_element_type=jnp.float32) / math.sqrt(dh)
        first = k_seen.shape[1] - q_blk.shape[1]          # the block's first position
        seen = ((first + jnp.arange(q_blk.shape[1]))[:, None]
                >= jnp.arange(k_seen.shape[1])[None, :])
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e9), axis=-1).astype(v_seen.dtype)
        return jnp.einsum("rkgqs,rskd->rqkgd", probs, v_seen)

    return jnp.concatenate([
        one_block(q[:, start:start + block], k[:, :start + block], v[:, :start + block])
        for start in range(0, t, block)], axis=1)


def attn_block(x, p, cfg: Qwen3NextConfig):
    import jax
    import jax.numpy as jnp

    rows, t, _ = x.shape
    dh, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    qg = (x @ p["q_proj"]).reshape(rows, t, nq, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = (x @ p["k_proj"]).reshape(rows, t, nkv, dh)
    v = (x @ p["v_proj"]).reshape(rows, t, nkv, dh)
    q = _rope(_norm(q, p["q_norm"], cfg.rms_norm_eps), cfg)
    k = _rope(_norm(k, p["k_norm"], cfg.rms_norm_eps), cfg)
    # query head j reads key/value head j // group
    out = causal_attention_in_blocks(
        q.reshape(rows, t, nkv, nq // nkv, dh), k, v, cfg.attn_block)
    out = out.reshape(rows, t, nq, dh) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return out.astype(x.dtype).reshape(rows, t, nq * dh) @ p["o_proj"]


# -- the expert layer ---------------------------------------------------------------

def route(x, router, cfg: Qwen3NextConfig):
    """``(chosen [tokens, 10], weights [tokens, 10] float32, load [experts]
    int32)``: softmax over all experts in float32, the top 10, their weights
    renormalised over the chosen."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(jnp.matmul(x, router, preferred_element_type=jnp.float32), axis=-1)
    picked, chosen = jax.lax.top_k(probs, cfg.num_experts_per_token)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    load = jnp.zeros((cfg.num_experts,), jnp.int32).at[chosen.reshape(-1)].add(1)
    return chosen, weights, load


def moe_block(x, p, cfg: Qwen3NextConfig):
    """``(output, load)`` of the expert layer for ``x`` [tokens, d]."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("moe.route"):
        chosen, weights, load = route(x, p["router"], cfg)
    with jax.named_scope("moe.experts"):
        mine = held_experts(x, chosen, weights, p["experts"], cfg)
    with jax.named_scope("moe.shared"):
        gate = jax.nn.sigmoid(jnp.matmul(x, p["shared_gate"],
                                         preferred_element_type=jnp.float32))
        shared = (gate * _swiglu(x, p["shared"])).astype(x.dtype)
    return shared + mine, load


# -- the model ----------------------------------------------------------------------

def forward(params: Dict, tokens, cfg: Qwen3NextConfig):
    """``(logits [rows, T, vocab_rows], load [layers, num_experts])``."""
    import jax
    import jax.numpy as jnp

    rows, t = tokens.shape
    h = params["embed"][tokens]
    loads = []
    for p in params["layers"]:
        x = _norm(h, p["attn_norm"], cfg.rms_norm_eps)
        if "gdn" in p:
            with jax.named_scope("gdn"):
                h = h + jax.checkpoint(lambda x, p: gdn_block(x, p, cfg))(x, p["gdn"])
        else:
            with jax.named_scope("attn"):
                h = h + attn_block(x, p["attn"], cfg)
        x = _norm(h, p["ffn_norm"], cfg.rms_norm_eps)
        out, load = moe_block(x.reshape(rows * t, -1), p["moe"], cfg)
        h = h + out.reshape(h.shape)
        loads.append(load)
    with jax.named_scope("head.loss"):
        logits = _norm(h, params["final_norm"], cfg.rms_norm_eps) @ params["head"]
    return logits, jnp.stack(loads)


def loss_fn(params, batch, cfg: Qwen3NextConfig):
    """``(mean next-token cross-entropy over the held rows of the
    vocabulary, load)``."""
    import jax

    tokens, targets = batch
    logits, load = forward(params, tokens, cfg)
    with jax.named_scope("head.loss"):
        return next_token_loss(logits, targets), load


def init_opt_state(params, cfg: Qwen3NextConfig):
    """``adamw.init_adamw_state`` (moments, master copies, the step count)
    and the last step's load."""
    import jax.numpy as jnp

    return {
        **init_adamw_state(params),
        "router_load": jnp.zeros((len(cfg.layer_kinds), cfg.num_experts), jnp.int32),
    }


def make_train_step(cfg: Qwen3NextConfig, lr: float = 1e-3):
    """Fused jitted train step: ``(params, opt, (tokens, targets)) -> (params,
    opt, loss)``: forward, backward, AdamW on every trained leaf; the step's
    load count replaces the state's."""
    import jax

    def step(params, opt, batch):
        (loss, load), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        params, new_opt = adamw_tree(params, grads, opt, lr)
        new_opt["router_load"] = load
        return params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))
