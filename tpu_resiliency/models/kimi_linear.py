"""Kimi-Linear-style decoder, one chip's share of it (pure JAX).

The second reference workload beside ``transformer.py``, and like it NOT part
of the resiliency capability surface: it exists so that the wrapper, the
tripwire, the straggler detector and the checkpoint paths meet a step that
is not a dense GPT-2 — a state tree whose leaves are not all "bfloat16 with
three float32 shadows", and a step whose device time depends on where a
router sends its tokens.

The layers, after moonshotai/Kimi-Linear-48B-A3B-Instruct (equations, and
every departure from the published model: ``kimi_linear_reference.py``):

- **Kimi Delta Attention** (``kda``): a gated delta rule with one decay a key
  channel, computed in chunks — inside a chunk the rule's WY form (one
  triangular solve), across chunks a ``lax.scan`` over the running state —
  and differentiated as written.
- **Latent attention without positions** (``mla``): keys and values expanded
  from one 512-wide latent, 64 more key channels shared by the heads, no
  rotation; no position enters the model anywhere.
- **A routed expert layer that holds a share of the experts**
  (``experts_held`` from ``expert_offset`` on): the router scores all
  ``num_experts``, picks 8 a token, and this chip computes the part of the
  result its own experts give.  No token is dropped and every shape is
  static; the (token, choice) pairs are sorted by held expert into the
  smallest of three buffers that holds them and ``jax.lax.ragged_dot``
  multiplies group by group, so the work follows the assignments that land
  here.  On one chip the layer runs without its exchange; what the absent
  experts would add is left out.
- SwiGLU feed-forwards, RMSNorm, an untied head over the held rows of the
  vocabulary.

bfloat16 parameters and matmuls; float32 master copy and moments, router
scores, softmaxes, norm statistics, decays, the scan's state and the loss.
``A_log`` and ``dt_bias`` are float32 themselves (no master copy); the
router's bias and the last step's load ride in the optimizer state and no
gradient touches them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

from ..telemetry import gauge
from .adamw import adamw_tree, init_adamw_state

_EXPERT_LOAD_MAX = gauge(
    "tpurx_model_expert_load_max", "largest load of a held expert in the last step")
_EXPERT_LOAD_MEAN = gauge(
    "tpurx_model_expert_load_mean", "mean load of the held experts in the last step")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    hidden_size: int = 2304
    # attention kind of every layer held here; the first ``first_k_dense``
    # have a dense feed-forward, the others the expert layer
    layer_kinds: Tuple[str, ...] = ("kda", "kda", "kda", "mla", "kda")
    first_k_dense: int = 1
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_experts: int = 256          # the router's outputs: every expert of the deployment
    experts_held: int = 8           # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    heads_held: int = 4             # of both attention kinds
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    vocab_rows: int = 20480         # rows of the embedding and columns of the head held here
    rms_norm_eps: float = 1e-5
    bias_update_rate: float = 1e-3
    kda_chunk: int = 64
    dtype: Any = None               # resolved to bf16 on TPU, f32 elsewhere

    def resolved_dtype(self):
        import jax
        import jax.numpy as jnp

        if self.dtype is not None:
            return self.dtype
        return jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32

    @property
    def n_expert_layers(self) -> int:
        return len(self.layer_kinds) - self.first_k_dense


def init_params(cfg: KimiLinearConfig, key=None) -> Dict:
    """Normal draws scaled by 1/sqrt(fan_in) (0.02 for the embedding), norm
    scales 1, gate bias 0; ``A_log`` = log of a uniform draw from [1, 16) and
    ``dt_bias`` the inverse softplus of a log-uniform draw from [1e-3, 1e-1),
    both float32 whatever the dtype."""
    import jax
    import jax.numpy as jnp

    key = key if key is not None else jax.random.PRNGKey(0)
    dt = cfg.resolved_dtype()
    d, heads = cfg.hidden_size, cfg.heads_held
    keys = iter(jax.random.split(key, 2 + 24 * len(cfg.layer_kinds)))

    def dense(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * scale).astype(dt)

    ones = lambda n: jnp.ones((n,), dtype=dt)  # noqa: E731

    def swiglu(width, experts=()):
        return {"w_gate": dense((*experts, d, width)), "w_up": dense((*experts, d, width)),
                "w_down": dense((*experts, width, d))}

    def kda():
        dh, inner, conv = cfg.kda_head_dim, heads * cfg.kda_head_dim, cfg.short_conv_kernel_size
        rate = jax.random.uniform(next(keys), (heads,), jnp.float32, 1.0, 16.0)
        step = jnp.exp(jax.random.uniform(
            next(keys), (inner,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "wq": dense((d, inner)), "wk": dense((d, inner)), "wv": dense((d, inner)),
            "conv_q": dense((conv, inner)), "conv_k": dense((conv, inner)),
            "conv_v": dense((conv, inner)),
            "wf1": dense((d, dh)), "wf2": dense((dh, inner)),
            "A_log": jnp.log(rate), "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "wb": dense((d, heads)),
            "wg1": dense((d, dh)), "wg2": dense((dh, inner)),
            "bg": jnp.zeros((inner,), dtype=dt), "head_norm": ones(dh),
            "wo": dense((inner, d)),
        }

    def mla():
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {
            "wq": dense((d, heads * qk)),
            "wkva": dense((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
            "kv_norm": ones(cfg.kv_lora_rank),
            "wkvb": dense((cfg.kv_lora_rank,
                           heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "wo": dense((heads * cfg.v_head_dim, d)),
        }

    params: Dict[str, Any] = {
        "embed": dense((cfg.vocab_rows, d), scale=0.02),
        "layers": [],
        "final_norm": ones(d),
        "head": dense((d, cfg.vocab_rows)),
    }
    for i, kind in enumerate(cfg.layer_kinds):
        layer = {"attn_norm": ones(d), "ffn_norm": ones(d),
                 kind: {"kda": kda, "mla": mla}[kind]()}
        if i < cfg.first_k_dense:
            layer["ffn"] = swiglu(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "router": dense((d, cfg.num_experts)),
                "experts": swiglu(cfg.moe_intermediate_size, (cfg.experts_held,)),
                "shared": swiglu(cfg.moe_intermediate_size),
            }
        params["layers"].append(layer)
    return params


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jnp.reciprocal(jnp.sqrt(var + eps)).astype(x.dtype)) * scale


def _swiglu(x, p):
    import jax

    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# -- Kimi Delta Attention -------------------------------------------------------

def kda_chunked(q, k, v, g, beta, chunk: int):
    """The gated delta rule ``S_t = (I - b_t k_t k_t^T) diag(exp g_t) S_{t-1}
    + b_t k_t v_t^T``, ``o_t = S_t^T q_t``, over ``q, k, g`` [rows, T, heads,
    dk], ``v`` [rows, T, heads, dv] and ``beta`` [rows, T, heads], all
    float32, in chunks of ``chunk`` tokens.

    With ``G_t`` the summed log-decay from the chunk's first token to t and
    ``S`` the state the chunk starts from, the rule's updates are ``S_t =
    diag(exp G_t) S + sum_{s<=t} diag(exp(G_t - G_s)) k_s u_s^T`` for
    pseudo-values ``U = X_v - X_k S``, where ``(I + A) [X_v, X_k] = diag(b)
    [V, K exp G]`` and ``A_ts = b_t sum_c k_tc k_sc exp(G_tc - G_sc)`` below the
    diagonal: one unit-triangular solve a chunk, outside the scan.  Every
    exponent is a difference of a later and an earlier sum, so none is
    positive.  A sequence that is no multiple of the chunk is padded with
    tokens that leave the state as it is."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    rows, t, heads, dk = q.shape
    pad = (-t) % chunk
    if pad:
        widen = lambda z: jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))  # noqa: E731
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    n = (t + pad) // chunk
    # [chunks, rows, heads, chunk, width]: the scan runs over the first axis
    by_chunk = lambda z: jnp.moveaxis(  # noqa: E731
        z.reshape(rows, n, chunk, heads, -1), (1, 3), (0, 2))
    q, k, v, g = map(by_chunk, (q, k, v, g))
    beta = by_chunk(beta)                                    # [..., chunk, 1]
    decay = jnp.cumsum(g, axis=-2)                           # G_t, inclusive

    @jax.checkpoint
    def pair_scores(q, k, decay):
        """``sum_c x_tc k_sc exp(G_tc - G_sc)`` for s <= t, x = k and x = q."""
        later = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))[..., None]
        rel = jnp.exp(jnp.where(
            later, decay[..., :, None, :] - decay[..., None, :, :], -jnp.inf))
        kk = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * rel, axis=-1)
        qk = jnp.sum(q[..., :, None, :] * k[..., None, :, :] * rel, axis=-1)
        return kk, qk

    kk, qk = pair_scores(q, k, decay)
    below = jnp.tril(jnp.ones((chunk, chunk), dtype=bool), -1)
    system = jnp.where(below, beta * kk, 0.0) + jnp.eye(chunk, dtype=kk.dtype)
    solved = jax.lax.linalg.triangular_solve(
        system, beta * jnp.concatenate([v, k * jnp.exp(decay)], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    x_v, x_k = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    q_in = q * jnp.exp(decay)                                # q_t exp G_t
    last = decay[..., -1:, :]                                # G at the chunk's end
    k_out = k * jnp.exp(last - decay)                        # k_s exp(G_end - G_s)

    def one_chunk(state, xs):
        x_v, x_k, q_in, qk, k_out, last = xs
        u = x_v - jnp.matmul(x_k, state, precision=hi)
        o = jnp.matmul(q_in, state, precision=hi) + jnp.matmul(qk, u, precision=hi)
        state = jnp.swapaxes(jnp.exp(last), -1, -2) * state + jnp.matmul(
            jnp.swapaxes(k_out, -1, -2), u, precision=hi)
        return state, o

    start = jnp.zeros((rows, heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(one_chunk, start, (x_v, x_k, q_in, qk, k_out, last))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(rows, n * chunk, heads, -1)
    return o[:, :t]


def _conv_silu(z, w):
    """silu of the causal depthwise convolution of ``z`` [rows, T, channels]
    with ``w`` [width, channels] (tap ``width - 1`` meets the current token),
    in float32."""
    import jax
    import jax.numpy as jnp

    width, t = w.shape[0], z.shape[1]
    padded = jnp.pad(z.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return jax.nn.silu(sum(padded[:, j:j + t] * w[j] for j in range(width)))


def kda_block(x, p, cfg: KimiLinearConfig):
    import jax
    import jax.numpy as jnp

    rows, t, _ = x.shape
    heads, dh = p["A_log"].shape[0], cfg.kda_head_dim
    split = lambda z: z.reshape(rows, t, heads, dh)  # noqa: E731
    f32 = jnp.float32

    def l2norm(z):
        return z * jax.lax.rsqrt(jnp.sum(jnp.square(z), axis=-1, keepdims=True) + 1e-6)

    # bfloat16 matmuls that come out in float32: what follows them (the
    # convolutions, the normalisation, the decays) is float32 arithmetic
    wide = lambda a, b: jnp.matmul(a, b, preferred_element_type=f32)  # noqa: E731
    q = l2norm(split(_conv_silu(wide(x, p["wq"]), p["conv_q"])))
    k = l2norm(split(_conv_silu(wide(x, p["wk"]), p["conv_k"])))
    v = split(_conv_silu(wide(x, p["wv"]), p["conv_v"]))
    # the decay gate's second, 128-wide matmul in float32 too: its output is
    # a log-decay that the scan sums over thousands of tokens
    pre = jnp.matmul(wide(x, p["wf1"]), p["wf2"].astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    g = -jnp.exp(p["A_log"].astype(f32))[None, None, :, None] * jax.nn.softplus(
        split(pre + p["dt_bias"].astype(f32)))
    beta = jax.nn.sigmoid(wide(x, p["wb"]))
    o = kda_chunked(q / math.sqrt(dh), k, v, g, beta, cfg.kda_chunk)
    gate = jax.nn.sigmoid(split(wide(x @ p["wg1"], p["wg2"]) + p["bg"].astype(f32)))
    o = _rmsnorm(o, p["head_norm"].astype(f32), cfg.rms_norm_eps) * gate
    return o.astype(x.dtype).reshape(rows, t, heads * dh) @ p["wo"]


# -- latent attention without positions -----------------------------------------

def mla_block(x, p, cfg: KimiLinearConfig):
    import jax
    import jax.numpy as jnp

    rows, t, _ = x.shape
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    heads = p["wo"].shape[0] // dv
    q = (x @ p["wq"]).reshape(rows, t, heads, nope + rope)
    latent = x @ p["wkva"]
    c, k_rope = latent[..., :cfg.kv_lora_rank], latent[..., cfg.kv_lora_rank:]
    kv = (_rmsnorm(c, p["kv_norm"], cfg.rms_norm_eps) @ p["wkvb"]).reshape(
        rows, t, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, :, None, :], (rows, t, heads, rope))], axis=-1)
    scores = jnp.einsum("rqhd,rkhd->rhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(causal[None, None], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("rhqk,rkhd->rqhd", probs, kv[..., nope:])
    return out.reshape(rows, t, heads * dv) @ p["wo"]


# -- the expert layer -----------------------------------------------------------

def route(x, router, bias, cfg: KimiLinearConfig, eps: float = 0.0):
    """``(chosen [tokens, 8], weights [tokens, 8] float32, load [experts]
    int32)``: sigmoid scores of all experts in float32, the top 8 of score +
    bias, weights renormalised over the chosen (``eps`` added to their sum
    where a family's public code has one) and scaled."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.matmul(x, router, preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(scores + bias, cfg.num_experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    weights = picked / (total + eps if eps else total) * cfg.routed_scaling_factor
    load = jnp.zeros((cfg.num_experts,), jnp.int32).at[chosen.reshape(-1)].add(1)
    return chosen, weights, load


BUFFER_LADDER = (16, 4, 1)  # the pair buffer's rows: a 16th, a quarter, all of tokens x 8


def held_experts(x, chosen, weights, experts, cfg: KimiLinearConfig, ladder=BUFFER_LADDER):
    """The held experts' part of the layer's output for ``x`` [tokens, d].

    Every (token, choice) pair whose expert is held here is one row of a
    buffer, the rows sorted by expert; ``ragged_dot`` multiplies group by
    group and leaves the rows behind the last group alone, so the matmuls'
    work is the held pairs'.  Nothing is dropped and every shape is static:
    the buffer that holds them all has ``tokens x 8`` rows, 32 times the
    expected load when 8 of 256 experts are held, and gathering and
    scattering that many rows would cost more than the experts.  So the
    buffer comes in three sizes (``BUFFER_LADDER``) and ``lax.switch`` takes
    the smallest that holds this step's pairs: the work follows the load in
    three strides.  A model whose held share sits on a rung (a quarter of the
    experts held: the expected load IS the middle size) gives its own
    ``ladder``, whose last entry is 1: every pair has to fit."""
    import jax
    import jax.numpy as jnp

    tokens, per_token = chosen.shape
    pairs = tokens * per_token
    local = chosen - cfg.expert_offset
    held = (local >= 0) & (local < cfg.experts_held)
    group = jnp.where(held, local, cfg.experts_held).reshape(-1)
    order = jnp.argsort(group, stable=True)       # the held pairs first, by expert
    sizes = jnp.sum(group[:, None] == jnp.arange(cfg.experts_held)[None, :],
                    axis=0, dtype=jnp.int32)
    mine = jnp.where(held, weights, 0.0).reshape(-1)

    def with_rows(rows):
        def compute(x, experts, order, sizes, mine):
            head = order[:rows]
            token = head // per_token
            live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
            xs = jnp.where(live, x[token], 0)
            h = jax.nn.silu(jax.lax.ragged_dot(xs, experts["w_gate"], sizes)) * (
                jax.lax.ragged_dot(xs, experts["w_up"], sizes))
            ys = jax.lax.ragged_dot(jnp.where(live, h, 0), experts["w_down"], sizes)
            ys = jnp.where(live, ys, 0).astype(jnp.float32) * mine[head][:, None]
            out = jnp.zeros((tokens, x.shape[1]), jnp.float32).at[token].add(ys)
            return out.astype(x.dtype)

        return compute

    ladder = sorted({max(1, pairs // part) for part in ladder})
    rung = sum((jnp.sum(sizes) > rows).astype(jnp.int32) for rows in ladder[:-1])

    # The backward pass computes the taken size again and differentiates that
    # (a custom rule: differentiated as written, ``switch`` would keep every
    # size's intermediates, and a layer's buffers would outlive the layer).
    # ``order``, ``sizes`` and ``rung`` are arguments and not closed over, so
    # that the rule can be staged: inside a ``scan`` or a ``checkpoint`` the
    # backward rule is traced after the forward trace's values are gone.
    @jax.custom_vjp
    def run(x, experts, mine, order, sizes, rung):
        return jax.lax.switch(rung, [with_rows(rows) for rows in ladder],
                              x, experts, order, sizes, mine)

    def run_fwd(x, experts, mine, order, sizes, rung):
        return run(x, experts, mine, order, sizes, rung), (x, experts, mine, order, sizes, rung)

    def run_bwd(saved, ct):
        *diff, order, sizes, rung = saved

        def back(rows):
            def pull(x, experts, mine, ct):
                return jax.vjp(lambda x, experts, mine: with_rows(rows)(
                    x, experts, order, sizes, mine), x, experts, mine)[1](ct)

            return pull

        return (*jax.lax.switch(rung, [back(rows) for rows in ladder], *diff, ct),
                None, None, None)

    run.defvjp(run_fwd, run_bwd)
    return run(x, experts, mine, order, sizes, rung)


def moe_block(x, p, bias, cfg: KimiLinearConfig):
    """``(output, load)`` of the expert layer for ``x`` [tokens, d]."""
    import jax

    with jax.named_scope("moe.route"):
        chosen, weights, load = route(x, p["router"], bias, cfg)
    with jax.named_scope("moe.experts"):
        mine = held_experts(x, chosen, weights, p["experts"], cfg)
    with jax.named_scope("moe.shared"):
        shared = _swiglu(x, p["shared"])
    return shared + mine, load


# -- the model ------------------------------------------------------------------

def forward(params: Dict, tokens, cfg: KimiLinearConfig, router_bias=None):
    """``(logits [rows, T, vocab_rows], load [expert layers, num_experts])``.
    ``router_bias`` [expert layers, num_experts] float32; zeros if None."""
    import jax
    import jax.numpy as jnp

    rows, t = tokens.shape
    if router_bias is None:
        router_bias = jnp.zeros((cfg.n_expert_layers, cfg.num_experts), jnp.float32)
    h = params["embed"][tokens]
    loads = []
    for p in params["layers"]:
        x = _rmsnorm(h, p["attn_norm"], cfg.rms_norm_eps)
        if "kda" in p:
            with jax.named_scope("kda"):
                h = h + kda_block(x, p["kda"], cfg)
        else:
            with jax.named_scope("mla"):
                h = h + mla_block(x, p["mla"], cfg)
        x = _rmsnorm(h, p["ffn_norm"], cfg.rms_norm_eps)
        if "moe" in p:
            out, load = moe_block(x.reshape(rows * t, -1), p["moe"],
                                  router_bias[len(loads)], cfg)
            h = h + out.reshape(h.shape)
            loads.append(load)
        else:
            with jax.named_scope("ffn.dense"):
                h = h + _swiglu(x, p["ffn"])
    with jax.named_scope("head.loss"):
        logits = _rmsnorm(h, params["final_norm"], cfg.rms_norm_eps) @ params["head"]
    return logits, jnp.stack(loads)


def next_token_loss(logits, targets):
    """Mean cross-entropy of ``logits`` [..., vocabulary rows] against
    ``targets`` [...], in float32."""
    import jax
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss_fn(params, batch, cfg: KimiLinearConfig, router_bias=None):
    """``(mean next-token cross-entropy over the held rows of the
    vocabulary, load)``."""
    import jax

    tokens, targets = batch
    logits, load = forward(params, tokens, cfg, router_bias)
    with jax.named_scope("head.loss"):
        return next_token_loss(logits, targets), load


def init_opt_state(params, cfg: KimiLinearConfig):
    """Two float32 moments a leaf; a float32 master copy of every leaf that
    is not float32 itself (None where it is: an empty subtree); the step
    count; the router's bias and the last step's load."""
    import jax.numpy as jnp

    return {
        **init_adamw_state(params),
        "router_bias": jnp.zeros((cfg.n_expert_layers, cfg.num_experts), jnp.float32),
        "router_load": jnp.zeros((cfg.n_expert_layers, cfg.num_experts), jnp.int32),
    }


def moved_bias(bias, load, rate: float):
    """The router's bias after a step: each expert's moved by ``rate`` towards
    the mean load, up where this step's tokens chose it less often than the
    mean and down where more (the auxiliary-loss-free rule)."""
    import jax.numpy as jnp

    spread = jnp.mean(load.astype(jnp.float32), axis=-1, keepdims=True) - load
    return bias + rate * jnp.sign(spread)


def make_train_step(cfg: KimiLinearConfig, lr: float = 1e-3):
    """Fused jitted train step: ``(params, opt, (tokens, targets)) -> (params,
    opt, loss)``: forward, backward, AdamW on every trained leaf, then the
    router's bias moved by ``bias_update_rate`` towards the experts that this
    step's tokens chose less often than the mean (``moved_bias``)."""
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


def routing_stats(opt, cfg: KimiLinearConfig) -> Dict[str, float]:
    """The held experts' load in the last step, from the state (no callback
    in the step): the largest and the mean over held experts and expert
    layers, and the held experts' share of all assignments; sets the gauges
    ``tpurx_model_expert_load_max`` and ``tpurx_model_expert_load_mean``."""
    import numpy as np

    load = np.asarray(opt["router_load"])
    mine = load[:, cfg.expert_offset:cfg.expert_offset + cfg.experts_held]
    stats = {"max": float(mine.max()), "mean": float(mine.mean()),
             "share": float(mine.sum() / max(int(load.sum()), 1))}
    _EXPERT_LOAD_MAX.set(stats["max"])
    _EXPERT_LOAD_MEAN.set(stats["mean"])
    return stats
