"""Plain reference for the ``lfm2_moe`` family: forward, loss, gradients, AdamW
and the router's bias update, in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``: the convolution as a sum over its
taps, attention as a softmax over every key a query sees, no kernel, no
sorting of tokens by expert (a held expert runs over every token, one expert
after the other).

Written from the equations; imports nothing of the rest of this repository.
The file exists twice, byte for byte: ``tpu_resiliency/models/
lfm2_moe_reference.py`` is the repository's reference for the model of
``lfm2_moe.py``, ``chipbench/reference/lfm2_moe.py`` the benchmark's copy,
which decides ``correct`` and which no later PR may edit
(``tests/test_lfm2_moe.py`` holds the two to equal numbers).

One chip's share of a layer: the weights say how many experts and rows of the
vocabulary are held (``Dims.expert_offset`` says which experts), the router
always scores all of its experts, and what the absent experts would add is
left out.  Both mixers and the dense feed-forward are held whole.  With every
expert held this is the uncut layer.

    norm(x) = w x / sqrt(mean(x^2) + 1e-5)
    u = norm_op(h);   h <- h + mixer(u);   h <- h + ffn(norm_ffn(h))

A layer's mixer is one of two, by its leaves.  The double-gated short
convolution (no activation anywhere in it; ``taps`` = 3):

    [B, C, x] = split_3(W_in u)                      W_in: d x 3d, no bias
    c_t = sum_{j=0..taps-1} w[j] * (B * x)_{t-(taps-1)+j}     per channel; zeros before position 0
    out = W_out (C * c)                              the last tap meets the current token

Grouped-query attention (32 query heads of width 64 over 8 key/value heads,
query head j reads key/value head ``j // 4``; q and k normed a head, with a
scale, then the whole head rotated as two halves of 32, theta 1e6, positions
from 0; causal; scale 1/8):

    q = rope(norm_64(W_q u));   k = rope(norm_64(W_k u));   v = W_v u        no bias
    out = W_o concat_heads( softmax_{s <= t}(q_t . k_s / sqrt(64)) v )

A layer's feed-forward is a dense SwiGLU where it has the leaves of one, else
the expert layer (sigmoid router over all experts, the top 4 of score + bias,
weights of the UNBIASED scores renormalised over the 4 chosen with 1e-6 on the
denominator, scaled by 1; no shared expert):

    swiglu(x) = W_down (silu(W_gate x) * W_up x)
    s = sigmoid(W_r x);  C = top4(s + bias);  g_e = s_e / (sum_{c in C} s_c + 1e-6)
    out = sum_{e in C, e held here} g_e swiglu_e(x)
    load_e = assignments to e this step;  bias_e += 1e-3 sign(mean load - load_e)

The head is the embedding (tied): one leaf, two gradient paths.

    loss = mean_t( logsumexp(z_t) - z_t[target_t] ),   z = norm_f(h_L) E^T,   h_0 = E[tokens]

    AdamW (lr 1e-3, b1 0.9, b2 0.95, eps 1e-8, wd 0.01, on every trained leaf):
    mu = b1 mu + (1-b1) g;  nu = b2 nu + (1-b2) g^2
    w  = w - lr ( mu/(1-b1^t) / (sqrt(nu/(1-b2^t)) + eps) + wd w )

Departure from the published model, written into the configuration's file:
no auxiliary load-balancing loss.

To fit a chip, attention runs over blocks of ``Dims.query_block`` queries
(each against every key, the ones a query does not see masked), one block
after the other and each recomputed in the backward pass, and every layer is
recomputed in the backward pass (``jax.checkpoint``): devices for memory, the
numbers are the equations'.

``precision`` selects a control, something the comparison has to refuse: the
same equations in the next lower precision than the configuration states, or
in full precision with a fault in the step.

- ``"reference"``: float32 throughout, matmuls at ``highest``.
- ``"bf16_everywhere"``: what the configuration keeps in float32 (master
  copy, moments, the gates' products and the convolution, router scores,
  softmax, norm statistics, the rotation, the loss) in bfloat16 as well.
- ``"half_batch"``: float32, but every step sees the first half of its
  batch's positions only (the model is causal: the second half's part of the
  loss, and of every gradient, is left out).
- ``"state_unchanged"``: float32, but every step returns the state it was
  given: the losses are the start's, no moment and no weight moves.
"""

from __future__ import annotations

import dataclasses
import math

LR, B1, B2, EPS, WD = 1e-3, 0.9, 0.95, 1e-8, 0.01
PRECISIONS = ("reference", "bf16_everywhere", "half_batch", "state_unchanged")


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the weights' shapes do not say."""

    rope_theta: float = 1e6
    experts_per_token: int = 4
    routed_scaling_factor: float = 1.0
    route_eps: float = 1e-6      # on the renormalised weights' denominator
    expert_offset: int = 0       # the first expert held here
    norm_eps: float = 1e-5
    bias_update_rate: float = 1e-3
    query_block: int = 512       # queries a checkpointed block of attention


def _dtype(precision: str):
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")
    return jnp.bfloat16 if precision == "bf16_everywhere" else jnp.float32


def norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-x))


def swiglu(x, p):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def short_conv(z, w):
    """Causal depthwise convolution over time: ``z`` [rows, T, channels],
    ``w`` [taps, channels]; tap ``taps - 1`` meets the current token."""
    import jax.numpy as jnp

    taps, t = w.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    out = jnp.zeros_like(z)
    for j in range(taps):
        out = out + padded[:, j:j + t] * w[j]
    return out


def gated_conv(u, p):
    """The double-gated short convolution of ``u`` [rows, T, d]."""
    import jax.numpy as jnp

    b, c, x = jnp.split(u @ p["in_proj"], 3, axis=-1)
    return (c * short_conv(b * x, p["conv"])) @ p["out_proj"]


def rope(x, dims: Dims):
    """``x`` [rows, T, heads, width] rotated over its whole width as two
    halves, positions 0..T-1."""
    import jax.numpy as jnp

    t, rot = x.shape[1], x.shape[-1]
    inv_freq = dims.rope_theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    half = jnp.concatenate([-x[..., rot // 2:], x[..., :rot // 2]], axis=-1)
    return x * cos + half * sin


def attention(u, p, dims: Dims):
    """Causal grouped-query attention over ``u`` [rows, T, d]."""
    import jax
    import jax.numpy as jnp

    rows, t, _ = u.shape
    dh = p["q_norm"].shape[0]
    nq, nkv = p["q_proj"].shape[1] // dh, p["k_proj"].shape[1] // dh
    eps = dims.norm_eps
    q = rope(norm((u @ p["q_proj"]).reshape(rows, t, nq, dh), p["q_norm"], eps), dims)
    k = rope(norm((u @ p["k_proj"]).reshape(rows, t, nkv, dh), p["k_norm"], eps), dims)
    v = (u @ p["v_proj"]).reshape(rows, t, nkv, dh)
    q = q.reshape(rows, t, nkv, nq // nkv, dh)            # query head j on head j // group

    def one_block(block):
        q_blk, position = block                                # [rows, Q, ...], [Q]
        seen = position[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.einsum("rqkgd,rskd->rkgqs", q_blk, k) / math.sqrt(dh)
        scores = jnp.where(seen, scores, -jnp.inf)
        scores = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        return jnp.einsum("rkgqs,rskd->rqkgd", probs, v)

    block = min(dims.query_block, t)
    pad = (-t) % block   # queries past the end: they see every key and are dropped
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    by_block = jnp.moveaxis(q.reshape(rows, (t + pad) // block, block, *q.shape[2:]), 1, 0)
    positions = jnp.arange(t + pad).reshape(-1, block)
    out = jax.lax.map(jax.checkpoint(one_block), (by_block, positions))
    out = jnp.moveaxis(out, 0, 1).reshape(rows, t + pad, nq * dh)[:, :t]
    return out @ p["o_proj"]


def route(x, router, bias, dims: Dims):
    """``(chosen experts [tokens, 4], their weights, load over all experts)``
    of ``x`` [tokens, d]: the bias picks, the unbiased scores weigh."""
    import jax
    import jax.numpy as jnp

    s = sigmoid(x @ router)
    _, chosen = jax.lax.top_k(s + bias.astype(s.dtype), dims.experts_per_token)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = (picked / (jnp.sum(picked, axis=-1, keepdims=True) + dims.route_eps)
               * dims.routed_scaling_factor)
    load = jnp.zeros((router.shape[1],), jnp.int32).at[chosen.reshape(-1)].add(1)
    return chosen, weights, load


def moe(x, p, bias, dims: Dims):
    """The held experts' part of the expert layer's output, and the load:
    every held expert over every token, one expert after the other, each
    weighted by what the router gave it (0 where it was not chosen)."""
    import jax
    import jax.numpy as jnp

    chosen, weights, load = route(x, p["router"], bias, dims)
    held = p["experts"]["w_gate"].shape[0]

    def one_expert(out, e_and_its_weights):
        e, one = e_and_its_weights
        mine = jnp.sum(jnp.where(chosen == dims.expert_offset + e, weights, 0.0), axis=-1)
        return out + mine[:, None] * swiglu(x, one), None

    out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                          (jnp.arange(held), p["experts"]))
    return out, load


def layer(h, p, layer_bias, dims: Dims):
    """``(h after the layer, load or None)``."""
    rows, t, _ = h.shape
    u = norm(h, p["operator_norm"], dims.norm_eps)
    h = h + (gated_conv(u, p["conv"]) if "conv" in p else attention(u, p["attn"], dims))
    x = norm(h, p["ffn_norm"], dims.norm_eps)
    if "moe" not in p:
        return h + swiglu(x, p["ffn"]), None
    out, load = moe(x.reshape(rows * t, -1), p["moe"], layer_bias, dims)
    return h + out.reshape(h.shape), load


def logits_of(weights, tokens, bias, dims: Dims, head=None):
    """Logits over the held rows of the vocabulary [rows, T, rows held], and
    the load of every expert layer [expert layers, experts].  ``head`` [rows
    held, d] is the embedding where it is None (the model's tied head); given
    apart, the two gradient paths of the one leaf can be taken apart."""
    import jax
    import jax.numpy as jnp

    h = weights["embed"][tokens]
    loads, n_moe = [], 0
    for p in weights["layers"]:
        layer_bias = None
        if "moe" in p:
            layer_bias, n_moe = bias[n_moe], n_moe + 1
        h, load = jax.checkpoint(lambda h, p, b: layer(h, p, b, dims))(h, p, layer_bias)
        if load is not None:
            loads.append(load)
    head = weights["embed"] if head is None else head
    z = norm(h, weights["embedding_norm"], dims.norm_eps) @ head.T
    return z, jnp.stack(loads)


def loss_of(weights, tokens, targets, bias, dims: Dims, precision: str = "reference",
            head=None):
    """Mean next-token cross-entropy over the held rows of the vocabulary,
    and the load."""
    import jax
    import jax.numpy as jnp

    dt = _dtype(precision)
    weights = jax.tree_util.tree_map(lambda w: w.astype(dt), weights)
    z, load = logits_of(weights, tokens, bias, dims,
                        head=None if head is None else head.astype(dt))
    top = jnp.max(z, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(jnp.exp(z - top), axis=-1))
    picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    return jnp.mean((lse - picked).astype(jnp.float32)), load


def zero_bias(weights):
    """The router's bias at its start: zeros [expert layers, experts]."""
    import jax.numpy as jnp

    routers = [p["moe"]["router"] for p in weights["layers"] if "moe" in p]
    return jnp.zeros((len(routers), routers[-1].shape[1]), jnp.float32)


def bias_update(bias, load, dims: Dims):
    import jax.numpy as jnp

    mean = jnp.mean(load.astype(jnp.float32), axis=-1, keepdims=True)
    return bias + dims.bias_update_rate * jnp.sign(mean - load.astype(jnp.float32))


def make_step(dims: Dims, precision: str = "reference"):
    """jitted ``(weights, mu, nu, count, tokens, targets[, bias]) -> (weights,
    mu, nu, count, loss, gradient norm per leaf, bias, load)``.  ``weights``
    are the master values (float32, or bfloat16 where the precision keeps no
    float32 copy); ``bias`` is the router's [expert layers, experts], zeros
    where it is not given."""
    import jax
    import jax.numpy as jnp

    dt = _dtype(precision)

    def step(weights, mu, nu, count, tokens, targets, bias=None):
        if bias is None:
            bias = zero_bias(weights)
        (loss, load), grads = jax.value_and_grad(
            lambda w: loss_of(w, tokens, targets, bias, dims, precision),
            has_aux=True)(weights)
        count = count + 1
        cf = count.astype(jnp.float32)

        def update(w, g, m, v):
            g = g.astype(dt)
            m2 = (B1 * m + (1 - B1) * g).astype(dt)
            v2 = (B2 * v + (1 - B2) * jnp.square(g)).astype(dt)
            m_hat = m2.astype(jnp.float32) / (1 - B1 ** cf)
            v_hat = v2.astype(jnp.float32) / (1 - B2 ** cf)
            w32 = w.astype(jnp.float32)
            w2 = w32 - LR * (m_hat / (jnp.sqrt(v_hat) + EPS) + WD * w32)
            return w2.astype(w.dtype), m2, v2

        flat_w, treedef = jax.tree_util.tree_flatten(weights)
        flat_g = jax.tree_util.tree_leaves(grads)
        out = [update(w, g, m, v) for w, g, m, v in zip(
            flat_w, flat_g, jax.tree_util.tree_leaves(mu), jax.tree_util.tree_leaves(nu))]
        unflat = lambda i: jax.tree_util.tree_unflatten(  # noqa: E731
            treedef, [o[i] for o in out])
        grad_norms = jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))) for g in flat_g])
        return (unflat(0), unflat(1), unflat(2), count, loss, grad_norms,
                bias_update(bias, load, dims), load)

    step.__name__ = f"lfm2_moe_reference_step_{precision}"
    return jax.jit(step, donate_argnums=(0, 1, 2))


def first_steps(start_weights, feed, dims: Dims, n_steps: int = 3,
                precision: str = "reference"):
    """Follow the first ``n_steps`` from ``start_weights`` (a float32 tree of
    the seed's draw) over ``feed``; returns the numbers the program is
    compared on (every step's loss, the first gradient's norm per leaf, the
    norm per leaf of the weights' change after the last step) and, beside
    them, the router's bias and every step's load."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dt = _dtype(precision)
    if precision == "half_batch":
        feed = [tuple(z[:, :z.shape[1] // 2] for z in batch) for batch in feed]
    with jax.default_matmul_precision("highest"):
        bias = zero_bias(start_weights)
        if precision == "state_unchanged":
            loss = jax.jit(lambda w, tokens, targets, bias: loss_of(
                w, tokens, targets, bias, dims))
            found = [loss(start_weights, *feed[i % len(feed)], bias) for i in range(n_steps)]
            still = [0.0] * len(jax.tree_util.tree_leaves(start_weights))
            return {"loss": [float(one) for one, _ in found],
                    "grad_norm": still, "change_norm": still,
                    "router_bias": np.asarray(bias, dtype=np.float64).tolist(),
                    "router_load": [np.asarray(load).tolist() for _, load in found]}
        step = make_step(dims, precision)
        # a copy: the step donates its weights, the start is compared later
        weights = jax.tree_util.tree_map(
            lambda w: jnp.array(w, dtype=dt, copy=True), start_weights)
        zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
            lambda w: jnp.zeros(w.shape, dt), start_weights)
        mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.int32)
        losses, loads, first_grad = [], [], None
        for i in range(n_steps):
            tokens, targets = feed[i % len(feed)]
            weights, mu, nu, count, loss, grad_norms, bias, load = step(
                weights, mu, nu, count, tokens, targets, bias)
            losses.append(float(loss))
            loads.append(np.asarray(load).tolist())
            if first_grad is None:
                first_grad = np.asarray(grad_norms, dtype=np.float64)

        def lfm2_moe_reference_change(new, old):
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(new),
                                jax.tree_util.tree_leaves(old))])

        change = jax.jit(lfm2_moe_reference_change)(weights, start_weights)
    return {"loss": losses, "grad_norm": first_grad.tolist(),
            "change_norm": np.asarray(change, dtype=np.float64).tolist(),
            "router_bias": np.asarray(bias, dtype=np.float64).tolist(),
            "router_load": loads}
