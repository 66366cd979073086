"""Plain reference for the ``mellum`` family: forward, loss, gradients and AdamW,
in ``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``:
attention as a softmax over every key a query sees under a dense mask, both
rotary tables written out from their formulas, no kernel, no sorting of tokens
by expert (a held expert runs over every token, one expert after the other).

Written from the equations; imports nothing of the rest of this repository.
The file exists twice, byte for byte: ``tpu_resiliency/models/
mellum_reference.py`` is the repository's reference for the model of
``mellum.py``, ``chipbench/reference/mellum.py`` the benchmark's copy, which
decides ``correct`` and which no later PR may edit (``tests/test_mellum.py``
holds the two to equal numbers).

One chip's share of a layer: the weights say how many experts and rows of the
vocabulary are held (``Dims.expert_offset`` says which experts), the router
always scores all of its experts, and what the absent experts would add is
left out.  Attention is held whole.  With every expert held this is the uncut
layer.

    norm(x) = w x / sqrt(mean(x^2) + 1e-6)
    h <- h + attn(norm_a(h));   h <- h + moe(norm_f(h))

Grouped-query attention (32 query heads of width 128 over 4 key/value heads,
query head j reads key/value head ``j // 8``; q and k normed a head, with a
scale, then the whole head rotated as two halves of 64, positions from 0;
scale 1/sqrt(128); no bias):

    q = rope_kind(norm_128(W_q u));   k = rope_kind(norm_128(W_k u));   v = W_v u
    out = W_o concat_heads( softmax_{s seen by t}(q_t . k_s / sqrt(128)) v )
    rope(x)_t = x * (c cos(t f)) + rotate_half(x) * (c sin(t f))     f repeated over both halves

A layer's kind (``Dims.layer_types``) decides what a query sees and which
frequencies turn its head; the leaves are the same:

    sliding_attention:  s seen by t  iff  t - window < s <= t        (1,024 keys, t among them)
                        f_i = theta^(-2i/128),  c = 1                         i = 0..63
    full_attention:     s seen by t  iff  s <= t
                        YaRN: low  = floor(128 ln(L / (beta_fast 2 pi)) / (2 ln theta))
                              high = ceil (128 ln(L / (beta_slow 2 pi)) / (2 ln theta)), both in [0, 127]
                              r_i = clip((i - low) / (high - low), 0, 1)
                              f_i = (1 - r_i) theta^(-2i/128) + r_i theta^(-2i/128) / factor
                              c = attention_factor  (on cos and on sin, so q . k carries c^2)
                        at every length, the original positions L included

The expert layer (softmax router over all experts in float32, the top 8,
their weights renormalised over the 8; no shared expert, no bias):

    swiglu(x) = W_down (silu(W_gate x) * W_up x)
    p = softmax(W_r x);  C = top8(p);  g_e = p_e / sum_{c in C} p_c
    out = sum_{e in C, e held here} g_e swiglu_e(x)

    loss = mean_t( logsumexp(z_t) - z_t[target_t] ),   z = norm_final(h_L) W_head,   h_0 = E[tokens]

    AdamW (lr 1e-6, b1 0.9, b2 0.95, eps 1e-8, wd 0.01, on every trained leaf):
    mu = b1 mu + (1-b1) g;  nu = b2 nu + (1-b2) g^2
    w  = w - lr ( mu/(1-b1^t) / (sqrt(nu/(1-b2^t)) + eps) + wd w )

Departures from the published model, written into the configuration's file:
no auxiliary load-balancing loss; no multi-token-prediction head (the
published config has no key for one).

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
  copy, moments, router scores, softmax, norm statistics, the rotation, the
  loss) in bfloat16 as well.
- ``"half_batch"``: float32, but every step sees the first half of its
  batch's positions only (the model is causal: the second half's part of the
  loss, and of every gradient, is left out).
- ``"state_unchanged"``: float32, but every step returns the state it was
  given: the losses are the start's, no moment and no weight moves.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

LR, B1, B2, EPS, WD = 1e-6, 0.9, 0.95, 1e-8, 0.01
PRECISIONS = ("reference", "bf16_everywhere", "half_batch", "state_unchanged")


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the weights' shapes do not say."""

    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + ("full_attention",)
    window: int = 1024           # keys a query of a sliding layer sees, itself among them
    rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_positions: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    experts_per_token: int = 8
    expert_offset: int = 0       # the first expert held here
    rms_norm_eps: float = 1e-6
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


def swiglu(x, p):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def yarn_range(dims: Dims, width: int):
    """``(low, high)`` of the YaRN ramp over the ``width // 2`` channel pairs."""
    def pair(turns):
        return (width * math.log(dims.yarn_original_positions / (turns * 2 * math.pi))
                / (2 * math.log(dims.rope_theta)))

    return max(math.floor(pair(dims.yarn_beta_fast)), 0), min(
        math.ceil(pair(dims.yarn_beta_slow)), width - 1)


def frequencies(dims: Dims, kind: str, width: int):
    """``(f [width // 2] float32, c)`` of a layer kind's rotary table."""
    import jax.numpy as jnp

    i = jnp.arange(width // 2, dtype=jnp.float32)
    f = dims.rope_theta ** (-2.0 * i / width)
    if kind == "sliding_attention":
        return f, 1.0
    if kind != "full_attention":
        raise ValueError(f"a layer is sliding_attention or full_attention, not {kind!r}")
    low, high = yarn_range(dims, width)
    r = jnp.clip((i - low) / (high - low if high != low else 0.001), 0.0, 1.0)
    return (1.0 - r) * f + r * f / dims.yarn_factor, dims.yarn_attention_factor


def rope(x, dims: Dims, kind: str):
    """``x`` [rows, T, heads, width] rotated over its whole width as two
    halves by the kind's table, positions 0..T-1."""
    import jax.numpy as jnp

    t, width = x.shape[1], x.shape[-1]
    f, c = frequencies(dims, kind, width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * f[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    cos, sin = (c * jnp.cos(angle)).astype(x.dtype), (c * jnp.sin(angle)).astype(x.dtype)
    half = jnp.concatenate([-x[..., width // 2:], x[..., :width // 2]], axis=-1)
    return x * cos + half * sin


def attention(u, p, dims: Dims, kind: str):
    """Grouped-query attention over ``u`` [rows, T, d] under the kind's mask."""
    import jax
    import jax.numpy as jnp

    rows, t, _ = u.shape
    dh = p["q_norm"].shape[0]
    nq, nkv = p["q_proj"].shape[1] // dh, p["k_proj"].shape[1] // dh
    eps = dims.rms_norm_eps
    q = rope(norm((u @ p["q_proj"]).reshape(rows, t, nq, dh), p["q_norm"], eps), dims, kind)
    k = rope(norm((u @ p["k_proj"]).reshape(rows, t, nkv, dh), p["k_norm"], eps), dims, kind)
    v = (u @ p["v_proj"]).reshape(rows, t, nkv, dh)
    q = q.reshape(rows, t, nkv, nq // nkv, dh)            # query head j on head j // group

    def one_block(block):
        q_blk, position = block                                # [rows, Q, ...], [Q]
        key = jnp.arange(t)[None, :]
        seen = position[:, None] >= key
        if kind == "sliding_attention":
            seen = seen & (key > position[:, None] - dims.window)
        scores = jnp.einsum("rqkgd,rskd->rkgqs", q_blk, k) / math.sqrt(dh)
        scores = jnp.where(seen, scores, -jnp.inf)
        scores = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        return jnp.einsum("rkgqs,rskd->rqkgd", probs, v)

    block = min(dims.query_block, t)
    pad = (-t) % block   # queries past the end: each sees keys, and is dropped
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    by_block = jnp.moveaxis(q.reshape(rows, (t + pad) // block, block, *q.shape[2:]), 1, 0)
    positions = jnp.arange(t + pad).reshape(-1, block)
    if kind == "sliding_attention":
        # a padded query past ``t - 1 + window`` would see no key at all: it is
        # dropped anyway, and is given the last real position so that it sees some
        positions = jnp.minimum(positions, t - 1)
    out = jax.lax.map(jax.checkpoint(one_block), (by_block, positions))
    out = jnp.moveaxis(out, 0, 1).reshape(rows, t + pad, nq * dh)[:, :t]
    return out @ p["o_proj"]


def route(x, router, dims: Dims):
    """``(chosen experts [tokens, 8], their weights, load over all experts)``
    of ``x`` [tokens, d]."""
    import jax
    import jax.numpy as jnp

    z = x @ router
    z = jnp.exp(z - jnp.max(z, axis=-1, keepdims=True))
    probs = z / jnp.sum(z, axis=-1, keepdims=True)
    picked, chosen = jax.lax.top_k(probs, dims.experts_per_token)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    load = jnp.zeros((router.shape[1],), jnp.int32).at[chosen.reshape(-1)].add(1)
    return chosen, weights, load


def moe(x, p, dims: Dims):
    """The held experts' part of the expert layer's output, and the load:
    every held expert over every token, one expert after the other, each
    weighted by what the router gave it (0 where it was not chosen)."""
    import jax
    import jax.numpy as jnp

    chosen, weights, load = route(x, p["router"], dims)
    held = p["experts"]["w_gate"].shape[0]

    def one_expert(out, e_and_its_weights):
        e, one = e_and_its_weights
        mine = jnp.sum(jnp.where(chosen == dims.expert_offset + e, weights, 0.0), axis=-1)
        return out + mine[:, None] * swiglu(x, one), None

    out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                          (jnp.arange(held), p["experts"]))
    return out, load


def layer(h, p, dims: Dims, kind: str):
    """``(h after the layer, load)``."""
    rows, t, _ = h.shape
    h = h + attention(norm(h, p["attn_norm"], dims.rms_norm_eps), p["attn"], dims, kind)
    x = norm(h, p["ffn_norm"], dims.rms_norm_eps)
    out, load = moe(x.reshape(rows * t, -1), p["moe"], dims)
    return h + out.reshape(h.shape), load


def logits_of(weights, tokens, dims: Dims):
    """Logits over the held rows of the vocabulary [rows, T, rows held], and
    the load of every layer [layers, experts]."""
    import jax
    import jax.numpy as jnp

    if len(dims.layer_types) != len(weights["layers"]):
        raise ValueError("Dims.layer_types names the kind of every layer of the weights")
    h = weights["embed"][tokens]
    loads = []
    for kind, p in zip(dims.layer_types, weights["layers"]):
        h, load = jax.checkpoint(lambda h, p, kind=kind: layer(h, p, dims, kind))(h, p)
        loads.append(load)
    z = norm(h, weights["final_norm"], dims.rms_norm_eps) @ weights["head"]
    return z, jnp.stack(loads)


def loss_of(weights, tokens, targets, dims: Dims, precision: str = "reference"):
    """Mean next-token cross-entropy over the held rows of the vocabulary,
    and the load."""
    import jax
    import jax.numpy as jnp

    dt = _dtype(precision)
    weights = jax.tree_util.tree_map(lambda w: w.astype(dt), weights)
    z, load = logits_of(weights, tokens, dims)
    top = jnp.max(z, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(jnp.exp(z - top), axis=-1))
    picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    return jnp.mean((lse - picked).astype(jnp.float32)), load


def make_step(dims: Dims, precision: str = "reference"):
    """jitted ``(weights, mu, nu, count, tokens, targets) -> (weights, mu, nu,
    count, loss, gradient norm per leaf, load)``.  ``weights`` are the master
    values (float32, or bfloat16 where the precision keeps no float32 copy)."""
    import jax
    import jax.numpy as jnp

    dt = _dtype(precision)

    def step(weights, mu, nu, count, tokens, targets):
        (loss, load), grads = jax.value_and_grad(
            lambda w: loss_of(w, tokens, targets, dims, precision), has_aux=True)(weights)
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
        return unflat(0), unflat(1), unflat(2), count, loss, grad_norms, load

    step.__name__ = f"mellum_reference_step_{precision}"
    return jax.jit(step, donate_argnums=(0, 1, 2))


def first_steps(start_weights, feed, dims: Dims, n_steps: int = 3,
                precision: str = "reference"):
    """Follow the first ``n_steps`` from ``start_weights`` (a float32 tree of
    the seed's draw) over ``feed``; returns the numbers the program is
    compared on (every step's loss, the first gradient's norm per leaf, the
    norm per leaf of the weights' change after the last step) and, beside
    them, every step's load."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dt = _dtype(precision)
    if precision == "half_batch":
        feed = [tuple(z[:, :z.shape[1] // 2] for z in batch) for batch in feed]
    with jax.default_matmul_precision("highest"):
        if precision == "state_unchanged":
            loss = jax.jit(lambda w, tokens, targets: loss_of(w, tokens, targets, dims))
            found = [loss(start_weights, *feed[i % len(feed)]) for i in range(n_steps)]
            still = [0.0] * len(jax.tree_util.tree_leaves(start_weights))
            return {"loss": [float(one) for one, _ in found],
                    "grad_norm": still, "change_norm": still,
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
            weights, mu, nu, count, loss, grad_norms, load = step(
                weights, mu, nu, count, tokens, targets)
            losses.append(float(loss))
            loads.append(np.asarray(load).tolist())
            if first_grad is None:
                first_grad = np.asarray(grad_norms, dtype=np.float64)

        def mellum_reference_change(new, old):
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(new),
                                jax.tree_util.tree_leaves(old))])

        change = jax.jit(mellum_reference_change)(weights, start_weights)
    return {"loss": losses, "grad_norm": first_grad.tolist(),
            "change_norm": np.asarray(change, dtype=np.float64).tolist(),
            "router_load": loads}
