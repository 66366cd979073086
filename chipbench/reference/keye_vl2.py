"""Plain reference for the ``keye_vl2`` family: forward, both losses, gradients
and AdamW, in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``: the selected set by a stable
sort of every index score of a query, attention as a softmax over exactly that
set, no kernel, no sorting of tokens by expert (a held expert runs over every
token, one expert after the other).

Written from the equations; imports nothing of the rest of this repository.
The file exists twice, byte for byte: ``tpu_resiliency/models/
keye_vl2_reference.py`` is the repository's reference for the model of
``keye_vl2.py``, ``chipbench/reference/keye_vl2.py`` the benchmark's copy,
which decides ``correct`` and which no later PR may edit
(``tests/test_keye_vl2.py`` holds the two to equal numbers).

One chip's share of a layer: the weights say how many experts and rows of the
vocabulary are held (``Dims.expert_offset`` says which experts), the router
always scores all of its experts, and what the absent experts would add is
left out.  Attention and indexer are held whole.  With every expert held this
is the uncut layer.  All layers are alike:

    norm(x) = x / sqrt(mean(x^2) + 1e-6) w
    u = norm1(h);   h <- h + attention(u);   h <- h + moe(norm2(h))

Attention (32 query heads of width 128 over 4 key/value heads, query head j
reads key/value head ``j // 8``; q and k normed a head, then the whole head
rotated as two halves of 64, theta 1e7, positions from 0 — on text the three
streams of the source's multimodal rotary are equal and its sections reduce
to this):

    q = W_q u;   k = W_k u;   v = W_v u              no bias
    q <- rope(norm_128(q));   k <- rope(norm_128(k))

The indexer (16 index heads of width 64 against ONE index key head; it reads
``sg(u)``, the layer input DETACHED; ``sg`` is stop-gradient):

    qI = rope(WI_q sg(u))                            16 heads of 64
    kI = rope(LayerNorm(WI_k sg(u)))                 one head of 64; scale and bias, eps 1e-6
    w  = WI_w sg(u) / sqrt(16 x 64)                  one weight an index head
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])   an exact zero is +0

The selection: ``S_t`` = the ``topk`` positions ``s <= t`` of largest ``I[t,
s]``, every ``s <= t`` where ``t < topk``; of equal scores the lower position
first (a stable sort's order, which is ``jax.lax.top_k``'s).

    o_t = sum_{s in S_t} softmax_{s in S_t}(q_t . k_s / sqrt(128)) v_s    per head
    out = W_o concat_heads(o_t)

The indexer's loss (the sparse stage of the published recipe), by layer:

    p_t = sg( mean over the 32 heads of softmax_{s in S_t}(q_t . k_s / sqrt(128)) )
    L_I = mean_t KL( p_t || softmax_{s in S_t} I[t, s] )

and, reported by layer beside it (no loss): the mean over heads and positions
of the share of ``softmax_{s <= t}`` (every key the query sees) that falls on
``S_t``.

Expert layer (softmax router over all experts, the top 8, their weights
renormalised over the 8 chosen; no shared expert):

    p = softmax(W_r x);  C = top8(p);  w_e = p_e / sum_{c in C} p_c
    out = sum_{e in C, e held here} w_e swiglu_e(x)
    swiglu(x) = W_down (silu(W_gate x) * W_up x)
    load_e = assignments to e this step

    L_LM = mean_t( logsumexp(z_t) - z_t[target_t] ),   z = norm_f(h_L) W_head
    loss = L_LM + mean over layers of L_I

Because the indexer reads a detached input and the selection is discrete,
every leaf outside the indexer gets the gradient of ``L_LM`` alone and the
indexer's five leaves a layer that of ``L_I`` alone.

    AdamW (lr 1e-3, b1 0.9, b2 0.95, eps 1e-8, wd 0.01, on every trained leaf):
    mu = b1 mu + (1-b1) g;  nu = b2 nu + (1-b2) g^2
    w  = w - lr ( mu/(1-b1^t) / (sqrt(nu/(1-b2^t)) + eps) + wd w )

Departures from the published model, both written into the configuration's
file: no auxiliary load-balancing loss; no vision tower and no multimodal
positions.

To fit a chip, attention runs over blocks of ``Dims.query_block`` queries
(each against every key, the ones a query does not see masked), one block
after the other and each recomputed in the backward pass, and the layers, all
alike, are one ``lax.scan`` over their stacked weights with every layer
recomputed in the backward pass (``jax.checkpoint``): devices for memory and
for the size of the compiled program, the numbers are the equations'.

``precision`` selects a control, something the comparison has to refuse: the
same equations in the next lower precision than the configuration states, or
in full precision with a fault in the step.

- ``"reference"``: float32 throughout, matmuls at ``highest``.
- ``"bf16_everywhere"``: what the configuration keeps in float32 (master
  copy, moments, index scores, softmaxes, the KL, router scores, norm
  statistics, the loss) in bfloat16 as well.
- ``"half_batch"``: float32, but every step sees the first half of its
  batch's positions only (the model is causal: the second half's part of both
  losses, and of every gradient, is left out).
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

    rope_theta: float = 1e7
    index_topk: int = 2048       # keys a query attends to
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


def layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w + b


def silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def swiglu(x, p):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def softmax_over(z, chosen):
    """Softmax of ``z`` over the positions ``chosen`` marks, 0 elsewhere."""
    import jax.numpy as jnp

    z = jnp.where(chosen, z, -jnp.inf)
    z = jnp.exp(z - jnp.max(z, axis=-1, keepdims=True))
    return z / jnp.sum(z, axis=-1, keepdims=True)


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


def selected(index, seen, topk: int):
    """bool like ``index`` [rows, Q, T]: per query the ``topk`` positions of
    largest index score among those it sees, every one it sees where those
    are no more; of equal scores the lower position first."""
    import jax.numpy as jnp

    # a position's place in the stable descending order of its query's scores
    # (two sorts: a scatter of top_k's indices takes a quarter of a second a block on the chip)
    order = jnp.argsort(-jnp.where(seen, index, -jnp.inf), axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1)
    return (place < topk) & seen


def indexer(u, p_idx, dims: Dims):
    """``(qI [rows, T, index heads, 64], kI [rows, T, 64], w [rows, T, index
    heads])`` of the layer input ``u``, which the caller has detached."""
    rows, t, _ = u.shape
    di = p_idx["k_norm"].shape[0]
    q_idx = rope((u @ p_idx["q_proj"]).reshape(rows, t, -1, di), dims)
    k_idx = rope(layer_norm(u @ p_idx["k_proj"], p_idx["k_norm"], p_idx["k_norm_bias"],
                            dims.rms_norm_eps)[:, :, None, :], dims)[:, :, 0]
    return q_idx, k_idx, (u @ p_idx["w_proj"]) / math.sqrt(q_idx.shape[2] * di)


def index_scores(q_idx, k_idx, w_idx):
    """``I[t, s]`` for the queries of ``q_idx`` [rows, Q, index heads, 64] and
    ``w_idx`` [rows, Q, index heads] against every key of ``k_idx``."""
    import jax.numpy as jnp

    dots = jnp.einsum("rqjd,rsd->rqjs", q_idx, k_idx)
    index = jnp.sum(jnp.maximum(dots, 0.0) * w_idx[..., None], axis=2)
    return jnp.where(index == 0, jnp.zeros_like(index), index)


def attention(u, p, p_idx, dims: Dims):
    """``(output [rows, T, d], KL [rows, T], selected mass [rows, T])`` of
    the sparse attention over ``u`` = norm1(h), the mass a mean over heads."""
    import jax
    import jax.numpy as jnp

    rows, t, _ = u.shape
    dh = p["q_norm"].shape[0]
    nq, nkv = p["q_proj"].shape[1] // dh, p["k_proj"].shape[1] // dh
    eps = dims.rms_norm_eps
    q = rope(norm((u @ p["q_proj"]).reshape(rows, t, nq, dh), p["q_norm"], eps), dims)
    k = rope(norm((u @ p["k_proj"]).reshape(rows, t, nkv, dh), p["k_norm"], eps), dims)
    v = (u @ p["v_proj"]).reshape(rows, t, nkv, dh)
    q = q.reshape(rows, t, nkv, nq // nkv, dh)            # query head j on head j // group
    q_idx, k_idx, w_idx = indexer(jax.lax.stop_gradient(u), p_idx, dims)

    def one_block(block):
        q_blk, qi_blk, wi_blk, position = block               # [rows, Q, ...], [Q]
        seen = position[:, None] >= jnp.arange(t)[None, :]
        index = index_scores(qi_blk, k_idx, wi_blk)
        chosen = selected(jax.lax.stop_gradient(index), seen, dims.index_topk)
        scores = jnp.einsum("rqkgd,rskd->rkgqs", q_blk, k) / math.sqrt(dh)
        probs = softmax_over(scores, chosen[:, None, None])
        out = jnp.einsum("rkgqs,rskd->rqkgd", probs, v)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))
        guess = softmax_over(index, chosen)
        live = chosen & (target > 0)
        ratio = jnp.where(live, target, 1.0) / jnp.where(live, guess, 1.0)
        kl = jnp.sum(jnp.where(live, target * jnp.log(ratio), 0.0), axis=-1)
        everything = softmax_over(jax.lax.stop_gradient(scores), seen)
        mass = jnp.mean(jnp.sum(jnp.where(chosen[:, None, None], everything, 0.0), axis=-1),
                        axis=(1, 2))
        return out, kl, mass

    block = min(dims.query_block, t)
    pad = (-t) % block   # queries past the end: they see every key and are dropped
    by_block = lambda z: jnp.moveaxis(jnp.pad(  # noqa: E731
        z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2)).reshape(
            rows, (t + pad) // block, block, *z.shape[2:]), 1, 0)
    positions = jnp.arange(t + pad).reshape(-1, block)
    out, kl, mass = jax.lax.map(
        jax.checkpoint(one_block), (by_block(q), by_block(q_idx), by_block(w_idx), positions))
    whole = lambda z: jnp.moveaxis(z, 0, 1).reshape(  # noqa: E731
        rows, t + pad, *z.shape[3:])[:, :t]
    return whole(out).reshape(rows, t, nq * dh) @ p["o_proj"], whole(kl), whole(mass)


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


def logits_of(weights, tokens, dims: Dims):
    """Logits over the held rows of the vocabulary [rows, T, rows held], and
    by layer the load [layers, experts], the indexer's KL and the selected
    keys' share of the dense attention mass [layers]."""
    import jax
    import jax.numpy as jnp

    eps = dims.rms_norm_eps
    rows, t = tokens.shape

    def layer_fn(h, p):
        out, kl, mass = attention(norm(h, p["attn_norm"], eps), p["attn"], p["indexer"], dims)
        h = h + out
        out, load = moe(norm(h, p["ffn_norm"], eps).reshape(rows * t, -1), p["moe"], dims)
        return h + out.reshape(h.shape), (load, jnp.mean(kl.astype(jnp.float32)),
                                          jnp.mean(mass.astype(jnp.float32)))

    stacked = jax.tree_util.tree_map(lambda *ws: jnp.stack(ws), *weights["layers"])
    h, (load, kl, mass) = jax.lax.scan(jax.checkpoint(layer_fn), weights["embed"][tokens], stacked)
    z = norm(h, weights["final_norm"], eps) @ weights["head"]
    return z, {"router_load": load, "index_kl": kl, "selected_mass": mass}


def loss_of(weights, tokens, targets, dims: Dims, precision: str = "reference"):
    """``(L_LM + L_I, by-layer numbers with "lm_loss" beside them)``: the
    mean next-token cross-entropy over the held rows of the vocabulary plus
    the indexer's KL, mean over layers."""
    import jax
    import jax.numpy as jnp

    dt = _dtype(precision)
    weights = jax.tree_util.tree_map(lambda w: w.astype(dt), weights)
    z, by_layer = logits_of(weights, tokens, dims)
    top = jnp.max(z, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(jnp.exp(z - top), axis=-1))
    picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    lm = jnp.mean((lse - picked).astype(jnp.float32))
    return lm + jnp.mean(by_layer["index_kl"]), {**by_layer, "lm_loss": lm}


def make_step(dims: Dims, precision: str = "reference"):
    """jitted ``(weights, mu, nu, count, tokens, targets) -> (weights, mu, nu,
    count, loss, gradient norm per leaf, by-layer numbers)``.  ``weights`` are
    the master values (float32, or bfloat16 where the precision keeps no
    float32 copy)."""
    import jax
    import jax.numpy as jnp

    dt = _dtype(precision)

    def step(weights, mu, nu, count, tokens, targets):
        (loss, by_layer), grads = jax.value_and_grad(
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
        return unflat(0), unflat(1), unflat(2), count, loss, grad_norms, by_layer

    step.__name__ = f"keye_vl2_reference_step_{precision}"
    return jax.jit(step, donate_argnums=(0, 1, 2))


def first_steps(start_weights, feed, dims: Dims, n_steps: int = 3,
                precision: str = "reference"):
    """Follow the first ``n_steps`` from ``start_weights`` (a float32 tree of
    the seed's draw) over ``feed``; returns the numbers the program is
    compared on (every step's loss ``L_LM + L_I``, the first gradient's norm
    per leaf, the norm per leaf of the weights' change after the last step)
    and, beside them, every step's by-layer numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dt = _dtype(precision)
    if precision == "half_batch":
        feed = [tuple(z[:, :z.shape[1] // 2] for z in batch) for batch in feed]
    with jax.default_matmul_precision("highest"):
        if precision == "state_unchanged":
            loss = jax.jit(lambda w, tokens, targets: loss_of(w, tokens, targets, dims)[0])
            still = [0.0] * len(jax.tree_util.tree_leaves(start_weights))
            return {"loss": [float(loss(start_weights, *feed[i % len(feed)]))
                             for i in range(n_steps)],
                    "grad_norm": still, "change_norm": still}
        step = make_step(dims, precision)
        # a copy: the step donates its weights, the start is compared later
        weights = jax.tree_util.tree_map(
            lambda w: jnp.array(w, dtype=dt, copy=True), start_weights)
        zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
            lambda w: jnp.zeros(w.shape, dt), start_weights)
        mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.int32)
        losses, first_grad = [], None
        by_layer = {"router_load": [], "index_kl": [], "selected_mass": [], "lm_loss": []}
        for i in range(n_steps):
            tokens, targets = feed[i % len(feed)]
            weights, mu, nu, count, loss, grad_norms, found = step(
                weights, mu, nu, count, tokens, targets)
            losses.append(float(loss))
            for name, column in by_layer.items():
                column.append(np.asarray(found[name]).tolist())
            if first_grad is None:
                first_grad = np.asarray(grad_norms, dtype=np.float64)

        def keye_vl2_reference_change(new, old):
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(new),
                                jax.tree_util.tree_leaves(old))])

        change = jax.jit(keye_vl2_reference_change)(weights, start_weights)
    return {"loss": losses, "grad_norm": first_grad.tolist(),
            "change_norm": np.asarray(change, dtype=np.float64).tolist(), **by_layer}
