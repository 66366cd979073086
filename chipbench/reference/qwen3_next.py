"""Plain reference for the ``qwen3_next`` family: forward, loss, gradients and
AdamW, in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``: the delta rule token by token, no
chunked algorithm, attention over the whole score matrix, no kernel, no
sorting of tokens by expert (a held expert runs over every token, one expert
after the other).

Written from the equations; imports nothing of the rest of this repository.
The file exists twice, byte for byte: ``tpu_resiliency/models/
qwen3_next_reference.py`` is the repository's reference for the model of
``qwen3_next.py``, ``chipbench/reference/qwen3_next.py`` the benchmark's copy,
which decides ``correct`` and which no later PR may edit
(``tests/test_qwen3_next.py`` holds the two to equal numbers).

One chip's share of a layer: the weights say how many experts and rows of the
vocabulary are held (``Dims.expert_offset`` says which experts), the router
always scores all of its experts, and what the absent experts would add is
left out.  Both attention kinds are held whole.  With every expert held this
is the uncut layer.

    norm(x) = x / sqrt(mean(x^2) + 1e-6) (1 + w)
    h <- h + mixer(norm(h));     h <- h + moe(norm'(h))

Gated DeltaNet (``nk`` key heads each serving ``nv / nk`` consecutive value
heads, all of width 128; ``conv4`` one causal depthwise convolution of width 4
over the q, k and v channels, no bias, whose last tap meets the current token;
the columns of ``W_qkvz`` and ``W_ba`` lie key head by key head: q, k, its
value heads' v, their z; its value heads' b, their a):

    [q, k, v, z] = W_qkvz x;   [b, a] = W_ba x
    [q, k, v] <- silu(conv4([q, k, v]))
    q_t <- l2norm(q_t) / sqrt(128);   k_t <- l2norm(k_t)          per key head
    beta_t = sigmoid(b_t);   alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))
    S_t = (I - beta_t k_t k_t^T) alpha_t S_{t-1} + beta_t k_t v_t^T,    S_0 = 0
    o_t = S_t^T q_t                                               per value head
    out = W_o concat_heads( o_t / sqrt(mean(o_t^2) + 1e-6) w_n * silu(z_t) )

(``alpha_t`` is one number a value head in (0, 1); the head norm's scale is
``w_n``, not ``1 + w_n``.)  The recurrence is the line above, token by token;
to fit a chip it runs as a scan over blocks of tokens, each block under
``jax.checkpoint`` around a scan over its tokens, so that the backward pass
keeps a state a block and not a token: a device for memory
(``Dims.checkpoint_blocks`` False is the same numbers without it).

Gated attention (``nq`` query heads of width 256 over ``nkv`` key/value
heads, query head j reads key/value head ``j // (nq / nkv)``; the first 64
channels of q and k rotated as two halves of 32, theta 1e7, positions from 0):

    [q, gate] = W_q x  split per head (256 + 256);   k = W_k x;   v = W_v x
    q <- rope(norm_256(q));   k <- rope(norm_256(k))
    out = W_o concat_heads( causal softmax(q k^T / sqrt(256)) v * sigmoid(gate) )

Expert layer (softmax router over all experts, the top 10, their weights
renormalised over the 10 chosen; one shared expert behind a sigmoid gate):

    p = softmax(W_r x);  C = top10(p);  w_e = p_e / sum_{c in C} p_c
    out = sigmoid(w_s . x) swiglu_shared(x) + sum_{e in C, e held here} w_e swiglu_e(x)
    swiglu(x) = W_down (silu(W_gate x) * W_up x)
    load_e = assignments to e this step

    loss = mean_t( logsumexp(z_t) - z_t[target_t] ),   z = norm_f(h_L) W_head

    AdamW (lr 1e-3, b1 0.9, b2 0.95, eps 1e-8, wd 0.01, on every trained leaf):
    mu = b1 mu + (1-b1) g;  nu = b2 nu + (1-b2) g^2
    w  = w - lr ( mu/(1-b1^t) / (sqrt(nu/(1-b2^t)) + eps) + wd w )

Departures from the published model, both written into the configuration's
file: no auxiliary load-balancing loss, no multi-token-prediction module.

Every layer is recomputed in the backward pass (``jax.checkpoint``), so that
the float32 activations of one layer at a time sit on the device.

``precision`` selects the control: the same equations computed in the next
lower precision than the configuration states.

- ``"reference"``: float32 throughout, matmuls at ``highest``.
- ``"bf16_everywhere"``: what the configuration keeps in float32 (master
  copy, moments, router scores, softmaxes, norm statistics, gates and decays,
  the scan's state, the loss) in bfloat16 as well.
"""

from __future__ import annotations

import dataclasses
import math

LR, B1, B2, EPS, WD = 1e-3, 0.9, 0.95, 1e-8, 0.01
PRECISIONS = ("reference", "bf16_everywhere")


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the weights' shapes do not say."""

    rotary_dim: int = 64         # the rotated channels of a 256-wide head
    rope_theta: float = 1e7
    experts_per_token: int = 10
    expert_offset: int = 0       # the first expert held here
    rms_norm_eps: float = 1e-6
    scan_block: int = 64         # tokens a checkpointed block of the recurrence
    checkpoint_blocks: bool = True


def _dtype(precision: str):
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")
    return jnp.float32 if precision == "reference" else jnp.bfloat16


def rms(x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def norm(x, w, eps):
    """The model's norm: the scale is ``1 + w``."""
    return rms(x, eps) * (1.0 + w)


def silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-x))


def conv4(z, w):
    """Causal depthwise convolution over time: ``z`` [rows, T, channels],
    ``w`` [width, channels]; tap ``width - 1`` meets the current token."""
    import jax.numpy as jnp

    width, t = w.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[j] for j in range(width))


def swiglu(x, p):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def delta_rule(q, k, v, alpha, beta, dims: Dims):
    """``o_t = S_t^T q_t`` of the gated delta rule, token by token: ``q, k, v``
    [rows, T, heads, width], ``alpha, beta`` [rows, T, heads]."""
    import jax
    import jax.numpy as jnp

    rows, t, heads, dk = q.shape
    block = min(dims.scan_block, t)
    pad = (-t) % block  # tokens that leave the state as it is: alpha 1, beta 0
    if pad:
        widen = lambda z, fill=0.0: jnp.pad(  # noqa: E731
            z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2), constant_values=fill)
        q, k, v, alpha, beta = widen(q), widen(k), widen(v), widen(alpha, 1.0), widen(beta)

    def token(state, qkvab):
        q_t, k_t, v_t, a_t, b_t = qkvab                   # [rows, heads, ...]
        state = a_t[..., None, None] * state              # alpha S
        seen = jnp.einsum("rhk,rhkv->rhv", k_t, state)    # k^T alpha S
        state = state + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("rhk,rhkv->rhv", q_t, state)

    def one_block(state, xs):
        return jax.lax.scan(token, state, xs)

    if dims.checkpoint_blocks:
        one_block = jax.checkpoint(one_block)
    # [blocks, tokens of a block, rows, heads, ...]
    by_block = lambda z: jnp.moveaxis(z, 1, 0).reshape(  # noqa: E731
        (t + pad) // block, block, *z.shape[:1], *z.shape[2:])
    start = jnp.zeros((rows, heads, dk, v.shape[-1]), q.dtype)
    _, o = jax.lax.scan(one_block, start, tuple(map(by_block, (q, k, v, alpha, beta))))
    o = o.reshape(t + pad, rows, heads, v.shape[-1])[:t]
    return jnp.moveaxis(o, 0, 1)                          # [rows, T, heads, dv]


def gdn(x, p, dims: Dims):
    """Gated DeltaNet over ``x`` [rows, T, d]."""
    import jax.numpy as jnp

    rows, t, _ = x.shape
    dh = p["head_norm"].shape[0]
    nv = p["A_log"].shape[0]
    nk = p["in_proj_qkvz"].shape[1] // (2 * dh) - nv
    per = nv // nk                                        # value heads a key head

    def l2norm(z):
        return z / jnp.sqrt(jnp.sum(jnp.square(z), axis=-1, keepdims=True) + 1e-6)

    qkvz = (x @ p["in_proj_qkvz"]).reshape(rows, t, nk, (2 + 2 * per) * dh)
    ba = (x @ p["in_proj_ba"]).reshape(rows, t, nk, 2 * per)
    flat = lambda z: z.reshape(rows, t, -1)  # noqa: E731
    q, k = flat(qkvz[..., :dh]), flat(qkvz[..., dh:2 * dh])
    v = flat(qkvz[..., 2 * dh:(2 + per) * dh])
    z = qkvz[..., (2 + per) * dh:].reshape(rows, t, nv, dh)
    b, a = flat(ba[..., :per]), flat(ba[..., per:])       # [rows, T, nv]
    mixed = silu(conv4(jnp.concatenate([q, k, v], axis=-1), p["conv"]))
    q = l2norm(mixed[..., :nk * dh].reshape(rows, t, nk, dh)) / math.sqrt(dh)
    k = l2norm(mixed[..., nk * dh:2 * nk * dh].reshape(rows, t, nk, dh))
    v = mixed[..., 2 * nk * dh:].reshape(rows, t, nv, dh)
    q, k = jnp.repeat(q, per, axis=2), jnp.repeat(k, per, axis=2)
    pre = a + p["dt_bias"]
    softplus = jnp.where(pre > 20.0, pre, jnp.log1p(jnp.exp(jnp.minimum(pre, 20.0))))
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * softplus)
    o = delta_rule(q, k, v, alpha, sigmoid(b), dims)
    o = rms(o, dims.rms_norm_eps) * p["head_norm"] * silu(z)
    return o.reshape(rows, t, nv * dh) @ p["out_proj"]


def rope(x, dims: Dims):
    """The first ``rotary_dim`` channels of ``x`` [rows, T, heads, width]
    rotated as two halves, positions 0..T-1."""
    import jax.numpy as jnp

    t, rot = x.shape[1], dims.rotary_dim
    inv_freq = dims.rope_theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    turn, keep = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-turn[..., rot // 2:], turn[..., :rot // 2]], axis=-1)
    return jnp.concatenate([turn * cos + half * sin, keep], axis=-1)


def attn(x, p, dims: Dims):
    """Gated attention over ``x`` [rows, T, d]."""
    import jax.numpy as jnp

    rows, t, _ = x.shape
    dh = p["q_norm"].shape[0]
    nq, nkv = p["q_proj"].shape[1] // (2 * dh), p["k_proj"].shape[1] // dh
    qg = (x @ p["q_proj"]).reshape(rows, t, nq, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = (x @ p["k_proj"]).reshape(rows, t, nkv, dh)
    v = (x @ p["v_proj"]).reshape(rows, t, nkv, dh)
    q = rope(norm(q, p["q_norm"], dims.rms_norm_eps), dims)
    k = rope(norm(k, p["k_norm"], dims.rms_norm_eps), dims)
    q = q.reshape(rows, t, nkv, nq // nkv, dh)            # query head j on head j // group
    scores = jnp.einsum("rqkgd,rskd->rkgqs", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores)
    probs = weights / jnp.sum(weights, axis=-1, keepdims=True)
    out = jnp.einsum("rkgqs,rskd->rqkgd", probs, v).reshape(rows, t, nq, dh)
    return (out * sigmoid(gate)).reshape(rows, t, nq * dh) @ p["o_proj"]


def route(x, router, dims: Dims):
    """``(chosen experts [tokens, 10], their weights, load over all experts)``
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


def routed(x, p, dims: Dims):
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


def shared(x, p):
    """The shared expert behind its sigmoid gate."""
    return sigmoid(x @ p["shared_gate"]) * swiglu(x, p["shared"])


def moe(x, p, dims: Dims):
    mine, load = routed(x, p, dims)
    return shared(x, p) + mine, load


def logits_of(weights, tokens, dims: Dims):
    """Logits over the held rows of the vocabulary [rows, T, rows held], and
    the load of every layer [layers, experts]."""
    import jax
    import jax.numpy as jnp

    eps = dims.rms_norm_eps
    rows, t = tokens.shape

    def layer_fn(h, p):
        x = norm(h, p["attn_norm"], eps)
        h = h + (gdn(x, p["gdn"], dims) if "gdn" in p else attn(x, p["attn"], dims))
        x = norm(h, p["ffn_norm"], eps)
        out, load = moe(x.reshape(rows * t, -1), p["moe"], dims)
        return h + out.reshape(h.shape), load

    h = weights["embed"][tokens]
    loads = []
    for p in weights["layers"]:
        h, load = jax.checkpoint(layer_fn)(h, p)
        loads.append(load)
    z = norm(h, weights["final_norm"], eps) @ weights["head"]
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

    step.__name__ = f"qwen3_next_reference_step_{precision}"
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
    with jax.default_matmul_precision("highest"):
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

        def qwen3_next_reference_change(new, old):
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(new),
                                jax.tree_util.tree_leaves(old))])

        change = jax.jit(qwen3_next_reference_change)(weights, start_weights)
    return {"loss": losses, "grad_norm": first_grad.tolist(),
            "change_norm": np.asarray(change, dtype=np.float64).tolist(),
            "router_load": loads}
