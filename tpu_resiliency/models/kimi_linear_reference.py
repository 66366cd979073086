"""Plain reference for the ``kimi_linear`` family: forward, loss, gradients,
AdamW and the router's bias update, in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``: token by token, no chunking, no
kernel, no sorting of tokens by expert (a held expert runs over every token,
one expert after the other).

Written from the equations; imports nothing of the rest of this repository.
The file exists twice, byte for byte: ``tpu_resiliency/models/
kimi_linear_reference.py`` is the repository's reference for the model of
``kimi_linear.py``, ``chipbench/reference/kimi_linear.py`` the benchmark's
copy, which decides ``correct`` and which no later PR may edit
(``tests/test_kimi_linear.py`` holds the two to equal numbers).

One chip's share of a layer: the weights say how many heads and experts are
held (``Dims.expert_offset`` says which experts), the router always scores
all of its experts, and what the absent experts and heads would add is left
out.  With every expert and head held this is the uncut layer.

    x = rms(h) g          rms(x) = x / sqrt(mean(x^2) + 1e-5)
    h <- h + attn(x);     h <- h + ffn(rms(h) g')

KDA, per head (key and value width 128; ``conv4`` a causal depthwise
convolution of width 4 whose last tap meets the current token):

    q_t, k_t = l2norm(silu(conv4(W_q x)_t)), l2norm(silu(conv4(W_k x)_t))
    v_t = silu(conv4(W_v x)_t)
    a_t = exp(-exp(A_log) softplus(W_f2 W_f1 x_t + dt_bias))   one decay a key channel
    b_t = sigmoid(W_b x_t)
    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T,     S_0 = 0
    o_t = S_t^T q_t / sqrt(128)
    out = W_o concat_heads( rms_head(o_t) g_h * sigmoid(W_g2 W_g1 x_t + c_g) )

MLA without positions (``mla_use_nope``: the 64 shared "rope" channels are
used as they come, no rotation):

    q = W_q x  (heads x (128 + 64));   [c, k_r] = W_kva x  (512 + 64)
    [k_n, v] = W_kvb (rms(c) g_c)  (heads x (128 + 128));   k = [k_n, k_r]
    out = W_o concat_heads( causal softmax(q k^T / sqrt(192)) v )

Expert layer (sigmoid router over all experts, top 8 of score + bias, the
weights renormalised over the 8 chosen and scaled by 2.446):

    s = sigmoid(W_r x);  C = top8(s + bias);  w_e = 2.446 s_e / sum_{c in C} s_c
    out = swiglu_shared(x) + sum_{e in C, e held here} w_e swiglu_e(x)
    swiglu(x) = W_down (silu(W_gate x) * W_up x)
    load_e = assignments to e this step;  bias_e += 1e-3 sign(mean load - load_e)

    loss = mean_t( logsumexp(z_t) - z_t[target_t] ),   z = (rms(h_L) g_f) W_head

    AdamW (lr 1e-3, b1 0.9, b2 0.95, eps 1e-8, wd 0.01, on every trained leaf):
    mu = b1 mu + (1-b1) g;  nu = b2 nu + (1-b2) g^2
    w  = w - lr ( mu/(1-b1^t) / (sqrt(nu/(1-b2^t)) + eps) + wd w )

Every layer is recomputed in the backward pass (``jax.checkpoint``), so that
the float32 activations of one layer at a time sit on the device.

``precision`` selects the control: the same equations computed in the next
lower precision than the configuration states.

- ``"reference"``: float32 throughout, matmuls at ``highest``.
- ``"bf16_everywhere"``: what the configuration keeps in float32 (master
  copy, moments, router scores, softmax, norm statistics, decays, the scan's
  state, the loss) in bfloat16 as well.
"""

from __future__ import annotations

import dataclasses
import math

LR, B1, B2, EPS, WD = 1e-3, 0.9, 0.95, 1e-8, 0.01
PRECISIONS = ("reference", "bf16_everywhere")


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the weights' shapes do not say."""

    heads: int                   # held here, of both attention kinds
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    expert_offset: int = 0       # the first expert held here
    rms_norm_eps: float = 1e-5
    bias_update_rate: float = 1e-3


def _dtype(precision: str):
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")
    return jnp.float32 if precision == "reference" else jnp.bfloat16


def rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


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


def kda(x, p, dims: Dims):
    """Kimi Delta Attention over ``x`` [rows, T, d], token by token."""
    import jax
    import jax.numpy as jnp

    rows, t, _ = x.shape
    heads = p["A_log"].shape[0]
    dh = p["head_norm"].shape[0]
    split = lambda z: z.reshape(rows, t, heads, dh)  # noqa: E731

    def l2norm(z):
        return z / jnp.sqrt(jnp.sum(jnp.square(z), axis=-1, keepdims=True) + 1e-6)

    q = l2norm(split(silu(conv4(x @ p["wq"], p["conv_q"]))))
    k = l2norm(split(silu(conv4(x @ p["wk"], p["conv_k"]))))
    v = split(silu(conv4(x @ p["wv"], p["conv_v"])))
    rate = jnp.exp(p["A_log"])[None, None, :, None]
    pre = split((x @ p["wf1"]) @ p["wf2"] + p["dt_bias"])
    softplus = jnp.where(pre > 20.0, pre, jnp.log1p(jnp.exp(jnp.minimum(pre, 20.0))))
    a = jnp.exp(-rate * softplus)
    b = sigmoid(x @ p["wb"])                              # [rows, T, heads]

    def token(state, qkvab):
        q_t, k_t, v_t, a_t, b_t = qkvab                   # [rows, heads, ...]
        state = a_t[..., None] * state                    # diag(a) S
        seen = jnp.einsum("rhk,rhkv->rhv", k_t, state)    # k^T diag(a) S
        state = state + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("rhk,rhkv->rhv", q_t, state)

    by_time = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    start = jnp.zeros((rows, heads, dh, dh), x.dtype)
    _, o = jax.lax.scan(token, start, tuple(map(by_time, (q / math.sqrt(dh), k, v, a, b))))
    o = jnp.moveaxis(o, 0, 1)                             # [rows, T, heads, dh]
    gate = sigmoid(split((x @ p["wg1"]) @ p["wg2"] + p["bg"]))
    o = rms(o, p["head_norm"], dims.rms_norm_eps) * gate
    return o.reshape(rows, t, heads * dh) @ p["wo"]


def mla(x, p, dims: Dims):
    """Latent attention without positions over ``x`` [rows, T, d]."""
    import jax.numpy as jnp

    rows, t, _ = x.shape
    heads, nope, rope, dv = (dims.heads, dims.qk_nope_head_dim,
                             dims.qk_rope_head_dim, dims.v_head_dim)
    q = (x @ p["wq"]).reshape(rows, t, heads, nope + rope)
    latent = x @ p["wkva"]
    c, k_rope = latent[..., :-rope], latent[..., -rope:]
    kv = (rms(c, p["kv_norm"], dims.rms_norm_eps) @ p["wkvb"]).reshape(
        rows, t, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, :, None, :], (rows, t, heads, rope))], axis=-1)
    scores = jnp.einsum("rqhd,rkhd->rhqk", q, k) / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores)
    probs = weights / jnp.sum(weights, axis=-1, keepdims=True)
    out = jnp.einsum("rhqk,rkhd->rqhd", probs, kv[..., nope:])
    return out.reshape(rows, t, heads * dv) @ p["wo"]


def route(x, router, bias, dims: Dims):
    """``(chosen experts [tokens, 8], their weights, load over all experts)``
    of ``x`` [tokens, d]."""
    import jax
    import jax.numpy as jnp

    s = sigmoid(x @ router)
    _, chosen = jax.lax.top_k(s + bias.astype(s.dtype), dims.experts_per_token)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * dims.routed_scaling_factor
    load = jnp.zeros((router.shape[1],), jnp.int32).at[chosen.reshape(-1)].add(1)
    return chosen, weights, load


def routed(x, p, bias, dims: Dims):
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


def moe(x, p, bias, dims: Dims):
    mine, load = routed(x, p, bias, dims)
    return swiglu(x, p["shared"]) + mine, load


def logits_of(weights, tokens, bias, dims: Dims):
    """Logits over the held rows of the vocabulary [rows, T, rows held], and
    the load of every expert layer [expert layers, experts]."""
    import jax
    import jax.numpy as jnp

    eps = dims.rms_norm_eps
    rows, t = tokens.shape

    def layer_fn(h, p, layer_bias):
        x = rms(h, p["attn_norm"], eps)
        h = h + (kda(x, p["kda"], dims) if "kda" in p else mla(x, p["mla"], dims))
        x = rms(h, p["ffn_norm"], eps)
        if "moe" not in p:
            return h + swiglu(x, p["ffn"]), None
        out, load = moe(x.reshape(rows * t, -1), p["moe"], layer_bias, dims)
        return h + out.reshape(h.shape), load

    h = weights["embed"][tokens]
    loads, n_moe = [], 0
    for p in weights["layers"]:
        layer_bias = None
        if "moe" in p:
            layer_bias, n_moe = bias[n_moe], n_moe + 1
        h, load = jax.checkpoint(layer_fn)(h, p, layer_bias)
        if load is not None:
            loads.append(load)
    z = rms(h, weights["final_norm"], eps) @ weights["head"]
    return z, jnp.stack(loads)


def loss_of(weights, tokens, targets, bias, dims: Dims, precision: str = "reference"):
    """Mean next-token cross-entropy over the held rows of the vocabulary,
    and the load."""
    import jax
    import jax.numpy as jnp

    dt = _dtype(precision)
    weights = jax.tree_util.tree_map(lambda w: w.astype(dt), weights)
    z, load = logits_of(weights, tokens, bias, dims)
    top = jnp.max(z, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(jnp.exp(z - top), axis=-1))
    picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    return jnp.mean((lse - picked).astype(jnp.float32)), load


def n_expert_layers(weights) -> int:
    return sum(1 for p in weights["layers"] if "moe" in p)


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
            bias = jnp.zeros((n_expert_layers(weights),
                              weights["layers"][-1]["moe"]["router"].shape[1]),
                             jnp.float32)
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

    step.__name__ = f"kimi_linear_reference_step_{precision}"
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
    with jax.default_matmul_precision("highest"):
        step = make_step(dims, precision)
        # a copy: the step donates its weights, the start is compared later
        weights = jax.tree_util.tree_map(
            lambda w: jnp.array(w, dtype=dt, copy=True), start_weights)
        zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
            lambda w: jnp.zeros(w.shape, dt), start_weights)
        mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.int32)
        bias = jnp.zeros((n_expert_layers(start_weights),
                          start_weights["layers"][-1]["moe"]["router"].shape[1]),
                         jnp.float32)
        losses, loads, first_grad = [], [], None
        for i in range(n_steps):
            tokens, targets = feed[i % len(feed)]
            weights, mu, nu, count, loss, grad_norms, bias, load = step(
                weights, mu, nu, count, tokens, targets, bias)
            losses.append(float(loss))
            loads.append(np.asarray(load).tolist())
            if first_grad is None:
                first_grad = np.asarray(grad_norms, dtype=np.float64)

        def kimi_linear_reference_change(new, old):
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(new),
                                jax.tree_util.tree_leaves(old))])

        change = jax.jit(kimi_linear_reference_change)(weights, start_weights)
    return {"loss": losses, "grad_norm": first_grad.tolist(),
            "change_norm": np.asarray(change, dtype=np.float64).tolist(),
            "router_bias": np.asarray(bias, dtype=np.float64).tolist(),
            "router_load": loads}
