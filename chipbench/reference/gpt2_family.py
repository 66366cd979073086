"""Plain reference for the GPT-2-family configurations: forward, loss,
gradients and the AdamW update, in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``.

Written from the equations; imports nothing of ``tpu_resiliency``.  It serves
``gpt2-xl-1chip`` and ``cerebras-gpt-1.3b-1chip`` (one architecture, two sets
of sizes).  Departures from the published models are the configuration
files' ``assumed``: RMSNorm without bias, no linear biases, tanh GELU.

    h_0   = E[tokens] + P[:T]
    a_l   = h_l + softmax(mask(q k^T / sqrt(d_head))) v  W_o     q,k,v = rms(h_l) g1 W_{q,k,v}
    h_l+1 = a_l + gelu(rms(a_l) g2 W_1) W_2
    loss  = mean_t( logsumexp(z_t) - z_t[target_t] ),            z = rms(h_L) g_f E^T
    rms(x) = x / sqrt(mean(x^2) + 1e-6)

    AdamW (lr 1e-3, b1 0.9, b2 0.95, eps 1e-8, wd 0.01, on every leaf):
    mu = b1 mu + (1-b1) g;  nu = b2 nu + (1-b2) g^2
    w  = w - lr ( mu/(1-b1^t) / (sqrt(nu/(1-b2^t)) + eps) + wd w )

The gradient is taken over blocks of rows, one after the other, so that the
float32 activations of the whole batch never sit on the device together.

``precision`` selects the control: the same equations computed in the next
lower precision than the configuration states, which the comparison that
decides ``correct`` has to refuse.

- ``"reference"``: float32 throughout, matmuls at ``highest``.
- ``"bf16_everywhere"``: what the configuration keeps in float32 (master
  copy, moments, softmax, norm statistics, loss) in bfloat16 as well.
"""

from __future__ import annotations

import math

LR, B1, B2, EPS, WD = 1e-3, 0.9, 0.95, 1e-8, 0.01
PRECISIONS = ("reference", "bf16_everywhere")


def _rules(precision: str):
    """(weight dtype in the forward, activation dtype, statistics dtype,
    optimizer-state dtype) of one precision."""
    import jax.numpy as jnp

    if precision == "reference":
        return jnp.float32, jnp.float32, jnp.float32, jnp.float32
    if precision == "bf16_everywhere":
        return jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, jnp.bfloat16
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def loss_of(params, tokens, targets, n_head: int, precision: str = "reference"):
    """Mean next-token cross-entropy of ``tokens`` [rows, T]."""
    import jax
    import jax.numpy as jnp

    w_dt, act_dt, stat_dt, _ = _rules(precision)

    def mm(x, w):
        return jnp.matmul(x, w.astype(w_dt))

    def rms(x, g):
        var = jnp.mean(jnp.square(x.astype(stat_dt)), axis=-1, keepdims=True)
        return x * (1.0 / jnp.sqrt(var + 1e-6)).astype(x.dtype) * g.astype(w_dt)

    def gelu(x):
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))

    rows, t = tokens.shape
    d = params["embed"].shape[1]
    d_head = d // n_head
    embed = params["embed"].astype(w_dt)
    h = (embed[tokens] + params["pos"].astype(w_dt)[:t][None]).astype(act_dt)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    for layer in params["layers"]:
        x = rms(h, layer["ln1_scale"])
        q = mm(x, layer["wq"]).reshape(rows, t, n_head, d_head)
        k = mm(x, layer["wk"]).reshape(rows, t, n_head, d_head)
        v = mm(x, layer["wv"]).reshape(rows, t, n_head, d_head)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d_head)
        scores = jnp.where(causal[None, None], scores, -1e9).astype(stat_dt)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        probs = (weights / jnp.sum(weights, axis=-1, keepdims=True)).astype(act_dt)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(rows, t, d)
        h = h + mm(attn, layer["wo"])
        x = rms(h, layer["ln2_scale"])
        h = h + mm(gelu(mm(x, layer["w1"])), layer["w2"])
    h = rms(h, params["ln_f_scale"])
    logits = jnp.matmul(h, embed.T).astype(stat_dt)
    top = jnp.max(logits, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean((lse - picked).astype(jnp.float32))


def make_step(n_head: int, precision: str = "reference", rows_per_block: int = 1):
    """jitted ``(weights, mu, nu, count, tokens, targets) -> (weights, mu, nu,
    count, loss, gradient norm per leaf)``.  ``weights`` are the master
    values (float32, or bfloat16 where the precision keeps no float32 copy)."""
    import jax
    import jax.numpy as jnp

    _, _, _, state_dt = _rules(precision)

    def step(weights, mu, nu, count, tokens, targets):
        rows = tokens.shape[0]
        block = rows_per_block if rows % rows_per_block == 0 else rows
        blocks = (tokens.reshape(rows // block, block, -1),
                  targets.reshape(rows // block, block, -1))
        grad_fn = jax.value_and_grad(
            lambda w, tk, tg: loss_of(w, tk, tg, n_head, precision))

        def one_block(carry, tk_tg):
            loss_sum, grad_sum = carry
            loss, grads = grad_fn(weights, *tk_tg)
            grad_sum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), grad_sum, grads)
            return (loss_sum + loss, grad_sum), None

        zeros = jax.tree_util.tree_map(
            lambda w: jnp.zeros(w.shape, jnp.float32), weights)
        (loss_sum, grad_sum), _ = jax.lax.scan(
            one_block, (jnp.zeros((), jnp.float32), zeros), blocks)
        n_blocks = rows // block
        loss = loss_sum / n_blocks
        # the program's gradient leaves the backward pass in the parameters'
        # type; the lower precisions round it there too
        grads = jax.tree_util.tree_map(
            lambda g, w: (g / n_blocks).astype(w.dtype).astype(jnp.float32),
            grad_sum, weights)
        count = count + 1
        cf = count.astype(jnp.float32)

        def update(w, g, m, v):
            g = g.astype(state_dt)
            m2 = (B1 * m + (1 - B1) * g).astype(state_dt)
            v2 = (B2 * v + (1 - B2) * jnp.square(g)).astype(state_dt)
            m_hat = m2.astype(jnp.float32) / (1 - B1 ** cf)
            v_hat = v2.astype(jnp.float32) / (1 - B2 ** cf)
            w32 = w.astype(jnp.float32)
            w2 = w32 - LR * (m_hat / (jnp.sqrt(v_hat) + EPS) + WD * w32)
            return w2.astype(w.dtype), m2, v2

        flat_w, treedef = jax.tree_util.tree_flatten(weights)
        out = [update(w, g, m, v) for w, g, m, v in zip(
            flat_w, jax.tree_util.tree_leaves(grads),
            jax.tree_util.tree_leaves(mu), jax.tree_util.tree_leaves(nu))]
        unflat = lambda i: jax.tree_util.tree_unflatten(  # noqa: E731
            treedef, [o[i] for o in out])
        grad_norms = jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(g)))
            for g in jax.tree_util.tree_leaves(grads)])
        return unflat(0), unflat(1), unflat(2), count, loss, grad_norms

    step.__name__ = f"chipbench_reference_step_{precision}"
    return jax.jit(step, donate_argnums=(0, 1, 2))


def first_steps(start_weights, feed, n_head: int, n_steps: int = 3,
                precision: str = "reference", rows_per_block: int = 1):
    """Follow the first ``n_steps`` from ``start_weights`` (a float32 tree of
    the seed's draw) over ``feed``; returns the numbers the program is
    compared on: every step's loss, the first gradient's norm per leaf, and
    the norm per leaf of the weights' change after the last step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _, _, _, state_dt = _rules(precision)
    with jax.default_matmul_precision("highest"):
        step = make_step(n_head, precision, rows_per_block)
        # a copy: the step donates its weights, the start is compared later
        weights = jax.tree_util.tree_map(
            lambda w: jnp.array(w, dtype=state_dt, copy=True), start_weights)
        zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
            lambda w: jnp.zeros(w.shape, state_dt), start_weights)
        mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.int32)
        losses, first_grad = [], None
        for i in range(n_steps):
            tokens, targets = feed[i % len(feed)]
            weights, mu, nu, count, loss, grad_norms = step(
                weights, mu, nu, count, tokens, targets)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = np.asarray(grad_norms, dtype=np.float64)

        def chipbench_reference_change(new, old):
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(new),
                                jax.tree_util.tree_leaves(old))])

        change = jax.jit(chipbench_reference_change)(weights, start_weights)
    return {"loss": losses, "grad_norm": first_grad.tolist(),
            "change_norm": np.asarray(change, dtype=np.float64).tolist()}
