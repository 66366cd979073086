"""Plain reference for the ``toy`` family, from the equations, in float32 with
``jax.default_matmul_precision("highest")``; imports nothing of the family.

    x_t  = E[token_t] * g            c_t = (x_1 + ... + x_t) / t
    z_t  = tanh(c_t M) H             loss = mean_t( logsumexp(z_t) - z_t[target_t] )

    AdamW (lr 1e-3, b1 0.9, b2 0.95, eps 1e-8, wd 0.01, on E, M, H and g):
    mu = b1 mu + (1-b1) grad;  nu = b2 nu + (1-b2) grad^2
    w  = w - lr ( mu/(1-b1^t) / (sqrt(nu/(1-b2^t)) + eps) + wd w )

``precision="bf16_everywhere"`` is the control: weights, moments, activations
and the loss's statistics in bfloat16.
"""

from __future__ import annotations

LR, B1, B2, EPS, WD = 1e-3, 0.9, 0.95, 1e-8, 0.01


def _dtype(precision: str):
    import jax.numpy as jnp

    return {"reference": jnp.float32, "bf16_everywhere": jnp.bfloat16}[precision]


def loss_of(w, tokens, targets, dt):
    import jax.numpy as jnp

    e, m, h, g = (w[k].astype(dt) for k in ("table", "mix", "head", "gain"))
    x = e[tokens] * g
    t = jnp.arange(1, tokens.shape[1] + 1).astype(dt)[None, :, None]
    z = jnp.tanh((jnp.cumsum(x, axis=1) / t) @ m) @ h
    top = jnp.max(z, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(jnp.exp(z - top), axis=-1))
    picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    return jnp.mean((lse - picked).astype(jnp.float32))


def make_step(precision: str = "reference"):
    """jitted ``(weights, mu, nu, count, tokens, targets) -> (weights, mu, nu,
    count, loss, gradient norm per leaf)``."""
    import jax
    import jax.numpy as jnp

    dt = _dtype(precision)

    def toy_reference_step(w, mu, nu, count, tokens, targets):
        loss, grads = jax.value_and_grad(loss_of)(w, tokens, targets, dt)
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

        out = jax.tree_util.tree_map(update, w, grads, mu, nu)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda _, o: o[i], w, out)
        norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                           for g in jax.tree_util.tree_leaves(grads)])
        return pick(0), pick(1), pick(2), count, loss, norms

    return jax.jit(toy_reference_step)


def first_steps(start, feed, n_steps: int = 3, precision: str = "reference"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    dt = _dtype(precision)
    with jax.default_matmul_precision("highest"):
        step = make_step(precision)
        w = jax.tree_util.tree_map(lambda x: x.astype(dt), start)
        zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jnp.zeros(x.shape, dt), start)
        mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.int32)
        losses, first_grad = [], None
        for i in range(n_steps):
            w, mu, nu, count, loss, norms = step(w, mu, nu, count, *feed[i % len(feed)])
            losses.append(float(loss))
            if first_grad is None:
                first_grad = np.asarray(norms, np.float64)
        change = [float(jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))))
            for a, b in zip(jax.tree_util.tree_leaves(w),
                            jax.tree_util.tree_leaves(start))]
    return {"loss": losses, "grad_norm": first_grad.tolist(), "change_norm": change}
