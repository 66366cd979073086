"""The AdamW update of one leaf, shared by the reference workloads' steps.

``transformer.make_train_step`` and ``kimi_linear.make_train_step`` both map
it over their trees; the benchmark holds the lowered text of the first to a
recorded hash (``chipbench/tests/test_chipbench_bits.py``), so the operations
and their order here are that step's, unchanged.
"""

from __future__ import annotations

B1, B2, EPS, WD = 0.9, 0.95, 1e-8, 0.01


def adamw_leaf(p, g, mu, nu, master, cf, lr, b1=B1, b2=B2, eps=EPS, wd=WD):
    """``(parameter, mu, nu, master)`` after one step; ``cf`` is the step
    count as float32.  The update runs in float32 against the master copy
    (sub-ulp updates accumulate there) and is cast down only for the compute
    parameter; a leaf without one (``master`` None) is its own master."""
    import jax.numpy as jnp

    g32 = g.astype(jnp.float32)
    mu2 = b1 * mu + (1 - b1) * g32
    nu2 = b2 * nu + (1 - b2) * jnp.square(g32)
    mu_hat = mu2 / (1 - b1 ** cf)
    nu_hat = nu2 / (1 - b2 ** cf)
    m = master if master is not None else p.astype(jnp.float32)
    m2 = m - lr * (mu_hat / (jnp.sqrt(nu_hat) + eps) + wd * m)
    return m2.astype(p.dtype), mu2, nu2, m2
