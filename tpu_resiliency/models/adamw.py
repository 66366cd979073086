"""The AdamW update of one leaf, shared by the reference workloads' steps.

``transformer.make_train_step`` maps it over its flat leaves, the two routed
models' steps over their trees (``adamw_tree``); the benchmark holds the
lowered text of the first to a recorded hash
(``chipbench/tests/test_chipbench_bits.py``), so the operations and their
order here are that step's, unchanged.
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


def init_adamw_state(params):
    """``{"mu", "nu", "count", "master"}`` for ``adamw_tree``: two float32
    moments a leaf, the step count, and a float32 master copy of every leaf
    that is not float32 itself (None where it is: an empty subtree)."""
    import jax
    import jax.numpy as jnp

    zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {
        "mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.int32),
        "master": jax.tree_util.tree_map(
            lambda p: None if p.dtype == jnp.float32 else p.astype(jnp.float32), params),
    }


def adamw_tree(params, grads, opt, lr):
    """``(params, {"mu", "nu", "count", "master"})`` after one step over a
    tree of parameters whose ``opt["master"]`` holds None (an empty subtree)
    for every leaf that is float32 itself; ``opt``'s other entries are the
    caller's."""
    import jax
    import jax.numpy as jnp

    count = opt["count"] + 1
    cf = count.astype(jnp.float32)
    no_master = lambda x: x is None  # noqa: E731
    out = jax.tree_util.tree_map(
        lambda p, g, mu, nu, master: adamw_leaf(p, g, mu, nu, master, cf, lr),
        params, grads, opt["mu"], opt["nu"], opt["master"], is_leaf=no_master)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda _, o: o[i], params, out)
    masters = jax.tree_util.tree_map(
        lambda _, o, old: None if old is None else o[3],
        params, out, opt["master"], is_leaf=no_master)
    return pick(0), {"mu": pick(1), "nu": pick(2), "count": count, "master": masters}
