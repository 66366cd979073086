"""What the harness relies on in a family's trees, checked at the rehearsal
cut without running anything: ``state_bytes`` is the summed bytes of the state
``make_state`` makes, ``n_params`` the drawn leaves' elements, and
``first_moment`` and ``master`` have the draw's tree.

    JAX_PLATFORMS=cpu python3 chipbench/tests/family_trees.py <checkout> <configuration file>

(as a command it checks a family of another checkout: the self-tests' copy.)
"""

import sys


def check(families, weights, config_file):
    import jax
    import jax.numpy as jnp

    family, sizes = families.of_file(config_file, rehearsal=True)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(weights.make_state_fn(family, sizes), key)
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state))
    assert nbytes == sizes.state_bytes, (nbytes, sizes.state_bytes)
    draw = jax.eval_shape(lambda k: family.draw_params(sizes, k, jnp.bfloat16), key)
    drawn = sum(x.size for x in jax.tree_util.tree_leaves(draw))
    assert drawn == sizes.n_params, (drawn, sizes.n_params)
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: x.shape, tree)  # noqa: E731
    assert shapes(family.first_moment(state)) == shapes(draw)
    assert shapes(family.master(state)) == shapes(draw)
    assert family.train_flops_per_token(sizes) > 0 and family.CONTROLS
    assert sizes.tokens_per_step == sizes.rows * sizes.seq and sizes.feed_batches >= 1
    return family, sizes


if __name__ == "__main__":
    sys.path.insert(0, sys.argv[1])
    from chipbench import families, weights

    found = check(families, weights, sys.argv[2])
    print(found[0].__name__, found[1].state_bytes)
