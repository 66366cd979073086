"""The benchmark's inputs: weights, optimizer state and token feed from ``--seed``.

Everything is made on the device in one jitted call, in the types the step is
run in and in the tree layout it takes: what those are, the configuration's
family says (``chipbench/families/``: ``draw_params``, ``make_state``).  The
plain reference draws the same numbers through the family's ``draw_params``
and keeps them in float32, so neither side is handed anything the other has
made.

Also here, because the comparison that decides ``correct`` needs them and no
later PR may move them: the bit-exact fingerprint of a state tree and the
per-leaf norms of a tree and of the parameters' change since the seed.
"""

from __future__ import annotations

ADAM_B1 = 0.9  # the steps' first-moment decay: first gradient = moment_1 / (1 - b1)


def seed_key(seed: int):
    """A key for any whole-number seed, those past 2**31 included."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def make_state_fn(family, sizes):
    """jitted ``seed key -> (params, opt)`` as the family's step takes them."""
    import jax
    import jax.numpy as jnp

    def chipbench_init_state(key):
        return family.make_state(sizes, family.draw_params(sizes, key, jnp.bfloat16))

    return jax.jit(chipbench_init_state)


def make_feed(sizes, key):
    """``feed_batches`` batches of (tokens, targets), rows all different, on
    the device; step ``i`` takes batch ``i % feed_batches``."""
    import jax
    import jax.numpy as jnp

    def chipbench_feed(key):
        tokens = jax.random.randint(
            jax.random.fold_in(key, 0x7E5D),
            (sizes.feed_batches, sizes.rows, sizes.seq), 0, sizes.vocab_size,
            dtype=jnp.int32)
        return tokens, jnp.roll(tokens, -1, axis=-1)

    tokens, targets = jax.jit(chipbench_feed)(key)
    return [(tokens[i], targets[i]) for i in range(sizes.feed_batches)]


def make_fingerprint_fn():
    """jitted ``tree -> uint32[n_leaves, 2]``: every leaf's exact bit pattern
    folded with its position, so equal fingerprints mean bit-equal leaves (up
    to a 64-bit hash).  One pass over the state on the device."""
    import jax
    import jax.numpy as jnp

    def mix(x):
        x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
        x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
        return x ^ (x >> 16)

    def one(leaf):
        flat = leaf.reshape(-1)
        bits = {2: jnp.uint16, 4: jnp.uint32}[flat.dtype.itemsize]
        lanes = jax.lax.bitcast_convert_type(flat, bits).astype(jnp.uint32)
        idx = jax.lax.iota(jnp.uint32, flat.shape[0])
        a = mix(lanes ^ (idx * jnp.uint32(0x9E3779B9)))
        b = mix((lanes + jnp.uint32(0x7F4A7C15)) ^ (idx * jnp.uint32(0x85EBCA77)))
        return jnp.stack([a.sum(dtype=jnp.uint32), b.sum(dtype=jnp.uint32)])

    def chipbench_fingerprint(tree):
        return jnp.stack([one(x) for x in jax.tree_util.tree_leaves(tree)])

    return jax.jit(chipbench_fingerprint)


def make_norm_fns(family, sizes):
    """jitted readers of what the reference is compared on: per-leaf norms of
    a tree of the draw's structure (the family's ``first_moment`` after one
    step gives the first gradient as the optimizer got it), and per-leaf norms
    of the change of the family's ``master`` since the seed's draw."""
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)])

    def chipbench_leaf_norms(tree):
        return norms(tree)

    def chipbench_change_norms(master, key):
        start = family.draw_params(sizes, key, jnp.bfloat16)
        return norms(jax.tree_util.tree_map(
            lambda m, s: m - s.astype(jnp.float32), master, start))

    return jax.jit(chipbench_leaf_norms), jax.jit(chipbench_change_norms)


def make_reference_start_fn(family, sizes):
    """jitted ``seed key -> float32 tree`` of the seed's draw as the program
    holds it (rounded to bfloat16): where the plain reference starts."""
    import jax
    import jax.numpy as jnp

    def chipbench_reference_start(key):
        return jax.tree_util.tree_map(
            lambda w: w.astype(jnp.float32),
            family.draw_params(sizes, key, jnp.bfloat16))

    return jax.jit(chipbench_reference_start)
