"""The benchmark's inputs: weights, optimizer state and token feed from ``--seed``.

Everything is made on the device in one jitted call, in the types the step is
run in (bf16 parameters; fp32 master copy, first and second moments), in the
tree layout ``make_train_step`` takes.  The plain reference draws the same
numbers through :func:`draw_params` and keeps them in float32, so neither side
is handed anything the other has made.

Also here, because the comparison that decides ``correct`` needs them and no
later PR may move them: the bit-exact fingerprint of a state tree and the
per-leaf norms of a tree and of the parameters' change since the seed.
"""

from __future__ import annotations

import dataclasses
import json
import math

ADAM_B1 = 0.9  # make_train_step's constant: first gradient = mu_1 / (1 - b1)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One configuration file, as the benchmark uses it."""

    name: str
    n_embd: int
    n_head: int
    n_layer: int
    n_inner: int
    n_positions: int
    vocab_size: int
    rows: int
    seq: int
    feed_batches: int

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

    @property
    def n_params(self) -> int:
        d, f = self.n_embd, self.n_inner
        per_layer = 4 * d * d + 2 * d * f + 2 * d
        return (self.vocab_size + self.n_positions) * d + self.n_layer * per_layer + d

    @property
    def state_bytes(self) -> int:
        # bf16 parameter + fp32 master, mu, nu; the step counter's 4 bytes
        return self.n_params * 14 + 4


def load_sizes(path: str, rehearsal: bool = False) -> Sizes:
    with open(path) as f:
        cfg = json.load(f)
    batch = dict(cfg["batch"])
    if rehearsal:
        cut = cfg["cpu_rehearsal_cut"]
        cfg = {**cfg, **cut}
        batch.update(rows=cut["rows"], seq=cut["n_positions"], feed_batches=4)
    return Sizes(
        name=cfg["name"], n_embd=cfg["n_embd"], n_head=cfg["n_head"],
        n_layer=cfg["n_layer"], n_inner=cfg["n_inner"],
        n_positions=cfg["n_positions"], vocab_size=cfg["vocab_size"],
        rows=batch["rows"], seq=batch["seq"], feed_batches=batch["feed_batches"],
    )


def seed_key(seed: int):
    """A key for any whole-number seed, those past 2**31 included."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def draw_params(sizes: Sizes, key, dtype):
    """The parameters in ``dtype``: normal draws scaled by 1/sqrt(fan_in)
    (0.02 for the two embeddings), norm scales 1.  Traceable."""
    import jax
    import jax.numpy as jnp

    d, f = sizes.n_embd, sizes.n_inner
    keys = iter(jax.random.split(key, 2 + 6 * sizes.n_layer))

    def dense(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
        draw = jax.random.normal(next(keys), shape, dtype=jnp.float32) * scale
        return draw.astype(dtype)

    ones = lambda: jnp.ones((d,), dtype=dtype)  # noqa: E731
    params = {
        "embed": dense((sizes.vocab_size, d), 0.02),
        "pos": dense((sizes.n_positions, d), 0.02),
        "layers": [],
        "ln_f_scale": ones(),
    }
    for _ in range(sizes.n_layer):
        params["layers"].append({
            "wq": dense((d, d)), "wk": dense((d, d)), "wv": dense((d, d)),
            "wo": dense((d, d)), "w1": dense((d, f)), "w2": dense((f, d)),
            "ln1_scale": ones(), "ln2_scale": ones(),
        })
    return params


def make_state_fn(sizes: Sizes):
    """jitted ``seed key -> (params, opt)`` as ``make_train_step`` takes them."""
    import jax
    import jax.numpy as jnp

    def chipbench_init_state(key):
        params = draw_params(sizes, key, jnp.bfloat16)
        f32 = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda p: p.astype(jnp.float32), tree)
        zeros = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda p: jnp.zeros(p.shape, jnp.float32), tree)
        opt = {"mu": zeros(params), "nu": zeros(params),
               "count": jnp.zeros((), jnp.int32), "master": f32(params)}
        return params, opt

    return jax.jit(chipbench_init_state)


def make_feed(sizes: Sizes, key):
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


def make_norm_fns(sizes: Sizes):
    """jitted readers of what the reference is compared on: per-leaf norms of
    a parameter-shaped tree (the first moment after one step gives the first
    gradient as the optimizer got it), and per-leaf norms of the master
    copy's change since the seed's draw."""
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)])

    def chipbench_leaf_norms(tree):
        return norms(tree)

    def chipbench_change_norms(master, key):
        start = draw_params(sizes, key, jnp.bfloat16)
        return norms(jax.tree_util.tree_map(
            lambda m, s: m - s.astype(jnp.float32), master, start))

    return jax.jit(chipbench_leaf_norms), jax.jit(chipbench_change_norms)


def make_reference_start_fn(sizes: Sizes):
    """jitted ``seed key -> float32 tree`` of the seed's draw as the program
    holds it (rounded to bfloat16): where the plain reference starts."""
    import jax
    import jax.numpy as jnp

    def chipbench_reference_start(key):
        return jax.tree_util.tree_map(
            lambda w: w.astype(jnp.float32), draw_params(sizes, key, jnp.bfloat16))

    return jax.jit(chipbench_reference_start)
