"""The ``gpt2`` family: the dense, learned-position, tied-head decoder of
``tpu_resiliency/models/transformer.py``, at the sizes a GPT-2-style
``config.json`` gives (``gpt2-xl-1chip``, ``cerebras-gpt-1.3b-1chip``).

Every leaf is a bfloat16 parameter with a float32 master copy and two float32
moments, in the tree layout ``make_train_step`` takes: 14 bytes a parameter.
The plain reference is ``chipbench/reference/gpt2_family.py``.
"""

from __future__ import annotations

import dataclasses
import math

CONTROLS = ("bf16_everywhere",)


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


def load_sizes(cfg: dict, rehearsal: bool = False) -> Sizes:
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


def make_state(sizes: Sizes, params):
    """``(params, opt)`` as ``make_train_step`` takes them.  Traceable."""
    import jax
    import jax.numpy as jnp

    f32 = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda p: p.astype(jnp.float32), tree)
    zeros = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda p: jnp.zeros(p.shape, jnp.float32), tree)
    opt = {"mu": zeros(params), "nu": zeros(params),
           "count": jnp.zeros((), jnp.int32), "master": f32(params)}
    return params, opt


def first_moment(state):
    return state[1]["mu"]


def master(state):
    return state[1]["master"]


def make_step(sizes: Sizes):
    """The product's fused forward + backward + AdamW step at these sizes.
    Looked up on its module at every call: the self-tests break it there."""
    import jax.numpy as jnp

    from tpu_resiliency.models import transformer

    return transformer.make_train_step(transformer.TransformerConfig(
        vocab=sizes.vocab_size, d_model=sizes.n_embd, n_heads=sizes.n_head,
        n_layers=sizes.n_layer, d_ff=sizes.n_inner, max_seq=sizes.n_positions,
        dtype=jnp.bfloat16))


def reference_first_steps(start, feed, sizes: Sizes, n_steps: int = 3,
                          precision=None):
    from chipbench.reference import gpt2_family

    return gpt2_family.first_steps(start, feed, sizes.n_head, n_steps=n_steps,
                                   precision=precision or "reference")


def make_reference_step(sizes: Sizes):
    from chipbench.reference import gpt2_family

    return gpt2_family.make_step(sizes.n_head)


def forward_flops_per_token(sizes: Sizes) -> float:
    """Multiply-adds counted as two, one token through the forward pass:
    the four attention projections and the two feed-forward matmuls of every
    layer, causal attention over the (T+1)/2 keys an average query sees
    (scores and weighted values), and the tied output head.  Norms, softmax,
    GELU and the embedding gather are not counted."""
    d, f, t = sizes.n_embd, sizes.n_inner, sizes.seq
    per_layer = 2 * (4 * d * d + 2 * d * f) + 2 * 2 * d * (t + 1) / 2
    return sizes.n_layer * per_layer + 2 * sizes.vocab_size * d


def train_flops_per_token(sizes: Sizes) -> float:
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3 * forward_flops_per_token(sizes)
