"""The ``toy`` family: the self-tests' proof that a family lands as new files.

Not the GPT-2 tree under other names: an untied head, one float32 leaf with
no bfloat16 copy (``gain``), and a buffer the optimizer never touches (``seen``,
int32, a count of every token id the step has met).  Its step is this file's
own and goes nowhere near the product's model; its plain reference is
``chipbench/reference/toy.py``.

    x = table[tokens] * gain;  c_t = mean(x_1 .. x_t);  z = tanh(c mix) head
    loss = mean cross-entropy;  AdamW as the reference's docstring has it
"""

from __future__ import annotations

import dataclasses

CONTROLS = ("bf16_everywhere",)
LR, B1, B2, EPS, WD = 1e-3, 0.9, 0.95, 1e-8, 0.01  # B1 is weights.ADAM_B1


@dataclasses.dataclass(frozen=True)
class Sizes:
    name: str
    width: int
    alphabet: int
    rows: int
    seq: int
    feed_batches: int

    @property
    def vocab_size(self) -> int:
        return self.alphabet

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

    @property
    def n_params(self) -> int:
        return 2 * self.alphabet * self.width + self.width ** 2 + self.width

    @property
    def state_bytes(self) -> int:
        shadowed = 2 * self.alphabet * self.width + self.width ** 2
        # bf16 + fp32 shadow and two moments; gain fp32 + two moments; seen; t
        return 14 * shadowed + 12 * self.width + 4 * self.alphabet + 4


def load_sizes(cfg: dict, rehearsal: bool = False) -> Sizes:
    shape = {**cfg["shape"], **(cfg["cpu_rehearsal_cut"] if rehearsal else {})}
    return Sizes(name=cfg["name"], width=shape["width"], alphabet=shape["alphabet"],
                 rows=shape["lines"], seq=shape["window"],
                 feed_batches=shape["batches_in_feed"])


def draw_params(sizes: Sizes, key, dtype):
    import jax
    import jax.numpy as jnp

    k_table, k_mix, k_head, k_gain = jax.random.split(key, 4)
    d, v = sizes.width, sizes.alphabet
    normal = lambda k, shape, scale: (  # noqa: E731
        jax.random.normal(k, shape, dtype=jnp.float32) * scale)
    return {
        "table": normal(k_table, (v, d), 1.0).astype(dtype),
        "mix": normal(k_mix, (d, d), d ** -0.5).astype(dtype),
        "head": normal(k_head, (d, v), d ** -0.5).astype(dtype),
        "gain": (1.0 + normal(k_gain, (d,), 0.1)).astype(dtype),
    }


def make_state(sizes: Sizes, params):
    import jax
    import jax.numpy as jnp

    f32 = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda p: p.astype(jnp.float32), tree)
    zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    shadowed = {k: params[k] for k in ("table", "mix", "head")}
    live = {**shadowed, "gain": params["gain"].astype(jnp.float32),
            "seen": jnp.zeros((sizes.alphabet,), jnp.int32)}
    opt = {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.int32),
           "shadow": f32(shadowed)}
    return live, opt


def first_moment(state):
    return state[1]["m"]


def master(state):
    return {**state[1]["shadow"], "gain": state[0]["gain"]}


def _loss(trained, tokens, targets):
    import jax
    import jax.numpy as jnp

    x = (trained["table"][tokens].astype(jnp.float32) * trained["gain"]).astype(
        jnp.bfloat16)
    steps = jnp.arange(1, tokens.shape[1] + 1, dtype=jnp.float32)[None, :, None]
    c = (jnp.cumsum(x.astype(jnp.float32), axis=1) / steps).astype(jnp.bfloat16)
    z = jnp.matmul(jnp.tanh(jnp.matmul(c, trained["mix"])), trained["head"])
    z = z.astype(jnp.float32)
    picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)


def make_step(sizes: Sizes):
    import jax
    import jax.numpy as jnp

    def toy_step(live, opt, batch):
        tokens, targets = batch
        trained = {k: live[k] for k in ("table", "mix", "head", "gain")}
        loss, grads = jax.value_and_grad(_loss)(trained, tokens, targets)
        t = opt["t"] + 1
        tf = t.astype(jnp.float32)
        new_live = {"seen": live["seen"].at[tokens.reshape(-1)].add(1)}
        new_opt = {"m": {}, "v": {}, "t": t, "shadow": {}}
        for k, g in grads.items():
            g = g.astype(jnp.float32)
            m = B1 * opt["m"][k] + (1 - B1) * g
            v = B2 * opt["v"][k] + (1 - B2) * jnp.square(g)
            w = opt["shadow"][k] if k in opt["shadow"] else live[k]
            w = w - LR * ((m / (1 - B1 ** tf)) / (jnp.sqrt(v / (1 - B2 ** tf)) + EPS)
                          + WD * w)
            new_opt["m"][k], new_opt["v"][k] = m, v
            if k in opt["shadow"]:
                new_opt["shadow"][k] = w
            new_live[k] = w.astype(live[k].dtype)
        return new_live, new_opt, loss

    return jax.jit(toy_step, donate_argnums=(0, 1))


def reference_first_steps(start, feed, sizes: Sizes, n_steps: int = 3,
                          precision=None):
    from chipbench.reference import toy

    return toy.first_steps(start, feed, n_steps=n_steps,
                           precision=precision or "reference")


def make_reference_step(sizes: Sizes):
    from chipbench.reference import toy

    return toy.make_step()


def train_flops_per_token(sizes: Sizes) -> float:
    """The two matmuls, forward and backward; the gather, the running mean
    and tanh are not counted."""
    return 3 * 2 * (sizes.width ** 2 + sizes.width * sizes.alphabet)
