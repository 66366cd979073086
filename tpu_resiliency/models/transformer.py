"""Decoder-only transformer reference workload (pure JAX, GSPMD-sharded).

This is the workload the resiliency stack wraps in benchmarks and the
driver's graft entry — NOT part of the resiliency capability surface (the
reference is workload-agnostic, SURVEY.md §2.8).  It exists so hang
detection, checkpoint overhead, and restart latency are measured against a
realistic MXU-bound training step.

TPU-first choices:
- bfloat16 activations/weights, fp32 master copy in the optimizer, so
  matmuls hit the MXU at full rate;
- dims padded to 128 multiples (MXU tiling);
- sharding via NamedSharding constraints (data on "data", heads/ffn on
  "model") — XLA inserts the all-reduces; no hand-written collectives;
- one fused train step under jit: fwd + bwd + adamw update.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np

from .adamw import adamw_leaf


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 1024
    dtype: Any = None  # resolved to bf16 on TPU, f32 elsewhere

    def resolved_dtype(self):
        import jax
        import jax.numpy as jnp

        if self.dtype is not None:
            return self.dtype
        return jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32


def _specs(cfg: TransformerConfig):
    """PartitionSpecs per parameter (heads/ffn on 'model').  Replicated
    leaves say ``P()``, the spelling jit gives its outputs: ``P(None)`` is
    the same placement but another cache key, and the step would compile a
    second time when its own outputs come back in."""
    from jax.sharding import PartitionSpec as P

    layer = {
        "wq": P(None, "model"), "wk": P(None, "model"), "wv": P(None, "model"),
        "wo": P("model", None),
        "w1": P(None, "model"), "w2": P("model", None),
        "ln1_scale": P(), "ln2_scale": P(),
    }
    return {
        "embed": P("model", None),        # vocab sharded over model axis
        "pos": P(),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
        "ln_f_scale": P(),
    }


def init_params(cfg: TransformerConfig, key=None, mesh=None) -> Dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    key = key if key is not None else jax.random.PRNGKey(0)
    dt = cfg.resolved_dtype()
    k = iter(jax.random.split(key, 4 + 8 * cfg.n_layers))

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else (1.0 / np.sqrt(shape[0]))
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dt)

    params: Dict[str, Any] = {
        "embed": dense(next(k), (cfg.vocab, cfg.d_model), scale=0.02),
        "pos": dense(next(k), (cfg.max_seq, cfg.d_model), scale=0.02),
        "layers": [],
        "ln_f_scale": jnp.ones((cfg.d_model,), dtype=dt),
    }
    for _ in range(cfg.n_layers):
        params["layers"].append(
            {
                "wq": dense(next(k), (cfg.d_model, cfg.d_model)),
                "wk": dense(next(k), (cfg.d_model, cfg.d_model)),
                "wv": dense(next(k), (cfg.d_model, cfg.d_model)),
                "wo": dense(next(k), (cfg.d_model, cfg.d_model)),
                "w1": dense(next(k), (cfg.d_model, cfg.d_ff)),
                "w2": dense(next(k), (cfg.d_ff, cfg.d_model)),
                "ln1_scale": jnp.ones((cfg.d_model,), dtype=dt),
                "ln2_scale": jnp.ones((cfg.d_model,), dtype=dt),
            }
        )
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_s = jax.tree_util.tree_leaves(
            _specs(cfg), is_leaf=lambda x: isinstance(x, P)
        )
        assert len(flat_p) == len(flat_s), "spec/param tree mismatch"
        placed = [
            jax.device_put(p, NamedSharding(mesh, s)) for p, s in zip(flat_p, flat_s)
        ]
        params = jax.tree_util.tree_unflatten(treedef, placed)
    return params


def _rmsnorm(x, scale):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jnp.reciprocal(jnp.sqrt(var + 1e-6)).astype(x.dtype)) * scale


def forward(params: Dict, tokens, cfg: TransformerConfig, mesh=None):
    """Causal LM forward -> logits [B, T, vocab]."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def constrain(x, spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        return x

    B, T = tokens.shape
    h = params["embed"][tokens] + params["pos"][:T][None, :, :]
    h = constrain(h, P("data", None, None))
    n_heads = cfg.n_heads
    head_dim = cfg.d_model // n_heads
    causal = jnp.tril(jnp.ones((T, T), dtype=bool))

    for layer in params["layers"]:
        x = _rmsnorm(h, layer["ln1_scale"])
        q = (x @ layer["wq"]).reshape(B, T, n_heads, head_dim)
        kk = (x @ layer["wk"]).reshape(B, T, n_heads, head_dim)
        v = (x @ layer["wv"]).reshape(B, T, n_heads, head_dim)
        q = constrain(q, P("data", None, "model", None))
        kk = constrain(kk, P("data", None, "model", None))
        v = constrain(v, P("data", None, "model", None))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(head_dim)
        scores = jnp.where(causal[None, None], scores, -1e9)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, cfg.d_model)
        h = h + attn @ layer["wo"]
        x = _rmsnorm(h, layer["ln2_scale"])
        ff = jax.nn.gelu(x @ layer["w1"])
        ff = constrain(ff, P("data", None, "model"))
        h = h + ff @ layer["w2"]
        h = constrain(h, P("data", None, None))

    h = _rmsnorm(h, params["ln_f_scale"])
    logits = h @ params["embed"].T  # weight tying
    return constrain(logits, P("data", None, None))


def loss_fn(params, batch, cfg: TransformerConfig, mesh=None):
    import jax
    import jax.numpy as jnp

    tokens, targets = batch
    logits = forward(params, tokens, cfg, mesh).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def init_opt_state(params):
    import jax
    import jax.numpy as jnp

    from jax.sharding import NamedSharding, PartitionSpec as P

    # every moment lives where its parameter lives (and is committed there
    # only if the parameter is): zeros made without a sharding would all
    # land on device 0, and the step would compile twice — once for these
    # inputs, once for its own outputs fed back in
    def f32(p):
        z = jnp.zeros(p.shape, dtype=jnp.float32)
        return jax.device_put(z, p.sharding) if p.committed else z

    leaves = jax.tree_util.tree_leaves(params)
    any_low = any(leaf.dtype != jnp.float32 for leaf in leaves)
    count = jnp.zeros((), dtype=jnp.int32)
    if leaves and leaves[0].committed:
        sh = leaves[0].sharding
        if isinstance(sh, NamedSharding):
            sh = NamedSharding(sh.mesh, P())  # replicated over the mesh
        count = jax.device_put(count, sh)
    state = {
        "mu": jax.tree_util.tree_map(f32, params),
        "nu": jax.tree_util.tree_map(f32, params),
        "count": count,
    }
    if any_low:
        # fp32 master copy: bf16 params would silently drop sub-ulp updates
        state["master"] = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), params
        )
    return state


def make_train_step(cfg: TransformerConfig, mesh=None, lr: float = 1e-3):
    """Fused jitted train step: (params, opt_state, batch) -> (params, opt_state, loss)."""
    import jax
    import jax.numpy as jnp

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh)
        )(params)
        count = opt["count"] + 1
        cf = count.astype(jnp.float32)
        has_master = "master" in opt

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_mu = jax.tree_util.tree_leaves(opt["mu"])
        flat_nu = jax.tree_util.tree_leaves(opt["nu"])
        flat_master = (
            jax.tree_util.tree_leaves(opt["master"])
            if has_master
            else [None] * len(flat_p)
        )
        new_p, new_mu, new_nu, new_master = [], [], [], []
        for p, g, mu, nu, m in zip(flat_p, flat_g, flat_mu, flat_nu, flat_master):
            a, b, c, d = adamw_leaf(p, g, mu, nu, m, cf, lr)
            new_p.append(a)
            new_mu.append(b)
            new_nu.append(c)
            new_master.append(d)
        new_opt = {
            "mu": jax.tree_util.tree_unflatten(treedef, new_mu),
            "nu": jax.tree_util.tree_unflatten(treedef, new_nu),
            "count": count,
        }
        if has_master:
            new_opt["master"] = jax.tree_util.tree_unflatten(treedef, new_master)
        return jax.tree_util.tree_unflatten(treedef, new_p), new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))


def make_batch(cfg: TransformerConfig, batch_size: int, seq: int, seed: int = 0, mesh=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, size=(batch_size, seq), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)
    t = jnp.asarray(tokens)
    tt = jnp.asarray(targets)
    if mesh is not None:
        sh = NamedSharding(mesh, P("data", None))
        t, tt = jax.device_put(t, sh), jax.device_put(tt, sh)
    return t, tt
