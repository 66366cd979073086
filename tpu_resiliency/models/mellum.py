"""Mellum-style decoder, one chip's share of it (pure JAX).

The sixth reference workload beside ``transformer.py``, ``kimi_linear.py``,
``qwen3_next.py``, ``keye_vl2.py`` and ``lfm2_moe.py``, and like them NOT part
of the resiliency capability surface: it exists so that the wrapper, the
tripwire, the straggler detector and the checkpoint paths meet a step of
8,192 tokens whose layers are of two kinds with the SAME leaves and different
programs, and 64 small experts' worth of pair-buffer gathers a layer.

The layers, after JetBrains/Mellum2-12B-A2.5B-Instruct (equations, and every
departure from the published model: ``mellum_reference.py``):

- **Grouped-query attention** in every layer: 32 query heads of 128 over 4
  key/value heads, an RMSNorm with a scale over every q and k head, the whole
  head rotated as two halves.  The layer's kind (``layer_types``) decides two
  things and no leaf:
  - ``sliding_attention``: query i sees the ``sliding_window`` keys ``i -
    window < j <= i``; the rotation's frequencies are the default ones
    (scope ``mellum.attn.window``; ``window_attention_in_blocks``: a block of
    queries against the key blocks some query of it can see, and no other).
  - ``full_attention``: causal; YaRN frequencies, cos and sin both scaled by
    ``attention_factor`` (scope ``mellum.attn.full``; by
    ``qwen3_next.causal_attention_in_blocks`` as it is).
- **The routed expert layer** of ``kimi_linear.py`` (``held_experts``) under
  ``qwen3_next.route``: softmax over all 64 experts, the top 8, renormalised;
  no shared expert and no bias.
- RMSNorm (``w x / rms``), a final norm and an untied head over the held rows
  of the vocabulary.

Both kinds have the same leaves, but not the same program, so the layers are
not one ``lax.scan``: the step is unrolled, every layer under a
``jax.checkpoint`` that keeps the layer's input and computes the layer again
in the backward pass (keeping the matmuls' results makes the step 18 ms
shorter, 292 against 310, and puts the tripwire's deciding tick 1-4 ms from
the next one: PERF.md section 6, PR 51); every block of attention scores is
recomputed by its own rule.

bfloat16 parameters and matmuls; float32 master copy and moments, router
scores, norm statistics, the q and k norms, both rotary tables and the
rotation, softmaxes and the loss.  The last step's load rides in the
optimizer state and no gradient touches it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

from ..telemetry import gauge
from .adamw import adamw_tree, init_adamw_state
from .kimi_linear import _rmsnorm, held_experts, next_token_loss
from .lfm2_moe import routing_stats
from .qwen3_next import causal_attention_in_blocks, route

__all__ = ["MellumConfig", "init_params", "forward", "loss_fn", "init_opt_state",
           "make_train_step", "routing_stats", "window_attention_in_blocks"]

_WINDOW_KEY_BLOCKS = gauge(
    "tpurx_model_window_key_blocks",
    "key blocks a sliding-window layer's step visits over what a causal layer's visits")

# Two sizes of pair buffer: a quarter of all tokens x 8 pairs, or all of them.  An
# eighth of the experts are held and the load stays at an eighth of the pairs at
# ``make_train_step``'s rate (7,400-8,700 of 65,536 rows a layer over a run's
# hundred steps, PERF.md section 6, PR 51), so the quarter is twice the load and
# the ``switch`` never leaves its first branch; ``kimi_linear``'s smallest size,
# a sixteenth, lies under the load, and the whole buffer alone costs the step 78
# ms (370 against 292) in gathers and scatters over rows no expert reads.  Where routers do
# swing (a rate of 1e-3: every layer within ten steps) no rung is off the load's
# path and the step's length follows it, and with it the beat period the
# tripwire's budget is calibrated from (PR 42's finding, at a larger swing).
PAIR_BUFFER_LADDER = (4, 1)


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    hidden_size: int = 2304
    # the kind of every layer held here; every layer has the expert layer
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + ("full_attention",)
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024      # keys a query of a sliding layer sees, itself among them
    rope_theta: float = 500000.0    # of both kinds' tables
    yarn_factor: float = 16.0       # the full layers' table: rope_parameters.full_attention
    yarn_original_positions: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    moe_intermediate_size: int = 896
    num_experts: int = 64           # the router's outputs: every expert of the deployment
    experts_held: int = 8           # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    num_experts_per_token: int = 8
    vocab_rows: int = 12288         # rows of the embedding and columns of the head held here
    rms_norm_eps: float = 1e-6
    attn_block: int = 512           # queries a block of scores
    dtype: Any = None               # resolved to bf16 on TPU, f32 elsewhere

    def resolved_dtype(self):
        import jax
        import jax.numpy as jnp

        if self.dtype is not None:
            return self.dtype
        return jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32


def init_params(cfg: MellumConfig, key=None) -> Dict:
    """Normal draws scaled by 1/sqrt(fan_in), every norm's scale 1, the
    embedding at the residual stream's scale 1 (the head is a leaf of its
    own, drawn as a head: at 0.02 the routers would send nearly every token
    the same way from the second layer on, PERF.md section 2)."""
    import jax
    import jax.numpy as jnp

    key = key if key is not None else jax.random.PRNGKey(0)
    dt = cfg.resolved_dtype()
    d, dh = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    keys = iter(jax.random.split(key, 2 + 8 * len(cfg.layer_types)))

    def dense(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32) * scale).astype(dt)

    ones = lambda n: jnp.ones((n,), dtype=dt)  # noqa: E731
    width, held = cfg.moe_intermediate_size, cfg.experts_held
    params: Dict[str, Any] = {
        "embed": dense((cfg.vocab_rows, d), scale=1.0),
        "layers": [],
        "final_norm": ones(d),
        "head": dense((d, cfg.vocab_rows)),
    }
    for _ in cfg.layer_types:
        params["layers"].append({
            "attn_norm": ones(d), "ffn_norm": ones(d),
            "attn": {"q_proj": dense((d, nq * dh)), "k_proj": dense((d, nkv * dh)),
                     "v_proj": dense((d, nkv * dh)), "o_proj": dense((nq * dh, d)),
                     "q_norm": ones(dh), "k_norm": ones(dh)},
            "moe": {"router": dense((d, cfg.num_experts)),
                    "experts": {"w_gate": dense((held, d, width)),
                                "w_up": dense((held, d, width)),
                                "w_down": dense((held, width, d))}},
        })
    return params


# -- the two rotary tables ------------------------------------------------------------

def yarn_correction_range(cfg: MellumConfig) -> Tuple[int, int]:
    """``(low, high)``: the channel pairs between which YaRN's ramp runs, the
    pair that turns ``beta_fast`` times over the original positions rounded
    down and the one that turns ``beta_slow`` times rounded up, clipped to
    the head (18 and 35 at the published sizes)."""
    dim = cfg.head_dim

    def pair_turning(turns):
        return (dim * math.log(cfg.yarn_original_positions / (turns * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    return (max(math.floor(pair_turning(cfg.yarn_beta_fast)), 0),
            min(math.ceil(pair_turning(cfg.yarn_beta_slow)), dim - 1))


def inv_freq_and_scale(cfg: MellumConfig, kind: str):
    """``(inv_freq [head_dim / 2] float32, the factor on cos and sin)`` of one
    layer kind.  ``sliding_attention``: ``theta^(-2i/dim)``, factor 1.
    ``full_attention`` (YaRN): pair i's frequency is the default one below
    ``low``, a ``yarn_factor``-th of it above ``high`` and a linear blend
    between, and cos and sin are both times ``yarn_attention_factor``."""
    import numpy as np

    dim = cfg.head_dim
    default = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if kind == "sliding_attention":
        return default.astype(np.float32), 1.0
    low, high = yarn_correction_range(cfg)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    blended = (1.0 - ramp) * default + ramp * default / cfg.yarn_factor
    return blended.astype(np.float32), cfg.yarn_attention_factor


def _rotate(x, cfg: MellumConfig, kind: str):
    """``x`` [rows, T, heads, head_dim] rotated over the whole head as two
    halves by the table of its layer's kind, positions 0..T-1, in float32."""
    import jax.numpy as jnp

    t, dim = x.shape[1], x.shape[-1]
    inv_freq, scale = inv_freq_and_scale(cfg, kind)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., dim // 2:], x32[..., :dim // 2]], axis=-1)
    return (x32 * (jnp.cos(angle) * scale) + half * (jnp.sin(angle) * scale)).astype(x.dtype)


# -- attention inside a window ------------------------------------------------------------

def first_key_block(start: int, block: int, window: int) -> int:
    """The first key position a block of queries starting at ``start`` is given:
    the oldest key its first query sees, rounded down to a block."""
    return max(0, start - window + 1) // block * block


def window_key_blocks(t: int, block: int, window: int) -> Tuple[int, int]:
    """``(key blocks ``window_attention_in_blocks`` visits over ``t``
    positions, key blocks ``causal_attention_in_blocks`` visits)``."""
    starts = range(0, t, block)
    ends = [min(start + block, t) for start in starts]
    blocks = lambda n: -(-n // block)  # noqa: E731
    return (sum(blocks(end - first_key_block(start, block, window))
                for start, end in zip(starts, ends)),
            sum(blocks(end) for end in ends))


def window_attention_in_blocks(q, k, v, block: int, window: int):
    """``softmax(q k^T / sqrt(width)) v`` where query i sees the keys ``i -
    window < j <= i``, for ``q`` [rows, T, kv heads, group, width] and ``k,
    v`` [rows, T, kv heads, width]: ``block`` queries at a time against the
    keys from the oldest one the block's first query sees, rounded down to a
    block, through the block's last query (the key blocks before and after
    are masked for every query of the block, so they are left out), the band
    masked inside; the scores and their softmax in float32, each block
    recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp

    t, dh = q.shape[1], q.shape[-1]

    def one_block(start):
        lo, hi = first_key_block(start, block, window), min(start + block, t)

        @jax.checkpoint
        def attend(q_blk, k_seen, v_seen):
            scores = jnp.einsum("rqkgd,rskd->rkgqs", q_blk, k_seen,
                                preferred_element_type=jnp.float32) / math.sqrt(dh)
            query = jnp.arange(start, hi)[:, None]
            key = jnp.arange(lo, hi)[None, :]
            seen = (key <= query) & (key > query - window)
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e9), axis=-1).astype(v_seen.dtype)
            return jnp.einsum("rkgqs,rskd->rqkgd", probs, v_seen)

        return attend(q[:, start:hi], k[:, lo:hi], v[:, lo:hi])

    return jnp.concatenate([one_block(start) for start in range(0, t, block)], axis=1)


def attn_block(u, p, cfg: MellumConfig, kind: str):
    """Grouped-query attention over ``u``: q and k normed a head in float32,
    then the whole head rotated by the kind's table; query head j reads
    key/value head ``j // group``; the kind's mask."""
    import jax.numpy as jnp

    rows, t, _ = u.shape
    dh, nq, nkv = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    f32 = jnp.float32

    def head_norm(z, w):
        normed = _rmsnorm(z.astype(f32), w.astype(f32), cfg.rms_norm_eps)
        return _rotate(normed, cfg, kind).astype(u.dtype)

    q = head_norm((u @ p["q_proj"]).reshape(rows, t, nq, dh), p["q_norm"])
    k = head_norm((u @ p["k_proj"]).reshape(rows, t, nkv, dh), p["k_norm"])
    v = (u @ p["v_proj"]).reshape(rows, t, nkv, dh)
    q = q.reshape(rows, t, nkv, nq // nkv, dh)
    if kind == "sliding_attention":
        out = window_attention_in_blocks(q, k, v, cfg.attn_block, cfg.sliding_window)
    else:
        out = causal_attention_in_blocks(q, k, v, cfg.attn_block)
    return out.reshape(rows, t, nq * dh) @ p["o_proj"]


# -- the expert layer ---------------------------------------------------------------

def moe_block(x, p, cfg: MellumConfig):
    """``(output, load)`` of the expert layer for ``x`` [tokens, d]: the held
    experts' part and nothing else (no shared expert)."""
    chosen, weights, load = route(x, p["router"], cfg)
    return held_experts(x, chosen, weights, p["experts"], cfg, ladder=PAIR_BUFFER_LADDER), load


# -- the model ----------------------------------------------------------------------

_SCOPES = {"sliding_attention": "mellum.attn.window", "full_attention": "mellum.attn.full"}


def _layer(h, p, cfg: MellumConfig, kind: str):
    """``(h after the layer, load)``: ``h + attn(norm(h))``, then ``+
    moe(norm(.))``."""
    import jax

    rows, t, _ = h.shape
    with jax.named_scope(_SCOPES[kind]):
        h = h + attn_block(_rmsnorm(h, p["attn_norm"], cfg.rms_norm_eps), p["attn"], cfg, kind)
    x = _rmsnorm(h, p["ffn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("mellum.moe"):
        out, load = moe_block(x.reshape(rows * t, -1), p["moe"], cfg)
    return h + out.reshape(h.shape), load


def forward(params: Dict, tokens, cfg: MellumConfig):
    """``(logits [rows, T, vocab_rows], load [layers, num_experts])``.  Of
    every layer the backward pass finds its input kept and computes the rest
    again (the attention's scores and the held experts' buffers by their own
    rules)."""
    import jax
    import jax.numpy as jnp

    visited, causal = window_key_blocks(tokens.shape[1], cfg.attn_block, cfg.sliding_window)
    _WINDOW_KEY_BLOCKS.set(visited / causal)  # when the step is traced: shapes, no values
    h = params["embed"][tokens]
    loads = []
    for kind, p in zip(cfg.layer_types, params["layers"]):
        h, load = jax.checkpoint(
            lambda h, p, kind=kind: _layer(h, p, cfg, kind),
            policy=jax.checkpoint_policies.nothing_saveable)(h, p)
        loads.append(load)
    with jax.named_scope("mellum.head"):
        logits = _rmsnorm(h, params["final_norm"], cfg.rms_norm_eps) @ params["head"]
    return logits, jnp.stack(loads)


def loss_fn(params, batch, cfg: MellumConfig):
    """``(mean next-token cross-entropy over the held rows of the
    vocabulary, load)``."""
    import jax

    tokens, targets = batch
    logits, load = forward(params, tokens, cfg)
    with jax.named_scope("mellum.head"):
        return next_token_loss(logits, targets), load


def init_opt_state(params, cfg: MellumConfig):
    """``adamw.init_adamw_state`` (moments, master copies, the step count)
    and the last step's load."""
    import jax.numpy as jnp

    return {**init_adamw_state(params),
            "router_load": jnp.zeros((len(cfg.layer_types), cfg.num_experts), jnp.int32)}


def make_train_step(cfg: MellumConfig, lr: float = 1e-6):
    """Fused jitted train step: ``(params, opt, (tokens, targets)) -> (params,
    opt, loss)``: forward, backward, AdamW on every trained leaf; the step's
    load count replaces the state's.  ``lr`` is a post-training rate (a
    trained model's preference or reinforcement tuning, a schedule's floor),
    not the siblings' 1e-3: this chip computes the held experts' outputs and
    no others, so every gradient teaches the routers to prefer them, and with
    no auxiliary loss against it a layer's held share leaves its eighth within
    ten steps at 1e-3 and within fifty at 1e-5 (PERF.md section 6, PR 51)."""
    import jax

    if set(cfg.layer_types) - set(_SCOPES):
        raise ValueError(f"layer_types names every layer's kind, one of {sorted(_SCOPES)}")

    def step(params, opt, batch):
        (loss, load), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        params, new_opt = adamw_tree(params, grads, opt, lr)
        new_opt["router_load"] = load
        return params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))
