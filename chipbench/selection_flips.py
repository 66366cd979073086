#!/usr/bin/env python3
"""How often the ``keye_vl2`` program and its plain reference choose another
key set, and route a token differently.

Not part of a benchmark run; read beside the limits' reasons
(``chipbench/routing_flips.py`` is the second family's and names its blocks).
The program rounds its activations to bfloat16, the reference does not, so
where a query's 2,048th and 2,049th index scores lie closer than that rounding
the two attend to another set, and where a token's 8th and 9th router scores do
they choose other experts.  On the chip, at the cell's own sizes, over the
three compared steps of each seed: of the queries that select (those that see
more than ``topk`` keys) the share whose set differs and the mean share of a
set's keys that differ; the share of (token, layer) pairs whose chosen experts
differ, and whose HELD chosen experts differ; the held experts' load in the
program (largest over mean); the indexer's KL and the selected mass by layer.

    chiprun -- python3 chipbench/selection_flips.py \
        chipbench/configs/keye-vl-2.0-30b-a3b-1chip.json 101 102
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def program_choices(cfg):
    """jitted ``(params, tokens) -> (chosen keys [layers, rows, T, T] bool,
    chosen experts [layers, tokens, 8])`` through the product's own blocks,
    layer by layer as its ``forward``."""
    import jax
    import jax.numpy as jnp

    from tpu_resiliency.models import keye_vl2 as kv

    def chipbench_program_choices(params, tokens):
        rows, t = tokens.shape
        seen = jnp.tril(jnp.ones((t, t), bool))
        h, keys, experts = params["embed"][tokens], [], []
        for p in params["layers"]:
            u = kv._norm(h, p["attn_norm"], cfg.rms_norm_eps)
            q, k, w = kv.indexer_inputs(u, p["indexer"], cfg)
            # a block of queries at a time: 16 index heads of [T, T] float32 would be 1 GB
            keys.append(jnp.concatenate([kv.select_keys(
                kv.index_scores(q[:, lo:lo + cfg.attn_block], k, w[:, lo:lo + cfg.attn_block]),
                seen[lo:lo + cfg.attn_block], cfg.index_topk)
                for lo in range(0, t, cfg.attn_block)], axis=1))
            h = h + kv.attn_block(u, p["attn"], p["indexer"], cfg)[0]
            x = kv._norm(h, p["ffn_norm"], cfg.rms_norm_eps).reshape(rows * t, -1)
            experts.append(kv.route(x, p["moe"]["router"], cfg)[0])
            h = h + kv.moe_block(x, p["moe"], cfg)[0].reshape(h.shape)
        return jnp.stack(keys), jnp.stack(experts)

    return jax.jit(chipbench_program_choices)


def reference_choices(dims):
    """The same through the plain reference's functions, float32 at highest."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import keye_vl2 as ref

    def chipbench_reference_choices(weights, tokens):
        rows, t = tokens.shape
        seen = jnp.tril(jnp.ones((t, t), bool))
        eps = dims.rms_norm_eps
        h, keys, experts = weights["embed"][tokens], [], []
        for p in weights["layers"]:
            u = ref.norm(h, p["attn_norm"], eps)
            q, k, w = ref.indexer(u, p["indexer"], dims)
            keys.append(jnp.concatenate([ref.selected(
                ref.index_scores(q[:, lo:lo + dims.query_block], k, w[:, lo:lo + dims.query_block]),
                seen[lo:lo + dims.query_block], dims.index_topk)
                for lo in range(0, t, dims.query_block)], axis=1))
            h = h + ref.attention(u, p["attn"], p["indexer"], dims)[0]
            x = ref.norm(h, p["ffn_norm"], eps).reshape(rows * t, -1)
            experts.append(ref.route(x, p["moe"]["router"], dims)[0])
            h = h + ref.moe(x, p["moe"], dims)[0].reshape(h.shape)
        return jnp.stack(keys), jnp.stack(experts)

    return jax.jit(chipbench_reference_choices)


def flips(keys, wanted_keys, experts, wanted_experts, topk, offset, held):
    """Shares of differing choices from ``[steps, layers, ...]`` arrays of
    both sides."""
    import numpy as np

    t = keys.shape[-1]
    selects = np.arange(t) >= topk                       # queries that see more than topk keys
    missing = np.sum(keys & ~wanted_keys, axis=-1)[..., selects]   # keys of a set the other lacks
    found = {"selecting_queries": int(missing.size),
             "key_set_differs": float(np.mean(missing > 0)) if missing.size else 0.0,
             "keys_of_a_set_that_differ": float(np.mean(missing) / topk) if missing.size else 0.0}
    a, b = np.sort(experts, axis=-1), np.sort(wanted_experts, axis=-1)
    mine = lambda c: np.where((c >= offset) & (c < offset + held), c, -1)  # noqa: E731
    found.update(
        pairs=int(a[..., 0].size), chosen_experts_differ=float(np.any(a != b, axis=-1).mean()),
        held_experts_differ=float(np.any(
            np.sort(mine(experts), -1) != np.sort(mine(wanted_experts), -1), axis=-1).mean()))
    return found


def one_seed(config_file, seed, n_steps=3, rehearsal=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import families, weights
    from chipbench.reference import keye_vl2 as ref
    from tpu_resiliency.models import keye_vl2 as kv

    family, sizes = families.of_file(config_file, rehearsal=rehearsal)
    cfg, dims = family.model_config(sizes, jnp.bfloat16), family.reference_dims(sizes)
    key = weights.seed_key(seed)
    feed = weights.make_feed(sizes, key)
    step, choices = family.make_step(sizes), program_choices(cfg)
    params, opt = weights.make_state_fn(family, sizes)(key)
    keys, experts, load, selection, by_layer = [], [], [], [], []
    for i in range(n_steps):
        found = choices(params, feed[i][0])
        keys.append(np.asarray(found[0]))
        experts.append(np.asarray(found[1]))
        params, opt, _ = step(params, opt, feed[i])
        load.append(kv.routing_stats(opt, cfg))
        selection.append(kv.selection_stats(opt, cfg))
        by_layer.append({"index_kl": np.asarray(opt["index_kl"]).tolist(),
                         "selected_mass": np.asarray(opt["selected_mass"]).tolist()})
    for leaf in jax.tree_util.tree_leaves((params, opt)):
        leaf.delete()
    with jax.default_matmul_precision("highest"):
        w = weights.make_reference_start_fn(family, sizes)(key)
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, w)  # noqa: E731
        mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.int32)
        ref_step, ref_choices = ref.make_step(dims), reference_choices(dims)
        wanted_keys, wanted_experts = [], []
        for i in range(n_steps):
            found = ref_choices(w, feed[i][0])
            wanted_keys.append(np.asarray(found[0]))
            wanted_experts.append(np.asarray(found[1]))
            w, mu, nu, count, *_ = ref_step(w, mu, nu, count, *feed[i])
    for leaf in jax.tree_util.tree_leaves((w, mu, nu)):
        leaf.delete()
    found = flips(np.stack(keys), np.stack(wanted_keys), np.stack(experts),
                  np.stack(wanted_experts), sizes.index_topk, sizes.expert_offset,
                  sizes.experts_held)
    found.update(seed=seed, held_load=load, selection=selection, by_layer=by_layer,
                 held_load_max_over_mean=max(s["max"] / max(s["mean"], 1e-9) for s in load))
    return found


if __name__ == "__main__":
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("chipbench selection_flips: read on the chip; no TPU here")
    print(json.dumps({"device": dev.device_kind}))
    for s in sys.argv[2:]:
        print(json.dumps(one_seed(sys.argv[1], int(s))), flush=True)
