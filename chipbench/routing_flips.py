#!/usr/bin/env python3
"""How often the program and the plain reference route a token differently.

Not part of a benchmark run; read beside the limits' reasons.  The program
rounds its activations to bfloat16, the reference does not, so where a token's
8th and 9th router scores lie closer than that rounding the two choose another
set of 8 experts, and (if one of the two experts is held here) a held expert
gains or loses that token.  On the chip, at the cell's own sizes, over the
three compared steps of each seed: the share of (token, expert layer) pairs
whose chosen set differs, the share whose HELD chosen set differs, and the
held experts' load in the program (largest over mean).

    chiprun -- python3 chipbench/routing_flips.py chipbench/configs/kimi-linear-48b-a3b-1chip.json 101 102
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def program_routes(cfg):
    """jitted ``(params, bias, tokens) -> chosen [expert layers, tokens, 8]``
    through the product's own blocks, layer by layer as its ``forward``."""
    import jax
    import jax.numpy as jnp

    from tpu_resiliency.models import kimi_linear as kl

    def chipbench_program_routes(params, bias, tokens):
        rows, t = tokens.shape
        h, chosen = params["embed"][tokens], []
        for p in params["layers"]:
            x = kl._rmsnorm(h, p["attn_norm"], cfg.rms_norm_eps)
            h = h + (kl.kda_block(x, p["kda"], cfg) if "kda" in p
                     else kl.mla_block(x, p["mla"], cfg))
            x = kl._rmsnorm(h, p["ffn_norm"], cfg.rms_norm_eps)
            if "moe" in p:
                x = x.reshape(rows * t, -1)
                chosen.append(kl.route(x, p["moe"]["router"], bias[len(chosen)], cfg)[0])
                h = h + kl.moe_block(x, p["moe"], bias[len(chosen) - 1], cfg)[0].reshape(h.shape)
            else:
                h = h + kl._swiglu(x, p["ffn"])
        return jnp.stack(chosen)

    return jax.jit(chipbench_program_routes)


def reference_routes(dims):
    """The same through the plain reference's functions, float32 at highest."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import kimi_linear as ref

    def chipbench_reference_routes(weights, bias, tokens):
        rows, t = tokens.shape
        h, chosen = weights["embed"][tokens], []
        for p in weights["layers"]:
            x = ref.rms(h, p["attn_norm"], dims.rms_norm_eps)
            h = h + (ref.kda(x, p["kda"], dims) if "kda" in p else ref.mla(x, p["mla"], dims))
            x = ref.rms(h, p["ffn_norm"], dims.rms_norm_eps)
            if "moe" in p:
                x = x.reshape(rows * t, -1)
                chosen.append(ref.route(x, p["moe"]["router"], bias[len(chosen)], dims)[0])
                h = h + ref.moe(x, p["moe"], bias[len(chosen) - 1], dims)[0].reshape(h.shape)
            else:
                h = h + ref.swiglu(x, p["ffn"])
        return jnp.stack(chosen)

    return jax.jit(chipbench_reference_routes)


def flips(program, reference, offset, held):
    """Shares of (token, layer) pairs whose chosen sets differ, from two
    arrays [steps, layers, tokens, 8]."""
    import numpy as np

    a, b = np.sort(program, axis=-1), np.sort(reference, axis=-1)
    differs = np.any(a != b, axis=-1)
    mine = lambda c: np.where((c >= offset) & (c < offset + held), c, -1)  # noqa: E731
    held_differs = np.any(np.sort(mine(program), -1) != np.sort(mine(reference), -1), axis=-1)
    return {"pairs": int(differs.size), "chosen_set_differs": float(differs.mean()),
            "held_set_differs": float(held_differs.mean())}


def one_seed(config_file, seed, n_steps=3, rehearsal=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import families, weights
    from chipbench.reference import kimi_linear as ref
    from tpu_resiliency.models import kimi_linear as kl

    family, sizes = families.of_file(config_file, rehearsal=rehearsal)
    cfg, dims = family.model_config(sizes, jnp.bfloat16), family.reference_dims(sizes)
    key = weights.seed_key(seed)
    feed = weights.make_feed(sizes, key)
    step, routes = family.make_step(sizes), program_routes(cfg)
    params, opt = weights.make_state_fn(family, sizes)(key)
    chosen, load = [], []
    for i in range(n_steps):
        chosen.append(np.asarray(routes(params, opt["router_bias"], feed[i][0])))
        params, opt, _ = step(params, opt, feed[i])
        load.append(kl.routing_stats(opt, cfg))
    for leaf in jax.tree_util.tree_leaves((params, opt)):
        leaf.delete()
    with jax.default_matmul_precision("highest"):
        w = weights.make_reference_start_fn(family, sizes)(key)
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, w)  # noqa: E731
        mu, nu, count, bias = zeros(), zeros(), jnp.zeros((), jnp.int32), None
        ref_step, ref_routes = ref.make_step(dims), reference_routes(dims)
        wanted = []
        for i in range(n_steps):
            if bias is None:
                bias = jnp.zeros((sizes.n_expert_layers, sizes.router_experts), jnp.float32)
            wanted.append(np.asarray(ref_routes(w, bias, feed[i][0])))
            w, mu, nu, count, _, _, bias, _ = ref_step(w, mu, nu, count, *feed[i], bias)
    for leaf in jax.tree_util.tree_leaves((w, mu, nu)):
        leaf.delete()
    found = flips(np.stack(chosen), np.stack(wanted), sizes.expert_offset, sizes.experts_held)
    found.update(seed=seed, held_load=load,
                 held_load_max_over_mean=max(s["max"] / s["mean"] for s in load))
    return found


if __name__ == "__main__":
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("chipbench routing_flips: read on the chip; no TPU here")
    print(json.dumps({"device": dev.device_kind}))
    for s in sys.argv[2:]:
        print(json.dumps(one_seed(sys.argv[1], int(s))), flush=True)
