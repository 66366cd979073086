#!/usr/bin/env python3
"""Compile a configuration's programs at full size for a described v5e chip.

No chip is attached and nothing runs: the TPU compiler says whether the step,
the snapshot copy and the plain reference's step fit one chip, and how many
bytes each needs (``memory_analysis()``).  Costs no chip time; the numbers go
into the configuration file's ``compiled_for_v5e`` by hand.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py chipbench/configs/<name>.json
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(config_file, with_reference=True):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import families, weights

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    family, sizes = families.of_file(config_file)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    def report(name, compiled):
        m = compiled.memory_analysis()
        row = {"argument_bytes": m.argument_size_in_bytes,
               "output_bytes": m.output_size_in_bytes,
               "alias_bytes": m.alias_size_in_bytes,
               "temp_bytes": m.temp_size_in_bytes,
               "peak_estimate_bytes": m.argument_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes
               + m.temp_size_in_bytes}
        print(name, json.dumps(row), flush=True)
        return row

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    params, opt = described(jax.eval_shape(weights.make_state_fn(family, sizes), key))
    batch = tuple(jax.ShapeDtypeStruct((sizes.rows, sizes.seq), jnp.int32,
                                       sharding=chip) for _ in range(2))
    out = {"state_bytes": sizes.state_bytes, "n_params": sizes.n_params}
    out["train_step"] = report(
        "train_step", family.make_step(sizes).lower(params, opt, batch).compile())
    leaves = jax.tree_util.tree_leaves((params, opt))
    # the expression of checkpointer._SNAP_FN / _SNAP_DONATE_FN
    out["snapshot_copy"] = report("snapshot_copy", jax.jit(
        lambda xs: [jnp.copy(x) for x in xs]).lower(leaves).compile())
    out["snapshot_copy_donating"] = report("snapshot_copy_donating", jax.jit(
        lambda old, new: [jnp.copy(x) for x in new], donate_argnums=(0,)
    ).lower(leaves, leaves).compile())
    out["fingerprint"] = report("fingerprint", weights.make_fingerprint_fn().lower(
        (params, opt)).compile())
    if with_reference:
        f32 = described(jax.eval_shape(
            weights.make_reference_start_fn(family, sizes), key))
        count = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        with jax.default_matmul_precision("highest"):
            out["reference_step"] = report(
                "reference_step", family.make_reference_step(sizes).lower(
                    f32, f32, f32, count, *batch).compile())
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1], with_reference="--no-reference" not in sys.argv)
