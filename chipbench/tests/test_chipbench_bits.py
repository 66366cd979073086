"""The yardstick may not move unseen: what the harness makes from a seed, and
the step program it times, held bit for bit to what the parent of PR 27 made
(``data/parent_bits_pr26.json``, recorded before the model-family seam went
in).  On the CPU at each configuration's rehearsal cut: the state's and the
feed's fingerprints, the plain reference's numbers for the first three steps,
and the program's.  At the cells' own sizes, for a described v5e chip: a hash
of the lowered step's and the lowered state program's text.

A benchmark PR that means to move one of them records anew and says why:

    JAX_PLATFORMS=cpu python3 chipbench/tests/test_chipbench_bits.py <out.json>
"""

import hashlib
import json
import os
import sys

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
RECORDED = os.path.join(HERE, "data", "parent_bits_pr26.json")


def config_file(name):
    return os.path.join(ROOT, "chipbench", "configs", f"{name}.json")


def bits_at_the_cut(name, seed):
    import numpy as np

    from chipbench import control, families, weights

    family, sizes = families.of_file(config_file(name), rehearsal=True)
    key = weights.seed_key(seed)
    fingerprint = weights.make_fingerprint_fn()
    state = weights.make_state_fn(family, sizes)(key)
    feed = weights.make_feed(sizes, key)
    start = weights.make_reference_start_fn(family, sizes)(key)
    return {
        "state_fingerprint": np.asarray(fingerprint(state)).tolist(),
        "feed_fingerprint": np.asarray(fingerprint(feed)).tolist(),
        "reference": family.reference_first_steps(start, feed, sizes, n_steps=3),
        "program": control.program_first_steps(family, sizes, key, feed),
    }


def described_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def lowered_at_full_size(name, chip):
    import jax
    import jax.numpy as jnp

    from chipbench import families, weights

    family, sizes = families.of_file(config_file(name))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    init_state = weights.make_state_fn(family, sizes)
    params, opt = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(init_state, key))
    batch = tuple(jax.ShapeDtypeStruct((sizes.rows, sizes.seq), jnp.int32,
                                       sharding=chip) for _ in range(2))
    step_text = family.make_step(sizes).lower(params, opt, batch).as_text()
    sha = lambda text: hashlib.sha256(text.encode()).hexdigest()  # noqa: E731
    return {"train_step_sha256": sha(step_text), "train_step_chars": len(step_text),
            "init_state_sha256": sha(init_state.lower(key).as_text())}


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def chip():
    try:
        return described_chip()
    except Exception as e:  # no TPU compiler here: nothing to lower for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
@pytest.mark.parametrize("name", ["gpt2-xl-1chip", "cerebras-gpt-1.3b-1chip"])
def test_state_feed_reference_and_program_equal_the_parents_bit_for_bit(
        recorded, name, seed):
    want = recorded["cut"][f"{name}.{seed}"]
    got = json.loads(json.dumps(bits_at_the_cut(name, seed)))
    for number in want:
        assert got[number] == want[number], (name, seed, number)


@pytest.mark.parametrize("name", ["gpt2-xl-1chip", "cerebras-gpt-1.3b-1chip"])
def test_the_lowered_step_at_the_cells_size_is_the_parents_program(
        recorded, chip, name):
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    assert lowered_at_full_size(name, chip) == recorded["lowered"][name]


if __name__ == "__main__":
    with open(RECORDED) as f:
        names = sorted(json.load(f)["lowered"])
    sharding = described_chip()
    with open(sys.argv[1], "w") as f:
        json.dump({
            "cut": {f"{n}.{s}": bits_at_the_cut(n, s)
                    for n in names for s in (7, 2**31 + 11)},
            "lowered": {n: lowered_at_full_size(n, sharding) for n in names},
        }, f, indent=1)
