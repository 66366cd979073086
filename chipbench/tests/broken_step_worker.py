"""The benchmark's worker with the timed path broken underneath: the step
returns its state unchanged.  ``test_chipbench_rehearsal.py`` puts this file
in ``chipbench.run.WORKER``'s place and has to see ``correct`` come out false.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import worker  # noqa: E402
from tpu_resiliency.models import transformer  # noqa: E402

sound_make_train_step = transformer.make_train_step


def make_train_step(cfg):
    step = sound_make_train_step(cfg)

    def broken(params, opt, batch):
        _, _, loss = step(*jax.tree_util.tree_map(jnp.copy, (params, opt)), batch)
        return params, opt, loss

    broken._cache_size = step._cache_size
    return broken


transformer.make_train_step = make_train_step

if __name__ == "__main__":
    worker.main()
