"""The serial reference reader the restore tests hold the engine against.

``load_checkpoint`` restores through ``_RestoreEngine`` (a plan, a reader
pool, resident and peer sources, in-place views).  This reads the same
committed checkpoint the plain way — ``metadata.json`` from disk, then one
leaf at a time through the public ``read_leaf`` (whole-shard verified reads,
delta provenance resolved) — and touches no resident or peer source, so a
test that compares the two trees compares the engine with an independent
reader of the same bytes.
"""

import jax.tree_util as jtu
import numpy as np

from tpu_resiliency.checkpointing.async_ckpt.writer import (
    read_leaf,
    read_metadata,
)


def serial_restore(ckpt_dir, template):
    """The checkpoint's leaves as numpy arrays in ``template``'s structure
    and dtypes, read from disk one leaf at a time."""
    meta = read_metadata(ckpt_dir)
    leaves, treedef = jtu.tree_flatten(template)
    assert len(leaves) == len(meta["leaf_paths"]), (
        len(leaves), len(meta["leaf_paths"]))
    return jtu.tree_unflatten(treedef, [
        np.asarray(read_leaf(ckpt_dir, meta, i),
                   dtype=getattr(tmpl, "dtype", None))
        for i, tmpl in enumerate(leaves)
    ])
