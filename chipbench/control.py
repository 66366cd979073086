#!/usr/bin/env python3
"""Readings the limits of ``chipbench/limits/<configuration>.json`` are set from.

Not part of a benchmark run.  On the chip, at the cell's own sizes, for each
seed: the program's first three steps (``make_train_step`` at the
configuration's widths, the benchmark's weights and feed), the plain
reference's, and the control's — the reference computed in the next lower
precision — each compared with the reference exactly as a run compares the
program.  A limit goes above the program's largest gap and below the
control's smallest.

    chiprun -- python3 chipbench/control.py chipbench/configs/<name>.json <n> 101 102 ...

(the controls run on the first ``n`` seeds, the program on all of them.)

Prints one JSON line per seed and a summary; ``chipbench/tests`` runs the same
at the tiny cut on the CPU and checks that the control is refused.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CONTROLS = ("bf16_everywhere",)


def program_first_steps(sizes, key, feed, n_steps=3):
    """The numbers a run takes from the program's first steps, without the
    hooks: the same jitted step, weights, feed and norm readers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import weights
    from tpu_resiliency.models.transformer import TransformerConfig, make_train_step

    cfg = TransformerConfig(
        vocab=sizes.vocab_size, d_model=sizes.n_embd, n_heads=sizes.n_head,
        n_layers=sizes.n_layer, d_ff=sizes.n_inner, max_seq=sizes.n_positions,
        dtype=jnp.bfloat16)
    step = make_train_step(cfg)
    leaf_norms, change_norms = weights.make_norm_fns(sizes)
    params, opt = weights.make_state_fn(sizes)(key)
    losses, grad = [], None
    for i in range(n_steps):
        params, opt, loss = step(params, opt, feed[i % len(feed)])
        if i == 0:
            grad = np.asarray(leaf_norms(opt["mu"]), np.float64) / (1 - weights.ADAM_B1)
        losses.append(float(loss))
    change = np.asarray(change_norms(opt["master"], key), np.float64)
    for leaf in jax.tree_util.tree_leaves((params, opt)):
        leaf.delete()
    return {"loss": losses, "grad_norm": grad.tolist(), "change_norm": change.tolist()}


def readings(config_file, seeds, rehearsal=False, controls=CONTROLS,
             control_seeds=None):
    """One row per seed; the controls run on the first ``control_seeds`` seeds
    (all of them by default)."""
    from chipbench import correct, weights
    from chipbench.reference import gpt2_family

    sizes = weights.load_sizes(config_file, rehearsal=rehearsal)
    rows = []
    for seed in seeds:
        key = weights.seed_key(seed)
        feed = weights.make_feed(sizes, key)
        program = program_first_steps(sizes, key, feed)

        start = lambda: weights.make_reference_start_fn(sizes)(key)  # noqa: E731

        reference = gpt2_family.first_steps(start(), feed, sizes.n_head)
        row = {"seed": seed, "program": correct.gaps(program, reference)}
        if control_seeds is not None and len(rows) >= control_seeds:
            controls = ()
        for name in controls:
            row[name] = correct.gaps(gpt2_family.first_steps(
                start(), feed, sizes.n_head, precision=name), reference)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def summary(rows, controls=CONTROLS):
    out = {}
    for number in ("loss_gap", "grad_norm_gap", "change_norm_gap"):
        out[number] = {"program_largest": max(r["program"][number] for r in rows)}
        for name in controls:
            out[number][f"{name}_smallest"] = min(
                r[name][number] for r in rows if name in r)
    return out


if __name__ == "__main__":
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("chipbench control: the limits are read on the chip; no TPU here")
    print(json.dumps({"device": dev.device_kind}))
    found = readings(sys.argv[1], [int(s) for s in sys.argv[3:]],
                     control_seeds=int(sys.argv[2]))
    print(json.dumps({"summary": summary(found)}))
