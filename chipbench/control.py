#!/usr/bin/env python3
"""Readings the limits of ``chipbench/limits/<configuration>.json`` are set from.

Not part of a benchmark run.  On the chip, at the cell's own sizes, for each
seed: the program's first three steps (the family's step at the
configuration's widths, the benchmark's weights and feed), the plain
reference's, and the controls' — the reference computed in the next lower
precision, by the names the family gives as its ``CONTROLS`` — each compared
with the reference exactly as a run compares the program.  A limit goes above
the program's largest gap and below the control's smallest.

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


def program_first_steps(family, sizes, key, feed, n_steps=3):
    """The numbers a run takes from the program's first steps, without the
    hooks: the same jitted step, weights, feed and norm readers."""
    import jax
    import numpy as np

    from chipbench import weights

    step = family.make_step(sizes)
    leaf_norms, change_norms = weights.make_norm_fns(family, sizes)
    state = weights.make_state_fn(family, sizes)(key)
    losses, grad = [], None
    for i in range(n_steps):
        params, opt, loss = step(*state, feed[i % len(feed)])
        state = (params, opt)
        if i == 0:
            grad = np.asarray(leaf_norms(family.first_moment(state)),
                              np.float64) / (1 - weights.ADAM_B1)
        losses.append(float(loss))
    change = np.asarray(change_norms(family.master(state), key), np.float64)
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    return {"loss": losses, "grad_norm": grad.tolist(), "change_norm": change.tolist()}


def readings(config_file, seeds, rehearsal=False, control_seeds=None):
    """One row per seed; the family's controls run on the first
    ``control_seeds`` seeds (all of them by default)."""
    from chipbench import correct, families, weights

    family, sizes = families.of_file(config_file, rehearsal=rehearsal)
    controls = family.CONTROLS
    rows = []
    for seed in seeds:
        key = weights.seed_key(seed)
        feed = weights.make_feed(sizes, key)
        program = program_first_steps(family, sizes, key, feed)

        start = lambda: weights.make_reference_start_fn(family, sizes)(key)  # noqa: E731

        reference = family.reference_first_steps(start(), feed, sizes)
        row = {"seed": seed, "program": correct.gaps(program, reference)}
        if control_seeds is not None and len(rows) >= control_seeds:
            controls = ()
        for name in controls:
            row[name] = correct.gaps(family.reference_first_steps(
                start(), feed, sizes, precision=name), reference)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def summary(rows):
    """Per number: the program's largest gap over the rows and each control's
    smallest over the rows that ran it."""
    controls = sorted({name for r in rows for name in r} - {"seed", "program"})
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
