"""The comparison that decides ``correct``: the program's first steps against
the plain reference, number by number, each with its own limit.

Compared (``chipbench/limits/<configuration>.json`` holds the limits, a file
to a configuration, and ``PERF.md`` the readings they were set from):

- ``loss_gap``: the widest relative gap of a step's loss over the first three
  steps.  The lower precisions hardly move it; it is held against part of the
  batch left out.
- ``grad_norm_gap``: the first gradient as the optimizer got it (the first
  moment after one step over ``1 - b1``), norm by leaf, worst leaf.
- ``change_norm_gap``: the norm by leaf of the master copy's change after
  three steps, worst leaf.  A step that returns its state unchanged reads 1.

A leaf's gap is the distance between the program's norm and the reference's,
not the norm of their difference, over the reference's norm of that leaf or
of the median leaf, whichever is larger (some gradients are all but zero).
"""

from __future__ import annotations

import json
import os
import statistics
from typing import Dict, List, Sequence

_LIMITS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "limits")


def load_limits(config_name: str, rehearsal: bool = False) -> Dict[str, float]:
    """The configuration's limits, from the file of its name; for the CPU
    rehearsal the tiny cut's own."""
    with open(os.path.join(_LIMITS_DIR, f"{config_name}.json")) as f:
        table = json.load(f)
    return dict(table["cpu_rehearsal_cut" if rehearsal else "limits"])


def worst_leaf_gap(program: Sequence[float], reference: Sequence[float]) -> Dict:
    """The widest ``|program - reference| / max(reference, median reference)``
    over the leaves, and which leaf it is."""
    if len(program) != len(reference) or not reference:
        raise ValueError("program and reference disagree on the leaves")
    floor = statistics.median(reference)
    gaps = [abs(p - r) / max(r, floor) for p, r in zip(program, reference)]
    worst = max(range(len(gaps)), key=gaps.__getitem__)
    return {"gap": gaps[worst], "leaf": worst}


def gaps(program: Dict[str, List[float]], reference: Dict[str, List[float]]) -> Dict:
    """The three numbers compared, from ``{"loss", "grad_norm", "change_norm"}``
    of each side."""
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(program["loss"], reference["loss"], strict=True))
    grad = worst_leaf_gap(program["grad_norm"], reference["grad_norm"])
    change = worst_leaf_gap(program["change_norm"], reference["change_norm"])
    return {
        "loss_gap": loss,
        "grad_norm_gap": grad["gap"], "grad_norm_leaf": grad["leaf"],
        "change_norm_gap": change["gap"], "change_norm_leaf": change["leaf"],
    }


def within(found: Dict, limits: Dict[str, float]) -> bool:
    """True when every compared number is finite and inside its limit."""
    return all(
        found[name] == found[name] and found[name] <= limit
        for name, limit in limits.items())
