"""Model families: one module per family, found by the ``model_type`` a
configuration's file gives.  Whatever the harness knows of a model it asks of
this module; ``worker.py``, ``control.py``, ``rehearse.py``, ``weights.py``,
``flops.py`` and the readers name no model.

A family module imports no jax at import (the jax-free parent and the readers
load it too; every function imports what it needs) and has:

- ``load_sizes(cfg, rehearsal) -> sizes``: the configuration file's dict as
  the family's own frozen dataclass; with ``rehearsal`` at the file's CPU
  cut, whose keys are the family's.  The harness reads from it only ``name``,
  ``rows``, ``seq``, ``feed_batches``, ``tokens_per_step``, ``vocab_size``
  (the ids the feed draws from), ``n_params`` and ``state_bytes`` (the bytes
  of the state ``make_state`` makes); everything else is the family's.
- ``draw_params(sizes, key, dtype)``: the seed's draw of every trained leaf,
  in ``dtype``; traceable.  The program starts from the draw in bfloat16, the
  plain reference from the same draw widened to float32, and the parameters'
  change is measured against it.
- ``make_state(sizes, params) -> (params, opt)``: the state as the family's
  step takes it, from the bfloat16 draw; traceable.  Which leaf has a float32
  shadow, which is float32 itself, and which buffers ride along that no
  gradient touches, the family says here.
- ``first_moment(state)`` and ``master(state)``: of a state, the optimizer's
  first moment and the values it updates in full precision, as trees of
  ``draw_params``'s structure.  The first gradient as the optimizer got it is
  the first moment after one step over ``1 - weights.ADAM_B1``.
- ``make_step(sizes)``: the product's jitted train step at these sizes,
  ``(params, opt, (tokens, targets)) -> (params, opt, loss)``.
- ``reference_first_steps(start, feed, sizes, n_steps=3, precision=None)``:
  the plain reference (the family's file under ``chipbench/reference/``, which
  imports nothing of the product) followed over the first steps: ``{"loss",
  "grad_norm", "change_norm"}``, the norms by leaf of ``draw_params``'s tree.
  ``precision`` is None or one of ``CONTROLS``, the lower precisions that
  ``control.py`` runs and the comparison has to refuse.
- ``make_reference_step(sizes)``: the reference's jitted step, ``(weights, mu,
  nu, count, tokens, targets)`` over float32 trees of ``draw_params``'s
  structure, which ``rehearse.py`` compiles for a described chip.
- ``train_flops_per_token(sizes)``: forward and backward operations a token,
  nothing recomputed counted: what ``step_mfu`` is a share of.

The functions that count a kernel's operations and bytes, for a roofline
metric a later family brings, belong in its module too, for that metric's
reader to import.  The snapshot copy's stay in ``flops.py``: it is the
product's kernel and no family's.

A later PR adds a module, never edits one.
"""

import importlib
import json
import re


def load(model_type: str):
    if not re.fullmatch(r"[a-z][a-z0-9_]*", model_type):
        raise ValueError(f"not a model family's name: {model_type!r}")
    try:
        return importlib.import_module(f"chipbench.families.{model_type}")
    except ModuleNotFoundError as e:
        if e.name != f"chipbench.families.{model_type}":
            raise
        raise ValueError(f"unknown model family {model_type!r}: "
                         f"no chipbench/families/{model_type}.py") from e


def of_file(config_file: str, rehearsal: bool = False):
    """``(family, sizes)`` of one configuration file."""
    with open(config_file) as f:
        cfg = json.load(f)
    family = load(cfg["model_type"])
    return family, family.load_sizes(cfg, rehearsal)
