"""Readers: one small function per metric, ``fn(readings, **args) -> number
or None``.  A metric's file (``chipbench/layer_metrics/<name>.json`` or
``chipbench/end_to_end/<name>.json``) names its reader as ``module:function``
and gives its arguments; a reader that finds nothing to read returns None and
the metric is left out of the line.  A later PR adds a module, never edits one.
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def read_metric(kind_dir: str, name: str, readings: dict):
    """The value of metric ``name`` (defined under ``chipbench/<kind_dir>/``)
    from one run's readings, or None."""
    with open(os.path.join(BENCH, kind_dir, f"{name}.json")) as f:
        spec = json.load(f)
    module, _, function = spec["reader"].partition(":")
    reader = getattr(importlib.import_module(f"chipbench.readers.{module}"), function)
    value = reader(readings, **spec.get("args", {}))
    return None if value is None else float(value)
