"""Job loops: one module per loop, found by the name a traffic file gives as
its ``loop``.  A later PR adds a module, never edits one.

A loop module has:

- ``enter(run, cw)``: called at every entry of the wrapped training function
  (``run.job.entries`` says which), after the common set-up of the first.
  ``run`` is what ``chipbench/worker.py`` hands it: the job's state, the
  readings ``run.R`` and the worker's helpers (``run_step``, ``save``,
  ``open_window``, ``close_window``, ``trace_start`` ...).
- ``tally(R) -> (attempted, failed, reasons)``: the loop's operations of the
  window from the readings, for the result line; ``reasons`` are why the run
  is not correct.  Called by the jax-free parent: no jax at import.
- ``SPANS``: the loop's own ``TraceAnnotation`` names, kept from the trace.
- ``TRACED_WINDOW``: what a traced run's window is, for the trace readers:
  ``{"kind": "device_ops"}`` (first to last operation of the traced slice) or
  ``{"kind": "spans", "from": <span>, "to": <span>}``.
"""

import importlib
import re


def load(name: str):
    if not re.fullmatch(r"[a-z][a-z0-9_]*", name):
        raise ValueError(f"not a job loop's name: {name!r}")
    try:
        return importlib.import_module(f"chipbench.loops.{name}")
    except ModuleNotFoundError as e:
        raise ValueError(f"unknown job loop {name!r}: no chipbench/loops/{name}.py") from e
