"""Arithmetic from the worker's host timestamps to cycle and episode numbers.

Pure Python on plain lists, so that ``chipbench/tests`` can drive it with
synthetic timestamps.  All times are seconds of one monotonic clock.

A *cycle* is ``steps_per_save`` steps and the one ``async_save`` that ends
them, from the return of one ``async_save`` to the return of the next.  Only
whole cycles count: a cycle the clock cut is not in ``save_returns`` at all.

An *episode* is one injected freeze and its recovery: from the freeze to the
end (``block_until_ready``) of the first step after re-entry whose loss equals
the no-fault trajectory's.  Only whole episodes count.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


def median(values: Sequence[float]) -> Optional[float]:
    values = list(values)
    return statistics.median(values) if values else None


def whole_cycles(window_open: float, saves: Sequence[Dict],
                 open_commit: Optional[float]) -> List[Dict]:
    """The window's whole cycles from its saves, oldest first.  Each save is
    ``{"call", "ret", "commit"}`` (``commit`` None while unseen); a cycle runs
    from the return of the save before it (``window_open`` for the first) to
    the return of its own.  ``opening_commit`` is when ``maybe_finalize`` saw
    the commit of the save that opened the cycle; ``open_commit`` is that of
    the set-up's last save, which opens the first."""
    out = []
    start, opening = window_open, open_commit
    for save in saves:
        out.append({"start": start, "call": save["call"], "end": save["ret"],
                    "opening_commit": opening})
        start, opening = save["ret"], save["commit"]
    return out


def cycle_times(cycles: Sequence[Dict]) -> List[float]:
    return [c["end"] - c["start"] for c in cycles]


def goodput_tokens_per_s(cycles: Sequence[Dict], steps_per_save: int,
                         tokens_per_step: int) -> Optional[float]:
    """All the tokens of the window's whole cycles over all their time: from
    the window's opening to the return of the last ``async_save``.  A stall
    in any one cycle moves it."""
    if not cycles:
        return None
    seconds = cycles[-1]["end"] - cycles[0]["start"]
    return len(cycles) * steps_per_save * tokens_per_step / seconds


def step_periods(step_ends: Sequence[float], lo: float, hi: float) -> List[float]:
    """Periods between consecutive step timestamps that both lie in [lo, hi)."""
    inside = [t for t in step_ends if lo <= t < hi]
    return [b - a for a, b in zip(inside, inside[1:])]


def split_step_periods(cycles: Sequence[Dict],
                       step_ends: Sequence[float]) -> Dict[str, List[float]]:
    """Step periods of ``cycles``, by what ran beside them.  ``draining``:
    both steps between the cycle's start and the commit of the save that
    opened it.  ``drain_free``: both steps after that commit and before the
    cycle's own ``async_save`` was called."""
    out: Dict[str, List[float]] = {"draining": [], "drain_free": []}
    for c in cycles:
        commit = c["opening_commit"]
        if commit is None or not c["start"] <= commit <= c["call"]:
            continue  # never seen, or not before the next save: a failed save
        out["draining"] += step_periods(step_ends, c["start"], commit)
        out["drain_free"] += step_periods(step_ends, commit, c["call"])
    return out


def save_stall_s(cycles: Sequence[Dict], steps_per_save: int,
                 drain_free_periods: Sequence[float]) -> Optional[float]:
    """Time one save takes from the job: the median cycle less
    ``steps_per_save`` drain-free step periods."""
    mid = median(cycle_times(cycles))
    period = median(drain_free_periods)
    if mid is None or period is None:
        return None
    return mid - steps_per_save * period


def uncommitted_saves(cycles: Sequence[Dict]) -> int:
    """Cycles whose opening save had not been seen committed when the cycle's
    own ``async_save`` was called: each a failed operation of the run.  (The
    window's last save opens no cycle; the read-back from disk checks it.)"""
    return sum(
        1 for c in cycles
        if c["opening_commit"] is None or c["opening_commit"] > c["call"])


def fits_another(now: float, deadline: float, last_duration: float,
                 margin: float) -> bool:
    """Whether a further whole cycle (margin 1.0 on the median so far) or
    episode (1.2 on the last one) still ends inside the window."""
    return now + last_duration * margin <= deadline


def episode_numbers(episodes: Sequence[Dict[str, float]]) -> Dict[str, List[float]]:
    """Per-episode durations from the worker's stamps (``freeze``, ``trip``,
    ``reenter``, ``restore_start``, ``restore_end``, ``recovered``); an
    episode that did not recover has no ``recovered`` and is left out."""
    whole = [e for e in episodes if e.get("recovered") is not None]
    return {
        "recover_s": [e["recovered"] - e["freeze"] for e in whole],
        "detect_s": [e["trip"] - e["freeze"] for e in whole
                     if e.get("trip") is not None],
        "abort_reenter_s": [e["reenter"] - e["trip"] for e in whole
                            if e.get("trip") is not None],
        "restore_s": [e["restore_end"] - e["restore_start"] for e in whole],
    }
