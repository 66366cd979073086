"""Readers of the save cycles (``steady_save``): host-clock arithmetic of
``chipbench/cycles.py`` over the worker's timestamps."""

from chipbench import cycles


def window_cycles(R, clean_only):
    """The window's whole cycles; with ``clean_only`` those the profiler did
    not touch, where any are left."""
    inside = [s for s in R.get("saves", []) if s["in_window"]]
    before = [s for s in R.get("saves", []) if not s["in_window"]]
    if not inside or not before or R.get("window_open") is None:
        return []
    found = cycles.whole_cycles(R["window_open"], inside, before[-1]["commit"])
    if clean_only:
        traced = set(R.get("traced_cycles", []))
        clean = [c for i, c in enumerate(found) if i not in traced]
        return clean or found
    return found


def _periods(R):
    return cycles.split_step_periods(window_cycles(R, True), R.get("step_ends", []))


def goodput_tokens_per_s(R):
    """Tokens of ALL whole cycles over the time from the window's opening to
    the return of the last ``async_save``."""
    found = window_cycles(R, False)
    if not found:
        return None
    return cycles.goodput_tokens_per_s(
        found, R["steps_per_save"], R["tokens_per_step"])


def median_cycle_s(R):
    """Median wall time of the whole cycles the profiler did not touch: the
    steadier statistic beside the end-to-end rate."""
    return cycles.median(cycles.cycle_times(window_cycles(R, True)))


def always_on_tax_pct(R):
    """Median wrapped step period after the commit, over the bare step."""
    period = cycles.median(_periods(R)["drain_free"]) if window_cycles(R, True) else None
    if period is None or not R.get("bare_step_s"):
        return None
    return 100.0 * (period / R["bare_step_s"] - 1.0)


def drain_step_slowdown_pct(R):
    """Median step period while a drain is in flight over the drain-free one."""
    if not window_cycles(R, True):
        return None
    periods = _periods(R)
    busy, free = cycles.median(periods["draining"]), cycles.median(periods["drain_free"])
    if busy is None or free is None:
        return None
    return 100.0 * (busy / free - 1.0)


def save_stall_ms(R):
    """Median cycle less ``steps_per_save`` drain-free step periods."""
    found = window_cycles(R, True)
    if not found:
        return None
    stall = cycles.save_stall_s(found, R["steps_per_save"], _periods(R)["drain_free"])
    return None if stall is None else stall * 1e3


def save_commit_s(R):
    """``async_save`` call to the commit ``maybe_finalize`` saw, median over
    the window's saves (the last one's commit is awaited after the window)."""
    return cycles.median(
        s["commit"] - s["call"] for s in R.get("saves", [])
        if s["in_window"] and s["commit"] is not None)


def save_call_ms(R):
    """Mean of ``tpurx_ckpt_save_call_ns`` over the window's saves."""
    a, b = R.get("save_call_hist_at_open"), R.get("save_call_hist_at_close")
    if not a or not b or b["count"] <= a["count"]:
        return None
    return (b["sum_ns"] - a["sum_ns"]) / (b["count"] - a["count"]) / 1e6
