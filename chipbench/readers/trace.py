"""Readers of the profiler trace, through ``chipbench/trace_reduce.py``."""

from chipbench import cycles, flops, trace_reduce


def traced_window(R):
    """(lo, hi) of what was traced, on the trace's clock, as the job loop's
    ``TRACED_WINDOW`` says.  ``device_ops``: the traced slice, from the first
    to the last operation the device ran (``steady_save``: the slice around
    one save, steps at both ends).  ``spans``: from the start of the first
    span named ``from`` to the end of the first named ``to``
    (``stall_inproc``: one whole episode, ``stall`` to ``first.step``)."""
    trace, how = R.get("trace") or {}, R.get("traced_window") or {}
    if how.get("kind") == "device_ops":
        ops = [op for d in (trace.get("devices") or {}).values() for op in d["ops"]]
        if not ops:
            return None
        return min(op[2] for op in ops), max(op[2] + op[3] for op in ops)
    if how.get("kind") != "spans":
        return None
    spans = trace.get("spans") or []
    first = trace_reduce.span_intervals(spans, how["from"])
    last = trace_reduce.span_intervals(spans, how["to"])
    return (first[0][0], last[0][1]) if first and last else None


def _devices(R):
    return list(((R.get("trace") or {}).get("devices") or {}).values())


def busy_and_window_s(R):
    """Seconds an operation ran on the device in the traced window, averaged
    over the chips, and the window's length."""
    window, devices = traced_window(R), _devices(R)
    if not window or not devices:
        return None
    busy = [trace_reduce.busy_seconds(d["ops"], *window) for d in devices]
    return sum(busy) / len(busy), window[1] - window[0]


def device_idle_share(R, step_module):
    """Idle share of ONE WHOLE CYCLE, composed, where the readings have
    ``steps_per_save``: the traced slice around the save as it is, plus the
    cycle's other steps at the busy time and period of the slice's steady
    steps (those the device ran before the save was called).  An episode is
    traced whole: 1 - busy/window."""
    found = busy_and_window_s(R)
    if not found:
        return None
    busy, window = found
    if R.get("steps_per_save"):
        dev = _devices(R)[0]
        waits = trace_reduce.span_intervals(R["trace"]["spans"], "save.wait_device")
        runs = trace_reduce.module_runs(
            dev["modules"], float("-inf"), float("inf"), step_module)
        if not waits or len(runs) < 3:
            return None
        steady = [r for r in runs if r[1] <= waits[0][0]]
        if len(steady) < 3:
            return None
        periods = [b[0] - a[0] for a, b in zip(steady, steady[1:])]
        busies = [trace_reduce.busy_seconds(dev["ops"], a[0], b[0])
                  for a, b in zip(steady, steady[1:])]
        rest = R["steps_per_save"] - len(runs)
        if rest < 0:
            return None
        busy += rest * cycles.median(busies)
        window += rest * cycles.median(periods)
    return 100.0 * (1.0 - busy / window)


def _runs(R, module_contains):
    """(device, executions of the named program in the whole trace)."""
    return [(d, trace_reduce.module_runs(
        d["modules"], float("-inf"), float("inf"), module_contains))
        for d in _devices(R)]


def module_device_us_per_run(R, module_contains):
    """Device time of the program's operations per execution."""
    times = [t for d, runs in _runs(R, module_contains)
             for t in trace_reduce.ops_within(d["ops"], runs)]
    return sum(times) / len(times) * 1e6 if times else None


def snapshot_copy_roofline(R, module_contains):
    """(2 x state bytes / peak HBM bytes/s) over the copy's device time:
    bandwidth-bound.  The copies are the executions under that name that move
    a whole state; one that takes under a quarter of the least time is some
    other small program of the same name and is left out."""
    if not _devices(R):
        return None
    kind = R["device"]["kind"]
    least = flops.snapshot_copy_least_s(R["state_bytes"], kind)
    times = [t for d, runs in _runs(R, module_contains)
             for t in trace_reduce.ops_within(d["ops"], runs) if t >= least / 4]
    if not times:
        return None
    return flops.snapshot_copy_roofline_pct(
        R["state_bytes"], sum(times) / len(times), kind)


def breakdown(R, top=10):
    window, devices = traced_window(R), _devices(R)
    if not window or not devices:
        return None
    spans = R["trace"]["spans"]
    return {
        "device_ops": trace_reduce.op_seconds(devices[0]["ops"], *window, top=top),
        "idle_gaps": trace_reduce.idle_gaps(devices[0]["ops"], *window, spans, top=top),
    }
