"""``steady_save``: cycles of ``steps_per_save`` steps and the one
``async_save`` that ends them, every hook armed, no fault.

The window opens at the return of the last warm-up save (a cycle boundary)
and holds every whole cycle that ends within ``--seconds``; a run given fewer
seconds than one cycle still completes one.  First-time costs sit in set-up:
the first save takes a fresh ring slot and compiles the plain snapshot copy,
the second reuses the drained slot and compiles the donating copy every later
save runs.
"""

import time

from chipbench import cycles

SPANS = ()
TRACED_WINDOW = {"kind": "device_ops"}


def enter(run, cw):
    job, traffic, args, R = run.job, run.traffic, run.args, run.R
    if job.entries > 1:
        run.abort("unexpected_restart", 4)  # a restart nobody injected: a false trip
    n = int(traffic["steps_per_save"])
    between = int(traffic["steps_between_warmup_saves"])
    for _ in range(int(traffic["warmup_saves"]) - 1):
        for _ in range(between):
            run.run_step(cw)
        run.save(cw, in_window=False)
        run.wait_commits(cw)
    for _ in range(between):
        run.run_step(cw)
    edge = run.save(cw, in_window=False)["ret"]  # its drain runs under cycle 1
    run.open_window(edge)
    before, after = int(traffic["trace_steps_before_save"]), int(
        traffic["trace_steps_after_save"])
    spent, carried, closed = [], 0, False
    while True:
        for i in range(n - carried):
            if (args.trace and not job.tracing and not job.trace_done
                    and i >= n - carried - before):
                run.trace_start(cw)
            run.run_step(cw)
        carried = 0
        rec = run.save(cw, in_window=True)
        spent.append(rec["ret"] - edge)
        edge = rec["ret"]
        more = cycles.fits_another(
            time.monotonic(), job.deadline, cycles.median(spent), 1.0)
        if job.tracing:
            # The profiler takes a slice around one save: the last steps
            # before it, the call, the copy, and the first steps of the
            # drain.  Starting it disturbs this cycle and collecting it
            # the next: neither feeds a host-clock per-layer metric.
            R["traced_cycles"] = [len(spent) - 1, len(spent)]
            if not more:
                run.close_window()
                closed = True
            for _ in range(after):
                run.run_step(cw)
            carried = after if more else 0
            run.trace_stop(cw)
        if not more:
            break
    if not closed:
        run.close_window()
    run.wait_commits(cw)


def tally(R):
    """Whole cycles of the window; a save never committed, or not committed
    when the next ``async_save`` was called, is a failed operation."""
    from chipbench.readers import cycle as cycle_readers

    found = cycle_readers.window_cycles(R, False)
    late = cycles.uncommitted_saves(found)
    lost = sum(1 for s in R["saves"] if s["commit"] is None)
    reasons = [f"{lost} save(s) never committed"] if lost else []
    if late:  # a failed operation, not a wrong result: the save did land
        print(f"failed operation: {late} save(s) not committed before the "
              "next async_save was called")
    return len(found), late + lost, reasons
