"""``stall_inproc``: a ping-less stall ``steps_after_entry`` steps after every
(re-)entry, recovered in this process from the resident copy of the one save
of set-up.  No save runs inside the window.

Set-up holds the save, the no-fault trajectory every episode is held to, and
one warm-up episode; the window opens at its recovery.  The next stall is
injected for as long as the last episode's duration x 1.2 still fits, so the
window holds whole episodes only.  The worker's own share of an episode is
small and constant: on re-entry the dead state goes first (the chip has no
room for a third copy), the restore template is the deleted arrays themselves
(shape, dtype and placement are all a template needs; no fresh random
weights), and the seconds outside product calls are reported.
"""

import time

from chipbench import cycles

SPANS = ("stall", "reenter", "restore", "first.step")
TRACED_WINDOW = {"kind": "spans", "from": "stall", "to": "first.step"}


def enter(run, cw):
    job = run.job
    if job.entries == 1:
        _stall_setup(run, cw)
        _inject_stall(run, cw)  # the warm-up episode: set-up, not the window
    if job.open_episode is None:
        run.abort("unexpected_restart", 4)  # a restart nobody injected: a false trip
    recovered_at = _reenter(run, cw)
    if _until_next_stall(run, cw, recovered_at) is False:
        run.close_window()


def _stall_setup(run, cw):
    job, traffic = run.job, run.traffic
    for _ in range(int(traffic["steps_between_warmup_saves"])):
        run.run_step(cw)
    job.saved = run.save(cw, in_window=False)
    run.wait_commits(cw)
    job.saved["fp_host"] = run.np.asarray(job.saved["fp"]).tolist()
    # the no-fault trajectory every episode is held to
    for _ in range(int(traffic["steps_after_entry"]) + 2):
        run.run_step(cw)
    run.fetch_pending()


def _inject_stall(run, cw):
    job = run.job
    run.fetch_pending()
    run.jax.block_until_ready(job.state)
    episode = {"freeze": time.monotonic(), "entry": job.entries,
               "in_window": job.window_open is not None,
               "traced": job.tracing}
    job.open_episode = episode
    run.R["episodes"].append(episode)
    run.report("inject", kind="stall", step=job.step)
    with run.annotate("stall"):
        while True:  # ping-less: the interpreter runs, progress beats stop
            time.sleep(0.02)


def _reenter(run, cw):
    job, R, jax, np = run.job, run.R, run.jax, run.np
    episode = job.open_episode
    episode["reenter"] = time.monotonic()
    trip = cw.quorum.trip_time
    episode["trip"] = trip if trip and trip >= episode["freeze"] else None
    with cw.disable_hang_protection(), run.annotate("reenter"):
        run.wait_commits(cw)  # nothing is in flight; the quick-start asks
        t0 = time.monotonic()
        # the dead state goes first (no room for a third copy); its
        # deleted arrays still say shape, dtype and placement, which is
        # all a restore template needs
        template = {"params": job.state[0], "opt": job.state[1]}
        for leaf in jax.tree_util.tree_leaves(template):
            leaf.delete()
        job.pending = None
        own = time.monotonic() - t0
        stats = {}
        episode["restore_start"] = time.monotonic()
        with run.annotate("restore"):
            restored = run.load_checkpoint(job.saved["path"], template, stats=stats)
            jax.block_until_ready(restored)
        episode["restore_end"] = time.monotonic()
        t0 = time.monotonic()
        job.state = (restored["params"], restored["opt"])
        job.step = job.saved["step"] + 1
        fp = run.fingerprint(job.state)
        del template, restored
        own += time.monotonic() - t0
        episode["restore_bytes"] = int(stats.get("bytes_read", 0))
        episode["restore_bytes_shm"] = int(stats.get("bytes_shm", 0))
    run.runner.on_train_start(step=job.step)
    with run.annotate("first.step"):
        loss = run.run_step(cw)
        jax.block_until_ready(loss)
    now = time.monotonic()
    run.fetch_pending()
    first = job.step - 1
    before = len(R["loss_mismatches"])
    episode["bit_equal"] = np.asarray(fp).tolist() == job.saved["fp_host"]
    episode["step_compiles"] = run.step_jit._cache_size()
    if episode["bit_equal"] and len(R["loss_mismatches"]) == before \
            and first in job.losses:
        episode["recovered"] = now
    episode["own_s"] = own
    R["own_seconds"].append(own)
    job.open_episode = None
    run.report("recovered" if "recovered" in episode else "not_recovered",
               recover_s=round(now - episode["freeze"], 3),
               detect_ms=episode["trip"] and round(
                   (episode["trip"] - episode["freeze"]) * 1e3, 1),
               restore_s=round(episode["restore_end"] - episode["restore_start"], 3),
               own_s=round(own, 4), source_shm=episode["restore_bytes_shm"],
               bit_equal=episode["bit_equal"])
    if job.tracing and episode.get("traced"):
        for _ in range(3):
            run.run_step(cw)
        run.trace_stop(cw)
    return now


def _until_next_stall(run, cw, last_recovered):
    """From a (re-)entry to the next injected stall, or False at the window's
    end."""
    job, R, traffic, args = run.job, run.R, run.traffic, run.args
    if job.window_open is None:  # the warm-up episode just ended
        run.open_window(last_recovered)
    whole = [e for e in R["episodes"] if e["in_window"] and "recovered" in e]
    steps = int(traffic["steps_after_entry"])
    if whole:
        last = whole[-1]["recovered"] - whole[-1]["freeze"]
        if not cycles.fits_another(
                time.monotonic() + steps * R["bare_step_s"], job.deadline, last, 1.2):
            return False
    for i in range(steps - 1):
        run.run_step(cw)
        if (args.trace and not job.trace_done and not job.tracing
                and job.window_open is not None and i == steps - 5):
            run.trace_start(cw)
    _inject_stall(run, cw)


def tally(R):
    """Whole episodes of the window; one not recovered bit-equal (the warm-up
    episode of set-up too) is a failed operation and makes the run not
    correct."""
    episodes = [e for e in R["episodes"] if e.get("in_window")]
    bad = [e for e in R["episodes"] if e.get("recovered") is None]
    reasons = ([f"{len(bad)} episode(s) not recovered bit-equal"] if bad else [])
    return len(episodes), len(bad), reasons
