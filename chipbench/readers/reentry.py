"""Reader of the first step after a re-entry (``stall_inproc``): the term of
``recover_s`` that follows the restore."""

from chipbench import cycles


def first_step_ms(R):
    """Median over the window's whole episodes (those the profiler did not
    touch, where any are left) of ``recovered - restore_end``: the restored
    state's fingerprint, the hooks' restart and the first step to its
    ``block_until_ready``."""
    whole = [e for e in R.get("episodes", [])
             if e.get("in_window") and e.get("recovered") is not None
             and e.get("restore_end") is not None]
    plain = [e for e in whole if not e.get("traced")] or whole
    found = cycles.median([e["recovered"] - e["restore_end"] for e in plain])
    return None if found is None else found * 1e3
