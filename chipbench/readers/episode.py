"""Readers of the fault episodes (``stall_inproc``)."""

from chipbench import cycles


def _episodes(R, all_episodes=False):
    """The window's whole episodes; unless ``all_episodes``, those the
    profiler did not touch, where any are left."""
    whole = [e for e in R.get("episodes", [])
             if e.get("in_window") and e.get("recovered") is not None]
    if all_episodes:
        return whole
    return [e for e in whole if not e.get("traced")] or whole


def median_s(R, key, all_episodes=False):
    return cycles.median(cycles.episode_numbers(_episodes(R, all_episodes))[key])


def median_ms(R, key):
    value = median_s(R, key)
    return None if value is None else value * 1e3


def restore_gbps(R):
    """Restored bytes over the seconds around ``load_checkpoint``."""
    rates = [e["restore_bytes"] / 1e9 / (e["restore_end"] - e["restore_start"])
             for e in _episodes(R) if e.get("restore_bytes")]
    return cycles.median(rates)
