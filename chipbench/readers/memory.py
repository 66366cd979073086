"""Readers of device memory."""


def hbm_overhead_gb(R):
    """``peak_bytes_in_use`` after the window less the same after the bare
    steps of set-up: memory the layer takes from the model."""
    after, before = R.get("memory_after_window"), R.get("memory_after_bare")
    if not after or not before or not after["peak"] or not before["peak"]:
        return None
    return (after["peak"] - before["peak"]) / 1e9


def snapshot_ring_gb(R):
    """Bytes of the live snapshot-ring slots' leaves at the window's end."""
    ring = R.get("snapshot_ring_bytes")
    return None if ring is None or R.get("rehearsal") else ring / 1e9

