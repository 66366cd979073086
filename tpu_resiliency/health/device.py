"""TPU device health probe.

Reference analog: ``GPUHealthCheck`` (NVML recovery action,
``shared_utils/health_check.py:253-447``).  TPUs expose no NVML; the honest
liveness signal is "can a fresh process initialize the runtime and run one
op".  Crucially the probe must run in a **subprocess**: initializing JAX in
the launcher would claim the TPU chips and starve the workers.

The subprocess runs a trivial computation with a wall-clock timeout and
prints a sentinel; hang, crash, or missing devices all fail the check, and so
does a JAX that came up on the CPU backend on a host that exposes TPU chips
(without ``JAX_PLATFORMS`` a failed TPU init only warns and falls back).  The
verdict names the platform the probe ran on.  Results are cached for
``cache_ttl`` seconds because a full probe costs a runtime init (about 10 s
on a v5e host).

A chip belongs to one process at a time (libtpu takes ``/tmp/libtpu_lockfile``;
a second opener fails within seconds with "accessing libtpu multi-process
lockfile"), so the probe is for the gap between cycles, when the workers are
gone.  A SIGKILLed holder leaves the lock *file* behind but not the lock, and
its HBM is reclaimed with the process: on a v5e the next opener, started
under 3 s after the kill of a process holding 8 GiB, came up in the usual
time with ``bytes_in_use`` back at idle (PERF.md, PR 21).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Optional

from .base import HealthCheck, HealthCheckResult

_PROBE_CODE = r"""
import json
import jax
devs = jax.devices()
assert devs, "no devices"
import jax.numpy as jnp
x = jnp.ones((8, 8))
y = (x @ x).sum()
assert float(y) == 512.0, float(y)
stats = []
for d in devs:
    try:
        ms = d.memory_stats() or {}
    except Exception:
        ms = {}
    stats.append({
        "id": d.id,
        "kind": getattr(d, "device_kind", "?"),
        "platform": getattr(d, "platform", "?"),
        "bytes_in_use": ms.get("bytes_in_use"),
        "bytes_limit": ms.get("bytes_limit"),
    })
print("TPURX_DEVICE_OK", json.dumps(stats))
"""


class DeviceHealthCheck(HealthCheck):
    name = "device"

    _cache: Optional[tuple[float, HealthCheckResult]] = None

    def __init__(
        self,
        timeout: float = 120.0,
        cache_ttl: float = 300.0,
        env=None,
        max_idle_hbm_frac: Optional[float] = None,
    ):
        self.timeout = timeout
        self.cache_ttl = cache_ttl
        self.env = env
        # The probe is a FRESH runtime client, so high bytes_in_use at probe
        # time means grants leaked by dead processes are still pinned in HBM
        # (the TPU analog of the reference's "GPU memory not reclaimed" gate,
        # which the launcher polls before respawn).  None disables the gate.
        self.max_idle_hbm_frac = max_idle_hbm_frac
        self.last_stats: list = []

    def _check(self) -> HealthCheckResult:
        cached = type(self)._cache
        if cached is not None and time.monotonic() - cached[0] < self.cache_ttl:
            return HealthCheckResult(cached[1].healthy, cached[1].message + " (cached)")
        env = dict(os.environ)
        if self.env:
            env.update(self.env)
        try:
            out = subprocess.run(
                [sys.executable, "-c", _PROBE_CODE],
                env=env,
                capture_output=True,
                text=True,
                timeout=self.timeout,
            )
        except subprocess.TimeoutExpired:
            result = HealthCheckResult(False, f"device probe hung (> {self.timeout}s)")
            type(self)._cache = (time.monotonic(), result)
            return result
        if out.returncode == 0 and "TPURX_DEVICE_OK" in out.stdout:
            result = self._judge_stats(out.stdout)
        else:
            tail = (out.stderr or out.stdout).strip().splitlines()[-3:]
            result = HealthCheckResult(
                False, f"device probe rc={out.returncode}: {' | '.join(tail)}"
            )
        type(self)._cache = (time.monotonic(), result)
        return result

    def _judge_stats(self, stdout: str) -> HealthCheckResult:
        import json

        line = next(
            (l for l in stdout.splitlines() if l.startswith("TPURX_DEVICE_OK")), ""
        )
        raw = line.partition(" ")[2].strip()
        try:
            stats = json.loads(raw) if raw.startswith("[") else []
        except ValueError:
            stats = []
        self.last_stats = stats
        n = len(stats) or raw or "?"
        if self.max_idle_hbm_frac is not None:
            for d in stats:
                used, limit = d.get("bytes_in_use"), d.get("bytes_limit")
                if used and limit and used / limit > self.max_idle_hbm_frac:
                    return HealthCheckResult(
                        False,
                        f"device {d['id']} HBM {used / limit:.0%} in use at idle "
                        f"(leaked grants?)",
                    )
        kinds = {d.get("kind") for d in stats} or {"?"}
        platforms = {d.get("platform") for d in stats} or {"?"}
        on = ", ".join(sorted(map(str, platforms)))
        if platforms != {"tpu"}:
            from .tpu import visible_tpu_chips

            chips = visible_tpu_chips()
            if chips:
                return HealthCheckResult(
                    False,
                    f"host exposes {len(chips)} TPU chip(s) but JAX came up "
                    f"on platform {on}",
                )
        return HealthCheckResult(
            True,
            f"{n} device(s) healthy ({', '.join(map(str, kinds))}) "
            f"on platform {on}",
        )

    @classmethod
    def clear_cache(cls) -> None:
        cls._cache = None
