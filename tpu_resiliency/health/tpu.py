"""TPU-deep host-side health checks.

Reference analogs: ``GPUHealthCheck`` driver/recovery-action inspection
(``shared_utils/health_check.py:253-447``) and the GB200 static topology
mapping (``:115-199``).  TPUs expose no NVML; the host-visible surface is the
accel driver's sysfs class (``/sys/class/accel/accel*``, one entry per chip)
plus the device nodes (``/dev/accel*``) on hosts that run the accel driver,
and — on a v5e host, where ``/sys/class/accel`` is empty and there is no
``/dev/accel*`` — one VFIO group node per chip (``/dev/vfio/<n>``) beside PCI
functions of Google's vendor id.  These checks are
**passive** — they never initialize the TPU runtime, so they are safe to run
from the rank-monitor watchdog while a worker owns the chips (the intrusive
runtime probe lives in :class:`tpu_resiliency.health.DeviceHealthCheck` and
is reserved for the pre-rendezvous gate when the chips are free).
"""

from __future__ import annotations

import glob
import os
from typing import Optional

from .base import HealthCheck, HealthCheckResult


_GOOGLE_PCI_VENDOR = "0x1ae0"


def _google_pci_present(pci_root: str = "/sys/bus/pci/devices") -> bool:
    """Is any PCI function of Google's vendor id on the bus?  (A VFIO group
    node alone could be any passed-through device.)"""
    for path in glob.glob(os.path.join(pci_root, "*", "vendor")):
        try:
            with open(path) as f:
                if f.read().strip() == _GOOGLE_PCI_VENDOR:
                    return True
        except OSError:
            continue
    return False


def visible_tpu_chips() -> list[str]:
    """Names of the TPU chips this host exposes, without touching the
    runtime (safe from a launcher that must stay off the chip)."""
    return TpuSysHealthCheck()._list_chips()


class TpuSysHealthCheck(HealthCheck):
    """Presence + readability of the accel devices the host is supposed to
    have.  Catches the "chip fell off the bus" / driver-wedge class of
    failures (reference: NVML device-count and recovery-action queries,
    ``health_check.py:352-447``) without touching the runtime.
    """

    name = "tpu_sys"

    def __init__(
        self,
        sys_accel: str = "/sys/class/accel",
        dev_glob: str = "/dev/accel*",
        expected_chips: Optional[int] = None,
        required: bool = False,
        vfio_glob: str = "/dev/vfio/[0-9]*",
    ):
        self.sys_accel = sys_accel
        self.dev_glob = dev_glob
        self.vfio_glob = vfio_glob
        # None -> learn the count on the first healthy observation; a later
        # drop below the learned count fails (the windowed-baseline idea the
        # reference applies to NIC link state, ``health_check.py:757``)
        self.expected_chips = expected_chips
        self._learned: Optional[int] = None
        # required=False: hosts without an accel driver (CPU CI, dev boxes)
        # pass with a note instead of failing every chain they appear in
        self.required = required

    def _list_chips(self) -> list[str]:
        try:
            sys_devs = sorted(
                d for d in os.listdir(self.sys_accel) if d.startswith("accel")
            )
        except OSError:
            sys_devs = []
        # either surface is sufficient evidence of a chip; prefer sysfs names
        chips = sys_devs or [
            os.path.basename(p) for p in sorted(glob.glob(self.dev_glob))
        ]
        if not chips and _google_pci_present():
            chips = [
                "vfio" + os.path.basename(p)
                for p in sorted(glob.glob(self.vfio_glob))
            ]
        return chips

    def _check(self) -> HealthCheckResult:
        chips = self._list_chips()
        if not chips:
            if self.required or self.expected_chips:
                return HealthCheckResult(False, "no accel devices visible")
            return HealthCheckResult(True, "no accel driver on this host (skipped)")
        expected = self.expected_chips or self._learned
        if expected is not None and len(chips) < expected:
            return HealthCheckResult(
                False, f"{len(chips)} accel device(s) visible, expected {expected}"
            )
        # unreadable sysfs entries indicate a wedged/unbound driver
        unreadable = []
        for chip in chips:
            path = os.path.join(self.sys_accel, chip)
            if os.path.isdir(path) and not os.access(path, os.R_OK):
                unreadable.append(chip)
        if unreadable:
            return HealthCheckResult(False, f"unreadable accel sysfs: {unreadable}")
        if self.expected_chips is None:
            self._learned = max(self._learned or 0, len(chips))
        return HealthCheckResult(True, f"{len(chips)} accel device(s) present")
