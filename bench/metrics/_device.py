"""Readings from the reduced device trace shared by several readers."""
from __future__ import annotations

import sys

from bench import work


def idle_pct(run):
    """Share of the traced window in which no operation ran on the device."""
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def renewal_roofline(run):
    """The least time the chip could take for the window's renewal work
    (``bench.work``) over the device's busy time in the window."""
    if not run.trace or not run.peaks or run.trace["busy_s"] <= 0:
        return None
    least = work.roofline(run.work, run.peaks)
    print(f"renewal roofline: {least['bound']}-bound, {least['flops']:.6g} "
          f"operations and {least['bytes']:.6g} bytes need "
          f"{least['seconds']:.6g} s at peak, device busy "
          f"{run.trace['busy_s']:.6g} s", file=sys.stderr)
    return 100.0 * least["seconds"] / run.trace["busy_s"]
