"""Percent of the traced window in which no operation ran on the device:
1 - (union of the op intervals of the device's ``XLA Ops`` line) / window,
averaged over the chips.  Layer: engine step / device.  Moves
``itl_p50_ms``."""
from harness import trace as TR
from harness.readings import Absent


def read(r):
    if r.trace is None:
        return None
    busy = [TR.busy_ns(dev) for dev in r.trace.devices]
    if not any(busy):
        raise Absent("no operation ran on the device in the traced window")
    window = r.trace.window[1] - r.trace.window[0]
    return 100.0 * (1.0 - sum(busy) / len(busy) / window)
