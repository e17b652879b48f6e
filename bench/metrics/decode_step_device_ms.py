"""Median device time of one decode step program: the ``jit_decode_step``
events on the device's module line, in the traced window, in ms.  ``None``
where the program emits no phase spans (its step programs are unnamed);
``Absent`` where it does and no decode step ran.  Layer: model step.
Moves ``itl_p50_ms``."""
import statistics

from harness import program
from harness.readings import Absent

MODULE = r"^jit_decode_step\b"


def read(r):
    prog = program.phase_spans(r)
    if prog is None:
        return None
    evs = program.module_events(prog, MODULE)
    if not evs:
        raise Absent(f"no module event matches {MODULE!r} in the trace")
    return statistics.median(e.dur for e in evs) * 1e-6
