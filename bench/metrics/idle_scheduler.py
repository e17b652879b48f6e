"""Percent of the traced window in which the device idles while the host's
innermost engine span is the scheduler's: ``engine.expire``,
``engine.admit`` or ``engine.plan_chunks``.  ``None`` where the program
emits no phase spans.  Layer: scheduler.  Moves ``itl_p50_ms``."""
from harness import program


def read(r):
    return program.idle_share(r, "scheduler")
