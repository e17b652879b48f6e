"""Percent of the traced window in which the device idles while the host's
innermost engine span is the pager's: ``engine.grow``,
``engine.check_tables`` or ``engine.drain_swap``.  ``None`` where the
program emits no phase spans.  Layer: pager.  Moves ``itl_p50_ms``."""
from harness import program


def read(r):
    return program.idle_share(r, "pager")
