"""Percent of the traced window in which the device idles while the host
builds, uploads or dispatches a step: the innermost engine span is
``engine.prefill_inputs``, ``engine.prefill_dispatch``,
``engine.decode_inputs`` or ``engine.decode_dispatch``.  ``None`` where the
program emits no phase spans.  Layer: launch.  Moves ``itl_p50_ms``."""
from harness import program


def read(r):
    return program.idle_share(r, "launch")
