"""Percent of the traced window in which the device idles under every other
engine span: the waits for sampled tokens (``engine.token_wait``,
``engine.first_token_wait``), ``engine.emit``, ``engine.journal``, and the
self time of ``engine.step``, ``engine.prefill`` and ``engine.decode``.
With the scheduler's, pager's and launch's shares it makes up the idle time
inside engine steps.  ``None`` where the program emits no phase spans.
Layer: engine step.  Moves ``itl_p50_ms``."""
from harness import program


def read(r):
    return program.idle_share(r, "engine step")
